"""Plain reference of what the timed path produces, in numpy alone.

It imports nothing of the program.  From the corpus the benchmark made and
the build's public partition (centroids, and which cluster holds which
passage) it derives by itself:

* whether that partition is a k-means fixed point of the corpus: every
  passage in its nearest centroid's cluster, and every centroid the mean
  of its members, in float64 (`partition_faults`);
* the database: each cluster serialized into one byte column in the wire
  format of ``docs/wire-format.md`` (``[n_docs u32]`` then, by ascending
  doc id, ``[doc_id u32][text_len u32][scale f32][offset f32][emb u8 × d]
  [text]``), zero-padded to ``m`` rows, ``m`` the largest payload rounded
  up to 256 bytes;
* the server's answer to a captured query, ``D · q mod 2^32``
  modulus-switched to ``2^16``, on any rows;
* the cluster a query must fetch (nearest centroid in float64, with every
  centroid within rounding of the nearest admitted);
* the rerank: cosine similarity in float64 over the passages' dequantized
  embeddings, and which passages may stand in the top k.

The columns are kept as one buffer of the clusters' payloads end to end
(the padding is never materialized), so the reference holds about a tenth
of the database's bytes.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse

HDR = 16
CHUNK = 256
Q_SWITCH_BITS = 16
#: Relative slack on squared distances within which two centroids tie.
PICK_RTOL = 1e-5
#: Slack on cosine scores (the program ranks in float32).
SCORE_ATOL = 1e-4
#: Distance within which a float32 centroid is its members' float64 mean.
MEAN_ATOL = 1e-4
#: Passages whose nearest centroid is checked (all of them, up to this).
ASSIGN_SAMPLE = 8192


def quantize(emb: np.ndarray) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-row affine u8 quantization → (q u8 (N, d), scale f32, offset f32).

    Arithmetic as the wire format fixes it: the scale is (max − min) / 255
    in float64 stored as float32, and the codes are rounded in float32.
    """
    lo = emb.min(axis=1)
    hi = emb.max(axis=1)
    scale64 = (hi.astype(np.float64) - lo.astype(np.float64)) / 255.0
    scale64 = np.where(hi > lo, scale64, 1.0)
    scale = scale64.astype(np.float32)
    q = np.clip(np.round((emb - lo[:, None]) / scale[:, None]), 0, 255)
    return q.astype(np.uint8), scale, lo.astype(np.float32)


def _sqdist(x: np.ndarray, c: np.ndarray) -> np.ndarray:
    """Squared distances (len(x), len(c)) in float64."""
    x = x.astype(np.float64)
    return (x * x).sum(1)[:, None] - 2.0 * x @ c.T + (c * c).sum(1)[None, :]


def partition_faults(embeddings: np.ndarray, assignment: np.ndarray,
                     centroids: np.ndarray, rng: np.random.Generator
                     ) -> tuple[int, int]:
    """(passages not in their nearest centroid's cluster, of a sample
    drawn from ``rng``; non-empty clusters whose centroid is not the mean
    of its members), both in float64.

    A centroid ties with the nearest within ``PICK_RTOL``; a centroid is
    its members' mean within ``MEAN_ATOL`` (float32 sums of ~100 unit
    vectors round at ~1e-6).  Empty clusters keep whatever centroid they
    had, so they are not held to a mean.
    """
    emb = np.asarray(embeddings, np.float32)
    a = np.asarray(assignment, np.int64)
    c = np.asarray(centroids, np.float64)
    n, d = c.shape
    pick = (np.arange(len(emb)) if len(emb) <= ASSIGN_SAMPLE else
            np.sort(rng.choice(len(emb), ASSIGN_SAMPLE, replace=False)))
    d2 = _sqdist(emb[pick], c)
    best = d2.min(1)
    own = d2[np.arange(len(pick)), a[pick]]
    misassigned = int((own - best > PICK_RTOL * (1.0 + np.abs(best))).sum())
    counts = np.bincount(a, minlength=n)
    sums = np.zeros((n, d))
    for lo in range(0, len(emb), 1 << 16):           # one-hot · embeddings
        part = a[lo:lo + (1 << 16)]
        onehot = scipy.sparse.csr_matrix(
            (np.ones(len(part)), (part, np.arange(len(part)))),
            shape=(n, len(part)))
        sums += onehot @ emb[lo:lo + (1 << 16)].astype(np.float64)
    full = counts > 0
    mean = sums[full] / counts[full, None]
    gap = np.linalg.norm(mean - c[full], axis=1)
    return misassigned, int((gap > MEAN_ATOL).sum())


class ReferenceIndex:
    """The cluster-packed database and rerank inputs, derived independently.

    ``texts``/``embeddings`` are the corpus by passage position (its doc id),
    ``assignment`` each passage's cluster and ``centroids`` the clusters'
    centres.
    """

    def __init__(self, texts, embeddings: np.ndarray, assignment: np.ndarray,
                 centroids: np.ndarray, n_clusters: int):
        emb = np.asarray(embeddings, np.float32)
        self.centroids = np.asarray(centroids, np.float64)
        self.texts = list(texts)
        self.assignment = np.asarray(assignment, np.int64)
        self.n = n_clusters
        q, scale, off = quantize(emb)
        self.deq = q.astype(np.float32) * scale[:, None] + off[:, None]
        lens = np.fromiter((len(t) for t in self.texts), np.int64,
                           len(self.texts))
        hdr = np.zeros((len(emb), 4), "<u4")
        hdr[:, 0] = np.arange(len(emb))
        hdr[:, 1] = lens
        hdr[:, 2] = scale.view(np.uint32)
        hdr[:, 3] = off.view(np.uint32)
        fixed = np.concatenate([hdr.view(np.uint8), q], axis=1).tobytes()
        width = HDR + emb.shape[1]
        order = np.argsort(self.assignment, kind="stable")
        bounds = np.searchsorted(self.assignment[order],
                                 np.arange(n_clusters + 1))
        self.members = [order[bounds[j]:bounds[j + 1]]
                        for j in range(n_clusters)]
        parts = []
        for j in range(n_clusters):
            parts.append(np.uint32(len(self.members[j])).astype("<u4")
                         .tobytes())
            for p in self.members[j].tolist():
                parts.append(fixed[p * width:(p + 1) * width])
                parts.append(self.texts[p])
        self.buf = np.frombuffer(b"".join(parts), np.uint8)
        counts = bounds[1:] - bounds[:-1]
        self.plen = 4 + counts * width + np.bincount(
            self.assignment, weights=lens, minlength=n_clusters
        ).astype(np.int64)
        self.start = np.concatenate([[0], np.cumsum(self.plen)[:-1]])
        self.m = int(-(-self.plen.max() // CHUNK) * CHUNK)

    def column(self, j: int) -> np.ndarray:
        """Cluster ``j``'s (m,) u8 column."""
        col = np.zeros(self.m, np.uint8)
        col[:self.plen[j]] = self.buf[self.start[j]:self.start[j]
                                      + self.plen[j]]
        return col

    def rows(self, rows: np.ndarray) -> np.ndarray:
        """``D[rows]`` transposed: (n, len(rows)) u8, every cluster."""
        rows = np.asarray(rows, np.int64)
        idx = self.start[:, None] + rows[None, :]
        inside = rows[None, :] < self.plen[:, None]
        return np.where(inside, self.buf[np.where(inside, idx, 0)],
                        0).astype(np.uint8)

    def matrix(self) -> np.ndarray:
        """The whole (n, m) column matrix (small corpora only)."""
        return self.rows(np.arange(self.m))

    # -- the server's answer --------------------------------------------------

    def answer_rows(self, rows: np.ndarray, qu: np.ndarray) -> np.ndarray:
        """Switched answer ``round(D[rows]·qu mod 2^32 / 2^16)`` as uint16.

        Exact in float64: each query word is split into 16-bit halves, so
        every partial sum stays below 2^8 · 2^16 · n < 2^53.
        """
        d = self.rows(rows).T.astype(np.float64)                # (R, n)
        qu = np.asarray(qu, np.uint64)
        lo = (d @ (qu & 0xFFFF).astype(np.float64)).astype(np.uint64)
        hi = (d @ (qu >> 16).astype(np.float64)).astype(np.uint64)
        raw = (lo + (hi << np.uint64(16))) % (1 << 32)
        half = 1 << (32 - Q_SWITCH_BITS - 1)
        return (((raw + half) % (1 << 32)) >> (32 - Q_SWITCH_BITS)
                ).astype(np.uint16)

    # -- the client -----------------------------------------------------------

    def clusters_for(self, query: np.ndarray) -> list[int]:
        """Clusters a query may fetch: the nearest, and any tied with it."""
        d2 = _sqdist(query[None, :], self.centroids)[0]
        best = d2.min()
        return [int(j) for j in
                np.nonzero(d2 - best <= PICK_RTOL * (1.0 + abs(best)))[0]]

    def topk_ok(self, query: np.ndarray, top: list, cluster: int,
                k: int) -> bool:
        """Is ``top`` ((doc_id, score, text), ...) a top-k of ``cluster``?"""
        pos = self.members[cluster]
        want = min(k, len(pos))
        if len(top) != want:
            return False
        q = query.astype(np.float64)
        q = q / (np.linalg.norm(q) + 1e-12)
        e = self.deq[pos].astype(np.float64)
        scores = (e / (np.linalg.norm(e, axis=1, keepdims=True) + 1e-12)) @ q
        kth = np.sort(scores)[::-1][want - 1] if want else np.inf
        by_id = {int(p): s for p, s in zip(pos, scores)}
        ids = [int(t[0]) for t in top]
        if len(set(ids)) != len(ids):
            return False
        for doc_id, score, text in top:
            s = by_id.get(int(doc_id))
            if (s is None or s < kth - SCORE_ATOL
                    or abs(float(score) - s) > SCORE_ATOL
                    or bytes(text) != self.texts[int(doc_id)]):
                return False
        return True

    def topk_ok_any(self, query: np.ndarray, top: list, k: int) -> bool:
        """``topk_ok`` for any cluster the query may fetch."""
        return any(self.topk_ok(query, top, j, k)
                   for j in self.clusters_for(query))
