"""PIR-RAG end-to-end system (paper §3): offline setup + online private query.

Offline (server): embed → K-means → chunk-transposed DB → PIR hint.
Online (client): embed query → pick cluster from PUBLIC centroids →
LWE-encrypted one-hot → server modular GEMV → decrypt whole cluster →
local exact re-rank → top-K documents, content in hand ("RAG-Ready").

The server never sees the query embedding, the chosen cluster, or the ranked
results; its entire view is one pseudorandom uint32 vector per query.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import chunking, clustering, pir, rerank
from repro.kernels import ops


@dataclasses.dataclass
class InflightBatch:
    """A dispatched serving batch: answer GEMM(s) in flight, decode deferred.

    Produced by `PirRagSystem.query_batch_async` — the plan stage has
    encoded every client query and the dispatch stage has enqueued the
    server GEMM(s); JAX async dispatch means the device crunches while the
    Python caller goes on to cut/encode the next batch.  `complete()` does
    the decode + re-rank (the first operation that forces the device
    values) and returns exactly what `query_batch` would have.

    Everything decode needs — client hint, per-bucket hints/configs, LWE
    states — is captured at PLAN time, so the batch stays decodable (and
    bit-identical to the synchronous path) even after a later epoch commit
    swaps the live system's buffers: epoch snapshots, not live pointers.
    """
    _complete: Callable[[], list]
    pending: tuple = ()            # device arrays the GEMM stage produced
    done: bool = False

    def complete(self) -> list:
        """Decode + re-rank; same return value as `query_batch`."""
        assert not self.done, "InflightBatch.complete() called twice"
        out = self._complete()
        self.done = True
        return out


def _no_span(name: str, **attrs):
    """Stand-in for `Obs.span` where the caller passed no `Obs`."""
    return contextlib.nullcontext()


def _fresh_client_key() -> jax.Array:
    """Root of a client-side key stream: one OS-entropy draw, then splits."""
    return jax.random.PRNGKey(int.from_bytes(os.urandom(7), "little"))


# Independent fold_in streams off the ONE public build seed.  Cluster seeding
# and LWE setup (the public matrix A's seed) must never share a stream:
# with a shared key, changing k-means knobs would silently re-derive A — and
# with it every hint, query and cached client state.  (Regression-pinned in
# tests/test_pipeline.py.)
_STREAM_KMEANS = 0
_STREAM_LWE = 1


def _derive_build_streams(seed: int) -> tuple[jax.Array, int]:
    """(kmeans key, a_seed) — two independent streams from one build seed."""
    root = jax.random.PRNGKey(seed)
    k_km = jax.random.fold_in(root, _STREAM_KMEANS)
    a_seed = int(jax.random.randint(jax.random.fold_in(root, _STREAM_LWE),
                                    (), 0, jnp.iinfo(jnp.int32).max))
    return k_km, a_seed


@dataclasses.dataclass
class QueryStats:
    uplink_bytes: int
    downlink_bytes: int
    client_ms: float
    server_ms: float
    cluster_index: int            # known to client only
    mode: str = "legacy"          # "legacy" (P one-hots) | "batch" (cuckoo)
    probes: int = 1               # clusters privately fetched
    n_buckets: int = 0            # batch mode: bucket queries sent (incl dummies)
    hint_bytes: int = 0           # one-time hint downlink of the path used


@dataclasses.dataclass
class LookupStats:
    """Accounting for one keyed embedding lookup (`PirRagSystem.lookup`)."""
    uplink_bytes: int
    downlink_bytes: int
    client_ms: float
    server_ms: float
    kappa: int                    # rows requested (multiset size)
    groups: int                   # distinct id groups privately fetched
    mode: str = "batch"           # "batch" (cuckoo) | "legacy" (G one-hots)
    n_buckets: int = 0            # batch mode: bucket queries sent (incl dummies)
    hint_bytes: int = 0           # one-time hint downlink of the path used


@dataclasses.dataclass
class PirRagSystem:
    """Bundles server-public state (centroids) and the two protocol roles."""
    centroids: np.ndarray         # PUBLIC: (n_clusters, d)
    db: chunking.ChunkedDB
    cfg: pir.PIRConfig
    server: pir.PIRServer
    hint: jax.Array               # client-side after one-time download
    setup_seconds: float          # total offline time
    index_seconds: float = 0.0    # clustering + packing (no crypto)
    hint_seconds: float = 0.0     # hint GEMM (int8-roofline op on TPU)
    assignment: np.ndarray | None = None  # (N,) doc→cluster (live index)
    batch: object | None = None           # batchpir.BatchPIR once enabled
    keyed: object | None = None           # batchpir.KeyedLayout (keyed system)
    mesh: object | None = None            # device mesh (sharded serving)
    mesh_axes: tuple | None = None        # mesh axes the DB rows shard over
    _qkey: jax.Array | None = None        # split stream for keyless queries

    # -- offline ------------------------------------------------------------

    @classmethod
    def build(cls, texts: Sequence[bytes], embeddings: np.ndarray, *,
              n_clusters: int, kmeans_iters: int = 25, chunk_size: int = 256,
              balance_factor: float | None = None, seed: int = 0,
              impl: str = "auto", q_switch: int | None = 1 << 16,
              doc_ids: Sequence[int] | None = None,
              mesh=None, mesh_axes: tuple | None = None,
              build_blocks: int | None = None,
              ) -> "PirRagSystem":
        """Offline setup: embed → K-means → chunk-transposed DB → PIR hint.

        texts: N byte strings; embeddings: (N, d) f32.  ``seed`` feeds two
        independent `fold_in` streams — cluster seeding and the public LWE
        matrix seed (`cfg.a_seed`) — so clustering knobs can never perturb
        key material.

        ``mesh=`` shards the ENTIRE build over the device mesh the server
        uses: K-means fits with the corpus row-sharded
        (`clustering.kmeans_fit_sharded`, one all-gather per Lloyd
        iteration), the balanced-assign distance sweep runs per shard, and
        column packing emits per-shard row slices that are placed directly
        on their owning devices — the row-sharded DB is constructed in
        place, never materialized on (or resharded through) one device.
        Everything downstream — centroids, assignment, packed columns,
        hint, answers, top-k — is bit-identical to the mesh=None build
        (property-tested under the 8-fake-device harness) whenever the
        shard count divides ``build_blocks`` (default
        ``lcm(clustering.BUILD_BLOCKS, shards)``, i.e. any power-of-two
        mesh up to 8 matches the unsharded build exactly).
        """
        t0 = time.perf_counter()
        k_km, a_seed = _derive_build_streams(seed)
        axes, shards = (clustering.resolve_mesh_axes(mesh, mesh_axes)
                        if mesh is not None else (None, 1))
        blocks = (build_blocks if build_blocks is not None
                  else math.lcm(clustering.BUILD_BLOCKS, shards))
        embf = np.asarray(embeddings, np.float32)
        if mesh is None:
            km = clustering.kmeans_fit(k_km, jnp.asarray(embf),
                                       k=n_clusters, iters=kmeans_iters,
                                       n_blocks=blocks, impl=impl)
        else:
            km = clustering.kmeans_fit_sharded(
                k_km, embf, k=n_clusters, iters=kmeans_iters, mesh=mesh,
                mesh_axes=axes, n_blocks=blocks, impl=impl)
        cents = np.asarray(km.centroids)
        if balance_factor is not None:
            cap = int(np.ceil(len(texts) / n_clusters * balance_factor))
            d2 = clustering.blocked_sqdist(embf, cents, n_blocks=blocks,
                                           mesh=mesh, mesh_axes=axes)
            assign = clustering.balanced_assign(embf, cents, cap,
                                                d2=np.asarray(d2))
        else:
            assign = np.asarray(km.assignment)
        db = chunking.build_chunked_db(texts, embf, assign, n_clusters,
                                       chunk_size, doc_ids=doc_ids,
                                       n_row_shards=shards)
        cfg = pir.make_config(db.m, db.n, impl=impl, q_switch=q_switch,
                              a_seed=a_seed)
        server = pir.PIRServer(
            cfg, db.row_shards if db.row_shards is not None
            else jnp.asarray(db.matrix), mesh=mesh, mesh_axes=axes)
        t_index = time.perf_counter()
        # row-sharded like the DB on a mesh: the client's hint strip is
        # row-local, so its decode splits over the chips with no collective
        hint = jax.block_until_ready(server.setup())
        t_end = time.perf_counter()
        return cls(centroids=cents, db=db, cfg=cfg, server=server, hint=hint,
                   setup_seconds=t_end - t0, index_seconds=t_index - t0,
                   hint_seconds=t_end - t_index, assignment=assign,
                   mesh=mesh, mesh_axes=server.mesh_axes,
                   _qkey=_fresh_client_key())

    @classmethod
    def build_keyed(cls, table: np.ndarray, *, group_size: int | None = None,
                    kappa: int = 8, n_buckets: int | None = None,
                    chunk_size: int = 256, seed: int = 0,
                    batch_seed: int = 101, impl: str = "auto",
                    q_switch: int | None = 1 << 16,
                    mesh=None, mesh_axes: tuple | None = None,
                    ) -> "PirRagSystem":
        """Offline setup for KEYED serving: a private embedding-table index.

        table: (V, d) f32 embedding rows.  A recsys lookup is keyed — the
        client knows row IDS, not contents — so there is no k-means: row i
        lands in group ``i // group_size`` (`batchpir.KeyedLayout`,
        default group_size ≈ √V), each group packs into one chunk-transposed
        column through the standard codec with the row's raw f32 bytes as
        the record payload, and the batch-PIR subsystem is enabled
        immediately (keyed serving IS batched serving — a DLRM request
        carries κ sparse ids).  `lookup` then recovers rows bit-identical
        to ``table[ids]``.

        ``centroids`` are the per-group row means: the keyed path never
        consults them, but they keep the legacy embedding-similarity
        `query` well-formed on a keyed system.  ``seed`` feeds the same
        two-stream discipline as `build` (the k-means stream is simply
        unused); ``mesh=`` row-shards the flat DB and spreads buckets
        across devices exactly as in the document build.
        """
        t0 = time.perf_counter()
        from repro import batchpir
        table = np.ascontiguousarray(table, np.float32)
        layout = batchpir.KeyedLayout.build(table.shape[0], table.shape[1],
                                            group_size)
        _, a_seed = _derive_build_streams(seed)
        axes, shards = (clustering.resolve_mesh_axes(mesh, mesh_axes)
                        if mesh is not None else (None, 1))
        assign = np.arange(layout.n_rows, dtype=np.int64) // layout.group_size
        texts = [layout.row_text(table[i]) for i in range(layout.n_rows)]
        # per-group means; bincount over segments keeps it one pass
        sums = np.zeros((layout.n_groups, layout.dim), np.float64)
        np.add.at(sums, assign, table)
        cnts = np.bincount(assign, minlength=layout.n_groups)[:, None]
        cents = (sums / np.maximum(cnts, 1)).astype(np.float32)
        db = chunking.build_chunked_db(texts, table, assign, layout.n_groups,
                                       chunk_size, n_row_shards=shards)
        cfg = pir.make_config(db.m, db.n, impl=impl, q_switch=q_switch,
                              a_seed=a_seed)
        server = pir.PIRServer(
            cfg, db.row_shards if db.row_shards is not None
            else jnp.asarray(db.matrix), mesh=mesh, mesh_axes=axes)
        t_index = time.perf_counter()
        hint = jax.block_until_ready(server.setup())
        t_hint = time.perf_counter()
        sys = cls(centroids=cents, db=db, cfg=cfg, server=server, hint=hint,
                  setup_seconds=t_hint - t0, index_seconds=t_index - t0,
                  hint_seconds=t_hint - t_index, assignment=assign,
                  keyed=layout, mesh=mesh, mesh_axes=server.mesh_axes,
                  _qkey=_fresh_client_key())
        sys.enable_batch(kappa=kappa, n_buckets=n_buckets, seed=batch_seed)
        sys.setup_seconds += sys.batch.setup_seconds
        return sys

    # -- key stream ----------------------------------------------------------

    def next_query_key(self) -> jax.Array:
        """Fresh LWE key material for one query, from ONE split stream.

        The stream root is drawn from OS entropy ONCE (never from the
        public build seed — LWE secrets must be unpredictable to the
        server) and then split per query, the same discipline PIRServeLoop
        uses per batch, so ad-hoc keyless callers can neither collide
        secrets within a process nor share them across processes.
        """
        if self._qkey is None:                     # systems built pre-stream
            self._qkey = _fresh_client_key()
        self._qkey, key = jax.random.split(self._qkey)
        return key

    # -- batch-PIR (multi-probe amortization) --------------------------------

    def enable_batch(self, *, kappa: int = 8, n_buckets: int | None = None,
                     seed: int = 101) -> "object":
        """Bucketize the DB for batch-PIR; multi_probe>1 then routes there.

        A sharded system passes its mesh through: buckets spread across the
        same devices the flat DB row-shards over.
        """
        from repro import batchpir
        self.batch = batchpir.build(
            self.db.matrix, self.db.used_bytes, self.cfg.params,
            kappa=kappa, n_buckets=n_buckets, seed=seed,
            a_seed=self.cfg.a_seed, impl=self.cfg.impl,
            mesh=self.mesh, mesh_axes=self.mesh_axes)
        return self.batch

    # -- keyed lookups (recsys serving) --------------------------------------

    def _require_keyed(self):
        if self.keyed is None or self.batch is None:
            raise ValueError("keyed lookups need a build_keyed() system")
        return self.keyed, self.batch

    def lookup(self, ids, *, key: jax.Array | None = None
               ) -> tuple[np.ndarray, LookupStats]:
        """Privately fetch embedding rows `ids` → ((κ, d) f32, accounting).

        ``ids`` is a multiset (duplicates fine); rows come back in caller
        order, bit-identical to ``table[ids]``.  The server sees B
        pseudorandom bucket ciphertexts — independent of κ, of duplicate
        structure, and of which ids were asked — and streams its bucketed
        DB once regardless of κ.  A structurally unplaceable distinct-group
        set (negligible probability) falls back to the legacy path: one
        flat-PIR one-hot per distinct group, still private, just without
        the one-pass amortization.
        """
        layout, bp = self._require_keyed()
        key = key if key is not None else self.next_query_key()
        from repro.batchpir import PlacementError
        t0 = time.perf_counter()
        try:
            qs, state = bp.client.query_rows(key, layout, ids)
        except PlacementError:
            return self._lookup_legacy(ids, key, t0)
        batch = jax.block_until_ready(qs)
        t1 = time.perf_counter()
        ans = [jax.block_until_ready(a) for a in bp.server.answer_batch(batch)]
        t2 = time.perf_counter()
        rows = bp.client.recover_rows(ans, state)
        t3 = time.perf_counter()
        acc = bp.client.accounting(state.base)
        stats = LookupStats(
            uplink_bytes=acc.uplink_bytes, downlink_bytes=acc.downlink_bytes,
            client_ms=1e3 * ((t1 - t0) + (t3 - t2)),
            server_ms=1e3 * (t2 - t1), kappa=len(state.ids),
            groups=len(state.base.placement), mode="batch",
            n_buckets=acc.n_buckets, hint_bytes=acc.hint_bytes)
        return rows, stats

    def _lookup_legacy(self, ids, key: jax.Array, t0: float
                       ) -> tuple[np.ndarray, LookupStats]:
        """Flat-PIR fallback: one one-hot query per DISTINCT id group."""
        layout = self.keyed
        ids = [int(i) for i in ids]
        groups = layout.groups_of(ids)
        client = pir.PIRClient(self.cfg, self.hint)
        qs, states = [], []
        for j, g in enumerate(groups):
            qu, st = client.query(jax.random.fold_in(key, j), int(g))
            qs.append(qu)
            states.append(st)
        if qs:
            batch = jax.block_until_ready(jnp.stack(qs, axis=1))
            t1 = time.perf_counter()
            ans = jax.block_until_ready(self.server.answer(batch))
        else:
            t1 = time.perf_counter()
            ans = None
        t2 = time.perf_counter()
        cols = {g: np.asarray(client.recover(ans[:, j], states[j]))
                for j, g in enumerate(groups)}
        rows = [layout.decode_row(cols[layout.group_of(i)], i) for i in ids]
        out = (np.stack(rows) if rows
               else np.zeros((0, layout.dim), np.float32))
        t3 = time.perf_counter()
        g = len(groups)
        stats = LookupStats(
            uplink_bytes=g * self.cfg.uplink_bytes,
            downlink_bytes=g * self.cfg.downlink_bytes,
            client_ms=1e3 * ((t1 - t0) + (t3 - t2)),
            server_ms=1e3 * (t2 - t1), kappa=len(ids), groups=g,
            mode="legacy", hint_bytes=self.cfg.hint_bytes)
        return out, stats

    def lookup_batch(self, ids_batch, *, seed: int | None = None,
                     key: jax.Array | None = None) -> list[np.ndarray]:
        """Batched keyed serving: C clients' bucket queries, one bucketed GEMM.

        ids_batch: a sequence of id multisets, one per client.  Returns one
        (κ_i, d) f32 array per client, bit-identical to ``table[ids_i]``.
        """
        return self.lookup_batch_async(ids_batch, seed=seed,
                                       key=key).complete()

    def lookup_batch_async(self, ids_batch, *, seed: int | None = None,
                           key: jax.Array | None = None) -> InflightBatch:
        """Plan + dispatch a keyed serving batch; decode deferred.

        The keyed mirror of `query_batch_async`: per-client placement
        failures fall back to that client's legacy lookup, everyone else
        stacks along the column axis of the shared bucketed GEMM, and the
        per-bucket hints/configs are snapshotted at plan time so
        `complete()` decodes against this batch's epoch even if a live
        commit lands in between.
        """
        layout, bp = self._require_keyed()
        if key is None:
            key = (jax.random.PRNGKey(seed) if seed is not None
                   else self.next_query_key())
        from repro.batchpir import PlacementError

        per_client, fallback = [], {}
        for i, ids in enumerate(ids_batch):
            k_i = jax.random.fold_in(key, i)
            try:
                per_client.append(bp.client.query_rows(k_i, layout, ids))
            except PlacementError:
                t0 = time.perf_counter()
                fallback[i] = self._lookup_legacy(ids, k_i, t0)[0]
                per_client.append(None)

        live = [i for i, pc in enumerate(per_client) if pc is not None]
        answers: list = []
        if live:
            stacked = jnp.stack([per_client[i][0] for i in live], axis=2)
            answers = bp.server.answer_batch(stacked)   # per bucket (m_b, C)
        hints = list(bp.client.hints)
        cfgs = list(bp.client.cfgs)

        def complete():
            out: list[np.ndarray | None] = [None] * len(per_client)
            for c_idx, i in enumerate(live):
                ans_i = [a[:, c_idx] for a in answers]
                out[i] = bp.client.recover_rows(ans_i, per_client[i][1],
                                                hints=hints, cfgs=cfgs)
            for i, rows in fallback.items():
                out[i] = rows
            return out

        return InflightBatch(_complete=complete, pending=tuple(answers))

    # -- online -------------------------------------------------------------

    def query(self, query_emb: np.ndarray, *, top_k: int = 10,
              multi_probe: int = 1, key: jax.Array | None = None,
              mode: str = "auto"
              ) -> tuple[list[tuple[int, float, bytes]], QueryStats]:
        """One fully private retrieval; returns top-k docs + accounting.

        multi_probe=P (beyond-paper): privately fetch the P nearest clusters.
        Recovers the boundary recall that single-cluster pruning loses (the
        paper's quality gap vs Graph-PIR); the server learns nothing either
        way, including the P cluster identities.  Two server shapes:

          legacy — P one-hot queries into ONE GEMM over the full DB: server
                   work and uplink/downlink scale P×.
          batch  — with `enable_batch()`: cuckoo-place the P clusters into
                   buckets and send one (real or dummy) query per bucket;
                   the server streams its bucketed DB once regardless of P.

        mode="auto" routes multi_probe>1 through batch-PIR when enabled,
        falling back to legacy on (negligible-probability) placement
        failure; "legacy"/"batch" force a path.
        """
        key = key if key is not None else self.next_query_key()

        t0 = time.perf_counter()
        d2 = clustering.pairwise_sqdist(
            jnp.asarray(query_emb, jnp.float32)[None, :],
            jnp.asarray(self.centroids))[0]
        order = np.argsort(np.asarray(d2))[:max(1, multi_probe)]

        if mode not in ("auto", "legacy", "batch"):
            raise ValueError(f"unknown query mode {mode!r}")
        use_batch = self.batch is not None and (
            mode == "batch" or (mode == "auto" and len(order) > 1))
        if use_batch:
            from repro.batchpir import PlacementError
            try:
                return self._query_via_batch(query_emb, order, top_k, key, t0)
            except PlacementError:
                if mode == "batch":
                    raise
        elif mode == "batch":
            raise ValueError("enable_batch() before mode='batch' queries")

        client = pir.PIRClient(self.cfg, self.hint)
        qs, states = [], []
        for j, cl in enumerate(order):
            qu, st = client.query(jax.random.fold_in(key, j), int(cl))
            qs.append(qu)
            states.append(st)
        batch = jax.block_until_ready(jnp.stack(qs, axis=1))
        t1 = time.perf_counter()

        ans = jax.block_until_ready(self.server.answer(batch))
        t2 = time.perf_counter()

        docs = []
        for j, st in enumerate(states):
            col = np.asarray(client.recover(ans[:, j], st))
            docs.extend(chunking.deserialize_docs(col, self.db.emb_dim))
        top = rerank.rerank(np.asarray(query_emb, np.float32), docs, top_k)
        t3 = time.perf_counter()

        p = len(order)
        stats = QueryStats(
            uplink_bytes=p * self.cfg.uplink_bytes,
            downlink_bytes=p * self.cfg.downlink_bytes,
            client_ms=1e3 * ((t1 - t0) + (t3 - t2)),
            server_ms=1e3 * (t2 - t1),
            cluster_index=int(order[0]),
            mode="legacy", probes=p, hint_bytes=self.cfg.hint_bytes)
        return top, stats

    def _query_via_batch(self, query_emb: np.ndarray, order: np.ndarray,
                         top_k: int, key: jax.Array, t0: float
                         ) -> tuple[list[tuple[int, float, bytes]], QueryStats]:
        """Batch-PIR leg of `query`: one bucketed pass for all probes."""
        bp = self.batch
        qs, state = bp.client.query(key, [int(c) for c in order])
        batch = jax.block_until_ready(qs)
        t1 = time.perf_counter()

        ans = [jax.block_until_ready(a) for a in bp.server.answer_batch(batch)]
        t2 = time.perf_counter()

        cols = bp.client.recover(ans, state)
        docs = []
        for c in order:
            docs.extend(chunking.deserialize_docs(cols[int(c)],
                                                  self.db.emb_dim))
        top = rerank.rerank(np.asarray(query_emb, np.float32), docs, top_k)
        t3 = time.perf_counter()

        acc = bp.client.accounting(state)
        stats = QueryStats(
            uplink_bytes=acc.uplink_bytes,
            downlink_bytes=acc.downlink_bytes,
            client_ms=1e3 * ((t1 - t0) + (t3 - t2)),
            server_ms=1e3 * (t2 - t1),
            cluster_index=int(order[0]),
            mode="batch", probes=len(order),
            n_buckets=acc.n_buckets, hint_bytes=acc.hint_bytes)
        return top, stats

    def query_batch(self, query_embs: np.ndarray, *,
                    top_k: int | Sequence[int] = 10,
                    multi_probe: int = 1,
                    seed: int | None = None, key: jax.Array | None = None
                    ) -> list[list[tuple[int, float, bytes]]]:
        """Batched serving: stack B clients' encrypted queries into one GEMM.

        top_k may be per-request (a sequence aligned with `query_embs`).
        multi_probe>1 with `enable_batch()` routes every client through the
        batch-PIR subsystem: all clients' per-bucket queries stack along the
        column axis of the SAME bucketed GEMM, so the server still streams
        its bucketed DB once per serving batch.

        Per-query LWE secrets are derived by `fold_in` from ONE caller key
        (or from `seed` if given; otherwise the system's split stream), so
        secrets never collide across batches or ad-hoc callers.
        """
        return self.query_batch_async(query_embs, top_k=top_k,
                                      multi_probe=multi_probe, seed=seed,
                                      key=key).complete()

    def query_batch_async(self, query_embs: np.ndarray, *,
                          top_k: int | Sequence[int] = 10,
                          multi_probe: int = 1,
                          seed: int | None = None,
                          key: jax.Array | None = None,
                          obs=None) -> InflightBatch:
        """Plan + dispatch a serving batch; decode deferred to `complete()`.

        The pipelined serving engine's staged entry point: the returned
        `InflightBatch` has the answer GEMM already enqueued on the device
        and carries plan-time snapshots of everything decode needs, so the
        caller can encode/cut further batches (or publish an epoch commit)
        while this one computes.  `query_batch` is literally
        ``query_batch_async(...).complete()`` — the two paths cannot
        diverge.

        With an `Obs` (``repro.obs``), the plan's stages open spans on it:
        ``serve.plan.pick`` (the cluster pick, whose distances come back
        to the host — it waits for the device work queued ahead of it),
        ``serve.plan.encrypt`` (every LWE encrypt; the legacy path's are
        one device program, `PIRClient.query_batch`) and
        ``serve.plan.dispatch`` (answer and decode enqueued).  On the
        legacy path the dispatch holds ``serve.plan.dispatch.answer`` (the
        queries placed on the DB's chips and the answer program enqueued)
        and ``serve.plan.dispatch.decode`` (the batched recover enqueued),
        and `complete()` opens ``serve.complete.fetch`` around its one
        device-to-host copy, then ``serve.complete.parse`` around every
        passage parse and ``serve.complete.rerank`` around every rerank.
        The legacy path also counts on the `Obs`, per batch:
        ``serve.encrypt.programs`` (one), ``serve.encrypt.batched_queries``
        (B·P), ``serve.answer.chips`` (the chips the DB's rows shard over),
        ``serve.answer.kernel_cols`` (the query columns the answer computes,
        B·P after the kernel's padding: `kernels.ops.kernel_cols`),
        ``serve.plan.query_bytes`` (the encrypted queries placed on those
        chips, 4·n·B·P a chip) and ``serve.complete.fetch_bytes`` (the
        decoded columns fetched, m·B·P).
        """
        span = obs.span if obs is not None else _no_span
        if key is None:
            key = (jax.random.PRNGKey(seed) if seed is not None
                   else self.next_query_key())
        n_req = len(query_embs)
        top_ks = ([int(top_k)] * n_req if np.isscalar(top_k)
                  else [int(t) for t in top_k])
        assert len(top_ks) == n_req, (len(top_ks), n_req)

        if multi_probe > 1 and self.batch is not None:
            return self._query_batch_via_batchpir_async(query_embs, top_ks,
                                                        multi_probe, key, span)

        # Legacy path: P one-hot columns per request (P=1 is the classic
        # one-column-per-client GEMM) — never silently fewer probes than
        # asked for just because batch-PIR isn't enabled.
        p = max(1, multi_probe)
        emb_dim = self.db.emb_dim
        with span("serve.plan.pick"):
            d2 = np.asarray(clustering.pairwise_sqdist(
                jnp.asarray(query_embs, jnp.float32),
                jnp.asarray(self.centroids)))
            orders = np.argsort(d2, axis=1)[:, :p]           # (B, P)
        with span("serve.plan.encrypt"):
            # the client object snapshots cfg + hint at THIS epoch; A is
            # the server's, built once per config
            client = pir.PIRClient(self.cfg, self.hint,
                                   a_matrix=self.server.a_matrix)
            # column b·P + j: request b's j-th probe, key fold_in(key, b·P + j)
            qs, secrets = client.query_batch(key, orders.reshape(-1))
            if obs is not None:     # counts only: never an index
                obs.counter("serve.encrypt.programs").inc()
                obs.counter("serve.encrypt.batched_queries").inc(orders.size)
        # dispatch: enqueue the GEMM AND the batched recover — the whole
        # answer→plaintext chain rides the device stream, so `complete`
        # is pure host work (one ready-array fetch + parse + rerank) and
        # never queues behind other in-flight device chains
        with span("serve.plan.dispatch"):
            with span("serve.plan.dispatch.answer"):
                ans = self.server.answer(qs)                 # (m, B·P)
            with span("serve.plan.dispatch.decode"):
                cols = client.recover_batch(ans, secrets)
        if obs is not None:         # counts only: never an index
            chips = self.server.n_shards
            obs.counter("serve.answer.chips").inc(chips)
            obs.counter("serve.answer.kernel_cols").inc(
                ops.kernel_cols(orders.size, self.server.cfg.impl))
            obs.counter("serve.plan.query_bytes").inc(qs.nbytes * chips)

        def complete():
            with span("serve.complete.fetch"):
                cols_np = np.asarray(cols)
            if obs is not None:
                obs.counter("serve.complete.fetch_bytes").inc(cols_np.nbytes)
            # every parse, then every rerank: the two show as spans apart
            with span("serve.complete.parse"):
                docs = [[] for _ in range(n_req)]
                for b in range(n_req):
                    for j in range(p):
                        docs[b].extend(chunking.deserialize_docs(
                            cols_np[:, b * p + j], emb_dim))
            with span("serve.complete.rerank"):
                return [rerank.rerank(np.asarray(query_embs[b], np.float32),
                                      docs[b], top_ks[b])
                        for b in range(n_req)]

        return InflightBatch(_complete=complete, pending=(cols,))

    def _query_batch_via_batchpir_async(self, query_embs: np.ndarray,
                                        top_ks: list[int], multi_probe: int,
                                        key: jax.Array, span
                                        ) -> InflightBatch:
        """Multi-probe serving batch: C clients × B buckets, one GEMM call.

        Per-client placement failures (negligible probability) fall back to
        that client's legacy multi-probe query; everyone else still shares
        the bucketed pass.  Decode state — the per-bucket hints and configs,
        which a later commit patches IN the shared lists — is snapshotted at
        plan time so `complete()` decodes against this batch's epoch.
        ``span`` opens the plan's pick/encrypt/dispatch spans (see
        `query_batch_async`); `complete()` decodes per client and has no
        single fetch to time.
        """
        from repro.batchpir import PlacementError
        bp = self.batch
        emb_dim = self.db.emb_dim
        with span("serve.plan.pick"):
            d2 = np.asarray(clustering.pairwise_sqdist(
                jnp.asarray(query_embs, jnp.float32),
                jnp.asarray(self.centroids)))
            orders = np.argsort(d2, axis=1)[:, :multi_probe]

        per_client, fallback = [], {}
        with span("serve.plan.encrypt"):
            for i in range(len(query_embs)):
                k_i = jax.random.fold_in(key, i)
                try:
                    qs, st = bp.client.query(k_i,
                                             [int(c) for c in orders[i]])
                    per_client.append((qs, st))
                except PlacementError:
                    fallback[i] = self.query(
                        query_embs[i], top_k=top_ks[i],
                        multi_probe=multi_probe, key=k_i, mode="legacy")[0]
                    per_client.append(None)

        live = [i for i, pc in enumerate(per_client) if pc is not None]
        answers: list = []
        with span("serve.plan.dispatch"):
            if live:
                stacked = jnp.stack([per_client[i][0] for i in live], axis=2)
                answers = bp.server.answer_batch(stacked)  # (m_b, C) each
        # plan-time decode snapshot (shallow list copies pin the epoch's
        # hint/config ARRAYS; commits replace list elements, never mutate)
        hints = list(bp.client.hints)
        cfgs = list(bp.client.cfgs)

        def complete():
            out: list[list | None] = [None] * len(query_embs)
            for c_idx, i in enumerate(live):
                ans_i = [a[:, c_idx] for a in answers]
                cols = bp.client.recover(ans_i, per_client[i][1],
                                         hints=hints, cfgs=cfgs)
                docs = []
                for cl in orders[i]:
                    docs.extend(chunking.deserialize_docs(cols[int(cl)],
                                                          emb_dim))
                out[i] = rerank.rerank(np.asarray(query_embs[i], np.float32),
                                       docs, top_ks[i])
            for i, top in fallback.items():
                out[i] = top
            return out

        return InflightBatch(_complete=complete, pending=tuple(answers))
