"""Seeded synthetic passage corpora and query streams, made in bulk.

The generator of the repository's ``data/corpus.make_corpus`` (one Gaussian
topic per cluster, unit-norm embeddings, text that starts with
``doc:<i> topic:<t>`` and is padded with lowercase filler), written with
array draws instead of a per-document loop so that 150k passages take about
a second.  The benchmark owns this copy: what it generates is part of the
yardstick, so a change to the program's generator cannot move it.

Every seed gets the same sizes.  The topic geometry and the text lengths
come from the configuration's fixed ``data_seed``; the run's seed flips the
sign of each embedding coordinate and draws every filler byte.  A sign flip
changes no distance, not even by rounding, so the build clusters every
seed's corpus alike and the database has the same height: the seed changes
what is stored and asked, not how much work a request is.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Corpus:
    texts: list[bytes]
    embeddings: np.ndarray        # (N, d) float32, unit norm


def _unit(x: np.ndarray) -> np.ndarray:
    return x / (np.linalg.norm(x, axis=-1, keepdims=True) + np.float32(1e-12))


def make_corpus(seed: int, data_seed: int, n_docs: int, *, emb_dim: int,
                n_topics: int, text_len: tuple[int, int],
                topic_spread: float) -> Corpus:
    """``n_docs`` passages over ``n_topics`` topics (see the module doc)."""
    base = np.random.default_rng([data_seed, 0])
    centers = _unit(base.standard_normal((n_topics, emb_dim), np.float32))
    topics = base.integers(0, n_topics, n_docs)
    emb = base.standard_normal((n_docs, emb_dim), np.float32)
    emb *= np.float32(topic_spread / np.sqrt(emb_dim))
    emb += centers[topics]
    emb = _unit(emb).astype(np.float32)
    lens = base.integers(text_len[0], text_len[1], n_docs)
    rng = np.random.default_rng([seed, 0])
    emb *= np.where(rng.random(emb_dim) < 0.5, np.float32(-1), np.float32(1))
    filler = rng.integers(97, 123, int(lens.sum()), np.uint8).tobytes()
    starts = np.cumsum(lens) - lens
    texts = []
    for i, (t, ln, s) in enumerate(zip(topics.tolist(), lens.tolist(),
                                       starts.tolist())):
        head = b"doc:%d topic:%d " % (i, t)
        texts.append((head + filler[s:s + max(0, ln - len(head))])[:ln])
    return Corpus(texts=texts, embeddings=emb)


def make_queries(seed: int, corpus: Corpus, anchors: np.ndarray,
                 noise: float) -> np.ndarray:
    """One query per anchor passage: its embedding plus noise, unit norm."""
    rng = np.random.default_rng([seed, 1])
    d = corpus.embeddings.shape[1]
    q = corpus.embeddings[anchors] + np.float32(noise / np.sqrt(d)) * \
        rng.standard_normal((len(anchors), d), np.float32)
    return _unit(q).astype(np.float32)
