"""Quickstart: a fully private RAG retrieval in ~40 lines.

    PYTHONPATH=src python examples/quickstart.py

Builds a synthetic corpus, runs the PIR-RAG offline phase (cluster → chunk →
hint), then answers one query where the server never learns the query
embedding, the cluster, or the documents returned.
"""
import sys

sys.path.insert(0, "src")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import pipeline  # noqa: E402
from repro.data import corpus as corpus_lib  # noqa: E402


def main():
    print("== PIR-RAG quickstart ==")
    corp = corpus_lib.make_corpus(0, n_docs=1200, emb_dim=64, n_topics=16)
    system = pipeline.PirRagSystem.build(
        corp.texts, corp.embeddings, n_clusters=16,
        balance_factor=1.3,          # beyond-paper: caps the downlink
    )
    print(f"offline setup: {system.setup_seconds:.2f}s | "
          f"db {system.db.m}×{system.db.n} u8 "
          f"({system.db.m * system.db.n / 2**20:.1f} MiB) | "
          f"padding waste {system.db.pad_fraction:.1%}")
    print(f"one-time hint download: {system.cfg.hint_bytes / 2**20:.1f} MiB")

    # the "user" asks something near document 37's topic
    query = corp.embeddings[37] + 0.05 * np.random.default_rng(1).standard_normal(64)
    top, stats = system.query(query.astype(np.float32), top_k=5,
                              key=jax.random.PRNGKey(42))

    print(f"\nuplink {stats.uplink_bytes} B  |  downlink "
          f"{stats.downlink_bytes / 2**20:.2f} MiB  |  server "
          f"{stats.server_ms:.1f} ms  |  client {stats.client_ms:.1f} ms")
    print("server's view: one pseudorandom uint32 vector — nothing else.\n")
    for doc_id, score, text in top:
        print(f"  doc {doc_id:5d}  cos={score:.3f}  {text[:48]!r}")
    assert any(d == 37 for d, _, _ in top), "expected the anchor doc in top-5"
    print("\nOK: anchor document retrieved privately.")


if __name__ == "__main__":
    main()
