"""Live-index demo: private retrieval over a corpus that never stops moving.

Walks the full lifecycle:

  1. build a PIR-RAG system and wrap it in a LiveIndex
  2. a client bootstraps a HintCache (one full hint download)
  3. stream insert / replace / delete batches; each commit publishes a
     versioned epoch with a sparse HintPatch
  4. the client syncs its cache from the patch log (KB, not MB) and
     privately retrieves the *updated* content
  5. a burst of deletes degrades pad_fraction and forces a full rebuild —
     the one case where the client re-downloads the hint

    PYTHONPATH=src python examples/live_index.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.data import corpus as corpus_lib
from repro.update import HintCache, LiveIndex


def kb(b):
    return f"{b / 1024:.1f} KB"


def main():
    corp = corpus_lib.make_corpus(0, 600, emb_dim=32, n_topics=12)
    live = LiveIndex.build(corp.texts, corp.embeddings, n_clusters=12,
                           max_pad_fraction=0.8)
    cache = HintCache(live.system.hint, live.system.cfg)
    print(f"built: {live.n_docs} docs, n={live.system.db.n} clusters, "
          f"m={live.system.db.m}; hint download {kb(cache.bytes_downloaded)}")

    # -- streaming mutations -------------------------------------------------
    live.insert(9001, b"breaking: newly published document", corp.embeddings[3])
    live.replace(42, b"doc 42, revised edition", corp.embeddings[42])
    live.delete(17)
    patch = live.commit()
    print(f"\nepoch {live.epoch}: 3 mutations -> {len(patch.cols)} clusters "
          f"touched, patch {kb(patch.wire_bytes)} "
          f"(vs {kb(live.system.cfg.hint_bytes)} full hint)")

    synced = cache.sync(live.epochs)
    print(f"client synced epoch {cache.epoch} for {kb(synced)}")

    top, stats = live.query(corp.embeddings[3], epoch=cache.epoch, top_k=3,
                            key=jax.random.PRNGKey(0))
    print(f"private query near the insert -> ids {[d for d, _, _ in top]}")
    assert any(d == 9001 for d, _, _ in top)
    top, _ = live.query(corp.embeddings[42], epoch=cache.epoch, top_k=3,
                        key=jax.random.PRNGKey(1))
    print("revised doc 42 text:",
          [t for d, _, t in top if d == 42][0].decode())

    # -- a stale client is rejected, syncs, retries -------------------------
    live.replace(100, b"doc 100 v2", corp.embeddings[100])
    live.commit()
    from repro.update import StaleEpochError
    try:
        live.query(corp.embeddings[100], epoch=cache.epoch)
    except StaleEpochError as e:
        print(f"\nstale client rejected ({e}); syncing "
              f"{kb(cache.sync(live.epochs))} and retrying")
    top, _ = live.query(corp.embeddings[100], epoch=cache.epoch, top_k=1,
                        key=jax.random.PRNGKey(2))
    print("retry ->", top[0][2].decode())

    # -- deletes until the planner forces a rebuild -------------------------
    for doc in range(0, 480):
        if doc in live._docs:
            live.delete(doc)
    patch = live.commit()
    st = live.commits[-1]
    print(f"\nepoch {live.epoch}: mass delete -> full rebuild "
          f"(reason: {st.reason}), patch {kb(patch.wire_bytes)}, "
          f"m {live.system.db.m}")
    cache.sync(live.epochs)
    print(f"client re-synced; lifetime downlink {kb(cache.bytes_downloaded)}")
    top, _ = live.query(corp.embeddings[500], epoch=cache.epoch, top_k=1,
                        key=jax.random.PRNGKey(3))
    print("post-rebuild query ->", top[0][0])


if __name__ == "__main__":
    main()
