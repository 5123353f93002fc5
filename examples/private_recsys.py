"""PIR-RAG × RecSys: private embedding serving for MIND.

    PYTHONPATH=src python examples/private_recsys.py

A recommendation request is KEYED: the client holds sparse feature ids
(its click history + a candidate item) and needs the matching embedding
rows — it does not need similarity search.  `PirRagSystem.build_keyed`
indexes the stacked item table for exactly this access pattern: row ids
map to fixed groups, a 3-way cuckoo placement turns the whole id multiset
into ONE batch of per-bucket PIR queries, and the server answers every
row in a single bucketed pass.  The provider sees only uint32 ciphertext
noise — never which items the user clicked or is being scored on.

The recovered rows are bit-identical to ``params["emb"]["table"][ids]``,
so scattering them into an otherwise-zero table
(`models.embedding.table_from_rows`) lets the UNMODIFIED `recsys.serve`
produce bitwise the same scores as the public-table run — checked below
by comparing the raw float bit patterns.
"""
import sys

sys.path.insert(0, "src")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import pipeline  # noqa: E402
from repro.models import embedding, recsys  # noqa: E402
from repro.configs.mind import SMOKE  # noqa: E402


def main():
    cfg = SMOKE
    rng = np.random.default_rng(0)
    params = recsys.init(jax.random.PRNGKey(0), cfg)

    # The provider's catalogue = the stacked item embedding table.  The
    # keyed index is built ONCE offline; per-request cost is independent
    # of how many ids the request touches.
    table = np.asarray(params["emb"]["table"], np.float32)
    system = pipeline.PirRagSystem.build_keyed(table, kappa=16, seed=0)

    # A user's private request: click history + one candidate to score.
    hist = rng.integers(0, cfg.vocab_per_field, (1, cfg.hist_len))
    mask = np.ones((1, cfg.hist_len), bool)
    target = rng.integers(0, cfg.vocab_per_field, (1,))
    batch = {"hist": jnp.asarray(hist), "hist_mask": jnp.asarray(mask),
             "target": jnp.asarray(target)}

    # Every embedding row the request touches, fetched in one keyed batch.
    ids = np.concatenate([hist.ravel(), target]).astype(np.int64)
    rows, stats = system.lookup(ids, key=jax.random.PRNGKey(1))
    assert np.array_equal(rows, table[ids]), "PIR rows must be bit-exact"

    # Private params: fetched rows scattered into a zero table; the model
    # code runs unmodified on them.
    priv = {"emb": embedding.table_from_rows(len(table), cfg.embed_dim,
                                             ids, rows),
            "bilinear": params["bilinear"]}
    score_priv = np.asarray(recsys.serve(priv, batch, cfg))
    score_pub = np.asarray(recsys.serve(params, batch, cfg))
    bitwise = np.array_equal(score_priv.view(np.uint32),
                             score_pub.view(np.uint32))
    assert bitwise, (score_priv, score_pub)

    print("private MIND scoring (provider sees only uint32 noise):")
    print(f"  score[private table] = {float(score_priv[0]):+.6f}")
    print(f"  score[public  table] = {float(score_pub[0]):+.6f}   "
          f"bitwise_equal={bitwise}")
    print(f"\nkeyed lookup: kappa={stats.kappa} ids in {stats.groups} "
          f"groups via {stats.n_buckets} bucket queries ({stats.mode})")
    print(f"uplink {stats.uplink_bytes} B (id-independent), downlink "
          f"{stats.downlink_bytes / 1024:.1f} KiB, server "
          f"{stats.server_ms:.1f} ms")


if __name__ == "__main__":
    main()
