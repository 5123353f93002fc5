"""Sharded offline build == single-device build, bit for bit.

The contract (ISSUE 5 tentpole): `PirRagSystem.build(mesh=...)` — mesh-
parallel K-means, sharded assignment sweeps, per-shard column packing and
in-place DB placement — produces exactly the artifacts of the mesh=None
build: centroids, assignment, packed columns, used-bytes accounting, hint,
and end-to-end top-k.  Property-tested as a seeded sweep over corpus
shapes/seeds inside one multi-device child interpreter (the fake-device
harness; see tests/_mesh_harness.py for why a subprocess is required).

All cases are slow-marked: CI runs them in the dedicated 8-fake-device step
alongside tests/test_sharded_pir.py.
"""
import pytest

from _mesh_harness import run_sub

pytestmark = pytest.mark.slow


def test_build_bit_identical_across_mesh_widths():
    out = run_sub('''
from repro.core import pipeline
from repro.data import corpus as corpus_lib

# property sweep: (seed, n_docs, n_clusters, emb_dim, balance_factor)
CASES = [
    (0, 480, 12, 32, None),
    (1, 600, 16, 16, None),
    (2, 512, 8, 32, 1.3),     # balanced assignment path
    (3, 450, 12, 16, 1.2),
]
for seed, n_docs, k, d, bf in CASES:
    corp = corpus_lib.make_corpus(seed, n_docs, emb_dim=d, n_topics=k)
    kw = dict(n_clusters=k, kmeans_iters=8, impl="xla", seed=seed,
              balance_factor=bf)
    ref = pipeline.PirRagSystem.build(corp.texts, corp.embeddings, **kw)
    probe = corp.embeddings[seed + 5]
    top_ref, _ = ref.query(probe, top_k=4, key=jax.random.PRNGKey(seed))
    for n_dev in (2, 8):
        mesh = make_mesh((n_dev,), ("chunks",),
                             devices=jax.devices()[:n_dev])
        got = pipeline.PirRagSystem.build(corp.texts, corp.embeddings,
                                          mesh=mesh, **kw)
        assert np.array_equal(ref.centroids, got.centroids), (seed, n_dev)
        assert np.array_equal(ref.assignment, got.assignment), (seed, n_dev)
        assert np.array_equal(ref.db.matrix, got.db.matrix), (seed, n_dev)
        assert np.array_equal(ref.db.used_bytes, got.db.used_bytes)
        assert np.array_equal(np.asarray(ref.hint), np.asarray(got.hint))
        assert ref.cfg.a_seed == got.cfg.a_seed
        # in-place construction: the sharded DB rows live one slice per
        # device, assembled without a single-device materialize
        assert len(got.server.db.sharding.device_set) == n_dev
        top_got, _ = got.query(probe, top_k=4, key=jax.random.PRNGKey(seed))
        assert top_ref == top_got, (seed, n_dev)
print("CASES_OK", len(CASES))
''')
    assert "CASES_OK 4" in out


def test_sharded_kmeans_and_sweeps_bit_identical():
    out = run_sub('''
from repro.core import clustering

rng = np.random.default_rng(0)
for seed, n, d, k in [(0, 1203, 32, 13), (1, 777, 16, 9)]:
    x = rng.standard_normal((n, d)).astype(np.float32)
    key = jax.random.PRNGKey(seed)
    ref = clustering.kmeans_fit(key, jnp.asarray(x), k=k, iters=7,
                                n_blocks=8)
    cents = np.asarray(ref.centroids)
    for n_dev in (2, 4, 8):
        mesh = make_mesh((n_dev,), ("chunks",),
                             devices=jax.devices()[:n_dev])
        got = clustering.kmeans_fit_sharded(key, x, k=k, iters=7,
                                            mesh=mesh, n_blocks=8)
        assert np.array_equal(cents, np.asarray(got.centroids))
        assert np.array_equal(np.asarray(ref.assignment),
                              np.asarray(got.assignment))
        assert np.array_equal(np.asarray(ref.inertia),
                              np.asarray(got.inertia))
        d2_ref = np.asarray(clustering.blocked_sqdist(x, cents, n_blocks=8))
        d2_got = np.asarray(clustering.blocked_sqdist(x, cents, n_blocks=8,
                                                      mesh=mesh))
        assert np.array_equal(d2_ref, d2_got)
        a_ref = np.asarray(clustering.assign_to_centroids(
            jnp.asarray(x), jnp.asarray(cents)))
        a_got = np.asarray(clustering.assign_to_centroids(x, cents,
                                                          mesh=mesh))
        assert np.array_equal(a_ref, a_got)
print("KMEANS_OK")
''')
    assert "KMEANS_OK" in out


def test_live_index_full_rebuild_stays_sharded():
    out = run_sub('''
from repro.data import corpus as corpus_lib
from repro.update.live import LiveIndex

corp = corpus_lib.make_corpus(1, 400, emb_dim=32, n_topics=8)

def mutate_and_rebuild(mesh):
    li = LiveIndex.build(corp.texts, corp.embeddings, n_clusters=8,
                         impl="xla", seed=2, mesh=mesh)
    li.replace(5, b"edited doc five", corp.embeddings[5])
    li.insert(400, b"fresh doc", corp.embeddings[7] + 0.01)
    li.commit()                                   # sparse delta epoch
    li.insert(401, b"x" * (li.system.db.m + 100), corp.embeddings[3])
    li.commit()                                   # overflow -> full rebuild
    assert li.commits[-1].full_rebuild
    return li

mesh = make_mesh((8,), ("chunks",))
ref = mutate_and_rebuild(None)
got = mutate_and_rebuild(mesh)
# the rebuilt epoch went through the SAME sharded build, not a host-side
# fallback that would materialize-then-reshard
assert got.system.mesh is mesh
assert got.system.server.n_shards == 8
assert len(got.system.server.db.sharding.device_set) == 8
assert np.array_equal(ref.system.db.matrix, got.system.db.matrix)
assert np.array_equal(np.asarray(ref.system.hint),
                      np.asarray(got.system.hint))
assert ref.system.cfg.a_seed == got.system.cfg.a_seed
q = corp.embeddings[10]
ta, _ = ref.query(q, epoch=ref.epoch, top_k=4, key=jax.random.PRNGKey(9))
tb, _ = got.query(q, epoch=got.epoch, top_k=4, key=jax.random.PRNGKey(9))
assert ta == tb
print("REBUILD_OK")
''')
    assert "REBUILD_OK" in out
