"""Sharded-PIR serving: cross-mode equivalence under the 8-device harness.

Correctness for the sharded subsystem means "bit-identical decode across
1-device and N-device layouts": the legacy single-device answer path, the
batch-PIR bucketed path, and the shard_map'd row-sharded path must all
recover the same plaintext bytes and rank the same top-k documents on the
same corpus, key stream, and mutation sequence.  Every case here runs in a
subprocess with 8 fake CPU devices (tests/_mesh_harness.py).

The whole file carries the `slow` marker: tier-1 (`pytest -x -q`) skips it
via addopts, and CI runs it in a dedicated job step.
"""
import pytest

from _hypothesis_compat import given, settings, st
from _mesh_harness import run_sub

pytestmark = pytest.mark.slow


def test_cross_mode_plaintexts_and_topk_bit_identical():
    """Legacy 1-device, sharded 8-device, and batch mode agree bit-for-bit
    on recovered plaintext bytes and top-k rankings — same corpus, same key
    stream, same mutation sequence (the ISSUE 3 acceptance property)."""
    run_sub("""
from repro.core import pipeline, pir
from repro.data import corpus as corpus_lib
from repro.update import LiveIndex

corp = corpus_lib.make_corpus(0, 300, emb_dim=24, n_topics=8)
mesh = make_mesh((8,), ("chunks",))
live1 = LiveIndex.build(corp.texts, corp.embeddings, n_clusters=8,
                        impl="xla", kmeans_iters=8)
live8 = LiveIndex.build(corp.texts, corp.embeddings, n_clusters=8,
                        impl="xla", kmeans_iters=8, mesh=mesh)
sys1, sys8 = live1.system, live8.system
sys1.enable_batch(kappa=4, seed=11)
sys8.enable_batch(kappa=4, seed=11)
assert sys8.server.mesh is not None and sys8.batch.server.mesh is not None

# prime the sharded bucket stack BEFORE mutating, so the commit exercises
# the in-place stack patch (not a lazy rebuild on the next answer)
warm = sys8.query(corp.embeddings[0], top_k=3, multi_probe=2,
                  key=jax.random.PRNGKey(1), mode="batch")
assert sys8.batch.server._stack is not None

# identical mutation sequence on both layouts
rng = np.random.default_rng(5)
for live in (live1, live8):
    live.replace(9, b"rewritten nine", corp.embeddings[9])
    live.delete(23)
    live.insert(10_000, b"brand new doc", corp.embeddings[50] + 0.01)
    live.replace(111, b"rewritten one-one-one", corp.embeddings[111])
    live.commit()
assert live1.epoch == live8.epoch == 1

# 1) hints identical after the patched commit
np.testing.assert_array_equal(np.asarray(sys1.hint), np.asarray(sys8.hint))

# 2) raw protocol: same key -> bit-identical answers and plaintext columns
client = pir.PIRClient(sys1.cfg, sys1.hint)
for trial, cl in enumerate([0, 3, 7]):
    qu, state = client.query(jax.random.PRNGKey(100 + trial), cl)
    a1 = np.asarray(sys1.server.answer(qu))
    a8 = np.asarray(sys8.server.answer(qu))
    np.testing.assert_array_equal(a1, a8)
    col1 = np.asarray(client.recover(jnp.asarray(a1), state))
    col8 = np.asarray(client.recover(jnp.asarray(a8), state))
    np.testing.assert_array_equal(col1, col8)
    np.testing.assert_array_equal(col1, sys1.db.matrix[:, cl])  # true bytes

# 3) batch mode: same key -> same per-cluster plaintext payloads as legacy
bp1, bp8 = sys1.batch, sys8.batch
key = jax.random.PRNGKey(77)
clusters = [1, 4, 6]
qs1, st1 = bp1.client.query(key, clusters)
qs8, st8 = bp8.client.query(key, clusters)
np.testing.assert_array_equal(np.asarray(qs1), np.asarray(qs8))
ans1 = [np.asarray(a) for a in bp1.server.answer_batch(qs1)]
ans8 = [np.asarray(a) for a in bp8.server.answer_batch(qs8)]
for a, b in zip(ans1, ans8):
    np.testing.assert_array_equal(a, b)
cols1 = bp1.client.recover([jnp.asarray(a) for a in ans1], st1)
cols8 = bp8.client.recover([jnp.asarray(a) for a in ans8], st8)
for cl in clusters:
    used = int(sys1.db.used_bytes[cl])
    np.testing.assert_array_equal(cols1[cl], cols8[cl])
    np.testing.assert_array_equal(cols1[cl][:used],
                                  sys1.db.matrix[:used, cl])  # = legacy bytes

# 4) end-to-end top-k rankings identical across all three modes
for trial in range(4):
    q = corp.embeddings[trial * 41] + 0.01
    key = jax.random.PRNGKey(500 + trial)
    top_legacy = sys1.query(q, top_k=6, multi_probe=3, key=key,
                            mode="legacy")[0]
    top_shard = sys8.query(q, top_k=6, multi_probe=3, key=key,
                           mode="legacy")[0]
    top_batch1 = sys1.query(q, top_k=6, multi_probe=3, key=key,
                            mode="batch")[0]
    top_batch8 = sys8.query(q, top_k=6, multi_probe=3, key=key,
                            mode="batch")[0]
    ids = [[d for d, _, _ in t]
           for t in (top_legacy, top_shard, top_batch1, top_batch8)]
    assert ids[0] == ids[1] == ids[2] == ids[3], ids
    texts = [[t for _, _, t in t_] for t_ in (top_legacy, top_shard,
                                              top_batch1, top_batch8)]
    assert texts[0] == texts[1] == texts[2] == texts[3]
    scores = [np.asarray([s for _, s, _ in t_]) for t_ in
              (top_legacy, top_shard, top_batch1, top_batch8)]
    np.testing.assert_array_equal(scores[0], scores[1])
    np.testing.assert_array_equal(scores[0], scores[2])
    np.testing.assert_array_equal(scores[0], scores[3])
print("OK cross-mode bit-identical")
""")


def test_sharded_answer_and_bucket_paths_have_no_collectives():
    """The compiled HLO of both sharded server GEMMs contains zero
    collective ops — the `pir_rules` zero-collective claim, executed."""
    run_sub("""
from repro.distributed import collectives
mesh = make_mesh((8,), ("chunks",))
fn = collectives.row_shard_gemm(mesh, ("chunks",), impl="xla",
                                q_switch=1 << 16)
db = jax.device_put(jnp.zeros((512, 128), jnp.uint8),
                    NamedSharding(mesh, P(("chunks",), None)))
q = jax.device_put(jnp.zeros((128, 4), jnp.uint32),
                   NamedSharding(mesh, P()))
got = np.asarray(fn(db, q))
assert got.shape == (512, 4) and not got.any()
hlo = fn.lower(db, q).compile().as_text()
for coll in ["all-reduce", "all-gather", "all-to-all",
             "collective-permute", "reduce-scatter"]:
    assert coll not in hlo, coll

fnb = collectives.bucket_shard_gemm(mesh, ("chunks",))
spec = NamedSharding(mesh, P(("chunks",), None, None))
st = jax.device_put(jnp.zeros((16, 256, 32), jnp.uint8), spec)
qb = jax.device_put(jnp.zeros((16, 32, 3), jnp.uint32), spec)
hlo = fnb.lower(st, qb).compile().as_text()
for coll in ["all-reduce", "all-gather", "all-to-all",
             "collective-permute", "reduce-scatter"]:
    assert coll not in hlo, coll
print("OK zero-collective")
""")


def test_serve_loop_sharded_deadline_batching_and_stale_retry():
    """PIRServeLoop on a sharded system: max_batch cutting, stale-epoch
    rejection + retry across a live mutation commit, correct final results
    — all through the 8-device zero-collective answer path."""
    run_sub("""
from repro.data import corpus as corpus_lib
from repro.launch.serve import PIRServeLoop
from repro.update import LiveIndex, journal as journal_lib

corp = corpus_lib.make_corpus(1, 200, emb_dim=16, n_topics=6)
mesh = make_mesh((8,), ("chunks",))
live = LiveIndex.build(corp.texts, corp.embeddings, n_clusters=6,
                       impl="xla", kmeans_iters=6, mesh=mesh)
assert live.system.server.mesh is not None
loop = PIRServeLoop(live, max_batch=4, deadline_ms=1e9)

# deadline batching: 4 requests cut as ONE sharded GEMM batch
for rid in range(4):
    loop.submit(rid, corp.embeddings[rid * 11])
assert loop.tick() == 4
assert all(r.batch_size == 4 for r in loop.responses)

# stale-epoch admission: queued requests straddle a mutation commit
for rid in range(10, 13):                  # formed against epoch 0
    loop.submit(rid, corp.embeddings[rid])
loop.submit_mutation(journal_lib.replace(9, b"live-updated nine",
                                         corp.embeddings[9]))
loop.drain()
assert live.epoch == 1
assert loop.stale_retries == 3, loop.stale_retries
assert len(loop.responses) == 7
assert all(r.epoch == 1 and r.retries == 1 for r in loop.responses[-3:])

# fresh query sees the mutated content through the sharded path
loop.submit(50, corp.embeddings[9])
loop.drain()
assert [t for d, _, t in loop.responses[-1].top
        if d == 9] == [b"live-updated nine"]
# exact private retrieval: each earlier response's anchor doc is in top-k
for r in loop.responses[:4]:
    assert r.rid * 11 in [d for d, _, _ in r.top]
print("OK sharded serve loop")
""")


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10),
       n_muts=st.integers(min_value=1, max_value=6))
def test_sharded_mutation_patch_equals_fresh_sharded_setup(seed, n_muts):
    """Property: any mutation batch patched on the SHARDED server leaves
    hint state (flat + per-bucket) bit-identical to a from-scratch sharded
    setup on the mutated database, and queries decode identically."""
    run_sub(f"""
from repro import batchpir
from repro.core import pir
from repro.data import corpus as corpus_lib
from repro.update import LiveIndex

SEED, N_MUTS = {seed}, {n_muts}
corp = corpus_lib.make_corpus(SEED, 160, emb_dim=16, n_topics=5)
mesh = make_mesh((8,), ("chunks",))
live = LiveIndex.build(corp.texts, corp.embeddings, n_clusters=5,
                       impl="xla", kmeans_iters=5, mesh=mesh)
live.system.enable_batch(kappa=4, seed=11)

rng = np.random.default_rng(1000 + SEED)
alive = list(range(160))
for i in range(N_MUTS):
    kind = rng.integers(0, 3)
    if kind == 0 and len(alive) > 1:
        did = int(alive.pop(rng.integers(len(alive))))
        live.delete(did)
    elif kind == 1:
        did = int(alive[rng.integers(len(alive))])
        live.replace(did, f"mutated {{did}} rev{{i}}".encode(),
                     corp.embeddings[did] + rng.normal(0, 0.01, 16))
    else:
        live.insert(10_000 + i, f"inserted {{i}}".encode(),
                    corp.embeddings[rng.integers(160)] + 0.02)
live.commit()
sys8 = live.system

# flat hint: patched == from-scratch sharded setup on the mutated matrix
fresh_srv = pir.PIRServer(sys8.cfg, jnp.asarray(sys8.db.matrix), mesh=mesh)
np.testing.assert_array_equal(np.asarray(sys8.hint),
                              np.asarray(fresh_srv.setup()))

# bucket hints: patched == recomputed-from-scratch on the mutated sub-DBs
# (bucket row budgets only ever grow on the delta path, so the comparison
# is against setup() on the server's own state — the exactness invariant
# the single-device suite pins too)
bp = sys8.batch
for h_patched, h_fresh in zip(bp.server.hints, bp.server.setup()):
    np.testing.assert_array_equal(np.asarray(h_patched),
                                  np.asarray(h_fresh))
# and the sharded bucketed answer matches a freshly-built sharded system's
# recovered payloads for the same key (heights may differ; payloads can't)
fresh_bp = batchpir.build(sys8.db.matrix, sys8.db.used_bytes,
                          sys8.cfg.params, kappa=bp.kappa,
                          n_buckets=bp.partition.n_buckets, seed=bp.seed,
                          a_seed=sys8.cfg.a_seed, impl=sys8.cfg.impl,
                          mesh=mesh)
key_b = jax.random.PRNGKey(21)
probe = [0, 3]
qs_p, st_p = bp.client.query(key_b, probe)
qs_f, st_f = fresh_bp.client.query(key_b, probe)
cols_p = bp.client.recover(bp.server.answer_batch(qs_p), st_p)
cols_f = fresh_bp.client.recover(fresh_bp.server.answer_batch(qs_f), st_f)
for cl in probe:
    used = int(sys8.db.used_bytes[cl])
    np.testing.assert_array_equal(cols_p[cl][:used], cols_f[cl][:used])
    np.testing.assert_array_equal(cols_p[cl][:used],
                                  sys8.db.matrix[:used, cl])

# decode equality: same key on patched vs fresh sharded server
client = pir.PIRClient(sys8.cfg, sys8.hint)
qu, state = client.query(jax.random.PRNGKey(7), 2)
col_patched = np.asarray(client.recover(sys8.server.answer(qu), state))
col_fresh = np.asarray(client.recover(fresh_srv.answer(qu), state))
np.testing.assert_array_equal(col_patched, col_fresh)
np.testing.assert_array_equal(col_patched, sys8.db.matrix[:, 2])
print("OK property", SEED, N_MUTS)
""")


def test_row_sharded_update_columns_bitwise_vs_single_device():
    """PIRServer.update_columns on random data: the sharded delta, the
    post-update DB, and subsequent answers all match 1-device bitwise, with
    a row count that does NOT divide the shard count (padding path)."""
    run_sub("""
from repro.core import pir

rng = np.random.default_rng(0)
m, n = 516, 96            # m % 8 != 0 -> exercises row padding
db = rng.integers(0, 256, (m, n), dtype=np.uint8)
cfg = pir.make_config(m, n, impl="xla")
mesh = make_mesh((8,), ("chunks",))
s1 = pir.PIRServer(cfg, jnp.asarray(db))
s8 = pir.PIRServer(cfg, jnp.asarray(db), mesh=mesh)
np.testing.assert_array_equal(np.asarray(s1.setup()),
                              np.asarray(s8.setup()))

cols = np.array([3, 17, 40])
new = rng.integers(0, 256, (m, 3), dtype=np.uint8)
d1 = np.asarray(s1.update_columns(jnp.asarray(cols), jnp.asarray(new)))
d8 = np.asarray(s8.update_columns(jnp.asarray(cols), jnp.asarray(new)))
np.testing.assert_array_equal(d1, d8)
np.testing.assert_array_equal(np.asarray(s1.db), np.asarray(s8.db)[:m])
assert not np.asarray(s8.db)[m:].any()       # padding rows stay zero

q = jnp.asarray(rng.integers(0, 2**32, (n, 5), dtype=np.uint32))
np.testing.assert_array_equal(np.asarray(s1.answer(q)),
                              np.asarray(s8.answer(q)))
print("OK sharded update bitwise")
""")
