"""Multi-device correctness on 8 fake CPU devices (subprocess-isolated).

The subprocess pattern lives in tests/_mesh_harness.py (XLA pins the device
count at first jax init, so every case runs in a child interpreter with
XLA_FLAGS=--xla_force_host_platform_device_count=8); the sharded-PIR
serving subsystem's equivalence suite (tests/test_sharded_pir.py) shares it.
"""
from _mesh_harness import run_sub


def test_sharded_embedding_lookup_matches_take():
    run_sub("""
from repro.distributed.collectives import sharded_embedding_lookup
mesh = jax.make_mesh((8,), ("model",))
table = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
idx = jax.random.randint(jax.random.PRNGKey(1), (32,), 0, 64)
table_sh = jax.device_put(table, NamedSharding(mesh, P("model", None)))
fn = jax.jit(sharded_embedding_lookup(mesh, "model"))
got = fn(table_sh, idx)
want = jnp.take(table, idx, axis=0)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
print("OK")
""")


def test_split_s_decode_attention_matches_reference():
    run_sub("""
from repro.distributed.collectives import split_s_decode_attention
mesh = jax.make_mesh((8,), ("seq",))
B, T, H, hd = 2, 64, 4, 8
kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(kq, (B, H, hd))
k = jax.random.normal(kk, (B, T, H, hd))
v = jax.random.normal(kv, (B, T, H, hd))
lengths = jnp.array([50, 64], jnp.int32)
scale = 1.0 / np.sqrt(hd)
k_sh = jax.device_put(k, NamedSharding(mesh, P(None, "seq")))
v_sh = jax.device_put(v, NamedSharding(mesh, P(None, "seq")))
fn = jax.jit(split_s_decode_attention(mesh, "seq", scale=scale))
got = fn(q, k_sh, v_sh, lengths)
# reference: plain masked softmax attention
s = jnp.einsum("bhd,bthd->bht", q, k) * scale
mask = jnp.arange(T)[None, None, :] < lengths[:, None, None]
s = jnp.where(mask, s, -1e30)
p = jax.nn.softmax(s, axis=-1)
want = jnp.einsum("bht,bthd->bhd", p, v)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
print("OK")
""")


def test_ring_psum_equals_allreduce():
    run_sub("""
from repro.distributed.collectives import ring_psum
mesh = jax.make_mesh((8,), ("data",))
x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
x_sh = jax.device_put(x, NamedSharding(mesh, P("data", None)))
# each device contributes its (1,4) row; ring sum = column-sum broadcast
fn = jax.jit(jax.shard_map(lambda b: b, mesh=mesh, in_specs=P("data", None), out_specs=P("data", None)))
got = ring_psum(mesh, "data")(x_sh)
np.testing.assert_allclose(np.asarray(got)[0], np.asarray(x).sum(0) / 1.0, rtol=1e-6)
print("OK")
""")


def test_dp_train_step_identical_to_single_device():
    """Data-parallel pjit train step == single-device step on same batch."""
    run_sub("""
from repro.configs import base as cfgbase
from repro.launch import steps as steps_lib
from repro.distributed import sharding as sh

arch = cfgbase.get("qwen3-4b")
bundle = steps_lib.make_bundle(arch, "train_4k", smoke=True)
batch = steps_lib.materialize_inputs(arch, "train_4k", jax.random.PRNGKey(0))
state = bundle.init_state(jax.random.PRNGKey(1))

# single-device reference
ref_state, ref_out = jax.jit(bundle.fn)(
    jax.tree.map(lambda x: x, state), jax.tree.map(lambda x: x, batch))

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = bundle.rules_for(False)
state_sh = sh.shardings_from_axes(mesh, bundle.state_axes, rules)
batch_sh = {k: NamedSharding(mesh, P(*[rules.get(a) for a in ax]))
            for k, ax in bundle.batch_axes.items()}
def wrapped(state, batch):
    with sh.use_rules(mesh, rules):
        return bundle.fn(state, batch)
fn = jax.jit(wrapped, in_shardings=(state_sh, batch_sh))
got_state, got_out = fn(jax.device_put(state, state_sh),
                        {k: jax.device_put(v, batch_sh[k]) for k, v in batch.items()})
np.testing.assert_allclose(float(got_out["loss"]), float(ref_out["loss"]), rtol=2e-2)
# updated params must match too (optimizer step determinism across shardings)
pa = jax.tree.leaves(ref_state["params"]); pb = jax.tree.leaves(got_state["params"])
worst = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))) for a, b in zip(pa, pb))
assert worst < 5e-2, worst
print("OK", worst)
""")


def test_pir_row_sharded_answer_bitwise_equal():
    """Row-sharded PIR answer == single-device answer, bit for bit, and the
    compiled HLO contains NO collective ops on the hot path."""
    run_sub("""
from repro.kernels import ref as kref
mesh = jax.make_mesh((8,), ("model",))
rng = np.random.default_rng(0)
db = jnp.asarray(rng.integers(0, 256, (512, 128), dtype=np.uint8))
q = jnp.asarray(rng.integers(0, 2**32, (128, 4), dtype=np.uint32))
db_sh = jax.device_put(db, NamedSharding(mesh, P("model", None)))
q_rep = jax.device_put(q, NamedSharding(mesh, P()))
fn = jax.jit(kref.modmatmul_ref,
             in_shardings=(NamedSharding(mesh, P("model", None)), NamedSharding(mesh, P())),
             out_shardings=NamedSharding(mesh, P("model", None)))
got = fn(db_sh, q_rep)
np.testing.assert_array_equal(np.asarray(got), np.asarray(kref.modmatmul_ref(db, q)))
hlo = fn.lower(db_sh, q_rep).compile().as_text()
for coll in ["all-reduce", "all-gather", "all-to-all", "collective-permute", "reduce-scatter"]:
    assert coll not in hlo, coll
print("OK zero-collective")
""")


def test_checkpoint_reshard_8_to_4_devices():
    run_sub("""
import tempfile
from repro.checkpoint import store
mesh8 = jax.make_mesh((8,), ("data",))
x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
state = {"w": jax.device_put(x, NamedSharding(mesh8, P("data", None))),
         "step": jnp.asarray(3)}
with tempfile.TemporaryDirectory() as d:
    store.save(d, state, step=3)
    mesh4 = jax.make_mesh((4,), ("data",))
    shardings = {"w": NamedSharding(mesh4, P(None, "data")), "step": NamedSharding(mesh4, P())}
    restored = store.restore(d, shardings=shardings)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(x))
    assert int(restored["step"]) == 3
print("OK")
""")
