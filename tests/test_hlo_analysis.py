"""HLO structural analysis: trip-count-aware FLOPs and collective bytes."""
import subprocess
import sys


def run_sub(body: str):
    prelude = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch import hlo_analysis as ha
"""
    proc = subprocess.run(
        [sys.executable, "-c", prelude + body], capture_output=True,
        text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
             "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    return proc.stdout


def test_scan_flops_are_trip_scaled():
    """cost_analysis counts the while body once; ours multiplies by trips."""
    run_sub("""
w = jax.ShapeDtypeStruct((16, 64, 64), jnp.float32)
x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
def scanned(w, x):
    def body(c, wi): return c @ wi, None
    return jax.lax.scan(body, x, w)[0]
cc = jax.jit(scanned).lower(w, x).compile()
stats = ha.analyze(cc.as_text())
want = 16 * 2 * 8 * 64 * 64            # 16 iterations of (8,64)@(64,64)
got = stats.total_flops
assert abs(got - want) / want < 0.01, (got, want)
ca = cc.cost_analysis()
if isinstance(ca, (list, tuple)):      # jax < 0.5 wraps it in a list
    ca = ca[0] if ca else {}
xla = ca.get("flops", 0)
assert xla < want / 2                   # demonstrates the undercount
print("OK", got, xla)
""")


def test_collective_bytes_allreduce():
    run_sub("""
mesh = jax.make_mesh((8,), ("d",))
x = jax.ShapeDtypeStruct((64, 128), jnp.float32, sharding=NamedSharding(mesh, P("d", None)))
f = jax.jit(lambda x: jax.lax.with_sharding_constraint(jnp.sum(x, axis=0, keepdims=True) * 2.0, NamedSharding(mesh, P(None, None))) , )
cc = f.lower(x).compile()
stats = ha.analyze(cc.as_text())
assert stats.total_collective_bytes > 0
assert "all-reduce" in stats.collective_bytes or "all-gather" in stats.collective_bytes, stats.collective_bytes
print("OK", stats.collective_bytes)
""")


def test_matmul_tp_collectives_and_flops():
    """Megatron-style 2-way TP matmul: per-device flops = half; all-reduce
    wire bytes match 2·S·(g-1)/g."""
    run_sub("""
mesh = jax.make_mesh((1, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
B, D, F = 32, 128, 256
x = jax.ShapeDtypeStruct((B, D), jnp.float32, sharding=NamedSharding(mesh, P(None, None)))
w1 = jax.ShapeDtypeStruct((D, F), jnp.float32, sharding=NamedSharding(mesh, P(None, "model")))
w2 = jax.ShapeDtypeStruct((F, D), jnp.float32, sharding=NamedSharding(mesh, P("model", None)))
def f(x, w1, w2):
    h = jax.nn.relu(x @ w1)
    y = h @ w2
    return jax.lax.with_sharding_constraint(y, NamedSharding(mesh, P(None, None)))
cc = jax.jit(f).lower(x, w1, w2).compile()
stats = ha.analyze(cc.as_text())
want_flops = (2*B*D*F + 2*B*F*D) / 2          # per device
assert abs(stats.total_flops - want_flops) / want_flops < 0.05, (stats.total_flops, want_flops)
ar = stats.collective_bytes.get("all-reduce", 0)
want_ar = 2 * (B * D * 4) * (2 - 1) / 2       # ring all-reduce of y
assert abs(ar - want_ar) / want_ar < 0.05, (ar, want_ar)
print("OK", stats.total_flops, ar)
""")
