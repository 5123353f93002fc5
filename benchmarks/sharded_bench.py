"""Sharded answer-GEMM scaling over 1 → 8 devices of one host.

Measures the online hot path  ans = D·Q (mod 2^32)  with the packed DB
row-sharded over submeshes of 1, 2, 4 and 8 devices (queries
replicated, zero collectives — `distributed.collectives.row_shard_gemm`),
plus the bucketed batch-PIR pass spread over the same submeshes.

On a CPU host the devices are fake and share one physical CPU, so
wall-clock SPEEDUP is not the point (XLA already multithreads the
single-device GEMM); what the sweep validates and records is that (a)
per-device DB bytes fall as 1/shards — the memory-capacity axis that lets
the 8.6 GB production DB fit HBM — while (b) total wall-clock stays flat
rather than regressing, i.e. the sharded path adds no hidden wire or
resharding cost on top of the kernel.
Results are bitwise-checked against the 1-device answer in-loop.

XLA pins the host device count at first init, so on a CPU host the sweep
runs in a child interpreter (same pattern as tests/_mesh_harness.py); on an
accelerator host it runs in-process over the chips present.  `run(fast=...)`
is what `benchmarks/run.py` calls to fill the `sharded` section of
BENCH_pirrag.json.

    PYTHONPATH=src python -m benchmarks.sharded_bench [--fast]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys


def sweep(*, m: int, n: int, batch: int, iters: int, devices) -> dict:
    """The answer and bucketed sweeps over submeshes of ``devices``."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import batchpir
    from repro.core import pir
    from repro.launch.mesh import make_chunk_mesh

    widths = [w for w in (1, 2, 4, 8) if w <= len(devices)]
    rng = np.random.default_rng(0)
    db_host = rng.integers(0, 256, (m, n), dtype=np.uint8)
    q_host = rng.integers(0, 2**32, (n, batch), dtype=np.uint32)
    cfg = pir.make_config(m, n)

    rows = []
    ref = None
    for n_dev in widths:
        mesh = make_chunk_mesh(n_dev, devices[:n_dev])
        server = pir.PIRServer(cfg, jnp.asarray(db_host), mesh=mesh)
        q = jnp.asarray(q_host)
        ans = jax.block_until_ready(server.answer(q))   # warm up + compile
        got = np.asarray(ans)
        if ref is None:
            ref = got
        else:
            np.testing.assert_array_equal(got, ref)     # bitwise across meshes
        t0 = time.perf_counter()
        for _ in range(iters):
            ans = server.answer(q)
        jax.block_until_ready(ans)
        dt = (time.perf_counter() - t0) / iters
        rows.append(dict(
            n_devices=n_dev,
            us_per_call=dt * 1e6,
            db_bytes_per_device=m * n // n_dev,
            hint_bytes_per_device=cfg.hint_bytes // n_dev,
            queries_per_s=batch / dt,
        ))

    # bucketed batch-PIR pass over the same submeshes
    used = np.full(n, m, np.int64)
    brows = []
    bref = None
    for n_dev in widths:
        mesh = make_chunk_mesh(n_dev, devices[:n_dev])
        bp = batchpir.build(db_host, used, cfg.params, kappa=4, seed=3,
                            mesh=mesh)
        qs, _ = bp.client.query(jax.random.PRNGKey(0), [0, 1, 2])
        got = [np.asarray(jax.block_until_ready(a))
               for a in bp.server.answer_batch(qs)]
        if bref is None:
            bref = got
        else:
            for a, b in zip(got, bref):
                np.testing.assert_array_equal(a, b)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = bp.server.answer_batch(qs)
        jax.block_until_ready(out[-1])
        dt = (time.perf_counter() - t0) / iters
        brows.append(dict(n_devices=n_dev, us_per_call=dt * 1e6,
                          n_buckets=bp.partition.n_buckets,
                          stored_bytes_per_device=bp.server.stored_bytes
                          // n_dev))

    base = rows[0]["us_per_call"]
    ratio = max(r["us_per_call"] for r in rows) / base
    checks = [("PASS" if ratio < 3.0 else "FAIL")
              + ": sharded answer stays within 3x of 1-device wall-clock "
              + "(worst %.2fx)" % ratio]
    widest = rows[-1]
    checks.append(("PASS" if widest["db_bytes_per_device"]
                   * widest["n_devices"] == m * n else "FAIL")
                  + ": per-device DB bytes scale exactly 1/shards")
    return dict(answer=rows, bucketed=brows, checks=checks,
                backend=jax.default_backend(),
                shape=dict(m=m, n=n, batch=batch))


_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, jax
from benchmarks.sharded_bench import sweep
print(json.dumps(sweep(devices=jax.devices(), **{params!r})))
"""


def run(*, fast: bool = False) -> dict:
    """The sweep on this host's devices; returns the parsed section.

    On an accelerator it runs in this process over the chips present (a
    child could not reach a chip this process holds); on a CPU host it runs
    in a child interpreter with 8 fake devices.
    """
    import jax

    params = (dict(m=16384, n=512, batch=32, iters=5) if fast
              else dict(m=65536, n=1024, batch=64, iters=10))
    if jax.default_backend() != "cpu":
        return sweep(devices=jax.devices(), **params)
    root = os.path.join(os.path.dirname(__file__), "..")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD.format(params=params)],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": os.pathsep.join(
                 [os.path.join(root, "src"), root]),
             "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
             "HOME": os.path.expanduser("~"),
             "JAX_PLATFORMS": "cpu"})
    if proc.returncode != 0:
        raise RuntimeError(proc.stdout + "\n" + proc.stderr)
    return json.loads(proc.stdout.splitlines()[-1])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    res = run(fast=args.fast)
    print("name,us_per_call,derived")
    for r in res["answer"]:
        print(f"sharded_answer_d{r['n_devices']},{r['us_per_call']:.1f},"
              f"db_per_dev={r['db_bytes_per_device']};"
              f"qps={r['queries_per_s']:.0f}")
    for r in res["bucketed"]:
        print(f"sharded_bucketed_d{r['n_devices']},{r['us_per_call']:.1f},"
              f"stored_per_dev={r['stored_bytes_per_device']}")
    for c in res["checks"]:
        print("#", c)


if __name__ == "__main__":
    main()
