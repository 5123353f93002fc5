"""Sharded offline build over 1 → 8 devices of one host.

Measures the full offline phase — mesh-parallel K-means (kmeans++ +
blocked Lloyd), balanced/plain assignment, per-shard column packing, and
the sharded hint GEMM — with `PirRagSystem.build(mesh=...)` over submeshes
of 1, 2, 4 and 8 devices, against the mesh=None host build as the
reference.

As with `sharded_bench`, on a CPU host the devices are fake and share one
physical CPU, so the sweep's point is not wall-clock speedup: it validates
that (a) the build's device-resident state (DB rows, hint rows) falls as
1/shards — the memory-capacity axis that lets a production-sized build run
where the single-device build cannot even materialize its DB — and (b)
total build wall-clock stays flat rather than regressing, i.e. the
collectives added
per Lloyd iteration (one tiled all-gather of block partials) and the
per-shard packing/placement add no hidden cost.  Every width is checked
**bit-identical** to the single-device build in-loop: centroids,
assignment, packed columns, hint, and an end-to-end top-k.

XLA pins the host device count at first init, so on a CPU host the sweep
runs in a child interpreter (same pattern as tests/_mesh_harness.py); on an
accelerator host it runs in-process over the chips present.  `run(fast=...)`
is what `benchmarks/run.py` calls to fill the `build` section of
BENCH_pirrag.json.

    PYTHONPATH=src python -m benchmarks.build_bench [--fast]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys


def sweep(*, n_docs: int, n_clusters: int, emb_dim: int, iters: int,
          devices) -> dict:
    """Host build, then the sharded build over submeshes of ``devices``."""
    import time

    import jax
    import numpy as np

    from repro.core import pipeline
    from repro.data import corpus as corpus_lib
    from repro.launch.mesh import make_chunk_mesh

    corp = corpus_lib.make_corpus(0, n_docs, emb_dim=emb_dim,
                                  n_topics=n_clusters)
    kw = dict(n_clusters=n_clusters, kmeans_iters=iters, seed=0,
              balance_factor=1.3)

    t0 = time.perf_counter()
    ref = pipeline.PirRagSystem.build(corp.texts, corp.embeddings, **kw)
    host_s = time.perf_counter() - t0
    probe = corp.embeddings[7]
    top_ref, _ = ref.query(probe, top_k=5, key=jax.random.PRNGKey(11))

    rows, checks = [], []
    for n_dev in [w for w in (1, 2, 4, 8) if w <= len(devices)]:
        mesh = make_chunk_mesh(n_dev, devices[:n_dev])
        t0 = time.perf_counter()
        sys_s = pipeline.PirRagSystem.build(corp.texts, corp.embeddings,
                                            mesh=mesh, **kw)
        dt = time.perf_counter() - t0
        identical = (
            np.array_equal(ref.centroids, sys_s.centroids)
            and np.array_equal(ref.assignment, sys_s.assignment)
            and np.array_equal(ref.db.matrix, sys_s.db.matrix)
            and np.array_equal(np.asarray(ref.hint), np.asarray(sys_s.hint))
            and top_ref == sys_s.query(probe, top_k=5,
                                       key=jax.random.PRNGKey(11))[0])
        rows.append(dict(
            n_devices=n_dev,
            build_s=dt,
            index_s=sys_s.index_seconds,        # clustering + packing
            hint_s=sys_s.hint_seconds,          # sharded hint GEMM
            db_bytes_per_device=sys_s.server.db.nbytes // n_dev,
            hint_bytes_per_device=sys_s.cfg.hint_bytes // n_dev,
            bit_identical=identical,
        ))

    checks.append(("PASS" if all(r["bit_identical"] for r in rows) else
                   "FAIL")
                  + ": sharded build bit-identical to single-device build at "
                  + "every mesh width (centroids/assignment/columns/hint/"
                  + "top-k)")
    checks.append(("PASS" if all(
        r["db_bytes_per_device"] * r["n_devices"]
        == rows[0]["db_bytes_per_device"] for r in rows) else "FAIL")
        + ": per-device DB bytes scale exactly 1/shards")
    worst = max(r["build_s"] for r in rows) / host_s
    checks.append(("PASS" if worst < 3.0 else "FAIL")
                  + ": sharded build stays within 3x of the host build "
                  + "(worst %.2fx)" % worst)
    return dict(rows=rows, host_s=host_s, checks=checks,
                backend=jax.default_backend(),
                shape=dict(n_docs=n_docs, n_clusters=n_clusters,
                           emb_dim=emb_dim, kmeans_iters=iters))


_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, jax
from benchmarks.build_bench import sweep
print(json.dumps(sweep(devices=jax.devices(), **{params!r})))
"""


def run(*, fast: bool = False) -> dict:
    """The sweep on this host's devices; returns the parsed section.

    On an accelerator it runs in this process over the chips present (a
    child could not reach a chip this process holds); on a CPU host it runs
    in a child interpreter with 8 fake devices.
    """
    import jax

    params = (dict(n_docs=1500, n_clusters=24, emb_dim=32, iters=10) if fast
              else dict(n_docs=6000, n_clusters=64, emb_dim=64, iters=20))
    if jax.default_backend() != "cpu":
        return sweep(devices=jax.devices(), **params)
    root = os.path.join(os.path.dirname(__file__), "..")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD.format(params=params)],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": os.pathsep.join(
                 [os.path.join(root, "src"), root]),
             "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
             "HOME": os.environ.get("HOME", "/root"),
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
    print(f"build_host,{res['host_s'] * 1e6:.0f},reference")
    for r in res["rows"]:
        print(f"build_d{r['n_devices']},{r['build_s'] * 1e6:.0f},"
              f"index_s={r['index_s']:.2f};hint_s={r['hint_s']:.2f};"
              f"db_per_dev={r['db_bytes_per_device']};"
              f"bit_identical={r['bit_identical']}")
    for c in res["checks"]:
        print("#", c)


if __name__ == "__main__":
    main()
