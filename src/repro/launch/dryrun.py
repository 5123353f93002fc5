import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import: XLA pins the device
# count at first init.  Only the dry-run gets 512 placeholder devices —
# tests/benches see 1 (this env var is set nowhere else).
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell we build the FULL config's step (state/inputs as
ShapeDtypeStructs — nothing is allocated), install the arch's sharding rule
table on the production mesh, `.lower().compile()`, and record:

  * memory_analysis()            — per-device bytes: proves fit
  * cost_analysis()              — XLA's raw counters (while bodies ×1)
  * hlo_analysis.analyze()       — trip-scaled dot FLOPs, HBM-traffic floor,
                                   per-kind collective wire bytes
  * wall times, HLO size, collective op counts

Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json and feed
launch/roofline.py and EXPERIMENTS.md §Dry-run/§Roofline.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --arch kimi-k2-1t-a32b \
        --shape train_4k --mesh pod
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro.configs import base as cfgbase
from repro.distributed import sharding as sh
from repro.launch import hlo_analysis, steps as steps_lib
from repro.launch.mesh import make_mesh, make_production_mesh

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             out_dir: str, *, rules_override: dict | None = None,
             tag: str = "") -> dict:
    mesh_name = "multipod" if multi_pod else "pod"
    arch = cfgbase.get(arch_name)
    rec = {
        "arch": arch_name, "shape": shape_name, "mesh": mesh_name,
        "mesh_shape": [2, 16, 16] if multi_pod else [16, 16],
        "n_devices": 512 if multi_pod else 256,
        "family": arch.family, "ok": False, "tag": tag,
    }
    t_start = time.perf_counter()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        if not multi_pod:
            # single-pod mesh uses 256 of the 512 host devices
            mesh = make_mesh((16, 16), ("data", "model"),
                             devices=jax.devices()[:256])
        bundle = steps_lib.make_bundle(arch, shape_name, smoke=False)
        rules = dict(bundle.rules_for(multi_pod))
        if rules_override:
            rules.update(rules_override)
        state_sh = sh.shardings_from_axes(mesh, bundle.state_axes, rules)
        batch_sh = sh.shardings_from_axes(
            mesh, bundle.batch_axes, rules)
        specs = steps_lib.input_specs_for(arch, shape_name, smoke=False)

        def wrapped(state, batch):
            with sh.use_rules(mesh, rules):
                return bundle.fn(state, batch)

        jitted = jax.jit(wrapped, in_shardings=(state_sh, batch_sh),
                         donate_argnums=(0,) if bundle.donate_state else ())
        t0 = time.perf_counter()
        lowered = jitted.lower(bundle.state_spec, specs)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()

        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "peak_per_device_bytes": int(
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes),
        }
        ca = compiled.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):      # jax < 0.5 wraps it in a list
            ca = ca[0] if ca else {}
        rec["xla_cost"] = {k: float(v) for k, v in ca.items()
                           if isinstance(v, (int, float))
                           and k in ("flops", "bytes accessed",
                                     "transcendentals")}
        txt = compiled.as_text()
        rec["hlo_bytes"] = len(txt)
        stats = hlo_analysis.analyze(txt)
        rec["hlo"] = {
            "dot_flops_per_device": stats.dot_flops,
            "dot_traffic_bytes_per_device": stats.dot_traffic_bytes,
            "collective_bytes_per_device": stats.collective_bytes,
            "collective_counts": stats.collective_counts,
            "n_whiles": stats.n_whiles,
            "max_trip": stats.max_trip,
        }
        rec["lower_s"] = t1 - t0
        rec["compile_s"] = t2 - t1
        rec["ok"] = True
    except Exception as e:  # record failures, keep sweeping
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = time.perf_counter() - t_start

    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    path = os.path.join(
        out_dir, f"{arch_name}__{shape_name}__{mesh_name}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    status = "OK " if rec["ok"] else "FAIL"
    peak = rec.get("memory", {}).get("peak_per_device_bytes", 0) / 2**30
    print(f"[dryrun] {status} {arch_name}:{shape_name}:{mesh_name}{suffix} "
          f"peak={peak:.2f}GiB compile={rec.get('compile_s', 0):.1f}s",
          flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(OUT_DIR))
    args = ap.parse_args()

    cells: list[tuple[str, str]] = []
    if args.all:
        for name, arch in sorted(cfgbase.all_archs().items()):
            for shape in arch.shapes:
                cells.append((name, shape))
    else:
        assert args.arch, "--arch or --all"
        arch = cfgbase.get(args.arch)
        shapes = [args.shape] if args.shape else list(arch.shapes)
        cells = [(args.arch, s) for s in shapes]

    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]
    n_fail = 0
    for arch_name, shape_name in cells:
        for multi_pod in meshes:
            rec = run_cell(arch_name, shape_name, multi_pod, args.out)
            n_fail += 0 if rec["ok"] else 1
    print(f"[dryrun] done, {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
