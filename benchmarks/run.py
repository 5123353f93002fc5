"""Benchmark orchestrator — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast]

Emits ``name,us_per_call,derived`` CSV rows plus PASS/FAIL validation of the
paper's qualitative claims (EXPERIMENTS.md §Paper-validation mirrors this
output), and writes the machine-readable perf trajectory to
``BENCH_pirrag.json`` at the repo root (kernel µs, fig2/fig3 rows, the
batch-PIR amortization section, and the obs instrumentation-overhead
section); CI uploads that JSON as an artifact per commit.

Every section runs inside a fault boundary: a section that raises is
reported (``meta.failed_sections``), the remaining sections still run and
the JSON is still written — but the process exits non-zero, so CI cannot
green-light a half-empty benchmark artifact.  ``meta`` also stamps the
commit hash, seed, device count and wall clock so any two artifacts are
comparable without spelunking the workflow logs.
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

#: Every corpus/workload generator in benchmarks/ derives from this.
BENCH_SEED = 0


def _git_commit() -> str:
    """The current commit hash, or "unknown" outside a git checkout."""
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except OSError:
        return "unknown"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller corpora (CI-sized)")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "experiments", "bench"))
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    t_start = time.perf_counter()
    results: dict = {}
    all_checks: list[str] = []
    failed: list[dict] = []

    def section(name: str, fn):
        """Run one section behind the fault boundary; record its result."""
        try:
            fn()
        except Exception as e:                      # noqa: BLE001 — the
            # boundary exists to keep one broken section from silently
            # wiping every other section's rows out of the artifact
            import traceback
            traceback.print_exc()
            failed.append({"section": name, "error": f"{type(e).__name__}: {e}"})
            print(f"# SECTION FAILED: {name}: {type(e).__name__}: {e}")

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")

    # ---- kernel + protocol micro-benchmarks (paper §3.3 hot loop) ----------
    def sec_kernel():
        from benchmarks import kernel_bench
        kr = kernel_bench.run(sizes=((4096, 512), (16384, 1024))
                              if args.fast else
                              ((4096, 512), (16384, 1024), (65536, 2048)))
        for r in kr:
            print(f"{r['name']},{r['us_per_call']:.1f},"
                  f"tpu_bound={r['tpu_bound']};"
                  f"qps_tpu={r['queries_per_s_tpu']:.0f}")
        pr = kernel_bench.run_protocol(m=16384 if args.fast else 65536)
        for r in pr:
            print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
        results["kernel"] = kr + pr
    section("kernel", sec_kernel)

    # ---- Fig 2: scalability -------------------------------------------------
    def sec_fig2():
        from benchmarks import scalability
        sizes = (500, 1000, 2000) if args.fast else (500, 1000, 2000, 4000)
        rows = scalability.run(sizes=sizes)
        for r in rows:
            print(f"fig2_{r['system']}_n{r['n_docs']},"
                  f"{r['query_s'] * 1e6:.0f},"
                  f"setup_s={r['setup_s']:.2f};up={r['uplink']};"
                  f"down={r['downlink']}")
        checks = scalability.validate(rows)
        results["scalability"] = {"rows": rows, "checks": checks}
        all_checks.extend(checks)
    section("scalability", sec_fig2)

    # ---- Fig 3: quality + RAG-Ready latency ---------------------------------
    def sec_fig3():
        from benchmarks import quality
        # 12 queries even in --fast: 6 is inside the per-query noise band of
        # the Fig-3a near-tie (see quality.py's variance note)
        qrows = quality.run(n_docs=1500 if args.fast else 5000, n_queries=12)
        for r in qrows:
            print(f"fig3_{r['system']},{r['t_retrieval_s'] * 1e6:.0f},"
                  f"ndcg10={r['ndcg10']:.3f};p10={r['p10']:.3f};"
                  f"rag_ready_s={r['t_rag_ready_s']:.3f}")
        checks = quality.validate(qrows)
        results["quality"] = {"rows": qrows, "checks": checks}
        all_checks.extend(checks)
    section("quality", sec_fig3)

    # ---- batch-PIR: κ-probe amortization (beyond-paper) ---------------------
    def sec_batchpir():
        from benchmarks import batchpir_bench
        bres = batchpir_bench.run(fast=args.fast)
        for r in bres["timing"]["rows"]:
            print(f"batchpir_k{r['kappa']},{r['batch_us']:.0f},"
                  f"legacy_us={r['legacy_us']:.0f};"
                  f"batch_vs_batch1={r['batch_vs_batch1']:.2f}")
        results["batchpir"] = bres
        all_checks.extend(bres["checks"])
    section("batchpir", sec_batchpir)

    # ---- keyed embedding-PIR: flat-κ recsys serving + LPT packing -----------
    def sec_recsys():
        from benchmarks import recsys_bench
        rres = recsys_bench.run(fast=args.fast)
        for r in rres["lookup"]["rows"]:
            print(f"recsys_k{r['kappa']},{r['server_us']:.0f},"
                  f"vs_k1={r['vs_kappa1']:.2f};up={r['uplink_bytes']}")
        pk = rres["packing"]
        print(f"recsys_packing,{pk['n_shards']},"
              f"seq={pk['imbalance_seq']:.3f};lpt={pk['imbalance_lpt']:.3f}")
        results["recsys"] = rres
        all_checks.extend(rres["checks"])
    section("recsys", sec_recsys)

    # ---- sharded serving: answer-GEMM scaling over 1→8 devices --------------
    def sec_sharded():
        from benchmarks import sharded_bench
        sres = sharded_bench.run(fast=args.fast)
        for r in sres["answer"]:
            print(f"sharded_answer_d{r['n_devices']},{r['us_per_call']:.1f},"
                  f"db_per_dev={r['db_bytes_per_device']};"
                  f"qps={r['queries_per_s']:.0f}")
        for r in sres["bucketed"]:
            print(f"sharded_bucketed_d{r['n_devices']},"
                  f"{r['us_per_call']:.1f},"
                  f"stored_per_dev={r['stored_bytes_per_device']}")
        results["sharded"] = sres
        all_checks.extend(sres["checks"])
    section("sharded", sec_sharded)

    # ---- sharded offline build: full build over 1→8 devices -----------------
    def sec_build():
        from benchmarks import build_bench
        bld = build_bench.run(fast=args.fast)
        print(f"build_host,{bld['host_s'] * 1e6:.0f},reference")
        for r in bld["rows"]:
            print(f"build_d{r['n_devices']},{r['build_s'] * 1e6:.0f},"
                  f"index_s={r['index_s']:.2f};hint_s={r['hint_s']:.2f};"
                  f"db_per_dev={r['db_bytes_per_device']}")
        results["build"] = bld
        all_checks.extend(bld["checks"])
    section("build", sec_build)

    # ---- pipelined serving engine: overlap win under mutation load ----------
    def sec_serve():
        from benchmarks import serve_bench
        vres = serve_bench.run(fast=args.fast)
        for r in vres["rows"]:
            print(f"serve_{r['engine']}_mut{r['mutate_every']},"
                  f"{1e6 / r['throughput_qps']:.0f},"
                  f"qps={r['throughput_qps']:.1f};p50={r['p50_ms']:.0f}ms;"
                  f"p99={r['p99_ms']:.0f}ms;retries={r['retries']};"
                  f"qdepth={r['queue_depth_peak']}")
        results["serve"] = vres
        all_checks.extend(vres["checks"])
    section("serve", sec_serve)

    # ---- open-loop traffic: SLO attainment, hint chains, admission ----------
    def sec_traffic():
        from benchmarks import traffic_bench
        tres = traffic_bench.run(fast=args.fast)
        for r in tres["rows"]:
            print(f"traffic_load{r['load_factor']},"
                  f"{1e6 / max(r['served_qps'], 1e-9):.0f},"
                  f"attain={r['attainment']:.3f};p50={r['p50_ms']:.0f}ms;"
                  f"served_p99={r['served_p99_ms']:.0f}ms;shed={r['shed']}")
        ch = tres["chain"]
        print(f"traffic_hint_chain,{ch['sync_bytes']},"
              f"frac_of_full={ch['frac_of_full']:.4f};"
              f"chain={ch['chain_patches']};raw={ch['raw_patches']}")
        results["traffic"] = tres
        all_checks.extend(tres["checks"])
    section("traffic", sec_traffic)

    # ---- fault-tolerant fleet: failover SLO, recovery, no-fault identity ----
    def sec_fleet():
        from benchmarks import fleet_bench
        fres = fleet_bench.run(fast=args.fast)
        ident, loss, rec = fres["identity"], fres["loss"], fres["recovery"]
        print(f"fleet_identity,{ident['n_responses']},"
              f"identical={ident['identical']};"
              f"clock={ident['clock_identical']}")
        print(f"fleet_shard_loss,{1e6 / max(loss['served_qps'], 1e-9):.0f},"
              f"attain={loss['attainment']:.3f};"
              f"p99={loss['served_p99_ms']:.0f}ms;"
              f"failovers={loss['failovers']};"
              f"detect={loss['failover_detect_ticks']}t;"
              f"failed={loss['failed']}")
        print(f"fleet_recovery,{rec['wall_s'] * 1e6:.0f},"
              f"epochs={rec['epochs']};eps={rec['epochs_per_s']:.0f}/s;"
              f"bit_identical={rec['bit_identical']}")
        results["fleet"] = fres
        all_checks.extend(fres["checks"])
    section("fleet", sec_fleet)

    # ---- Graph-PIR sketch tuning sweep --------------------------------------
    def sec_graph():
        from benchmarks import graph_bench
        gres = graph_bench.run(fast=args.fast)
        for r in gres["rows"]:
            print(f"graph_sketch{r['sketch_bits']},{r['query_s'] * 1e6:.0f},"
                  f"recall10={r['recall10']:.3f};"
                  f"rec_bytes={r['record_bytes']}")
        results["graph"] = gres
        all_checks.extend(gres["checks"])
    section("graph", sec_graph)

    # ---- closed RAG loop: retrieval-only vs serial vs overlapped generation -
    def sec_rag():
        from benchmarks import rag_bench
        rres = rag_bench.run(fast=args.fast)
        for name, r in rres["rows"].items():
            extra = ""
            if "generate_ms" in r:
                extra = (f";tok={r['tokenize_ms']:.1f}ms"
                         f";pre={r['prefill_ms']:.1f}ms"
                         f";gen={r['generate_ms']:.1f}ms")
            print(f"rag_{name},{1e6 / r['throughput_qps']:.0f},"
                  f"qps={r['throughput_qps']:.1f};p50={r['p50_ms']:.0f}ms;"
                  f"p99={r['p99_ms']:.0f}ms{extra}")
        results["rag"] = rres
        all_checks.extend(rres["checks"])
    section("rag", sec_rag)

    # ---- observability: instrumentation overhead + span coverage ------------
    def sec_obs():
        from benchmarks import obs_bench
        ores = obs_bench.run(fast=args.fast)
        for r in ores["rows"]:
            print(f"{r['name']},{r['wall_on_s'] * 1e6:.0f},"
                  f"overhead={r['overhead_pct']:+.2f}%;"
                  f"coverage={r['coverage']:.3f};spans={r['n_spans']}")
        results["obs"] = ores
        all_checks.extend(ores["checks"])
    section("obs", sec_obs)

    print("\n# paper-claim validation")
    for c in all_checks:
        print("#", c)

    meta = {
        "commit": _git_commit(),
        "seed": BENCH_SEED,
        "n_devices": jax.device_count(),
        "backend": jax.default_backend(),
        "fast": args.fast,
        "utc": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"),
        "wall_s": round(time.perf_counter() - t_start, 2),
        "failed_sections": failed,
    }
    with open(os.path.join(args.out, "bench_results.json"), "w") as f:
        json.dump(dict(meta=meta, **results), f, indent=1, default=float)
    # Machine-readable perf trajectory for CI: one JSON at the repo root,
    # uploaded as a workflow artifact per commit.
    root_json = os.path.join(os.path.dirname(__file__), "..",
                             "BENCH_pirrag.json")
    out = {"meta": meta}
    for src, dst in (("kernel", "kernel"), ("scalability", "fig2"),
                     ("quality", "fig3"), ("batchpir", "batchpir"),
                     ("recsys", "recsys"),
                     ("sharded", "sharded"), ("build", "build"),
                     ("serve", "serve"), ("traffic", "traffic"),
                     ("fleet", "fleet"),
                     ("graph", "graph"), ("rag", "rag"), ("obs", "obs")):
        if src in results:
            out[dst] = results[src]
    with open(root_json, "w") as f:
        json.dump(out, f, indent=1, default=float)
    n_fail = sum(1 for c in all_checks if c.startswith("FAIL"))
    print(f"\n# {len(all_checks) - n_fail} claims PASS, {n_fail} FAIL")
    if failed:
        print(f"# {len(failed)} section(s) RAISED: "
              + ", ".join(f["section"] for f in failed))
        sys.exit(1)


if __name__ == "__main__":
    main()
