#!/usr/bin/env python3
"""Chip benchmark of the private-retrieval serve path: one cell, one run.

    python3 benchmarks/chip/run.py --workload msmarco.steady --seed 7 \
        --seconds 20 --trace 0
    python3 benchmarks/chip/run.py --workload msmarco.steady --seed 7 \
        --seconds 8 --sweep 40,60,80    # offered rates in one process: knee
    python3 benchmarks/chip/run.py --workload msmarco.steady --seed 7 \
        --seconds 8 --control           # the control: must come out not correct

A cell (``BENCHMARK.json`` → ``workloads``) names a configuration
(``configs/<config>.json``: corpus and engine sizes) and a traffic mix
(``traffic/<mix>.json``: arrival rate, top k) and the chips it runs on.
The run generates the corpus from ``--seed`` (``corpus.py``), builds the
index through the program's own entry (``PirRagSystem.build``; a cell on
several chips builds it row-sharded over a ``("chunks",)`` mesh of them),
wraps it in the production engine (``serve.engine.PipelinedServeLoop``),
warms every batch size, then offers open-loop arrivals for ``--seconds``
and times each request from its due time to its reranked passages.  Each
metric is read by its own file, ``metrics/<name>.py``: the end-to-end ones
with ``--trace 0``; the per-layer ones with ``--trace 1``, which also
records a profiler trace of the window (``trace_reduce.py``).  A reader
gets the window's `Run`: requests, batches, the engine's spans and
counters (``Run.obs``), the trace.  After the window the run compares
what the timed path produced with ``reference.py`` (``check.py``) and
prints each compared number beside its limit, on standard error and as
the last key of the result.  The last line of standard output is one
JSON object.

The run needs a TPU: with none, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import check  # noqa: E402
import corpus as corpus_lib  # noqa: E402
import observe  # noqa: E402
import openloop  # noqa: E402
import trace_reduce  # noqa: E402
import work  # noqa: E402

#: JAX's persistent compilation cache, at a fixed path in the checkout
#: (``JAX_COMPILATION_CACHE_DIR`` wins where it is set).
CACHE_DIR = ROOT / ".jax_cache"
#: Profiler traces of ``--trace 1`` runs (ignored by git).
TRACE_DIR = HERE / "out" / "trace"
#: Batches whose answers are kept for the comparison: about this many.
CAPTURED_BATCHES = 8


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# The cell, from BENCHMARK.json and its files
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def check_traffic(traffic: dict) -> None:
    """Refuse a traffic mix that the run would not serve as it says: the
    engine is driven with single-probe queries on a Poisson schedule."""
    if traffic.get("arrival") != "poisson":
        raise SystemExit(f"traffic arrival {traffic.get('arrival')!r}: "
                         "only 'poisson' is generated; no result")
    if traffic.get("multi_probe") != 1:
        raise SystemExit(f"traffic multi_probe {traffic.get('multi_probe')!r}"
                         ": only single-probe queries are served; no result")


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((ROOT / cfg["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    check_traffic(traffic)
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", cells)]
    reporting = {m["name"]: set(m.get("workloads", cells))
                 for m in bench["end_to_end"]}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", reporting[m["moves"]])]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


def read_metric(name: str, run: "Run"):
    """``metrics/<name>.py``'s ``read(run)``: a number, or None."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


# ---------------------------------------------------------------------------
# What the window saw
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """Everything a metric reader may read (host clock: perf_counter)."""
    cell: Cell
    seconds: float
    t0: float                       # window opens
    t1: float                       # window closes
    setup_s: float
    due: np.ndarray                 # per request (absolute)
    lag_s: np.ndarray               # per request: submit − due
    responses: dict                 # rid → final engine Response
    batches: list                   # observe.Batch, dispatched in the window
    build: dict                     # index_s, hint_s (program's clock)
    m: int
    n: int
    peaks: dict
    chips: int                      # chips the DB's rows shard over
    shard_rows: int                 # DB rows one chip holds (m on one chip)
    obs: object = None              # the engine's repro.obs.Obs (counters)
    trace: object = None            # trace_reduce.Reduced (--trace 1)

    @staticmethod
    def pct(values, q: float) -> float:
        """Order statistic at rank ceil(q/100·n) − 1 (the repo's rule)."""
        arr = np.sort(np.asarray(values, np.float64))
        if arr.size == 0:
            return math.nan
        return float(arr[max(0, math.ceil(q / 100.0 * arr.size) - 1)])

    def latencies_ms(self) -> np.ndarray:
        """Due time → reranked passages, per request; +inf when missing."""
        out = np.full(len(self.due), np.inf)
        for rid, r in self.responses.items():
            if not r.failed:
                out[rid] = (r.t_done - self.due[rid]) * 1e3
        return out

    def batch_timings(self) -> list:
        """One engine BatchTiming per batch planned in the window."""
        seen = {}
        for r in self.responses.values():
            if r.timing is not None and r.timing.t_plan >= self.t0:
                seen[id(r.timing)] = r.timing
        return sorted(seen.values(), key=lambda t: t.t_plan)


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------

def require_device(chips: int):
    """The device JAX finds, or exit: a run measures a TPU or nothing."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"no TPU with {chips} chip(s): JAX found {len(devs)} "
            f"{devs[0].platform} device(s); no result")
        raise SystemExit(2)
    return devs[0]


def load_peaks(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def enable_cache():
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


@dataclasses.dataclass
class Built:
    corp: corpus_lib.Corpus
    system: object          # the PirRagSystem the engine serves
    engine: dict

    def loop(self, seed: int):
        from repro.serve.engine import PipelinedServeLoop
        return PipelinedServeLoop(
            self.system, max_batch=self.engine["max_batch"],
            deadline_ms=self.engine["deadline_ms"],
            depth=self.engine["depth"], seed=seed % (2**31 - 1))


def build(cell: Cell, seed: int) -> Built:
    """Corpus from the seed → the program's index, row-sharded over the
    cell's chips when it has more than one."""
    from repro.core import pipeline
    from repro.launch.mesh import make_chunk_mesh
    cfg = cell.config
    mesh = make_chunk_mesh(cell.chips) if cell.chips > 1 else None
    t = time.perf_counter()
    corp = corpus_lib.make_corpus(
        seed, cfg["data_seed"], cfg["n_docs"], emb_dim=cfg["emb_dim"],
        n_topics=cfg["n_clusters"], text_len=tuple(cfg["text_len"]),
        topic_spread=cfg["topic_spread"])
    log(f"corpus: {cfg['n_docs']} passages in {time.perf_counter() - t:.2f}s")
    t = time.perf_counter()
    system = pipeline.PirRagSystem.build(
        corp.texts, corp.embeddings, n_clusters=cfg["n_clusters"],
        seed=cfg["build_seed"], mesh=mesh)
    log(f"build: {time.perf_counter() - t:.2f}s; m={system.db.m} "
        f"n={system.db.n} (index {system.index_seconds:.2f}s, hint "
        f"{system.hint_seconds:.2f}s); DB {system.server.db.nbytes} B over "
        f"{cell.chips} chip(s), pad {system.db.pad_fraction:.4f}")
    return Built(corp, system, cfg["engine"])


def warm_up(built: Built, top_k: int):
    """Serve one batch of every size 1..max_batch through the engine."""
    loop = built.loop(0)
    corp = built.corp
    n = len(corp.texts)
    times = []
    for b in range(1, built.engine["max_batch"] + 1):
        t = time.perf_counter()
        for i in range(b):
            loop.submit(i, corp.embeddings[(b * 31 + i) % n], top_k=top_k)
        loop.drain()
        times.append(round(time.perf_counter() - t, 3))
    log(f"warm-up: seconds per batch size 1..{len(times)}: {times}")
    if any(r.failed for r in loop.responses):
        raise RuntimeError("a warm-up request failed")


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Offered:
    """The window's arrivals, made from the seed before it opens."""
    due: np.ndarray          # query due offsets (s)
    queries: np.ndarray      # (n, d) embeddings


def offered(cell: Cell, corp, seed: int, seconds: float,
            rate: float | None = None) -> Offered:
    tr = cell.traffic
    rate = tr["rate_rps"] if rate is None else rate
    due = openloop.schedule(seed, 0, rate, seconds)
    anchors = np.random.default_rng([seed, 4]).integers(
        0, len(corp.texts), len(due))
    queries = corpus_lib.make_queries(seed, corp, anchors, tr["query_noise"])
    return Offered(due, queries)


def serve_window(loop, off: Offered, top_k: int, t0: float,
                 t_end: float) -> np.ndarray:
    """Drive the engine over the window; returns each query's lag (s)."""
    def submit(i):
        loop.submit(i, off.queries[i], top_k=top_k)

    return openloop.drive(loop.tick, submit, t0 + off.due, t_end)


def finish(loop, n_requests: int) -> dict:
    """Drain the engine; each request's final response."""
    loop.drain()
    return finish_partial(loop, n_requests)


def finish_partial(loop, n_requests: int) -> dict:
    """Each request's final response among those the engine has given."""
    out = {}
    for r in loop.responses:
        if r.rid < n_requests and (r.rid not in out or out[r.rid].failed):
            out[r.rid] = r
    return out


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

class ServedPathError(Exception):
    """The timed path raised: the run is over and not correct."""

    def __init__(self, result: dict):
        super().__init__("served path raised")
        self.result = result


def _device(devs: list, peaks: list | None = None) -> dict:
    """The device record; ``memory_peak_bytes`` is the fullest chip's."""
    import jax
    known = [p for p in peaks or [] if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": max(known) if known else None,
            "memory_peak_bytes_per_chip": peaks}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, replace=None,
             capture_all: bool = False) -> dict:
    """One run of one cell; returns the result object (printed by main).

    ``replace`` puts another answer in the program's place (the control,
    or a planted fault), to show that the comparison fails it.
    """
    import jax
    dev = require_device(cell.chips) if require_tpu else jax.devices()[0]
    devs = jax.devices()[:cell.chips]      # the build's mesh takes these
    peaks = load_peaks(dev.device_kind) if require_tpu else {}
    enable_cache()
    counter = observe.CompileCounter()
    built = build(cell, seed)
    system = built.system
    top_k = cell.traffic["top_k"]
    warm_up(built, top_k)
    off = offered(cell, built.corp, seed, seconds)
    loop = built.loop(seed)
    n_batches = max(1.0, len(off.due) / built.engine["max_batch"])
    tap = observe.Tap(system, np.random.default_rng([seed, 5]),
                      1.0 if capture_all else
                      min(1.0, CAPTURED_BATCHES / n_batches),
                      replace=replace)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    counter.on = True
    t0 = time.perf_counter()
    setup_s = t0 - T_PROCESS
    error = None
    lag = np.full(len(off.due), np.nan)
    try:
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                lag = serve_window(loop, off, top_k, t0, t0 + seconds)
        finally:
            t1 = time.perf_counter()
            counter.on = False
            if trace:
                jax.profiler.stop_trace()
        n_in_window = len(tap.batches)
        responses = finish(loop, len(off.due))
    except Exception as e:               # the program under test failed
        traceback.print_exc()
        error, n_in_window = e, len(tap.batches)
        responses = finish_partial(loop, len(off.due))
    log(f"window: {len(off.due)} requests offered over {seconds}s, "
        f"{n_in_window} batches dispatched, {len(responses)} answered; "
        f"programs lowered in the window: {counter.n}")
    mem_peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devs]
    run = Run(cell=cell, seconds=seconds, t0=t0, t1=t1, setup_s=setup_s,
              due=t0 + off.due, lag_s=lag, responses=responses,
              batches=tap.batches[:n_in_window],
              build={"index_s": system.index_seconds,
                     "hint_s": system.hint_seconds},
              m=system.db.m, n=system.db.n, peaks=peaks, chips=cell.chips,
              shard_rows=system.server.db.shape[0] // cell.chips,
              obs=loop.obs)
    if trace:
        run.trace = trace_reduce.reduce_dir(TRACE_DIR)
        if peaks:
            least, bound = work.least_seconds(
                run.shard_rows, run.n, built.engine["max_batch"],
                peaks["int8_ops_per_s"], peaks["hbm_bytes_per_s"])
            log(f"answer roofline at b={built.engine['max_batch']} over "
                f"{run.shard_rows} rows a chip: {bound}-bound, least "
                f"{least * 1e3:.3f} ms")
    checks = check.compare(cell, run, tap, system, built.corp, off.queries,
                           seed, on_tpu=dev.platform == "tpu" and error is None)
    if error is not None:
        checks["served_path_errors"] = (1, 0)
        raise ServedPathError({
            "correct": False, "attempted": len(off.due),
            "failed": checks["requests_unanswered"][0], "metrics": {},
            "device": _device(devs, mem_peaks),
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in checks.items()}})
    metrics = {}
    for spec in (cell.per_layer if trace else cell.end_to_end):
        v = read_metric(spec["name"], run)
        if v is not None:
            metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    device = _device(devs, mem_peaks)
    result = {
        "correct": all(v <= lim for v, lim in checks.values()),
        "attempted": len(off.due),
        "failed": checks["requests_unanswered"][0],
        "metrics": metrics,
        "device": device,
    }
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
        log(f"idle seconds by host stage: {run.trace.gap_s_by_stage()}")
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def sweep(cell: Cell, seed: int, seconds: float, rates: list[float]) -> dict:
    """Offer each rate for ``seconds`` in one process; find the knee.

    The knee is the highest rate at which the requests served in the
    window keep up with those offered (≥ 97%) and the queue left at the
    close is under two batches.
    """
    dev = require_device(cell.chips)
    enable_cache()
    built = build(cell, seed)
    top_k = cell.traffic["top_k"]
    warm_up(built, top_k)
    points = []
    for i, rate in enumerate(rates):
        off = offered(cell, built.corp, seed + i, seconds, rate)
        loop = built.loop(seed + i)
        t0 = time.perf_counter()
        lag = serve_window(loop, off, top_k, t0, t0 + seconds)
        t1 = time.perf_counter()
        queued = loop.batcher.depth + sum(len(b[0]) for b in loop._inflight)
        done = sum(1 for r in loop.responses if r.t_done <= t1)
        responses = finish(loop, len(off.due))
        lat = [(responses[k].t_done - t0 - off.due[k]) * 1e3
               if k in responses else math.inf for k in range(len(off.due))]
        pt = {"rate": rate, "offered": len(off.due),
              "served_in_window": done, "served_rps": done / seconds,
              "queued_at_close": queued, "p50_ms": Run.pct(lat, 50),
              "p95_ms": Run.pct(lat, 95),
              "batch_mean": float(np.mean([r.batch_size
                                           for r in responses.values()])),
              "lag_p95_ms": Run.pct(lag[~np.isnan(lag)] * 1e3, 95)}
        log(json.dumps(pt))
        points.append(pt)
    ok = [p["rate"] for p in points
          if p["served_in_window"] >= 0.97 * p["offered"]
          and p["queued_at_close"] < 2 * built.engine["max_batch"]]
    return {"workload": cell.name, "device": dev.device_kind,
            "knee_rps": max(ok) if ok else None, "points": points}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated offered rates (requests/s): find "
                         "the knee instead of measuring the cell")
    ap.add_argument("--control", action="store_true",
                    help="put the control (three of four limbs) in the "
                         "answer's place; the run must come out not correct")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    if args.sweep:
        print(json.dumps(sweep(cell, args.seed, args.seconds,
                               [float(r) for r in args.sweep.split(",")])))
        return 0
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          replace=observe.control() if args.control else None)
    except ServedPathError as e:
        result = e.result
    for k, c in result["checks"].items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
