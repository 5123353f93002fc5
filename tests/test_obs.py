"""Privacy-safe observability (ISSUE 7): scrub gate, registry, tracer.

Three layers under test:

  * `scrub` — the typed allowlist is the privacy boundary: arrays, bytes
    and free-form strings must raise at RECORD time, in tests and
    production alike.
  * `MetricsRegistry` — counters/gauges/histograms merge associatively
    (property: any fold shape yields the identical fleet view), and the
    histogram percentile shares the slo fold's rank rule (inf propagation
    included).
  * `Tracer` — FakeClock-driven span trees are deterministic (stable ids,
    byte-identical exports) and correctly nested across the pipelined
    engine's in-flight depth; a full serve-loop export contains zero
    query-derived payload bytes (the audit greps the serialized JSON).
  * profiler mirroring — the engine's spans, read back from a CPU profiler
    trace, carry one batch id per batch across its plan and retire spans;
    idle ticks leave nothing there; a profiler session changes no response.
"""
import copy
import glob
import json

import numpy as np
import pytest

from test_serve_engine import (FakeClock, N_DOCS, _drive_scripted,
                               _get_base, _script_from_rng)

from repro.obs import (Histogram, MetricsRegistry, Obs, PrivacyViolation,
                       Span, Tracer, percentile, scrub, span_coverage,
                       validate_chrome_trace)
from repro.serve import PipelinedServeLoop
from repro.traffic.slo import _pct


# -- scrub: the privacy boundary ---------------------------------------------

def test_scrub_allows_numbers_and_registered_enums():
    assert scrub(True) is True
    assert scrub(np.bool_(False)) is False
    assert scrub(7) == 7 and type(scrub(np.int64(7))) is int
    assert scrub(1.5) == 1.5 and type(scrub(np.float32(1.5))) is float
    assert scrub(float("inf")) == float("inf")
    assert scrub("pipelined") == "pipelined"
    assert scrub("shed") == "shed"


@pytest.mark.parametrize("bad", [
    np.zeros(8),                      # a query embedding
    np.zeros((4, 4), np.uint32),      # an LWE ciphertext block
    b"decoded plaintext",
    bytearray(b"x"),
    "SELECT secret",                  # free-form string: not in the vocab
    None,
    [1, 2, 3],
    {"k": 1},
    complex(1, 2),
])
def test_scrub_rejects_payload_types(bad):
    with pytest.raises(PrivacyViolation):
        scrub(bad, where="test.attr")


def test_span_attrs_pass_through_scrub():
    tr = Tracer(clock=FakeClock())
    with pytest.raises(PrivacyViolation):
        tr.span("t", query=np.zeros(4))
    with tr.span("t", n=3, engine="sync"):
        pass
    assert tr.spans[-1].attrs == {"n": 3, "engine": "sync"}
    reg = MetricsRegistry()
    with pytest.raises(PrivacyViolation):
        reg.counter("c").inc(np.zeros(2))
    with pytest.raises(PrivacyViolation):
        reg.histogram("h").record(b"bytes")


# -- registry: merge algebra + the shared rank rule --------------------------

def _random_registry(seed: int) -> MetricsRegistry:
    rng = np.random.default_rng(seed)
    reg = MetricsRegistry()
    for _ in range(40):
        roll = rng.integers(0, 3)
        if roll == 0:
            reg.counter(f"c{rng.integers(0, 4)}").inc(int(rng.integers(1, 9)))
        elif roll == 1:
            reg.gauge(f"g{rng.integers(0, 3)}").set(float(rng.normal()))
        else:
            h = reg.histogram(f"h{rng.integers(0, 3)}")
            v = float(rng.exponential(20.0))
            h.record(float("inf") if rng.integers(0, 10) == 0 else v)
    return reg


@pytest.mark.parametrize("seeds", [(1, 2, 3), (10, 11, 12), (5, 5, 9)])
def test_registry_merge_is_associative(seeds):
    a, b, c = (_random_registry(s) for s in seeds)
    left = a.merge(b).merge(c)
    right = a.merge(b.merge(c))
    assert json.dumps(left.to_dict(), sort_keys=True) == \
        json.dumps(right.to_dict(), sort_keys=True)
    # operands untouched (merge is pure)
    assert json.dumps(a.to_dict()) == \
        json.dumps(_random_registry(seeds[0]).to_dict())


def test_registry_merge_identity_and_disjoint():
    a, empty = _random_registry(4), MetricsRegistry()
    assert a.merge(empty).to_dict() == a.to_dict()
    b = MetricsRegistry()
    b.counter("only_b").inc(2)
    merged = a.merge(b).to_dict()
    assert merged["only_b"] == 2
    assert merged["c0"] == a.to_dict()["c0"]


def test_percentile_shared_rank_rule_matches_slo():
    """slo._pct and obs.percentile are literally the same rank rule."""
    for vals in ([1.0, 2.0, 3.0], [5.0] * 98 + [float("inf")] * 2,
                 [float("inf")], [], [7.5]):
        arr = np.asarray(vals, np.float64)
        for q in (50, 90, 99):
            assert _pct(arr, q) == percentile(vals, q)
    assert percentile([5.0] * 98 + [float("inf")] * 2, 99) == float("inf")
    assert percentile([5.0] * 98 + [float("inf")] * 2, 50) == 5.0


def test_histogram_percentile_consistent_with_exact():
    """Bucketed percentile lands in the same bucket as the exact one."""
    rng = np.random.default_rng(0)
    vals = list(rng.exponential(30.0, size=500)) + [float("inf")] * 6
    h = Histogram("lat")
    for v in vals:
        h.record(v)
    for q in (50, 90, 99):
        exact = percentile(vals, q)
        bucketed = h.percentile(q)
        if np.isinf(exact):
            assert np.isinf(bucketed)
        else:
            # the bucket's upper edge is >= the exact order statistic and
            # no more than one bucket above it
            assert bucketed >= exact
            below = [b for b in h.bounds if b < bucketed]
            assert not below or below[-1] <= exact
    assert h.percentile(100) == float("inf")
    assert h.n == 506 and h.n_inf == 6


def test_histogram_merge_requires_same_bounds():
    a = Histogram("x", bounds=(1.0, 2.0))
    b = Histogram("x", bounds=(1.0, 3.0))
    with pytest.raises(AssertionError):
        a.merge_from(b)
    with pytest.raises(AssertionError):
        Histogram("nan").record(float("nan"))


def test_registry_rejects_type_confusion():
    reg = MetricsRegistry()
    reg.counter("m")
    with pytest.raises(AssertionError):
        reg.gauge("m")


# -- tracer: deterministic trees, nesting, export ----------------------------

def _nested_schedule(tr: Tracer):
    with tr.span("a", n=1):
        with tr.span("b"):
            tr.instant("mark", n=2)
        with tr.span("c"):
            pass
    with tr.span("d"):
        pass


def test_span_tree_deterministic_under_fake_clock():
    exports = []
    for _ in range(2):
        tr = Tracer(clock=FakeClock())
        _nested_schedule(tr)
        exports.append(json.dumps(tr.to_chrome(), sort_keys=True))
    assert exports[0] == exports[1]
    tr = Tracer(clock=FakeClock())
    _nested_schedule(tr)
    by_name = {s.name: s for s in tr.spans}
    assert by_name["a"].parent is None and by_name["d"].parent is None
    assert by_name["b"].parent == by_name["a"].sid
    assert by_name["c"].parent == by_name["a"].sid
    assert tr.instants[0].parent == by_name["b"].sid
    # sequential sids in open order: a=0, b=1, mark=2, c=3, d=4
    assert [by_name[n].sid for n in "abcd"] == [0, 1, 3, 4]


def test_untraced_runs_read_the_clock_identically():
    """keep=False must not change virtual time: BatchTiming parity depends
    on traced and untraced runs making the SAME clock reads."""
    clocks = []
    for keep in (False, True):
        fc = FakeClock()
        tr = Tracer(clock=fc, keep=keep)
        with tr.span("a", n=1):
            with tr.span("b"):
                pass
        clocks.append(fc.t)
    assert clocks[0] == clocks[1]
    tr = Tracer(clock=FakeClock(), keep=False)
    _nested_schedule(tr)
    assert tr.spans == [] and tr.instants == []


def test_span_coverage():
    def sp(t0, t1, parent=None):
        return Span(name="s", sid=0, parent=parent, t0=t0, t1=t1)
    assert span_coverage([sp(0, 1), sp(1, 2)]) == 1.0
    assert span_coverage([sp(0, 1), sp(3, 4)]) == pytest.approx(0.5)
    assert span_coverage([sp(0, 2), sp(1, 4)]) == 1.0
    # nested spans don't double-cover under roots_only
    assert span_coverage([sp(0, 4), sp(1, 2, parent=0)]) == 1.0
    assert span_coverage([]) == 0.0


def test_validate_chrome_trace():
    tr = Tracer(clock=FakeClock())
    _nested_schedule(tr)
    obj = tr.to_chrome()
    assert validate_chrome_trace(obj) == []
    assert validate_chrome_trace({"traceEvents": [{"ph": "X"}]})
    assert validate_chrome_trace([1, 2]) == ["top level must be an object"]


# -- the serve loop under trace: nesting, determinism, privacy ---------------

def _traced_loop(base, *, depth=2, trace=True):
    fc = FakeClock()
    obs = Obs(clock=fc, trace=trace)
    return PipelinedServeLoop(copy.deepcopy(base), max_batch=4,
                              deadline_ms=1e9, clock=fc, seed=0,
                              depth=depth, obs=obs), obs


def test_serve_trace_spans_nest_across_inflight_depth(base_live):
    """Plan spans parent under THEIR tick; the gemm/complete spans of a
    batch retired `depth` ticks later parent under the RETIRING tick —
    the pipeline overlap made visible in the trace structure."""
    corp, base = base_live
    loop, obs = _traced_loop(base, depth=3)
    for rid in range(16):
        loop.submit(rid, corp.embeddings[rid % N_DOCS])
        loop.tick()
    loop.drain()
    spans = obs.tracer.spans
    by_sid = {s.sid: s for s in spans}
    ticks = [s for s in spans if s.name == "serve.tick"]
    assert len(ticks) >= 4
    roots = {s.name for s in spans if s.parent is None}
    assert roots <= {"serve.tick", "serve.drain"}
    for s in spans:
        if s.name in ("serve.plan", "serve.gemm", "serve.complete"):
            parent = by_sid[s.parent]
            assert parent.name in ("serve.tick", "serve.drain")
            assert parent.t0 <= s.t0 and s.t1 <= parent.t1
    # with depth 3 some batch's complete span must sit under a YOUNGER
    # tick than its plan span (the in-flight window is real)
    plan_parents = [s.parent for s in spans if s.name == "serve.plan"]
    done_parents = [s.parent for s in spans if s.name == "serve.complete"]
    assert len(plan_parents) == len(done_parents)
    assert any(d > p for p, d in zip(plan_parents, done_parents))


def test_serve_trace_deterministic(base_live):
    """Same scripted schedule, same FakeClock: byte-identical exports."""
    corp, base = base_live
    ops = _script_from_rng(np.random.default_rng(23), 40)
    exports = []
    for _ in range(2):
        loop, obs = _traced_loop(base)
        _drive_scripted(loop, corp, ops)
        exports.append(json.dumps(obs.tracer.to_chrome(), sort_keys=True))
    assert exports[0] == exports[1]


def test_serve_trace_privacy_audit(base_live):
    """Full serve-loop export (mutations, multi-probe, retries): every args
    value re-passes the allowlist, and the serialized JSON contains no
    document payload bytes and no embedding-derived digit strings."""
    corp, base = base_live
    ops = _script_from_rng(np.random.default_rng(7), 50)
    loop, obs = _traced_loop(base)
    _drive_scripted(loop, corp, ops)
    assert loop.responses, "audit needs a real run"
    trace = obs.tracer.to_chrome()
    assert validate_chrome_trace(trace) == []
    for ev in trace["traceEvents"]:
        for key, val in ev["args"].items():
            scrub(val, where=f"{ev['name']}.{key}")     # raises on leak
    blob = json.dumps(trace)
    for text, _ in list(loop_docs(loop))[:20]:
        assert text.decode("latin-1") not in blob
    # embedding components serialize with long mantissas; no args float
    # should reproduce one (timings/counts never equal embedding values)
    emb_strs = {f"{v:.6f}" for v in np.asarray(corp.embeddings[:20]).ravel()
                if abs(v) > 1e-3}
    assert not any(s in blob for s in emb_strs)
    # metrics export is clean too
    json.dumps(obs.metrics_dict())


def loop_docs(loop):
    """The live index's (text, emb) pairs (test helper)."""
    return loop.live._docs.values()


def test_rag_trace_privacy_audit(base_live):
    """Generation spans record counts and timings ONLY — never token ids,
    prompt bytes or document text.  The audit drives a generator-equipped
    pipelined loop (coalesced micro-batches included), re-scrubs every
    exported args value, and greps the serialized JSON for each response's
    token ids and each packed document's payload."""
    import os

    from repro.rag import Generator

    corp, base = base_live
    fc = FakeClock()
    obs = Obs(clock=fc, trace=True)
    gen = Generator.tiny(seed=1, context_budget=64, max_new_tokens=4)
    loop = PipelinedServeLoop(copy.deepcopy(base), max_batch=4,
                              deadline_ms=1e9, clock=fc, seed=0, depth=2,
                              gen_coalesce=2, obs=obs, generator=gen)
    for rid in range(16):
        loop.submit(rid, corp.embeddings[rid % N_DOCS], top_k=3)
        loop.tick()
    loop.drain()
    assert all(r.tokens is not None for r in loop.responses)

    trace = obs.tracer.to_chrome()
    assert validate_chrome_trace(trace) == []
    names = {ev["name"] for ev in trace["traceEvents"]}
    assert {"rag.tokenize", "rag.prefill", "rag.generate"} <= names
    # every emitted name is registered in the schema's closed vocabulary
    # (what scripts/check_trace.py enforces in CI)
    with open(os.path.join(os.path.dirname(__file__), "..", "scripts",
                           "trace_schema.json")) as f:
        allowed = set(json.load(f)["$spanNames"])
    assert names <= allowed, names - allowed
    for ev in trace["traceEvents"]:
        for key, val in ev["args"].items():
            scrub(val, where=f"{ev['name']}.{key}")     # raises on leak
    blob = json.dumps(trace)
    # no generated token sequence appears in any serialized form
    for r in loop.responses:
        assert str(list(r.tokens)) not in blob
        assert ",".join(str(t) for t in r.tokens) not in blob
    # no retrieved document payload appears either
    for text, _ in list(loop_docs(loop))[:20]:
        assert text.decode("latin-1") not in blob
    # generation counters are aggregates, never per-token values
    m = obs.metrics_dict()
    assert m["rag.generated_tokens"] == 16 * gen.max_new_tokens
    json.dumps(m)


def test_serve_metrics_populated(base_live):
    corp, base = base_live
    loop, obs = _traced_loop(base, trace=False)
    for rid in range(12):
        loop.submit(rid, corp.embeddings[rid % N_DOCS])
        loop.tick()
    loop.drain()
    m = obs.metrics_dict()
    assert m["serve.responses"] == 12
    assert m["serve.batch_size"]["n"] >= 1
    assert m["serve.latency_ms"]["n"] == 12
    assert m["serve.queue_depth"]["hi"] >= 1


def test_commit_spans_and_counters(base_live):
    from repro.update import journal as journal_lib
    corp, base = base_live
    loop, obs = _traced_loop(base)
    for rid in range(8):
        loop.submit(rid, corp.embeddings[rid % N_DOCS])
        if rid % 3 == 0:
            d = rid % N_DOCS
            loop.submit_mutation(journal_lib.replace(
                d, f"obs {d}".encode(), corp.embeddings[d]))
        loop.tick()
    loop.drain()
    names = {s.name for s in obs.tracer.spans}
    assert {"commit.stage", "commit.publish"} <= names
    m = obs.metrics_dict()
    assert m["commit.epochs"] == loop.epoch >= 1
    assert m["commit.patch_bytes"]["n"] == loop.epoch


@pytest.fixture(scope="module")
def base_live():
    return _get_base()


# -- the spans mirrored into the profiler's trace -----------------------------

MIRRORED = ("serve.plan", "serve.plan.pick", "serve.plan.encrypt",
            "serve.plan.dispatch", "serve.plan.dispatch.answer",
            "serve.plan.dispatch.decode", "serve.gemm", "serve.complete",
            "serve.complete.fetch", "serve.complete.parse",
            "serve.complete.rerank")


def _drive_profiled(base, corp, ops, log_dir=None):
    """Run `ops` through a pipelined loop, under a CPU profiler session when
    ``log_dir`` is given: then idle ticks follow the drain, inside a
    ``test.idle`` annotation.  Returns (loop, host events or None); each
    event is (name, start_ns, end_ns, stats, thread line)."""
    import jax
    from jax.profiler import ProfileData, TraceAnnotation
    loop, _ = _traced_loop(base, trace=False)
    if log_dir is None:
        _drive_scripted(loop, corp, ops)
        return loop, None
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        _drive_scripted(loop, corp, ops)
        with TraceAnnotation("test.idle"):
            for _ in range(50):
                assert loop.tick() == 0
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats), line.name)
              for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events]
    return loop, events


@pytest.fixture(scope="module")
def profiled(base_live, tmp_path_factory):
    """The same scripted schedule (single- and multi-probe batches,
    mutations) with and without a profiler session recording."""
    corp, base = base_live
    ops = _script_from_rng(np.random.default_rng(23), 40) + [("drain",)]
    loop, events = _drive_profiled(base, corp, ops,
                                   tmp_path_factory.mktemp("profile"))
    plain, _ = _drive_profiled(base, corp, ops)
    return loop, events, plain


def test_profiler_trace_holds_every_mirrored_span(profiled):
    loop, events, _ = profiled
    names = {e[0] for e in events}
    assert set(MIRRORED) <= names
    assert "serve.tick" not in names           # the spin root stays out
    assert not any(n.startswith("bench.") for n in names)
    # each plan holds exactly one pick, one encrypt and one dispatch; a
    # single-probe (legacy path) plan's dispatch one answer and one decode
    for name, t0, t1, stats, line in events:
        if name == "serve.plan":
            inner = [e[0] for e in events if e[4] == line
                     and t0 <= e[1] and e[2] <= t1
                     and e[0].startswith("serve.plan.")]
            legacy = (["serve.plan.dispatch.answer",
                       "serve.plan.dispatch.decode"]
                      if stats["multi_probe"] == 1 else [])
            assert sorted(inner) == sorted(["serve.plan.dispatch",
                                            "serve.plan.encrypt",
                                            "serve.plan.pick"] + legacy)


def test_profiler_spans_join_by_batch_id(profiled):
    """A batch's plan (under the tick that dispatched it) and its gemm and
    complete (under the tick that retired it) carry one `bid`, once per
    stage, and it is the `bid` of the batch's BatchTiming."""
    loop, events, _ = profiled
    bids = {}
    for stage in ("serve.plan", "serve.gemm", "serve.complete"):
        bids[stage] = [e[3]["bid"] for e in events if e[0] == stage]
        assert len(bids[stage]) == len(set(bids[stage])) > 3
    assert set(bids["serve.plan"]) == set(bids["serve.gemm"]) \
        == set(bids["serve.complete"])
    served = {r.timing.bid for r in loop.responses if r.timing is not None}
    assert served == set(bids["serve.plan"])
    batch = {e[3]["bid"]: e[3]["batch"] for e in events
             if e[0] == "serve.plan"}
    for r in loop.responses:
        assert batch[r.timing.bid] == r.batch_size


def test_idle_ticks_leave_no_profiler_event(profiled):
    _, events, _ = profiled
    (_, t0, t1, _, line), = [e for e in events if e[0] == "test.idle"]
    assert [e for e in events if e[4] == line and t0 <= e[1] <= t1
            and e[0] != "test.idle"] == []


def test_profiler_session_changes_no_response(profiled):
    loop, _, plain = profiled
    assert loop.responses, "the comparison needs a real run"

    def fields(lp):
        return [(r.rid, r.top, r.t_done, r.batch_size, r.epoch, r.retries,
                 r.timing) for r in lp.responses]

    assert fields(loop) == fields(plain)
