"""The engine's mirrored spans read back from a profiler trace.

``testdata/serve_window.xplane.pb.gz`` is a one-second ``--trace 1`` window
of ``msmarco.steady`` recorded on a TPU v5 lite from a program that
mirrored no span; ``testdata/serve_window_spans.xplane.pb.gz`` is a
1.5-second window of the same cell, recorded on a TPU v5 lite from a
program that mirrors them.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import importlib.util
import sys
import types
from collections import defaultdict
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import serve_spans  # noqa: E402
import trace_reduce  # noqa: E402

UNMIRRORED = HERE / "testdata" / "serve_window.xplane.pb.gz"
MIRRORED = HERE / "testdata" / "serve_window_spans.xplane.pb.gz"
READERS = ("client.pick_ms.lat", "client.encrypt_ms.lat",
           "complete.fetch_ms.lat", "engine.inflight_ms.lat",
           "device_idle.plan.lat", "device_idle.waiting.lat")


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(path):
    """What a reader sees of a ``--trace 1`` run whose window is ``path``."""
    return types.SimpleNamespace(trace=trace_reduce.reduce_file(str(path)),
                                 serve_spans=serve_spans.reduce_file(path))


def test_overlap_of_interval_lists():
    assert serve_spans._overlap([(0, 2), (3, 5)], [(1, 4)]) == 2
    assert serve_spans._overlap([(0, 1)], [(1, 2)]) == 0
    assert serve_spans._overlap([], [(0, 1)]) == 0


def test_stages_inherit_the_batch_id_around_them():
    events = [(0.0, 4.0, "serve.plan", 7, 0), (0.5, 1.0, "serve.plan.pick",
                                                None, 0),
              (1.0, 3.0, "serve.plan.encrypt", None, 0),
              (1.5, 2.0, "serve.plan.pick", None, 1),     # another thread
              (5.0, 6.0, "serve.complete.fetch", None, 0)]
    got = [(name, bid) for _, _, name, bid in
           serve_spans._inherit_bids(events)]
    assert got == [("serve.plan", 7), ("serve.plan.pick", 7),
                   ("serve.plan.encrypt", 7), ("serve.plan.pick", None),
                   ("serve.complete.fetch", None)]


def test_idle_split_and_inflight_on_a_made_up_window():
    s = serve_spans.Spans(
        window_s=10.0,
        events=[("serve.plan", 0.0, 2.0, 0), ("serve.plan", 4.0, 2.0, 1),
                ("serve.gemm", 5.0, 0.5, 0), ("serve.complete", 5.5, 0.5, 0),
                ("serve.complete", 7.0, 1.5, 1),
                ("serve.plan", 9.5, 1.0, 2)],          # runs past the window
        idle=[(1.0, 2.0), (5.0, 1.0), (8.0, 2.0)])
    assert s.idle_s() == 5.0
    assert s.idle_s(("serve.plan",)) == 1.0 + 1.0 + 0.5
    assert s.idle_s(serve_spans.ENGINE_STAGES) == 1.0 + 1.0 + 0.5 + 0.5
    assert s.mean_ms("serve.plan") == 2000.0          # the third is cut
    assert s.inflight_ms() == 3000.0                  # bid 0: 5.0 − 2.0


@pytest.fixture(scope="module")
def unmirrored():
    return _run(UNMIRRORED)


def test_unmirrored_window_keeps_its_numbers(unmirrored):
    """The reduction that the accepted metrics read is the one it was."""
    r = unmirrored.trace
    assert r.window_s == pytest.approx(1.000026547, abs=1e-9)
    assert r.busy_s == pytest.approx(0.161292355, abs=1e-9)
    assert len(r.gaps) == 1991
    assert r.op_s["jit_modmatmul_pallas"] == pytest.approx(0.122675518,
                                                            abs=1e-9)
    s = unmirrored.serve_spans
    assert s.events == []
    assert s.window_s == r.window_s
    assert s.idle_s() == pytest.approx(r.window_s - r.busy_s, abs=1e-9)


def test_readers_are_silent_without_mirrored_spans(unmirrored):
    for name in READERS:
        assert _reader(name)(unmirrored) is None, name


@pytest.fixture(scope="module")
def mirrored():
    return _run(MIRRORED)


def test_mirrored_window_reads_every_new_metric(mirrored):
    got = {name: _reader(name)(mirrored) for name in READERS}
    assert all(isinstance(v, float) and v >= 0 for v in got.values()), got
    total = _reader("device_idle.lat")(mirrored)
    assert got["device_idle.plan.lat"] + got["device_idle.waiting.lat"] \
        <= total + 1e-9


def test_mirrored_plan_stages_fit_in_their_plan(mirrored):
    """Per batch, pick + encrypt + dispatch ≤ plan, and each stage has
    its batch's id."""
    per_bid: dict = defaultdict(dict)
    for name, start, dur, bid in mirrored.serve_spans.events:
        if name.startswith("serve.plan"):
            assert bid is not None, name
            assert name not in per_bid[bid], (name, bid)
            per_bid[bid][name] = dur
    whole = [d for d in per_bid.values() if len(d) == 4]
    assert whole
    for d in whole:
        assert d["serve.plan.pick"] + d["serve.plan.encrypt"] \
            + d["serve.plan.dispatch"] <= d["serve.plan"]
