"""The reduction from a profiler trace to busy time, programs and gaps.

``testdata/serve_window.xplane.pb.gz`` is a one-second ``--trace 1`` window
of ``msmarco.steady`` recorded on a TPU v5 lite.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import trace_reduce  # noqa: E402

RECORDED = HERE / "testdata" / "serve_window.xplane.pb.gz"


def test_union_merges_overlaps():
    assert trace_reduce._union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [
        (0, 2.5), (3, 4)]


def test_gap_goes_to_innermost_covering_stage():
    stages = [(0.0, 10.0, "bench.tick"), (2.0, 6.0, "bench.complete")]
    assert trace_reduce._stage(stages, 3.0, 5.0) == "bench.complete"
    assert trace_reduce._stage(stages, 7.0, 9.0) == "bench.tick"
    assert trace_reduce._stage(stages, 11.0, 12.0) == "none"


@pytest.fixture(scope="module")
def recorded():
    r = trace_reduce.reduce_file(str(RECORDED))
    assert r is not None, "the recorded window holds no device or window"
    return r


def test_recorded_window_busy_and_gaps_tile_it(recorded):
    r = recorded
    assert 0 < r.busy_s < r.window_s
    idle = sum(d for _, d, _ in r.gaps)
    assert abs(idle + r.busy_s - r.window_s) < 1e-6 * max(1.0, r.window_s)


def test_recorded_window_names_the_answer_kernel(recorded):
    kernels = [d for name, _, d in recorded.modules
               if name == "jit_modmatmul_pallas"]
    assert kernels and all(d > 0 for d in kernels)
    assert recorded.op_s["jit_modmatmul_pallas"] >= sum(kernels)
    starts = [s for _, s, _ in recorded.modules]
    assert starts == sorted(starts)


def test_recorded_breakdown_shape(recorded):
    b = recorded.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in b["device_ops"])
    stages = {w for w, _ in b["idle_gaps"]}
    assert stages <= {"none", "bench.submit", "bench.tick", "bench.plan",
                      "bench.complete", "bench.commit"}
