"""A cell on four chips, rehearsed on four virtual CPU devices, and the
per-chip readings of its trace and device.

The four-device runs drive a whole run of a tiny cell with ``chips: 4``
(row-sharded build over a ``("chunks",)`` mesh, the production engine, the
comparison) in one subprocess that sets
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` before JAX starts.
A sound run must come out correct with no collective in the server's
answer program; the control and each planted fault must not.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import check  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
import work  # noqa: E402
from test_correct import SEED, TINY, TRAFFIC  # noqa: E402
from test_serve_spans import _reader  # noqa: E402

RECORDED = sorted((HERE / "testdata").glob("serve_window*.xplane.pb.gz"))
PEAKS = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
KINDS = ("sound", "control", "altered", "half")

FOUR_DEVICE_RUNS = """
import json, sys
sys.path.insert(0, {here!r})
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import check, observe, run
from repro.launch.mesh import make_chunk_mesh

cell = run.Cell("tiny4", 4, {tiny!r}, {traffic!r},
                [{{"name": "rag_ready_p50_ms", "unit": "ms"}},
                 {{"name": "setup_s", "unit": "s"}}], [])
replace = {{"sound": None, "control": observe.control(),
           "altered": observe.fault("altered"),
           "half": observe.fault("half")}}
out = {{}}
for kind, rep in replace.items():
    try:
        out[kind] = run.run_cell(cell, {seed}, 2.0, False, require_tpu=False,
                                 replace=rep, capture_all=True)
    except run.ServedPathError as e:
        out[kind] = e.result
# the count sees a collective where a program has one
mesh = make_chunk_mesh(4)
summed = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, "chunks"),
                               mesh=mesh, in_specs=P("chunks"), out_specs=P()))
text = summed.lower(jnp.ones(8)).compile().as_text()
out["psum_collectives"] = len(check.COLLECTIVE.findall(text))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_device_runs():
    """Each kind of run of the tiny four-chip cell, in one subprocess."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    code = FOUR_DEVICE_RUNS.format(here=str(HERE), src=str(ROOT / "src"),
                                   tiny=TINY, traffic=TRAFFIC, seed=SEED)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("kind", KINDS)
def test_four_chip_run(four_device_runs, kind):
    res = four_device_runs[kind]
    if kind == "sound":
        assert res["correct"], res["checks"]
        assert res["checks"]["answer_collectives"]["value"] == 0
        assert res["device"]["count"] == 4
        assert len(res["device"]["memory_peak_bytes_per_chip"]) == 4
    else:
        assert not res["correct"], res["checks"]
    if kind == "control":
        assert (res["checks"]["answer_words_wrong"]["value"] > 0
                or "served_path_errors" in res["checks"])


def test_collective_count_sees_a_psum(four_device_runs):
    assert four_device_runs["psum_collectives"] >= 1


def test_collective_count_on_hlo_text():
    text = "\n".join([
        "%ag = u8[8] all-gather-start(u8[2] %p), dimensions={0}",
        "%agd = u8[8] all-gather-done(%ag)",
        "%ar = u32[4] all-reduce(u32[4] %x), to_apply=%add",
        "%cp = u32[4] collective-permute(u32[4] %x)",
        "%c = u32[4] custom-call(%x), custom_call_target=\"tpu_custom_call\""])
    assert len(check.COLLECTIVE.findall(text)) == 3


def _synthetic(chips: int, m: int = 1_635_072, n: int = 4096,
               module: str = "jit_modmatmul_pallas"):
    """A window of three answers of 16, 7 and 3 real queries."""
    modules = [(module, 0.0, 0.080), ("jit_matmul", 0.09, 0.010),
               ("jit__threefry_split", 0.2, 0.001),
               (module, 0.3, 0.070), ("jit__threefry_split", 0.4, 0.001),
               (module, 0.5, 0.060)]
    return types.SimpleNamespace(
        trace=types.SimpleNamespace(modules=modules), peaks=PEAKS, m=m, n=n,
        chips=chips, shard_rows=m // chips,
        batches=[types.SimpleNamespace(b=b) for b in (16, 7, 3)])


@pytest.mark.parametrize("chips,module", [(1, "jit_modmatmul_pallas"),
                                          (4, "jit_local")])
def test_roofline_counts_the_rows_one_chip_holds(chips, module):
    r = _synthetic(chips, module=module)
    rows = r.m // chips
    want = 100.0 * sum(
        max(work.answer_bytes(rows, r.n, b) / PEAKS["hbm_bytes_per_s"],
            work.answer_ops(rows, r.n, b) / PEAKS["int8_ops_per_s"])
        for b in (16, 7, 3)) / (0.080 + 0.070 + 0.060)
    got = _reader("modmatmul_roofline.lat")(r)
    assert got == pytest.approx(want, rel=1e-12)
    if chips > 1:           # all m rows against one chip would read chips×
        whole = _reader("modmatmul_roofline.lat")(_synthetic(1))
        assert got == pytest.approx(whole / chips, rel=1e-3)
    assert _reader("decode.device_ms.lat")(r) == pytest.approx(5.0)


@pytest.mark.parametrize("name", ["modmatmul_roofline.lat",
                                  "decode.device_ms.lat"])
def test_unknown_answer_program_reads_nothing(name):
    assert _reader(name)(_synthetic(4, module="jit_renamed")) is None


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_one_chip_readings_of_recorded_traces(path):
    """On one chip both readers read the recorded window's
    ``jit_modmatmul_pallas`` programs over all ``m`` rows, as they did
    before they knew the sharded program."""
    red = trace_reduce.reduce_file(str(path))
    m, n = 1_559_040, 4096
    spent = [d for name, _, d in red.modules
             if name == "jit_modmatmul_pallas"]
    assert spent
    r = types.SimpleNamespace(
        trace=red, peaks=PEAKS, m=m, n=n, chips=1, shard_rows=m,
        batches=[types.SimpleNamespace(b=8)] * len(spent))
    least = work.least_seconds(m, n, 8, PEAKS["int8_ops_per_s"],
                               PEAKS["hbm_bytes_per_s"])[0]
    assert _reader("modmatmul_roofline.lat")(r) == pytest.approx(
        100.0 * least * len(spent) / sum(spent), rel=1e-12)
    per_batch, cur = [], None
    for name, _, dur in red.modules:
        if name == "jit_modmatmul_pallas":
            cur = 0.0
        elif cur is not None and name == "jit__threefry_split":
            per_batch.append(cur)
            cur = None
        elif cur is not None:
            cur += dur
    assert per_batch
    assert _reader("decode.device_ms.lat")(r) == pytest.approx(
        1e3 * sum(per_batch) / len(per_batch), rel=1e-12)


def test_device_reports_the_fullest_chip():
    import jax
    devs = jax.devices()[:1]
    one = run._device(devs, [7])
    assert one["memory_peak_bytes"] == 7
    assert one["memory_peak_bytes_per_chip"] == [7]
    four = run._device(devs, [5, 9, None, 8])
    assert four["memory_peak_bytes"] == 9
    assert run._device(devs)["memory_peak_bytes"] is None


@pytest.mark.parametrize("change", [{"multi_probe": 4},
                                    {"arrival": "bursty"},
                                    {"arrival": None}])
def test_traffic_the_run_cannot_serve_is_refused(change):
    steady = json.loads((HERE / "traffic" / "steady.json").read_text())
    run.check_traffic(steady)
    with pytest.raises(SystemExit) as e:
        run.check_traffic({**steady, **change})
    assert e.value.code != 0
