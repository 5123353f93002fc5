"""The ``dpr-wiki.sharded4`` cell: its configuration served row-sharded on
four virtual CPU devices and compared with the reference, the cell as the
harness loads it, and the readers of the four-chip serve path's spans and
counters.

The four-device run loads ``configs/dpr-wiki-c4096.json`` itself and
shrinks only its scale (passages, clusters, the engine's batch), keeping
the 768-d embeddings and 400-800 B passages, in one subprocess that sets
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` before JAX starts.

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

import run  # noqa: E402
import serve_spans  # noqa: E402
from test_correct import SEED  # noqa: E402
from test_serve_spans import _reader  # noqa: E402

CELL = "dpr-wiki.sharded4"
CONFIG = HERE / "configs" / "dpr-wiki-c4096.json"
SPAN_READERS = {"plan.answer_ms.lat": "serve.plan.dispatch.answer",
                "plan.decode_ms.lat": "serve.plan.dispatch.decode",
                "complete.parse_ms.lat": "serve.complete.parse",
                "complete.rerank_ms.lat": "serve.complete.rerank"}

FOUR_DEVICE_RUNS = """
import json, sys
sys.path.insert(0, {here!r})
sys.path.insert(0, {src!r})
import observe, run

cell = run.load_cell({cell!r})
cell.config = {{**cell.config, "n_docs": 600, "n_clusters": 16,
               "engine": {{**cell.config["engine"], "max_batch": 4}}}}
cell.traffic = {{**cell.traffic, "rate_rps": 8.0}}
seen = []
read = run.read_metric
run.read_metric = lambda name, r: seen.append(r) or read(name, r)
out = {{}}
for kind, rep in (("sound", None), ("control", observe.control())):
    try:
        out[kind] = run.run_cell(cell, {seed}, 2.0, False, require_tpu=False,
                                 replace=rep, capture_all=True)
    except run.ServedPathError as e:
        out[kind] = e.result
r = seen[0]
out["m"], out["chips"] = r.m, r.chips
out["fetch_kb"] = read("complete.fetch_kb.lat", r)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_device_runs():
    """The shrunk configuration's sound run and control, in one
    subprocess on four virtual devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    code = FOUR_DEVICE_RUNS.format(here=str(HERE), src=str(ROOT / "src"),
                                   cell=CELL, seed=SEED)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("kind", ["sound", "control"])
def test_four_chip_configuration_against_the_reference(four_device_runs,
                                                       kind):
    res = four_device_runs[kind]
    assert res["checks"]["answer_collectives"]["value"] == 0
    if kind == "sound":
        assert res["correct"], res["checks"]
        assert res["failed"] == 0
        assert len(res["device"]["memory_peak_bytes_per_chip"]) == 4
        assert four_device_runs["chips"] == 4
    else:
        assert not res["correct"], res["checks"]


def test_fetched_kib_per_query_is_the_db_height(four_device_runs):
    assert four_device_runs["fetch_kb"] == four_device_runs["m"] / 1024


def test_cell_loads_with_its_chips_traffic_and_metrics():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = run.load_cell(CELL)
    traffic = json.loads((HERE / "traffic" / "dpr4-steady.json").read_text())
    assert cell.chips == 4
    assert cell.config == json.loads(CONFIG.read_text())
    assert cell.traffic["rate_rps"] == traffic["rate_rps"]
    assert [m["name"] for m in cell.end_to_end] == ["rag_ready_p50_ms",
                                                    "setup_s"]
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    unlisted = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    got = {m["name"] for m in cell.per_layer}
    assert got == listed | unlisted
    assert set(SPAN_READERS) | {"complete.fetch_kb.lat",
                                "modmatmul_roofline.lat"} <= got
    for name in got:
        assert (HERE / "metrics" / f"{name}.py").is_file(), name


def test_configuration_entry_matches_its_file():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry, = [c for c in bench["configs"] if c["name"] == "dpr-wiki-c4096"]
    cfg = json.loads((ROOT / entry["file"]).read_text())
    one_chip = json.loads((HERE / "configs" /
                           "msmarco-passage-c4096.json").read_text())
    assert set(cfg) == set(one_chip)
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"] == ["n_docs"]
    assert cfg["n_docs"] < cfg["published"]["n_docs"]
    for key in ("emb_dim", "n_clusters", "lwe_k", "engine", "guarantees"):
        assert cfg[key] == one_chip[key], key


def _spans_run(events):
    return types.SimpleNamespace(trace=object(), serve_spans=serve_spans.Spans(
        window_s=10.0, events=events, idle=[]))


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_reader_means_its_stage(name):
    span = SPAN_READERS[name]
    r = _spans_run([("serve.plan", 0.0, 1.0, 0), (span, 0.1, 0.002, 0),
                    (span, 2.0, 0.004, 1), (span, 9.999, 0.5, 2),
                    ("serve.plan.dispatch", 2.0, 0.01, 1)])
    assert _reader(name)(r) == pytest.approx(3.0)     # the third is cut
    assert _reader(name)(_spans_run([("serve.plan", 0.0, 1.0, 0)])) is None


def test_fetch_kib_reader_is_silent_without_its_counter():
    from repro.obs import Obs
    read = _reader("complete.fetch_kb.lat")
    assert read(types.SimpleNamespace(obs=None)) is None
    obs = Obs()
    obs.counter("serve.encrypt.batched_queries").inc(12)
    assert read(types.SimpleNamespace(obs=obs)) is None
    obs.counter("serve.complete.fetch_bytes").inc(12 * 1_065_216)
    assert read(types.SimpleNamespace(obs=obs)) == 1040.25
