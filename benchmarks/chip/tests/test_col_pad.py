"""The reader of ``answer.col_pad.lat``: the query columns the answer kernel
computes per real query, from the engine's ``serve.answer.kernel_cols`` and
``serve.encrypt.batched_queries`` counters on ``Run.obs``.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import run  # noqa: E402
from test_correct import _run  # noqa: E402
from test_serve_spans import _reader  # noqa: E402

NAME = "answer.col_pad.lat"


def test_column_padding_of_a_recorded_run(monkeypatch):
    """Off the chip the answer is XLA's, which computes each batch's
    columns as they are: the window reads no padding."""
    seen = []
    read = run.read_metric
    monkeypatch.setattr(run, "read_metric",
                        lambda name, r: seen.append(r) or read(name, r))
    assert _run()["correct"]
    counts = seen[0].obs.metrics_dict()
    assert counts["serve.encrypt.batched_queries"] > 0
    assert (counts["serve.answer.kernel_cols"]
            == counts["serve.encrypt.batched_queries"])
    assert read(NAME, seen[0]) == 1.0


def test_column_padding_reader_is_silent_without_its_counter():
    from repro.obs import Obs
    read = _reader(NAME)
    assert read(types.SimpleNamespace(obs=None)) is None
    obs = Obs()
    obs.counter("serve.encrypt.batched_queries").inc(3 + 7 + 16)
    assert read(types.SimpleNamespace(obs=obs)) is None
    obs.counter("serve.answer.kernel_cols").inc(3 * 128)   # a fixed tile
    assert read(types.SimpleNamespace(obs=obs)) == pytest.approx(384 / 26)
