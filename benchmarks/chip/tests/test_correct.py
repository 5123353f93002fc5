"""The comparison that decides ``correct``, at a size a CPU test can hold.

Each test drives a whole run of a tiny cell (corpus, build, warm-up, the
open-loop window through the production engine, the comparison) with the
harness's look for a chip skipped.  A sound run must come out correct; the
control and each planted fault must not.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import corpus  # noqa: E402
import observe  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

TINY = {"data_seed": 0, "build_seed": 0, "n_docs": 600, "emb_dim": 64,
        "n_clusters": 16, "text_len": [64, 200], "topic_spread": 0.5,
        "lwe_k": 1024,
        "engine": {"max_batch": 4, "deadline_ms": 20.0, "depth": 2}}
TRAFFIC = {"rate_rps": 8.0, "top_k": 5, "multi_probe": 1, "query_noise": 0.25}
SEED = 2**31 + 12345            # seeds run past 32 signed bits


def _cell():
    names = [("rag_ready_p50_ms", "ms"), ("rag_ready_p95_ms", "ms"),
             ("setup_s", "s")]
    e2e = [{"name": n, "unit": u} for n, u in names]
    return run.Cell("tiny", 1, TINY, TRAFFIC, e2e, [])


def _run(replace=None, seed=SEED):
    try:
        return run.run_cell(_cell(), seed, 2.0, False, require_tpu=False,
                            replace=replace, capture_all=True)
    except run.ServedPathError as e:
        return e.result


@pytest.fixture(scope="module")
def tiny():
    """A tiny corpus, its k-means partition and its reference index."""
    from repro.core import pipeline
    c = corpus.make_corpus(SEED, 0, 600, emb_dim=64, n_topics=16,
                           text_len=(64, 200), topic_spread=0.5)
    system = pipeline.PirRagSystem.build(c.texts, c.embeddings,
                                         n_clusters=16, seed=0)
    return c, np.asarray(system.centroids), np.asarray(system.assignment)


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["attempted"] == 16 and res["failed"] == 0
    assert set(res["metrics"]) == {"rag_ready_p50_ms", "rag_ready_p95_ms",
                                   "setup_s"}
    assert res["checks"]["passages_misassigned"]["value"] == 0
    assert res["checks"]["centroids_off_mean"]["value"] == 0
    assert list(res)[-1] == "checks"


def test_run_carries_the_engines_obs(monkeypatch):
    """A counter reader needs only ``run.obs``: the window's engine counts
    one encrypt program per batch it dispatched."""
    seen = []
    read = run.read_metric
    monkeypatch.setattr(run, "read_metric",
                        lambda name, r: seen.append(r) or read(name, r))
    assert _run()["correct"]
    r = seen[0]
    programs = r.obs.counter("serve.encrypt.programs").value
    assert programs >= len(r.batches) > 0
    assert r.chips == 1 and r.shard_rows == r.m


def test_control_three_limbs_is_not_correct():
    res = _run(observe.control())
    assert not res["correct"]
    assert res["checks"]["answer_words_wrong"]["value"] > 0


@pytest.mark.parametrize("kind", ["altered", "half"])
def test_planted_fault_is_not_correct(kind):
    res = _run(observe.fault(kind))
    assert not res["correct"], res["checks"]


def test_partition_of_the_build_is_a_fixed_point(tiny):
    c, cents, assign = tiny
    rng = np.random.default_rng(0)
    assert reference.partition_faults(c.embeddings, assign, cents, rng) == (
        0, 0)


@pytest.mark.parametrize("fault", ["moved_passage", "shifted_centroid",
                                   "swapped_centroids"])
def test_partition_fault_is_caught(tiny, fault):
    c, cents, assign = tiny
    cents, assign = cents.copy(), assign.copy()
    if fault == "moved_passage":            # one passage in another cluster
        assign[7] = (assign[7] + 1) % 16
    elif fault == "shifted_centroid":       # a centroid off its members' mean
        cents[3] += 0.01
    else:                                   # centroids that do not match
        cents[[2, 5]] = cents[[5, 2]]       # the clusters they label
    rng = np.random.default_rng(0)
    miss, off = reference.partition_faults(c.embeddings, assign, cents, rng)
    assert miss + off > 0


def test_no_tpu_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.require_device(1)
    assert e.value.code != 0
    assert capsys.readouterr().out == ""


def test_reference_packs_like_the_program():
    from repro.core import chunking
    c = corpus.make_corpus(5, 0, 800, emb_dim=64, n_topics=16,
                           text_len=(64, 300), topic_spread=0.5)
    a = np.random.default_rng(1).integers(0, 16, 800)
    db = chunking.build_chunked_db(c.texts, c.embeddings, a, 16)
    ref = reference.ReferenceIndex(c.texts, c.embeddings, a,
                                   np.zeros((16, 64)), 16)
    assert ref.m == db.m
    assert np.array_equal(ref.matrix(), db.matrix.T)
    rows = np.array([0, 5, db.m - 1])
    qu = np.random.default_rng(2).integers(0, 2**32, (16, 3), np.uint64)
    raw = (db.matrix[rows].astype(np.uint64) @ qu) % (1 << 32)
    want = (((raw + (1 << 15)) % (1 << 32)) >> 16).astype(np.uint16)
    assert np.array_equal(ref.answer_rows(rows, qu.astype(np.uint32)), want)


def test_seeds_share_sizes():
    a = corpus.make_corpus(1, 0, 300, emb_dim=32, n_topics=8,
                           text_len=(64, 128), topic_spread=0.5)
    b = corpus.make_corpus(2, 0, 300, emb_dim=32, n_topics=8,
                           text_len=(64, 128), topic_spread=0.5)
    assert [len(t) for t in a.texts] == [len(t) for t in b.texts]
    assert a.texts != b.texts
    assert np.array_equal(np.abs(a.embeddings), np.abs(b.embeddings))
    assert not np.array_equal(a.embeddings, b.embeddings)
