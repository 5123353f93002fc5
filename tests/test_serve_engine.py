"""Pipelined serving engine ≡ synchronous loop, bit-for-bit.

The contract under test (ISSUE 4): `PipelinedServeLoop` may move work in
time — async answer dispatch, deferred decode, shadow-epoch commits,
donated in-place patches — but every response (payload, epoch, retry
count, batch size) and the loop-level counters must be IDENTICAL to
`PIRServeLoop` over the same submit/mutation/tick/drain schedule.

Fast tests run a scripted interleaving in tier-1; the hypothesis property
(random interleavings) and the sharded-mesh variant are slow-marked and run
in CI's multi-device step.
"""
import copy

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from _mesh_harness import run_sub

from repro.data import corpus as corpus_lib
from repro.serve import PIRServeLoop, PipelinedServeLoop
from repro.update import LiveIndex, journal as journal_lib

N_DOCS = 200


class FakeClock:
    """Deterministic monotone clock: batch cuts don't depend on wall time."""

    def __init__(self, step: float = 1e-4):
        self.t = 0.0
        self.step = step

    def __call__(self) -> float:
        self.t += self.step
        return self.t


_BASE: dict = {}


def _get_base():
    """Build the reference corpus + live index once per process.

    Not a fixture: the hypothesis property below must stay usable under the
    `_hypothesis_compat` shim, whose `given` wrapper presents a zero-arg
    signature (no fixture injection).  Each engine run gets a deepcopy, so
    the cached base is never mutated.
    """
    if not _BASE:
        corp = corpus_lib.make_corpus(1, N_DOCS, emb_dim=16, n_topics=6)
        live = LiveIndex.build(corp.texts, corp.embeddings, n_clusters=6,
                               impl="xla", kmeans_iters=6)
        live.system.enable_batch(kappa=4)
        _BASE["corp"], _BASE["live"] = corp, live
    return _BASE["corp"], _BASE["live"]


@pytest.fixture(scope="module")
def base_live():
    return _get_base()


def _signature(loop):
    return [(r.rid, r.epoch, r.retries, r.batch_size,
             tuple((d, t) for d, _, t in r.top)) for r in loop.responses]


def _drive_scripted(loop, corp, ops):
    """ops: list of ("submit", rid, emb_idx, top_k, multi_probe) |
    ("mutate", kind, doc_id, tag) | ("tick",) | ("drain",)."""
    for op in ops:
        if op[0] == "submit":
            _, rid, idx, top_k, mp = op
            loop.submit(rid, corp.embeddings[idx], top_k=top_k,
                        multi_probe=mp)
        elif op[0] == "mutate":
            _, kind, doc, tag = op
            if kind == "delete":
                loop.submit_mutation(journal_lib.delete(doc))
            else:
                mut = getattr(journal_lib, kind)
                loop.submit_mutation(mut(doc, f"{kind} {doc} {tag}".encode(),
                                         corp.embeddings[doc % N_DOCS]))
        elif op[0] == "tick":
            loop.tick()
        elif op[0] == "drain":
            loop.drain()
    loop.drain()


def _script_from_rng(rng, n_ops: int):
    """Random interleaving over live doc ids (insert/delete kept consistent)."""
    ops = []
    alive = set(range(N_DOCS))
    next_id = N_DOCS
    rid = 0
    for _ in range(n_ops):
        roll = rng.integers(0, 10)
        if roll < 6:
            ops.append(("submit", rid, int(rng.integers(0, N_DOCS)),
                        int(rng.integers(1, 6)),
                        int(rng.choice([1, 1, 2, 3]))))
            rid += 1
        elif roll < 8 and alive:
            kind = ["replace", "insert", "delete"][int(rng.integers(0, 3))]
            if kind == "insert":
                ops.append(("mutate", "insert", next_id, rid))
                alive.add(next_id)
                next_id += 1
            elif kind == "delete" and len(alive) > N_DOCS // 2:
                doc = int(sorted(alive)[int(rng.integers(0, len(alive)))])
                ops.append(("mutate", "delete", doc, rid))
                alive.discard(doc)
            else:
                doc = int(sorted(alive)[int(rng.integers(0, len(alive)))])
                ops.append(("mutate", "replace", doc, rid))
        elif roll == 8:
            ops.append(("tick",))
        else:
            ops.append(("drain",))
    return ops


def _compare_engines(corp, live_factory, ops, *, depth, donate=True,
                     max_batch=4):
    sync = PIRServeLoop(live_factory(), max_batch=max_batch,
                        deadline_ms=1e9, clock=FakeClock(), seed=0)
    _drive_scripted(sync, corp, ops)
    pipe = PipelinedServeLoop(live_factory(), max_batch=max_batch,
                              deadline_ms=1e9, clock=FakeClock(), seed=0,
                              depth=depth, donate=donate)
    _drive_scripted(pipe, corp, ops)
    assert _signature(sync) == _signature(pipe)
    assert sync.stale_retries == pipe.stale_retries
    assert sync.epoch == pipe.epoch
    assert pipe.inflight == 0
    return sync, pipe


def test_pipelined_matches_sync_scripted(base_live):
    """Deterministic interleaving: mutations, multi-probe, partial drains."""
    corp, base = base_live
    rng = np.random.default_rng(11)
    ops = _script_from_rng(rng, 60)
    for depth in (1, 3):
        _compare_engines(corp, lambda: copy.deepcopy(base), ops, depth=depth)


def test_pipelined_static_system(base_live):
    """No LiveIndex: pure pipelining over a static corpus still matches."""
    corp, base = base_live
    ops = [("submit", rid, rid % N_DOCS, 4, 1) for rid in range(9)]
    sys_factory = lambda: copy.deepcopy(base.system)  # noqa: E731
    sync = PIRServeLoop(sys_factory(), max_batch=4, deadline_ms=1e9,
                        clock=FakeClock(), seed=0)
    _drive_scripted(sync, corp, ops)
    pipe = PipelinedServeLoop(sys_factory(), max_batch=4, deadline_ms=1e9,
                              clock=FakeClock(), seed=0, depth=2)
    _drive_scripted(pipe, corp, ops)
    assert _signature(sync) == _signature(pipe)


def test_idle_ticks_retire_inflight_batches(base_live):
    """Regression: during a traffic lull, tick() must flush the pipeline —
    finished batches may not sit decoded-but-unreported behind `depth`."""
    corp, base = base_live
    loop = PipelinedServeLoop(copy.deepcopy(base), max_batch=4,
                              deadline_ms=1e9, clock=FakeClock(), seed=0,
                              depth=4)
    for rid in range(4):
        loop.submit(rid, corp.embeddings[rid])
    loop.tick()                         # dispatches one batch, depth not hit
    assert loop.inflight == 1 and not loop.responses
    assert loop.tick() == 0             # idle tick: nothing to dispatch...
    assert loop.inflight == 0 and len(loop.responses) == 4   # ...but retires


def test_batch_timing_parity_sync_vs_pipelined(base_live):
    """BatchTiming audit (ISSUE 7): both engines must stamp every response
    with a well-formed timing derived from their obs spans — same batch
    partition, monotone boundaries, positive components — even though the
    pipelined engine legitimately reports residual (near-zero overlapped)
    gemm time where the sync engine reports the full device wait."""
    corp, base = base_live
    ops = _script_from_rng(np.random.default_rng(17), 50)
    sync, pipe = _compare_engines(corp, lambda: copy.deepcopy(base), ops,
                                  depth=2)
    for loop in (sync, pipe):
        for r in loop.responses:
            t = r.timing
            assert t is not None, f"missing timing on rid {r.rid}"
            # FakeClock advances per read, so every span has positive width
            assert t.encode_s > 0 and t.gemm_s > 0 and t.decode_s > 0
            assert t.t_plan < r.t_done

    def partition(loop):
        """rids grouped by shared BatchTiming object (the batch identity)."""
        groups: dict = {}
        for r in loop.responses:
            groups.setdefault(id(r.timing), []).append(r.rid)
        return sorted(map(tuple, groups.values()))

    # the engines batch identically, so requests must SHARE timing structs
    # identically — a parity regression here means one engine fragmented
    # (or merged) a batch's timing without changing its responses
    assert partition(sync) == partition(pipe)


def test_donated_commits_stay_exact(base_live):
    """After donated shadow commits, server-side state is bit-identical to a
    from-scratch setup of the mutated corpus (the live-index invariant)."""
    import jax.numpy as jnp
    from repro.kernels import ops as kops

    corp, base = base_live
    live = copy.deepcopy(base)
    loop = PipelinedServeLoop(live, max_batch=4, deadline_ms=1e9,
                              clock=FakeClock(), seed=0, depth=2,
                              donate=True)
    rng = np.random.default_rng(5)
    for rid in range(24):
        loop.submit(rid, corp.embeddings[rid % N_DOCS])
        if rid % 6 == 0:
            d = int(rng.integers(0, N_DOCS))
            loop.submit_mutation(journal_lib.replace(
                d, f"donated {d}@{rid}".encode(), corp.embeddings[d]))
        loop.tick()
    loop.drain()
    assert live.epoch >= 3
    sys = live.system
    # donated column scatters patched the device DB exactly (host mirror is
    # repacked independently), and the patched hint equals a fresh H = D·A
    np.testing.assert_array_equal(np.asarray(sys.server.db), sys.db.matrix)
    fresh = kops.hint_gemm(jnp.asarray(sys.db.matrix),
                           sys.server.a_matrix, impl="xla")
    assert jnp.array_equal(fresh, sys.hint)


@pytest.mark.slow
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**9))
def test_property_random_interleavings(seed):
    """Random submit/mutation/tick/drain interleavings: responses, epochs
    and retry counts identical at a random pipeline depth."""
    corp, base = _get_base()
    rng = np.random.default_rng(seed)
    ops = _script_from_rng(rng, int(rng.integers(20, 70)))
    depth = int(rng.integers(1, 5))
    _compare_engines(corp, lambda: copy.deepcopy(base), ops, depth=depth,
                     max_batch=int(rng.integers(2, 6)))


_MESH_BODY = """
from repro.data import corpus as corpus_lib
from repro.serve import PIRServeLoop, PipelinedServeLoop
from repro.update import LiveIndex, journal as journal_lib

class FakeClock:
    def __init__(self): self.t = 0.0
    def __call__(self): self.t += 1e-4; return self.t

mesh = make_mesh((8,), ("chunks",))
corp = corpus_lib.make_corpus(1, 160, emb_dim=16, n_topics=6)

def build(m):
    return LiveIndex.build(corp.texts, corp.embeddings, n_clusters=6,
                           impl="xla", kmeans_iters=5, mesh=m)

def drive(loop):
    rng = np.random.default_rng(13)
    for rid in range(28):
        loop.submit(rid, corp.embeddings[rid % 160], top_k=4)
        if rid % 6 == 2:
            d = int(rng.integers(0, 160))
            loop.submit_mutation(journal_lib.replace(
                d, f"mesh {d}@{rid}".encode(), corp.embeddings[d]))
        loop.tick()
    loop.drain()
    return ([(r.rid, r.epoch, r.retries, r.batch_size,
              tuple((d, t) for d, _, t in r.top)) for r in loop.responses],
            loop.stale_retries, loop.epoch)

ref = drive(PIRServeLoop(build(None), max_batch=4, deadline_ms=1e9,
                         clock=FakeClock(), seed=0))
for donate in (False, True):
    got = drive(PipelinedServeLoop(build(mesh), max_batch=4, deadline_ms=1e9,
                                   clock=FakeClock(), seed=0, depth=2,
                                   donate=donate))
    assert got == ref, f"donate={donate} diverged from single-device sync"
print("MESH-OK")
"""


def test_keyed_lookup_mixed_batch_both_engines():
    """Keyed lookups flow through both engines alongside similarity queries
    in ONE tick's batch, stay bit-exact against the live table through a
    replace epoch, and the engines agree response-for-response."""
    rng = np.random.default_rng(6)
    table = rng.standard_normal((144, 8)).astype(np.float32)
    new_row = rng.standard_normal(8).astype(np.float32)
    asks = [rng.integers(0, 144, size=k).tolist() for k in (3, 9)]

    def drive(loop):
        layout = loop._serving_system().keyed
        for rid, ids in enumerate(asks):
            loop.submit_lookup(rid, ids)
        loop.submit(10, table[7], top_k=3)            # mixed-kind tick
        served = loop.tick(force=True)
        loop.drain()
        assert served >= 1
        loop.submit_mutation(journal_lib.replace(
            asks[0][0], layout.row_text(new_row), new_row))
        loop.submit_lookup(11, asks[0])               # re-fetch after commit
        loop.drain()
        return loop.responses

    sync = PIRServeLoop(
        LiveIndex.build_keyed(table, kappa=9, impl="xla", seed=0),
        max_batch=8, deadline_ms=1e9, clock=FakeClock(), seed=0)
    pipe = PipelinedServeLoop(
        LiveIndex.build_keyed(table, kappa=9, impl="xla", seed=0),
        max_batch=8, deadline_ms=1e9, clock=FakeClock(), seed=0, depth=2)
    rs, rp = drive(sync), drive(pipe)

    patched = table.copy()
    patched[asks[0][0]] = new_row
    for resp in (rs, rp):
        by_rid = {r.rid: r for r in resp}
        assert set(by_rid) == {0, 1, 10, 11}
        for rid, ids in enumerate(asks):
            np.testing.assert_array_equal(by_rid[rid].top, table[ids])
        assert by_rid[10].top and by_rid[10].epoch == 0
        assert by_rid[11].epoch == 1                  # post-commit epoch
        np.testing.assert_array_equal(by_rid[11].top, patched[asks[0]])
    # engines agree on everything, row payloads included
    assert [(r.rid, r.epoch, r.batch_size) for r in rs] == \
           [(r.rid, r.epoch, r.batch_size) for r in rp]
    for a, b in zip(rs, rp):
        if a.rid == 10:
            assert a.top == b.top
        else:
            np.testing.assert_array_equal(a.top, b.top)


@pytest.mark.slow
def test_pipelined_sharded_matches_single_device_sync():
    """8-fake-device mesh: pipelined sharded serving (shadow commits via the
    row-shard scatter, donated and not) ≡ the single-device sync loop."""
    out = run_sub(_MESH_BODY)
    assert "MESH-OK" in out
