"""PIR serving engines: deadline batching, epoch admission, pipelining.

Two engines share one policy core (batching, epoch admission control, the
per-batch LWE key stream):

`PIRServeLoop` — the synchronous reference.  Each tick commits pending
mutations, cuts a batch, runs the answer GEMM and decodes it before
returning: correct, simple, and the bit-exactness oracle for everything
else — but the device sits idle while the host encodes, deserializes and
re-ranks.

`PipelinedServeLoop` — the production engine.  Each tick is split into
plan → dispatch → complete stages and exploits JAX async dispatch so the
three overlap across batches:

    tick T:   publish shadow commit (pointer swap — `serve.epochs`)
              plan batch N      (cut, admit, encode)        host
              dispatch batch N  (answer GEMM enqueued)      device
              complete batch N-depth (decode, re-rank)      host+device

While batch N's GEMM streams the database on the device, the host is
decoding batch N−depth and will cut/encode batch N+1 — the serve loop no
longer blocks host-side on every answer before cutting the next batch.
Mutation commits stage their patches into shadow buffers and publish with
a pointer swap (`update.live.stage/publish`), so a commit never stops the
world and in-flight batches keep decoding against their epoch's snapshot.

Responses are BIT-IDENTICAL to the synchronous loop — same payloads,
epochs, retry counts, in the same order (property-tested under random
interleavings of submits/mutations/drains, single-device and sharded):
pipelining moves work in time, never across an epoch boundary.

Both engines optionally close the RAG loop: pass ``generator=`` (a
`repro.rag.generate.Generator`) and every served query batch runs the
tokenize → prefill → decode completion stage before its responses land
(`Response.tokens` + `RagTiming`).  Under the pipelined engine batch N's
generation runs while batch N+1's retrieval GEMM is already dispatched —
retrieval for the next query overlaps decode of the previous one, which
is what `benchmarks/rag_bench.py` measures as overlapped RAG-Ready
Latency.  Generated tokens are bit-identical across engines (they depend
only on retrieved docs, rids and the generator seed, never on timing).
"""
from __future__ import annotations

import dataclasses
import heapq
import time
from collections import deque
from typing import Callable, Iterable

import jax
import numpy as np

from repro.fleet.faults import InjectedCommitFault
from repro.fleet.retry import DEFAULT_POLICY, RetryPolicy
from repro.obs import Obs
from repro.serve.epochs import ShadowCommitter


@dataclasses.dataclass
class Request:
    rid: int
    query_emb: np.ndarray | None   # None for keyed embedding lookups
    t_arrival: float
    epoch: int = 0                 # hint epoch the query was formed against
    retries: int = 0
    top_k: int = 5                 # per-request result size
    multi_probe: int = 1           # clusters to fetch (>1 → batch-PIR able)
    lookup_ids: tuple | None = None  # keyed row ids (recsys lookup request)


@dataclasses.dataclass(frozen=True)
class BatchTiming:
    """Per-batch latency components, shared by every response in the batch.

    ``t_plan`` is when the batch's encode began — a request's queue time is
    ``t_plan − t_arrival``.  ``encode_s`` is host-side query formulation +
    GEMM enqueue; ``gemm_s`` is the complete-stage wait for device results
    (under the pipelined engine this is the RESIDUAL wait after overlap,
    often ~0); ``decode_s`` is host-side decode + re-rank.  ``bid`` is the
    batch's sequential id in its engine, the ``bid`` attribute of every
    span the batch opens — the join of its plan and retire spans.
    """
    t_plan: float
    encode_s: float
    gemm_s: float
    decode_s: float
    bid: int


@dataclasses.dataclass(frozen=True)
class RagTiming:
    """Per-batch generation-stage components (shared by the batch).

    Seconds on the loop clock, one entry per `rag.*` span: `tokenize_s`
    is host-side doc decode + prompt packing, `prefill_s` the prompt
    forward filling the KV cache, `generate_s` the decode step loop.
    `prompt_tokens` is the batch's summed TRUE prompt length (before
    padding); `new_tokens` the fixed per-request generation length.
    """
    tokenize_s: float
    prefill_s: float
    generate_s: float
    prompt_tokens: int
    new_tokens: int


@dataclasses.dataclass
class Response:
    rid: int
    top: list
    t_done: float
    batch_size: int
    epoch: int = 0
    retries: int = 0
    t_arrival: float = 0.0               # copied from the request
    timing: BatchTiming | None = None    # its batch's latency components
    failed: bool = False                 # terminal: retry budget/deadline hit
    staleness: int = 0                   # epochs behind the fleet head (failover)
    tokens: tuple | None = None          # generated ids (loops with a generator)
    rag: RagTiming | None = None         # generation components (ditto)


class DeadlineBatcher:
    """Cut a batch at max_batch or when the head request ages past deadline."""

    def __init__(self, *, max_batch: int = 64, deadline_ms: float = 20.0):
        self.max_batch = max_batch
        self.deadline_ms = deadline_ms
        self.queue: deque[Request] = deque()

    @property
    def depth(self) -> int:
        """Requests currently queued (admission-controller observable)."""
        return len(self.queue)

    def oldest_age_ms(self, now: float) -> float:
        """Age of the head request in ms (0.0 when the queue is empty).

        The backlog gauge: under open-loop overload the head age grows
        without bound unless something sheds or defers load — operators
        and the admission controller both watch this.
        """
        if not self.queue:
            return 0.0
        return (now - self.queue[0].t_arrival) * 1e3

    def shed_tail(self, n: int) -> list[Request]:
        """Remove up to `n` requests from the TAIL and return them.

        Load shedding drops the youngest requests: the head of the queue
        has waited longest and is closest to its deadline, so it keeps its
        place.  The caller (admission controller) owns accounting shed
        requests into the SLO summary.
        """
        shed = []
        while self.queue and len(shed) < n:
            shed.append(self.queue.pop())
        shed.reverse()                   # back in arrival order
        return shed

    def submit(self, req: Request):
        """Append an arriving request (FIFO tail)."""
        self.queue.append(req)

    def requeue(self, req: Request):
        """Put ONE rejected request back at the head (it keeps its arrival)."""
        self.queue.appendleft(req)

    def requeue_front(self, reqs: Iterable[Request]):
        """Put rejected requests back at the head, preserving THEIR order.

        The batcher owns retry ordering: callers hand over the stale
        requests in cut order and this re-queues them FIFO ahead of
        everything younger.  (Naively calling `requeue` in iteration order
        would reverse same-epoch retries relative to each other.)
        """
        self.queue.extendleft(reversed(list(reqs)))

    def ready(self, now: float) -> bool:
        """True when a batch should be cut: size or head-age trigger."""
        if not self.queue:
            return False
        if len(self.queue) >= self.max_batch:
            return True
        age_ms = (now - self.queue[0].t_arrival) * 1e3
        return age_ms >= self.deadline_ms

    def cut(self) -> list[Request]:
        """Dequeue up to max_batch requests in arrival order."""
        batch = []
        while self.queue and len(batch) < self.max_batch:
            batch.append(self.queue.popleft())
        return batch


class PIRServeLoop:
    """Synchronous deadline-batched serving; optionally wraps a LiveIndex.

    `system` may be a PirRagSystem (static corpus) or, with `live=...`, the
    LiveIndex whose `.system` is queried at its current epoch.  A system
    built with ``mesh=`` serves every batch through the sharded
    zero-collective answer path; the loop itself is layout-agnostic (its
    batching, epoch admission and key-stream logic never look at the mesh).
    """

    #: span attribute naming this engine (registered enum in repro.obs.scrub)
    ENGINE = "sync"

    def __init__(self, system, *, max_batch: int = 64,
                 deadline_ms: float = 20.0,
                 clock: Callable[[], float] = time.perf_counter,
                 live=None, seed: int = 0, obs: Obs | None = None,
                 retry: RetryPolicy | None = DEFAULT_POLICY,
                 faults=None, generator=None):
        self.live = live if live is not None else (
            system if hasattr(system, "epochs") else None)
        self.system = system if self.live is None else self.live.system
        self.batcher = DeadlineBatcher(max_batch=max_batch,
                                       deadline_ms=deadline_ms)
        self.clock = clock
        # Observability: spans time every tick stage (BatchTiming is built
        # from their boundaries) and the registry carries serving counters.
        # The default Obs(trace=False) keeps the timeline without retaining
        # spans; pass Obs(trace=True, clock=<same clock>) to export traces.
        self.obs = obs if obs is not None else Obs(clock=clock, trace=False)
        if self.live is not None:
            self.live.set_obs(self.obs)
        self.responses: list[Response] = []
        self.mutations: deque = deque()
        self.stale_retries = 0
        # Bounded retry: every re-admission (stale reject, dropped answer)
        # charges the request's budget; exhaustion or a blown deadline
        # yields a TERMINAL failed response instead of another requeue.
        # retry=None restores the historical unbounded behaviour.
        self.retry = retry
        self.failed_requests = 0
        # Fault-injection hook (repro.fleet.faults.FaultInjector): guards
        # the answer drop/delay sites post-admission, and arms the wrapped
        # live index's commit-stage and hint-chain sites with the SAME
        # injector (one invocation-counter space per run).  None (default)
        # keeps the tick fault-free with zero extra clock reads.
        # Generation completion stage (repro.rag.generate.Generator):
        # when set, every served QUERY batch runs tokenize → prefill →
        # decode before its responses land, and `Response.tokens`/`.rag`
        # carry the generated ids + stage timing (t_done moves to the end
        # of generation, so SLO latency covers the full RAG answer).
        # None (the default) keeps the retrieval-only path byte-identical
        # to loops without the hook — zero extra clock reads.
        self.generator = generator
        self.faults = faults
        if faults is not None and self.live is not None:
            self.live.faults = faults
            self.live.epochs.faults = faults
        self._backoff: list = []      # (ready_t, seq, Request) min-heap
        self._delayed: list = []      # (ready_t, seq, Request) min-heap
        self._seq = 0                 # heap tiebreak = admission order
        self._tick_no = 0
        self._bid = 0                 # next dispatched group's batch id
        # Commit-failure retry state: an injected stage failure leaves the
        # journal batch pending; the commit is retried with exponential
        # tick backoff instead of being lost or hammered.
        self._commit_retry = False
        self._commit_attempts = 0
        self._commit_not_before = 0   # tick number gating the next attempt
        # Admission hook: when set, pending mutations fold into an epoch
        # only on ticks where commit_gate() is True — the controller defers
        # commits under backlog so queued requests don't go stale mid-wait
        # (freshness degrades instead of latency; see traffic.admission).
        self.commit_gate: Callable[[], bool] | None = None
        self._key = jax.random.PRNGKey(seed)   # per-batch query-key stream

    @property
    def epoch(self) -> int:
        """Published epoch requests are admitted at (0 for static corpora)."""
        return self.live.epoch if self.live is not None else 0

    def submit(self, rid: int, query_emb: np.ndarray, *, top_k: int = 5,
               multi_probe: int = 1, epoch: int | None = None):
        """A client submits a query formed against its cached hint's epoch.

        ``epoch=None`` (the default) models a freshly synced client and
        stamps the published head; the traffic generator passes each
        session's actual cached epoch, so lazily syncing clients hit the
        stale-reject/retry path exactly as they would in production.
        """
        self.batcher.submit(Request(rid, query_emb, self.clock(),
                                    epoch=self.epoch if epoch is None
                                    else epoch, top_k=top_k,
                                    multi_probe=multi_probe))

    def submit_lookup(self, rid: int, ids, *, epoch: int | None = None):
        """A client submits a keyed embedding lookup (row id multiset).

        Lookups batch through the same deadline/admission policy as
        queries; each tick serves all queued lookups in ONE bucketed pass
        of the keyed batch-PIR subsystem.  Needs a `build_keyed` system.
        """
        self.batcher.submit(Request(rid, None, self.clock(),
                                    epoch=self.epoch if epoch is None
                                    else epoch,
                                    lookup_ids=tuple(int(i) for i in ids)))

    def submit_mutation(self, mut):
        """Queue a journal record; folded into an epoch at the next tick."""
        assert self.live is not None, "mutations need a LiveIndex"
        self.mutations.append(mut)

    def _commit_mutations(self):
        """Fold queued mutations into one epoch between query batches.

        An injected stage failure (`InjectedCommitFault`) leaves the batch
        pending in the journal; the commit retries on a later tick under
        exponential tick backoff (`_commit_failed`) — bounded by the fault
        plan, never dropped.
        """
        if self.live is None or not (self.mutations or self._commit_retry):
            return None
        if self.commit_gate is not None and not self.commit_gate():
            return None                  # deferred: serve stale-epoch answers
        if self._tick_no < self._commit_not_before:
            return None                  # backing off after a failed commit
        while self.mutations:
            self.live.journal.append(self.mutations.popleft())
        try:
            patch = self.live.commit()
        except InjectedCommitFault:
            self._commit_failed()
            return None
        self._commit_retry = False
        self._commit_attempts = 0
        return patch

    def _commit_failed(self):
        """Record one failed commit attempt and arm the tick backoff."""
        self._commit_attempts += 1
        self._commit_retry = True
        self._commit_not_before = (self._tick_no
                                   + min(2 ** (self._commit_attempts - 1), 16))
        self.obs.counter("fleet.commit_failures").inc()

    # -- policy core shared by both engines ----------------------------------

    def _admit(self, batch: list[Request], cur: int,
               now: float) -> list[Request]:
        """Epoch admission control: reject-and-requeue stale requests.

        A query encrypted against a superseded hint would decode garbage,
        so it is rejected; the client syncs its cached hint
        (HintCache.sync) and re-encrypts against the head.  Retried
        requests go back to the queue head in their original FIFO order —
        after their backoff, if the policy sets one — UNLESS the retry
        charge exhausts their budget or deadline, which ends them with a
        terminal failed response (no more ping-pong under epoch churn).
        """
        fresh = [r for r in batch if r.epoch == cur]
        stale = [r for r in batch if r.epoch != cur]
        if stale:
            self.stale_retries += len(stale)
            self.obs.counter("serve.stale_retries").inc(len(stale))
            for r in stale:
                r.retries += 1
            kept, give_up = self._split_budget(stale, now)
            for r in kept:
                r.epoch = cur
            self._requeue_retries(kept, now)
            self._fail(give_up, cur, now)
        return fresh

    def _split_budget(self, reqs: list[Request],
                      now: float) -> tuple[list[Request], list[Request]]:
        """(still in budget, out of budget) under the retry policy."""
        if self.retry is None:
            return reqs, []
        kept, give_up = [], []
        for r in reqs:
            if (self.retry.exhausted(r.retries)
                    or self.retry.past_deadline(r.t_arrival, now)):
                give_up.append(r)
            else:
                kept.append(r)
        return kept, give_up

    def _requeue_retries(self, reqs: list[Request], now: float):
        """Requeue retried requests, honouring the policy's backoff."""
        if not reqs:
            return
        if self.retry is None or self.retry.backoff_base_ms <= 0:
            self.batcher.requeue_front(reqs)
            return
        immediate = []
        for r in reqs:
            d = self.retry.backoff_s(r.rid, r.retries)
            if d <= 0:
                immediate.append(r)
            else:
                self._seq += 1
                heapq.heappush(self._backoff, (now + d, self._seq, r))
        if immediate:
            self.batcher.requeue_front(immediate)

    def _fail(self, reqs: list[Request], epoch: int, now: float):
        """Terminal failure: emit failed responses (never silence)."""
        if not reqs:
            return
        self.failed_requests += len(reqs)
        self.obs.counter("serve.failed").inc(len(reqs))
        hist = self.obs.histogram("serve.retries",
                                  bounds=(1, 2, 4, 8, 16, 32, 64))
        for r in reqs:
            hist.record(r.retries)
            self.responses.append(Response(
                r.rid, [], now, 0, epoch=epoch, retries=r.retries,
                t_arrival=r.t_arrival, failed=True))

    def _release_held(self, now: float, force: bool = False):
        """Move matured backoff/delayed requests back to the queue head.

        Pure heap pops against the tick's existing `now` read — with empty
        heaps (the no-fault, no-backoff path) this is two truthiness
        checks, so the response stream stays bit-identical.  Maturity is
        respected even during drain (the clock keeps advancing there, so
        held requests always mature); `force` only matters to callers via
        the batcher-ready bypass, not here.
        """
        del force
        due = []
        for heap in (self._delayed, self._backoff):
            while heap and heap[0][0] <= now:
                due.append(heapq.heappop(heap))
        if due:
            due.sort(key=lambda e: e[1])   # original admission order
            self.batcher.requeue_front([r for _, _, r in due])

    def _inject_answer_faults(self, fresh: list[Request], cur: int,
                              now: float) -> list[Request]:
        """Guard the answer drop/delay sites on the just-cut batch.

        A DROP loses the whole batch pre-dispatch: each request is charged
        one retry and re-queued (or terminally failed).  A DELAY holds the
        batch in the delayed heap for the event's `delay_s` of loop-clock
        time — late, not lost, so no retry is charged.
        """
        if self.faults is None or not fresh:
            return fresh
        if self.faults.fire("serve.answer.drop"):
            self.obs.counter("fleet.answer_drops").inc(len(fresh))
            for r in fresh:
                r.retries += 1
            kept, give_up = self._split_budget(fresh, now)
            self._requeue_retries(kept, now)
            self._fail(give_up, cur, now)
            return []
        delay = self.faults.fire("serve.answer.delay")
        if delay:
            self.obs.counter("fleet.answer_delays").inc(len(fresh))
            ready = now + max(ev.delay_s for ev in delay)
            for r in fresh:
                self._seq += 1
                heapq.heappush(self._delayed, (ready, self._seq, r))
            return []
        return fresh

    def _probe_groups(self, fresh: list[Request]
                      ) -> list[tuple[tuple[str, int], list[Request]]]:
        """One GEMM per request kind/shape: single-probe queries share the
        classic column-stacked GEMM; each distinct multi_probe value shares
        the bucketed batch-PIR GEMM; keyed lookups share the keyed bucketed
        GEMM (all clients in one streamed pass).  Keys are ("lookup", 0) or
        ("query", multi_probe) — sorted, so group order is deterministic."""
        groups: dict[tuple[str, int], list[Request]] = {}
        for r in fresh:
            k = (("lookup", 0) if r.lookup_ids is not None
                 else ("query", r.multi_probe))
            groups.setdefault(k, []).append(r)
        return [(k, groups[k]) for k in sorted(groups)]

    def _next_bid(self) -> int:
        """The next dispatched group's batch id (sequential per engine)."""
        bid, self._bid = self._bid, self._bid + 1
        return bid

    def _plan_group(self, system, kind: tuple[str, int],
                    reqs: list[Request], kq):
        """Encode + dispatch one request group → its `InflightBatch`.

        The one place both engines form batches, so the sync and pipelined
        paths cannot diverge per kind: lookups route through
        `lookup_batch_async` (results are (κ, d) row arrays), queries
        through `query_batch_async` (results are top-k doc lists), which
        opens the plan's pick/encrypt/dispatch spans on this engine's
        `Obs`."""
        if kind[0] == "lookup":
            return system.lookup_batch_async(
                [r.lookup_ids for r in reqs], key=kq)
        embs = np.stack([r.query_emb for r in reqs])
        return system.query_batch_async(embs, top_k=[r.top_k for r in reqs],
                                        multi_probe=kind[1], key=kq,
                                        obs=self.obs)

    def _serving_system(self):
        return self.live.system if self.live is not None else self.system

    # -- the synchronous tick -------------------------------------------------

    def tick(self, force: bool = False) -> int:
        """Serve one batch if ready; returns number of requests served.

        force=True flushes a partial batch regardless of the deadline
        (used by drain) WITHOUT touching the configured deadline_ms.

        The tick is one root span; plan (encode) / gemm (device wait) /
        complete (decode + re-rank) are nested spans whose boundaries ARE
        the `BatchTiming` components — one timeline, two consumers.
        """
        self._tick_no += 1
        with self.obs.span("serve.tick", mirror=False,
                           engine=self.ENGINE) as tick_sp:
            self.obs.gauge("serve.queue_depth").set(self.batcher.depth)
            self._commit_mutations()
            now = self.clock()
            self._release_held(now, force=force)
            if (not self.batcher.ready(now)
                    and not (force and self.batcher.queue)):
                return 0
            cur = self.epoch
            fresh = self._admit(self.batcher.cut(), cur, now)
            fresh = self._inject_answer_faults(fresh, cur, now)
            if not fresh:
                return 0
            tick_sp.set(batch=len(fresh), epoch=cur)

            system = self._serving_system()
            for kind, reqs in self._probe_groups(fresh):
                self._key, kq = jax.random.split(self._key)
                # query_batch ≡ query_batch_async().complete(); the async
                # form only adds the component span boundaries — responses
                # stay bit-identical to the one-call path
                bid = self._next_bid()
                with self.obs.span("serve.plan", batch=len(reqs),
                                   kind=kind[0], multi_probe=kind[1],
                                   bid=bid) as sp_plan:
                    infl = self._plan_group(system, kind, reqs, kq)
                with self.obs.span("serve.gemm", batch=len(reqs),
                                   bid=bid) as sp_gemm:
                    jax.block_until_ready(infl.pending)
                with self.obs.span("serve.complete", batch=len(reqs),
                                   bid=bid) as sp_done:
                    results = infl.complete()
                self._record(reqs, results, cur, sp_done.t1, BatchTiming(
                    t_plan=sp_plan.t0, encode_s=sp_plan.dur,
                    gemm_s=sp_gemm.dur, decode_s=sp_done.dur, bid=bid))
            return len(fresh)

    def _generate_dispatch(self, reqs: list[Request], results: list):
        """Tokenize + prefill + ENQUEUE the decode chain (no device block).

        Returns the in-flight handle `_generate_wait` resolves into ids
        and a `RagTiming`.  Both engines share this; they differ only in
        WHEN they wait: the sync loop blocks immediately (serial
        end-to-end), the pipelined loop parks the handle and blocks at
        the NEXT tick's retire, so the decode chain's device time runs
        while the host encodes/recovers the following batch.
        """
        gen = self.generator
        with self.obs.span("rag.tokenize", batch=len(reqs)) as sp_tok:
            grid, lengths, prompts = gen.pack(results)
        n_prompt = int(lengths.sum())
        self.obs.counter("rag.docs_dropped").inc(
            sum(p.n_docs_dropped for p in prompts))
        with self.obs.span("rag.prefill", batch=len(reqs),
                           prompt_tokens=n_prompt) as sp_pre:
            state = gen.prefill(grid, lengths)
        t0 = self.clock()
        ids_dev = gen.decode_async(state, [r.rid for r in reqs])
        dispatch_s = self.clock() - t0
        return ids_dev, sp_tok.dur, sp_pre.dur, dispatch_s, n_prompt

    def _generate_wait(self, reqs: list[Request], handle
                       ) -> tuple[np.ndarray, RagTiming, float]:
        """Block on a dispatched decode chain → (ids, RagTiming, t_done).

        The `rag.generate` span covers the residual device wait (near
        zero when the pipeline hid it); `generate_s` adds the host-side
        step-dispatch time so the component is the full decode-loop cost
        either way.  Spans carry token COUNTS and timings only — ids and
        text never reach the trace.
        """
        gen = self.generator
        ids_dev, tok_s, pre_s, dispatch_s, n_prompt = handle
        with self.obs.span("rag.generate", batch=len(reqs),
                           new_tokens=gen.max_new_tokens) as sp_gen:
            ids = np.asarray(jax.block_until_ready(ids_dev))
        self.obs.counter("rag.generated_tokens").inc(
            len(reqs) * gen.max_new_tokens)
        rag = RagTiming(tokenize_s=tok_s, prefill_s=pre_s,
                        generate_s=dispatch_s + sp_gen.dur,
                        prompt_tokens=n_prompt,
                        new_tokens=int(gen.max_new_tokens))
        return ids, rag, sp_gen.t1

    def _generate(self, reqs: list[Request], results: list,
                  t_done: float) -> tuple[np.ndarray, RagTiming, float]:
        """Run the generation completion stage on one served query group.

        tokenize → prefill → decode, each under its `rag.*` span.
        Returns (ids (B, N), shared RagTiming, new t_done = end of
        generation).  Tokens depend only on the retrieved docs, rids and
        the generator seed, so sync/pipelined/fleet agree bit-for-bit.
        """
        del t_done                       # superseded: answer isn't ready
        return self._generate_wait(      # ...until generation finishes
            reqs, self._generate_dispatch(reqs, results))

    def _record(self, reqs: list[Request], results: list, epoch: int,
                t_done: float, timing: BatchTiming, staleness: int = 0):
        """Complete one served group: generate (if configured) + append."""
        ids, rag = None, None
        if (self.generator is not None and reqs
                and reqs[0].lookup_ids is None):
            ids, rag, t_done = self._generate(reqs, results, t_done)
        self._append(reqs, results, epoch, t_done, timing, ids, rag,
                     staleness)

    def _append(self, reqs: list[Request], results: list, epoch: int,
                t_done: float, timing: BatchTiming, ids, rag,
                staleness: int = 0):
        """Append one served group's responses (shared batch timing).

        The single append point for every engine and both generation
        postures (inline and deferred) — response construction cannot
        diverge between them.
        """
        self.obs.counter("serve.responses").inc(len(reqs))
        self.obs.histogram("serve.batch_size",
                           bounds=(1, 2, 4, 8, 16, 32, 64, 128)
                           ).record(len(reqs))
        lat_hist = self.obs.histogram("serve.latency_ms")
        retry_hist = self.obs.histogram("serve.retries",
                                        bounds=(1, 2, 4, 8, 16, 32, 64))
        for i, (req, top) in enumerate(zip(reqs, results)):
            lat_hist.record((t_done - req.t_arrival) * 1e3)
            retry_hist.record(req.retries)
            # batch_size = this group's GEMM width, not the tick total
            self.responses.append(Response(
                req.rid, top, t_done, len(reqs), epoch=epoch,
                retries=req.retries, t_arrival=req.t_arrival, timing=timing,
                staleness=staleness,
                tokens=(tuple(int(t) for t in ids[i])
                        if ids is not None else None),
                rag=rag))

    def drain(self):
        """Serve everything still queued, force-flushing partial batches.

        Bypasses the commit gate: drain means "finish ALL the work", so a
        controller deferring commits must not keep it spinning forever.
        """
        gate, self.commit_gate = self.commit_gate, None
        try:
            while (self.batcher.queue or self.mutations
                   or self._backoff or self._delayed or self._commit_retry):
                self.tick(force=True)
        finally:
            self.commit_gate = gate


class PipelinedServeLoop(PIRServeLoop):
    """Plan/dispatch/complete pipelined serving over the same policy core.

    ``depth`` bounds the number of dispatched-but-undecoded batches: the
    tick that pushes batch N completes batch N−depth, so at steady state
    the device always has a GEMM in flight while the host decodes an older
    batch and encodes a younger one.  depth=1 still overlaps one GEMM with
    host work; larger depths additionally ride out commit spikes.

    Mutation commits go through `ShadowCommitter`: patches are computed
    into shadow buffers (donated in place where the aliasing contract
    allows) and published as a pointer swap at the exact tick boundary the
    synchronous loop commits on — which is why responses, epochs and retry
    counts stay bit-identical.
    """

    ENGINE = "pipelined"

    def __init__(self, system, *, depth: int = 2, donate: bool = True,
                 gen_coalesce: int = 1, **kwargs):
        super().__init__(system, **kwargs)
        self.depth = max(1, int(depth))
        self.gen_coalesce = max(1, int(gen_coalesce))
        self._inflight: deque = deque()
        self._gen_pending: deque = deque()
        self._shadow = (ShadowCommitter(self.live, donate=donate)
                        if self.live is not None else None)

    @property
    def inflight(self) -> int:
        """Batches dispatched on device but not yet decoded."""
        return len(self._inflight)

    def set_depth(self, depth: int):
        """Adjust the in-flight bound (admission-controller depth hook).

        Takes effect at the next tick/retire: a shrink retires the excess
        batches then, a grow simply lets more dispatches accumulate.
        Dynamic depth trades completion latency (responses wait behind up
        to `depth` batches) against overlap headroom (commit spikes and
        slow decodes ride out without stalling dispatch).
        """
        self.depth = max(1, int(depth))

    def _commit_mutations(self):
        if self._shadow is None or not (self.mutations or self._commit_retry):
            return None
        if self.commit_gate is not None and not self.commit_gate():
            return None                  # deferred: serve stale-epoch answers
        if self._tick_no < self._commit_not_before:
            return None                  # backing off after a failed commit
        try:
            patch = self._shadow.commit(self.mutations)
        except InjectedCommitFault:
            self._commit_failed()
            return None
        self._commit_retry = False
        self._commit_attempts = 0
        return patch

    def tick(self, force: bool = False) -> int:
        """Plan + dispatch one batch if ready; complete anything past depth.

        Returns the number of requests DISPATCHED (their responses land
        when the pipeline retires them — per-request completion timestamps
        are taken at the complete stage).  The plan span's boundaries seed
        each in-flight batch's `BatchTiming`; its gemm/complete spans are
        opened by the LATER tick that retires it, which is exactly the
        nesting the trace shows (a complete span parented by a younger
        tick than its plan span — the pipeline overlap made visible).
        """
        self._tick_no += 1
        with self.obs.span("serve.tick", mirror=False,
                           engine=self.ENGINE) as tick_sp:
            self.obs.gauge("serve.queue_depth").set(self.batcher.depth)
            self._commit_mutations()
            now = self.clock()
            self._release_held(now, force=force)
            if (not self.batcher.ready(now)
                    and not (force and self.batcher.queue)):
                # idle tick: nothing to dispatch, so retire EVERYTHING in
                # flight — during a traffic lull responses must not sit
                # decoded-but-unreported behind the depth bound
                self._retire(0)
                return 0
            cur = self.epoch
            fresh = self._admit(self.batcher.cut(), cur, now)
            fresh = self._inject_answer_faults(fresh, cur, now)
            if not fresh:
                return 0
            tick_sp.set(batch=len(fresh), epoch=cur)

            system = self._serving_system()
            for kind, reqs in self._probe_groups(fresh):
                self._key, kq = jax.random.split(self._key)
                bid = self._next_bid()
                with self.obs.span("serve.plan", batch=len(reqs),
                                   kind=kind[0], multi_probe=kind[1],
                                   bid=bid) as sp_plan:
                    infl = self._plan_group(system, kind, reqs, kq)
                self._inflight.append((reqs, cur, infl, sp_plan.t0,
                                       sp_plan.dur, bid))
            self.obs.gauge("serve.inflight").set(len(self._inflight))
            self._retire(self.depth)
            return len(fresh)

    def _record(self, reqs: list[Request], results: list, epoch: int,
                t_done: float, timing: BatchTiming, staleness: int = 0):
        """Park generation instead of blocking the tick on it.

        A query group retiring with a generator lands on ``_gen_pending``;
        `_retire_gen` completes it on a LATER tick, coalescing up to
        ``gen_coalesce`` parked groups into ONE generation micro-batch —
        retrieval for the next batches proceeds while generation waits,
        and the coalesced micro-batch pays one prefill + one decode-step
        chain where the serial engine pays one PER GROUP.  Tokens are
        bit-identical to the sync engine's: per-row transformer math does
        not depend on who shares the batch (pinned by the rag serve
        tests), and sampled rows key off (seed, rid, step) only.
        Responses simply land a tick later, like retrieval responses
        already do in this engine.
        """
        if (self.generator is not None and reqs
                and reqs[0].lookup_ids is None):
            self._gen_pending.append((reqs, results, epoch, timing,
                                      staleness))
            return
        super()._record(reqs, results, epoch, t_done, timing, staleness)

    def _retire_gen(self, count: int):
        """Coalesce the `count` oldest parked groups into one micro-batch.

        One pack/prefill/decode chain serves every coalesced group; the
        (B_total, N) id grid is split back per group, which keeps each
        group's epoch/staleness/BatchTiming intact.  The micro-batch's
        RagTiming is shared by its responses, exactly like BatchTiming is
        shared by a retrieval batch.
        """
        groups = [self._gen_pending.popleft() for _ in range(count)]
        reqs_all = [r for g in groups for r in g[0]]
        results_all = [res for g in groups for res in g[1]]
        ids, rag, t_done = self._generate_wait(
            reqs_all, self._generate_dispatch(reqs_all, results_all))
        i = 0
        for reqs, results, epoch, timing, staleness in groups:
            self._append(reqs, results, epoch, t_done, timing,
                         ids[i:i + len(reqs)], rag, staleness)
            i += len(reqs)

    def _retire(self, limit: int):
        """Complete (decode + record) oldest in-flight batches beyond limit.

        The gemm component recorded here is the RESIDUAL device wait at
        retire time: at steady state the GEMM (and the batched recover
        chained behind it) overlapped host work for `depth` ticks
        already, so near-zero gemm_s is the pipeline doing its job (the
        sync engine reports the full device time instead).  Generation
        groups parked by `_record` on EARLIER ticks complete after this
        tick's retrieval completions, in micro-batches of
        ``gen_coalesce`` groups; a partial micro-batch keeps waiting for
        more groups — except on an idle tick or drain (limit 0), which
        flushes everything (during a lull responses must not sit
        generated-but-unreported behind the coalescing bound).
        """
        n_parked = len(self._gen_pending)
        while len(self._inflight) > limit:
            (reqs, epoch, infl, t_plan, encode_s,
             bid) = self._inflight.popleft()
            with self.obs.span("serve.gemm", batch=len(reqs),
                               bid=bid) as sp_gemm:
                jax.block_until_ready(infl.pending)
            with self.obs.span("serve.complete", batch=len(reqs),
                               bid=bid) as sp_done:
                results = infl.complete()
            self._record(reqs, results, epoch, sp_done.t1, BatchTiming(
                t_plan=t_plan, encode_s=encode_s, gemm_s=sp_gemm.dur,
                decode_s=sp_done.dur, bid=bid))
        while n_parked >= self.gen_coalesce:
            self._retire_gen(self.gen_coalesce)
            n_parked -= self.gen_coalesce
        if limit == 0:
            while self._gen_pending:
                self._retire_gen(min(len(self._gen_pending),
                                     self.gen_coalesce))

    def drain(self):
        """Serve and complete everything: queue, mutations, and pipeline.

        Bypasses the commit gate like the synchronous drain.
        """
        gate, self.commit_gate = self.commit_gate, None
        try:
            while (self.batcher.queue or self.mutations
                   or self._backoff or self._delayed or self._commit_retry):
                self.tick(force=True)
        finally:
            self.commit_gate = gate
        with self.obs.span("serve.drain", engine=self.ENGINE):
            self._retire(0)
