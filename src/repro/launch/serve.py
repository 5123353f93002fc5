"""Thin CLI over the serving engines in `repro.serve`.

The engines themselves live in `repro.serve.engine` (synchronous reference
loop + pipelined plan/dispatch/complete engine with shadow-epoch commits);
this module keeps the historical import surface alive, parses flags, and
holds the two steps every caller of the serve path shares — `build_loop`
(offline build wrapped in an engine) and `run_requests` (submit, tick,
drain) — which `chip_smoke.py` calls too:

    PYTHONPATH=src python -m repro.launch.serve --docs 2000 --requests 64 \
        --engine pipelined --mutate-every 8

`--engine sync` serves through the blocking reference loop; `pipelined`
(default) overlaps batch N's answer GEMM with decoding batch N−depth and
encoding batch N+1, and commits mutations via shadow buffers + pointer
swap.  Results are bit-identical either way — only the timeline changes.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
# Re-exported for backward compatibility: the serving classes began life in
# this module and tests/examples import them from here.
from repro.serve.engine import (DeadlineBatcher, PIRServeLoop,  # noqa: F401
                                PipelinedServeLoop, Request, Response)


def build_loop(texts, embeddings, *, n_clusters: int,
               engine: str = "pipelined", live: bool = False, mesh=None,
               replicas: int = 2, faults=None, multi_probe: int = 1,
               **loop_kw):
    """Offline build of the index, wrapped in the serving engine.

    Returns ``(loop, live_index, replica_group)``; the last two are None
    unless ``live`` (mutations) or ``engine="fleet"`` asked for them.
    Kernels dispatch on the backend (``impl="auto"``): the Pallas MXU
    kernels on TPU, the exact XLA oracle elsewhere.
    """
    from repro.core import pipeline
    from repro.update import LiveIndex

    loop_cls = (PipelinedServeLoop if engine == "pipelined"
                else PIRServeLoop)
    index = group = None
    if engine == "fleet":
        from repro.fleet import FleetServeLoop, ReplicaGroup
        index = LiveIndex.build(texts, embeddings, n_clusters=n_clusters,
                                mesh=mesh)
        group = ReplicaGroup.from_live(index, n_replicas=replicas,
                                       n_shards=4)
        loop = FleetServeLoop(group, faults=faults, **loop_kw)
    elif live:
        index = LiveIndex.build(texts, embeddings, n_clusters=n_clusters,
                                mesh=mesh)
        loop = loop_cls(index, **loop_kw)
    else:
        system = pipeline.PirRagSystem.build(texts, embeddings,
                                             n_clusters=n_clusters, mesh=mesh)
        loop = loop_cls(system, **loop_kw)
    if multi_probe > 1:
        loop.system.enable_batch(kappa=max(4, multi_probe))
    return loop, index, group


def run_requests(loop, embeddings, n_requests: int, rng, *, top_k: int = 5,
                 multi_probe: int = 1, mutate_every: int = 0,
                 live=None) -> tuple[list[int], float]:
    """Serve ``n_requests`` queries, one per tick, then drain the loop.

    Request ``rid`` queries with the embedding of a random anchor document;
    with a live index, every ``mutate_every``-th request also replaces a
    random document (same embedding, new text), which commits through the
    delta-hint path.  Returns (anchor doc per rid, wall seconds).
    """
    from repro.update import journal as journal_lib

    n_docs = len(embeddings)
    anchors = []
    t0 = time.perf_counter()
    for rid in range(n_requests):
        anchors.append(int(rng.integers(0, n_docs)))
        loop.submit(rid, embeddings[anchors[-1]], top_k=top_k,
                    multi_probe=multi_probe)
        if live is not None and mutate_every and rid % mutate_every == 0:
            d = int(rng.integers(0, n_docs))
            loop.submit_mutation(journal_lib.replace(
                d, f"refreshed doc {d}".encode(), embeddings[d]))
        loop.tick()
    loop.drain()
    return anchors, time.perf_counter() - t0


def main(argv=None):  # pragma: no cover - exercised by examples/tests
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", type=int, default=2000)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--deadline-ms", type=float, default=50.0)
    ap.add_argument("--engine", choices=("sync", "pipelined", "fleet"),
                    default="pipelined",
                    help="blocking reference loop vs plan/dispatch/complete "
                         "pipeline (bit-identical responses); `fleet` wraps "
                         "the pipelined engine in a replica group with "
                         "failover + journal-replay recovery (docs/fleet.md)")
    ap.add_argument("--depth", type=int, default=2,
                    help="pipelined engine: max batches in flight")
    ap.add_argument("--mutate-every", type=int, default=0,
                    help="if >0, replace a random doc every N requests "
                         "(exercises the live-index delta path)")
    ap.add_argument("--multi-probe", type=int, default=1,
                    help="clusters fetched per query; >1 routes through "
                         "the batch-PIR subsystem (one bucketed pass)")
    ap.add_argument("--top-k", type=int, default=5)
    ap.add_argument("--shard", type=int, default=0,
                    help="shard over this many local devices (0 = single-"
                         "device).  Covers the OFFLINE build too: K-means "
                         "fits mesh-parallel and the DB is packed and "
                         "placed shard-by-shard (docs/architecture.md), "
                         "then served through the zero-collective answer "
                         "path — results bit-identical either way")
    ap.add_argument("--replicas", type=int, default=2,
                    help="fleet engine: replica ranks (R full copies of "
                         "the index on disjoint device rows)")
    ap.add_argument("--shard-loss", metavar="TICK:DEV:TICKS", default=None,
                    help="fleet engine: inject one shard loss, e.g. "
                         "'8:0:16' = device 0 down for 16 fleet ticks "
                         "starting at tick 8 (exercises failover + "
                         "journal-replay failback)")
    ap.add_argument("--chaos", type=int, metavar="SEED", default=None,
                    help="fleet engine: inject a seeded random fault plan "
                         "(shard loss, answer drops/delays, commit "
                         "failures, chain corruption)")
    ap.add_argument("--generate", type=int, metavar="N", default=0,
                    help="if >0, close the RAG loop: feed each request's "
                         "retrieved docs through the tiny byte-level LM "
                         "and emit N tokens per response (docs/rag.md); "
                         "the pipelined engine defers + coalesces "
                         "generation micro-batches")
    ap.add_argument("--gen-coalesce", type=int, default=4,
                    help="pipelined/fleet engines: parked generation "
                         "groups merged into one decode micro-batch")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="export a Chrome-trace (chrome://tracing / "
                         "Perfetto) of the run's spans to this path; "
                         "privacy-scrubbed at record time "
                         "(docs/observability.md)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the metrics registry (counters/gauges/"
                         "histograms) as JSON after the run")
    args = ap.parse_args(argv)
    enable_compile_cache()

    from repro.data import corpus as corpus_lib
    from repro.launch.mesh import make_chunk_mesh

    mesh = make_chunk_mesh(args.shard) if args.shard > 1 else None

    from repro.obs import Obs

    corp = corpus_lib.make_corpus(0, args.docs, emb_dim=64, n_topics=24)
    rng = np.random.default_rng(0)
    obs = Obs(trace=args.trace is not None)
    loop_kw = dict(max_batch=args.max_batch, deadline_ms=args.deadline_ms,
                   obs=obs)
    gen = None
    if args.generate > 0:
        from repro.rag import Generator
        gen = Generator.tiny(seed=0, max_new_tokens=args.generate)
        loop_kw["generator"] = gen
    if args.engine in ("pipelined", "fleet"):
        loop_kw["depth"] = args.depth
        if gen is not None:
            loop_kw["gen_coalesce"] = args.gen_coalesce
    faults = None
    if args.engine == "fleet":
        from repro.fleet import FaultPlan
        if args.shard_loss is not None:
            at, dev, down = (int(x) for x in args.shard_loss.split(":"))
            faults = FaultPlan.single_shard_loss(
                at_tick=at, device=dev, down_ticks=down).compile()
        elif args.chaos is not None:
            faults = FaultPlan.random(
                args.chaos, n_events=6, horizon=max(args.requests // 2, 8),
                n_devices=args.replicas * 4).compile()
    loop, live, group = build_loop(
        corp.texts, corp.embeddings, n_clusters=24, engine=args.engine,
        live=args.mutate_every > 0, mesh=mesh, replicas=args.replicas,
        faults=faults, multi_probe=args.multi_probe, **loop_kw)

    t0 = time.perf_counter()
    _, dt = run_requests(loop, corp.embeddings, args.requests, rng,
                         top_k=args.top_k, multi_probe=args.multi_probe,
                         mutate_every=args.mutate_every, live=live)
    if not loop.responses:
        print(f"served 0 requests in {dt:.2f}s")
        return
    lat = [r.t_done - t0 for r in loop.responses]
    sizes = [r.batch_size for r in loop.responses]
    print(f"[{args.engine}] served {len(loop.responses)} requests "
          f"in {dt:.2f}s; mean batch {np.mean(sizes):.1f}; "
          f"p50/p99 completion {np.percentile(lat, 50):.2f}/"
          f"{np.percentile(lat, 99):.2f}s"
          + (f"; epoch {loop.epoch}; stale retries {loop.stale_retries}"
             if live is not None else ""))
    if gen is not None:
        rags = [r.rag for r in loop.responses if r.rag is not None]
        n_tok = sum(len(r.tokens) for r in loop.responses
                    if r.tokens is not None)
        print(f"generation: {n_tok} tokens across "
              f"{len(loop.responses)} responses; "
              f"{sum(g.prompt_tokens for g in rags)} prompt tokens; "
              f"mean gen stage "
              f"{1e3 * float(np.mean([g.generate_s for g in rags])):.1f}ms")
    if group is not None:
        stale = sum(r.staleness > 0 for r in loop.responses)
        print(f"fleet: authority rank {group.authority_rank}; "
              f"{group.failovers} failover(s), {group.failbacks} "
              f"failback(s), {loop.failed_requests} failed, "
              f"{stale} served stale, "
              f"{len(group.replay_reports)} journal replay(s)")
    if args.trace is not None:
        from repro.obs import span_coverage
        obs.export_chrome(args.trace)
        cov = span_coverage(obs.tracer.spans)
        print(f"trace: {len(obs.tracer.spans)} spans + "
              f"{len(obs.tracer.instants)} instants -> {args.trace} "
              f"(root-span coverage {cov:.1%})")
    if args.metrics:
        import json
        print(json.dumps(obs.metrics_dict(), indent=1))


if __name__ == "__main__":
    main()
