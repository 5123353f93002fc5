"""The comparison that decides ``correct``: what the timed path produced
against ``reference.py``, once the window has closed and been drained.

Every number compared is a count of disagreements with the plain reference
(exact comparisons), so every limit is 0:

* ``requests_unanswered``: requests due in the window with no answer, or a
  failed one, after the drain;
* ``passages_misassigned``: passages (a sample drawn from the seed) that the
  build put in another cluster than their nearest centroid's;
* ``centroids_off_mean``: clusters whose centroid is not the mean of the
  passages the build put in them (with the above: the partition is a
  k-means fixed point of the corpus, so the centroids the reference picks
  clusters by are the corpus's own, not just the program's);
* ``answer_words_wrong``: words of the server's answer, for a sample of
  batches drawn from the seed, on 2048 sampled rows and the last 256;
* ``cluster_bytes_wrong``: bytes of those batches' decoded clusters that
  differ from the reference column of the cluster the query must fetch;
* ``topk_wrong``: top-k lists (those batches', and 64 more sampled from
  all answers) that are not a top k of that cluster, scores and texts
  included;
* ``lwe_k_differs``: 1 if the program runs another LWE secret dimension
  than the configuration states;
* ``answer_kernel_absent`` (on a TPU): 1 if the answer program the server
  dispatches holds no ``tpu_custom_call`` (the Pallas kernel).  On one
  chip that is ``ops.modmatmul`` over the DB; on a row-sharded DB it is the
  server's own compiled ``shard_map`` (``PIRServer._answer_fn``) over its
  sharded DB and a replicated (n, max_batch) query;
* ``answer_collectives`` (row-sharded DB only): all-gathers, all-reduces,
  reduce-scatters, collective-permutes and all-to-alls in that compiled
  program.  Each chip answers its own rows, so the deployment claims none.
"""
from __future__ import annotations

import re
import sys
import time

import numpy as np

import reference

ANSWER_ROWS = 2048
TOPK_SAMPLE = 64
#: A collective instruction in compiled HLO text (an async one counts once,
#: at its ``-start``).
COLLECTIVE = re.compile(r"\b(all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start)?\(")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def compare(cell, run, tap, system, corp, queries, seed: int, *,
            on_tpu: bool = False) -> dict:
    """Each compared number with its limit."""
    t = time.perf_counter()
    rng = np.random.default_rng([seed, 3])
    cents = np.asarray(system.centroids)
    n_req = len(run.due)
    checks = {"requests_unanswered": sum(
        1 for i in range(n_req)
        if i not in run.responses or run.responses[i].failed)}
    (checks["passages_misassigned"],
     checks["centroids_off_mean"]) = reference.partition_faults(
        corp.embeddings, system.assignment, cents, rng)
    log(f"partition checked in {time.perf_counter() - t:.1f}s")
    t = time.perf_counter()
    ref = reference.ReferenceIndex(corp.texts, corp.embeddings,
                                   system.assignment, cents,
                                   cell.config["n_clusters"])
    log(f"reference index: m={ref.m} (program m={system.db.m}) in "
        f"{time.perf_counter() - t:.1f}s")
    t = time.perf_counter()
    words = nbytes = topk = 0
    n_words = n_bytes = n_topk = 0
    m = min(ref.m, system.db.m)
    for cap in tap.captured:
        if "ans" not in cap:
            continue
        qu, ans = np.asarray(cap["qu"]), np.asarray(cap["ans"])
        cols = np.asarray(cap["cols"])
        rows = np.union1d(rng.choice(m, min(ANSWER_ROWS, m), replace=False),
                          np.arange(max(0, m - 256), m))
        want = ref.answer_rows(rows, qu)
        words += int((ans[rows] != want).sum()) + (
            ans.shape[0] != ref.m) * ans.size
        n_words += want.size
        if "results" not in cap:        # its complete stage never returned
            continue
        for b in range(cols.shape[1]):
            cands = ref.clusters_for(cap["embs"][b])
            nbytes += (cols.shape[0] if cols.shape[0] != ref.m else
                       min(int((cols[:, b] != ref.column(j)).sum())
                           for j in cands))
            n_bytes += cols.shape[0]
            topk += not ref.topk_ok_any(cap["embs"][b], cap["results"][b],
                                        cap["top_k"][b])
            n_topk += 1
    served = sorted(rid for rid, r in run.responses.items() if not r.failed)
    for rid in rng.choice(served, min(TOPK_SAMPLE, len(served)),
                          replace=False):
        r = run.responses[int(rid)]
        topk += not ref.topk_ok_any(queries[rid], r.top,
                                    cell.traffic["top_k"])
        n_topk += 1
    checks["answer_words_wrong"] = words
    checks["cluster_bytes_wrong"] = nbytes
    checks["topk_wrong"] = topk
    checks["lwe_k_differs"] = int(system.cfg.params.k
                                  != cell.config["lwe_k"])
    log(f"compared {n_words} answer words, {n_bytes} cluster bytes, "
        f"{n_topk} top-k lists in {time.perf_counter() - t:.1f}s")
    sharded = system.server.mesh is not None
    if on_tpu or sharded:
        t = time.perf_counter()
        text = answer_program(system, cell.config["engine"]["max_batch"])
        if on_tpu:
            checks["answer_kernel_absent"] = int("tpu_custom_call" not in text)
        if sharded:
            checks["answer_collectives"] = len(COLLECTIVE.findall(text))
        log(f"answer program inspected in {time.perf_counter() - t:.1f}s")
    return {k: (v, 0) for k, v in checks.items()}


def answer_program(system, max_batch: int) -> str:
    """The compiled text of the answer program the server runs."""
    import jax
    import jax.numpy as jnp
    server = system.server
    if server.mesh is None:
        from repro.kernels import ops
        q = jnp.zeros((system.db.n, 16), jnp.uint32)
        return jax.jit(lambda d, x: ops.modmatmul(d, x, impl=system.cfg.impl)
                       ).lower(server.db, q).compile().as_text()
    from jax.sharding import NamedSharding, PartitionSpec
    q = jax.device_put(jnp.zeros((system.db.n, max_batch), jnp.uint32),
                       NamedSharding(server.mesh, PartitionSpec()))
    return server._answer_fn.lower(server.db, q).compile().as_text()
