"""Bring-up smoke of the private-retrieval serve path on TPU.

    python chip_smoke.py             # one chip: build, serve, mutate, check
    python chip_smoke.py --chips 4   # row-sharded build + serve on four chips

One process drives the document-RAG path through the functions
``python -m repro.launch.serve`` uses (`build_loop`, `run_requests`), at the
repo's production cluster count (4096 clusters, LWE k = 1024) with a seeded
synthetic passage corpus large enough that the u8 database on each chip is
at least 1 GiB.  Every GEMM goes through the backend's kernels: on TPU the
Pallas MXU kernels (answer, hint, delta, bucketed and k-means assignment).

Each check compares the system with a plain reference: sampled answer and
hint rows with a numpy uint64 product, decrypted clusters with the packed
database columns, and every request's top-k with the document its query was
drawn from.  Earlier lines report what ran; the last line is one JSON object
naming the device, printed only when every check passed.  The script exits
non-zero, printing no result, when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

GIB = 1 << 30
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter")


@dataclasses.dataclass(frozen=True)
class Size:
    """One smoke configuration (all corpora are generated from ``seed``)."""
    n_docs: int
    n_clusters: int = 4096
    emb_dim: int = 768
    text_len: tuple[int, int] = (200, 600)
    requests: int = 48
    mutate_every: int = 12
    max_batch: int = 16
    mp_docs: int = 10_000          # multi-probe phase's own corpus
    mp_requests: int = 8
    kappa: int = 4
    answer_rows: int = 4096        # sampled answer rows checked exactly
    hint_rows: int = 256           # sampled hint rows checked exactly
    clusters_checked: int = 8      # decrypted clusters checked byte for byte
    min_db_bytes_per_chip: int = GIB
    seed: int = 0


#: One v5e chip.  Unbalanced k-means on this generator at 4096 clusters
#: makes its largest cluster ~12x the mean (400k passages gave m = 1.39M
#: rows, a 5.3 GiB DB, on a v5e), and m sets the DB height, so ~150k
#: passages (768-d embedding + ~400 B of text) give a u8 DB of ~2 GiB —
#: at least 1 GiB — plus an (m, 1024) u32 hint of the same size, leaving
#: room for a commit's delta GEMM on the same chip.
ONE_CHIP = Size(n_docs=150_000)
#: Four chips, rows sharded: ~100-word passages as DPR splits Wikipedia,
#: sized by the same ~12x so each chip's DB row slice is at least 1 GiB.
FOUR_CHIPS = Size(n_docs=400_000, text_len=(400, 800), requests=32,
                  mutate_every=0)


def log(msg: str) -> None:
    print(msg, flush=True)


def _peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _corpus(size: Size, n_docs: int, seed: int):
    from repro.data import corpus as corpus_lib
    t0 = time.perf_counter()
    corp = corpus_lib.make_corpus(seed, n_docs, emb_dim=size.emb_dim,
                                  n_topics=size.n_clusters,
                                  text_len=size.text_len)
    log(f"corpus: {n_docs} docs, emb_dim {size.emb_dim}, text_len "
        f"{size.text_len} in {time.perf_counter() - t0:.1f}s")
    return corp


def _report_build(system, n_chips: int) -> int:
    db, server = system.db, system.server
    db_bytes = server.db.nbytes
    log(f"db: shape {tuple(server.db.shape)} (m={db.m}, n={db.n}), "
        f"{db_bytes} bytes ({db_bytes / GIB:.3f} GiB, "
        f"{db_bytes / n_chips / GIB:.3f} GiB per chip), "
        f"{int(db.used_bytes.sum())} bytes of records, "
        f"pad fraction {db.pad_fraction:.4f}, largest/mean cluster "
        f"{db.cluster_sizes.max() / db.cluster_sizes.mean():.2f}")
    log(f"hint: shape {tuple(system.hint.shape)}, {system.hint.nbytes} bytes "
        f"(LWE k={system.cfg.params.k}, p={system.cfg.params.p})")
    log(f"build: index {system.index_seconds:.1f}s, "
        f"hint {system.hint_seconds:.1f}s")
    return db_bytes // n_chips


def _anchors_in_top(loop, anchors) -> bool:
    ok = [anchors[r.rid] in [d for d, _, _ in r.top] for r in loop.responses]
    log(f"served {len(loop.responses)} responses; anchor doc in top-k for "
        f"{sum(ok)}/{len(ok)}")
    return (len(ok) == len(anchors) and all(ok)
            and not any(r.failed for r in loop.responses))


def _answer_rows_match(answer_fn, db, n_rows: int, rng,
                       n_shards: int = 1) -> bool:
    """Raw answer (D·q mod 2^32) vs numpy uint64 on sampled rows and on the
    last 256 rows of each shard's slice, where a partial kernel tile ends."""
    import jax.numpy as jnp
    import numpy as np
    q = rng.integers(0, 2**32, (db.shape[1], 8), dtype=np.uint32)
    got = np.asarray(answer_fn(jnp.asarray(q)))[:db.shape[0]]
    per = db.shape[0] // n_shards
    tails = [np.arange(max(0, e - 256), e)
             for e in range(per, db.shape[0] + 1, per)]
    rows = np.union1d(rng.choice(db.shape[0], min(n_rows, db.shape[0]),
                                 replace=False), np.concatenate(tails))
    want = (db[rows].astype(np.uint64) @ q.astype(np.uint64)) % (1 << 32)
    return bool(np.array_equal(got[rows], want.astype(np.uint32)))


def _hint_rows_match(system, n_rows: int, rng) -> bool:
    """Hint H = D·A mod 2^32 on sampled rows vs numpy uint64."""
    import numpy as np
    db = system.db.matrix
    a = np.asarray(system.server.a_matrix).astype(np.uint64)
    rows = rng.choice(db.shape[0], min(n_rows, db.shape[0]), replace=False)
    want = (db[rows].astype(np.uint64) @ a) % (1 << 32)
    got = np.asarray(system.hint[rows])
    return bool(np.array_equal(got, want.astype(np.uint32)))


def _assignment_matches_reference(x, cents, impl: str) -> bool:
    """The build's assignment kernel vs float64 numpy: every point goes to
    a centroid at the reference's minimum distance (up to f32 rounding)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    got = np.asarray(ops.kmeans_assign(jnp.asarray(x), jnp.asarray(cents),
                                       impl=impl)[0])
    x64, c64 = x.astype(np.float64), cents.astype(np.float64)
    d2 = ((x64 * x64).sum(1)[:, None] - 2 * x64 @ c64.T
          + (c64 * c64).sum(1)[None, :])
    best = d2.min(1)
    picked = d2[np.arange(len(x)), got]
    return bool(np.all(picked - best <= 1e-5 * (1.0 + np.abs(best))))


def _clusters_decrypt(system, clusters) -> bool:
    """Private fetch of whole clusters == the packed DB columns."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import pir
    client = pir.PIRClient(system.cfg, system.hint)
    key = jax.random.PRNGKey(1234)
    qs, secrets = [], []
    for i, j in enumerate(clusters):
        qu, st = client.query(jax.random.fold_in(key, i), int(j))
        qs.append(qu)
        secrets.append(st.secret)
    ans = system.server.answer(jnp.stack(qs, axis=1))
    cols = np.asarray(client.recover_batch(ans, jnp.stack(secrets, axis=1)))
    return all(np.array_equal(cols[:, i], system.db.matrix[:, j])
               for i, j in enumerate(clusters))


def _program_text(fn, *args) -> str:
    import jax
    return jax.jit(fn).lower(*args).compile().as_text()


def run_one_chip(size: Size) -> dict[str, bool]:
    """Build → serve with live mutations → multi-probe → checks, one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import chunking
    from repro.kernels import ops
    from repro.launch import serve

    dev = jax.devices()[0]
    rng = np.random.default_rng(size.seed)
    checks: dict[str, bool] = {}

    corp = _corpus(size, size.n_docs, size.seed)
    t0 = time.perf_counter()
    loop, live, _ = serve.build_loop(
        corp.texts, corp.embeddings, n_clusters=size.n_clusters,
        engine="pipelined", live=True, max_batch=size.max_batch)
    log(f"phase build: {time.perf_counter() - t0:.1f}s")
    system = live.system
    db_per_chip = _report_build(system, 1)
    checks["db_at_least_min_bytes"] = (
        db_per_chip >= size.min_db_bytes_per_chip)
    sample = rng.choice(size.n_docs, min(8192, size.n_docs), replace=False)
    checks["kmeans_assignment_matches_float64"] = (
        _assignment_matches_reference(corp.embeddings[sample],
                                      system.centroids, system.cfg.impl))
    log(f"peak_bytes_in_use after build: {_peak_bytes(dev)}")

    anchors, secs = serve.run_requests(
        loop, corp.embeddings, size.requests, rng, top_k=5,
        mutate_every=size.mutate_every, live=live)
    system = live.system
    log(f"phase serve: {size.requests} single-probe requests in "
        f"{secs:.1f}s; epoch {live.epoch}; stale retries "
        f"{loop.stale_retries}; commits {len(live.commits)}")
    checks["single_probe_anchor_in_top_k"] = _anchors_in_top(loop, anchors)
    checks["mutations_committed_as_deltas"] = live.epoch >= 1 and not any(
        c.full_rebuild for c in live.commits)

    # refreshed docs must come back, with their new text, from their cluster
    refreshed = sorted(d for d, (text, _) in live._docs.items()
                       if text.startswith(b"refreshed doc "))
    clusters = sorted({live._cluster_of[d] for d in refreshed})
    extra = rng.choice(system.db.n, size.clusters_checked, replace=False)
    clusters = (clusters + [int(c) for c in extra
                            if c not in clusters])[:size.clusters_checked]
    checks["decrypted_clusters_equal_db_columns"] = _clusters_decrypt(
        system, clusters)
    texts = {doc_id: text for j in clusters for doc_id, _, text in
             chunking.deserialize_docs(system.db.matrix[:, j],
                                       system.db.emb_dim)}
    checks["refreshed_docs_served"] = all(
        texts.get(d) == f"refreshed doc {d}".encode() for d in refreshed
        if live._cluster_of[d] in clusters)

    impl = system.cfg.impl
    db_dev = system.server.db
    checks["answer_rows_match_numpy_u64"] = _answer_rows_match(
        lambda q: ops.modmatmul(db_dev, q, impl=impl), system.db.matrix,
        size.answer_rows, rng)
    # a height that is not whole kernel row tiles, as a row shard can be
    odd = rng.integers(0, 256, (16 * 256 + 192, system.db.n), dtype=np.uint8)
    odd_dev = jnp.asarray(odd)
    checks["partial_row_tile_matches_numpy_u64"] = _answer_rows_match(
        lambda q: ops.modmatmul(odd_dev, q, impl=impl), odd, len(odd), rng)
    checks["hint_rows_match_numpy_u64"] = _hint_rows_match(
        system, size.hint_rows, rng)
    text = _program_text(lambda d, q: ops.modmatmul(d, q, impl=impl), db_dev,
                         jnp.zeros((system.db.n, 8), jnp.uint32))
    checks["answer_program_has_tpu_custom_call"] = "tpu_custom_call" in text
    log(f"answer program: tpu_custom_call "
        f"{checks['answer_program_has_tpu_custom_call']}")
    log(f"peak_bytes_in_use after serve: {_peak_bytes(dev)}")

    # Multi-probe: 3κ buckets each hold ~1/κ of the clusters at full height,
    # so sub-DBs are ~3x the flat DB, their (m_b, k) hints ~12x, plus the
    # padded stack: it cannot share the chip with the flat DB above.  It
    # gets its own corpus at the same cluster count.
    del loop, live, system, db_dev
    gc.collect()
    mp = _corpus(size, size.mp_docs, size.seed + 1)
    t0 = time.perf_counter()
    mp_loop, _, _ = serve.build_loop(
        mp.texts, mp.embeddings, n_clusters=size.n_clusters,
        engine="pipelined", multi_probe=size.kappa,
        max_batch=size.mp_requests, deadline_ms=1e6)
    bp = mp_loop.system.batch
    log(f"multi-probe (own corpus, {size.mp_docs} docs): flat db "
        f"{mp_loop.system.server.db.nbytes} bytes; {bp.partition.n_buckets} "
        f"buckets, width {bp.partition.width}, stored "
        f"{bp.server.stored_bytes} bytes, hints {bp.server.hint_bytes} "
        f"bytes; built in {time.perf_counter() - t0:.1f}s")
    anchors, secs = serve.run_requests(
        mp_loop, mp.embeddings, size.mp_requests, rng, top_k=5,
        multi_probe=size.kappa)
    sizes = sorted({r.batch_size for r in mp_loop.responses})
    log(f"phase multi-probe: {size.mp_requests} requests (kappa "
        f"{size.kappa}) in {secs:.1f}s, batch sizes {sizes}")
    checks["multi_probe_anchor_in_top_k"] = _anchors_in_top(mp_loop, anchors)
    subs = bp.server.sub_dbs
    q = rng.integers(0, 2**32, (len(subs), bp.partition.width, 2),
                     dtype=np.uint32)
    got = ops.bucketed_modmatmul(subs, jnp.asarray(q), impl=impl)
    checks["bucket_answers_match_numpy_u64"] = all(
        np.array_equal(np.asarray(g), ((np.asarray(s).astype(np.uint64)
                                        @ q[b].astype(np.uint64))
                                       % (1 << 32)).astype(np.uint32))
        for b, (g, s) in enumerate(zip(got, subs)))
    log(f"peak_bytes_in_use at end: {_peak_bytes(dev)}")
    return checks


def run_four_chips(size: Size, n_chips: int) -> dict[str, bool]:
    """Row-sharded build + serve over ``n_chips`` devices, vs the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed import collectives
    from repro.launch import serve
    from repro.launch.mesh import make_chunk_mesh

    if len(jax.devices()) < n_chips:
        raise SystemExit(f"--chips {n_chips} needs {n_chips} devices, "
                         f"JAX sees {len(jax.devices())}")
    mesh = make_chunk_mesh(n_chips)
    rng = np.random.default_rng(size.seed)
    checks: dict[str, bool] = {}

    corp = _corpus(size, size.n_docs, size.seed)
    t0 = time.perf_counter()
    loop, _, _ = serve.build_loop(
        corp.texts, corp.embeddings, n_clusters=size.n_clusters,
        engine="pipelined", mesh=mesh, max_batch=size.max_batch)
    log(f"phase sharded build: {time.perf_counter() - t0:.1f}s over "
        f"{n_chips} devices")
    system = loop.system
    server = system.server
    per_chip = _report_build(system, n_chips)
    checks["db_per_chip_at_least_min_bytes"] = (
        per_chip >= size.min_db_bytes_per_chip)
    hint_rows = sorted({s.data.shape[0]
                        for s in system.hint.addressable_shards})
    checks["hint_rows_split_over_chips"] = (
        hint_rows == [system.db.m // n_chips])
    log(f"hint rows per chip: {hint_rows}")
    log("peak_bytes_in_use after build: "
        f"{[_peak_bytes(d) for d in mesh.devices.flat]}")

    anchors, secs = serve.run_requests(loop, corp.embeddings, size.requests,
                                       rng, top_k=5)
    log(f"phase sharded serve: {size.requests} requests in {secs:.1f}s")
    checks["single_probe_anchor_in_top_k"] = _anchors_in_top(loop, anchors)

    axes, impl = server.mesh_axes, system.cfg.impl
    raw = collectives.row_shard_gemm(mesh, axes, impl=impl)
    rep = NamedSharding(mesh, P())
    checks["answer_rows_match_numpy_u64"] = _answer_rows_match(
        lambda q: raw(server.db, jax.device_put(q, rep)), system.db.matrix,
        size.answer_rows, rng, n_chips)
    checks["hint_rows_match_numpy_u64"] = _hint_rows_match(
        system, size.hint_rows, rng)
    clusters = rng.choice(system.db.n, size.clusters_checked, replace=False)
    checks["decrypted_clusters_equal_db_columns"] = _clusters_decrypt(
        system, clusters)
    # the program `server.answer` dispatched for every request above
    text = server._answer_fn.lower(server.db, jax.device_put(
        jnp.zeros((system.db.n, 8), jnp.uint32), rep)).compile().as_text()
    found = [c for c in COLLECTIVES if c in text]
    checks["answer_program_has_tpu_custom_call"] = "tpu_custom_call" in text
    checks["answer_program_has_no_collective"] = not found
    log(f"answer program: tpu_custom_call "
        f"{checks['answer_program_has_tpu_custom_call']}, collectives "
        f"{found or 'none'}")
    log("peak_bytes_in_use at end: "
        f"{[_peak_bytes(d) for d in mesh.devices.flat]}")
    return checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the row-sharded build + serve and its "
                         "comparison with the host reference")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); refusing to "
              "report a result", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device: {dev.device_kind} x{len(devices)} ({dev.platform}); "
        f"compile cache {enable_compile_cache()}")

    t0 = time.perf_counter()
    checks = (run_four_chips(FOUR_CHIPS, args.chips) if args.chips > 1
              else run_one_chip(ONE_CHIP))
    for name, ok in checks.items():
        log(f"check {name}: {'PASS' if ok else 'FAIL'}")
    log(f"wall: {time.perf_counter() - t0:.1f}s")
    if not all(checks.values()):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
