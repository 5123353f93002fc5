"""K-means clustering for the offline corpus-partitioning phase.

kmeans++ seeding + Lloyd iterations, fully in JAX.  The implementation is
*block-canonical*: the corpus is split into ``n_blocks`` equal row blocks and
every reduction (centroid sums, counts, inertia) is computed per block and
combined in a pinned block order.  That makes the result a function of
``n_blocks`` alone, not of how the blocks are placed — the single-device path
(`kmeans_fit`) and the mesh path (`kmeans_fit_sharded`, blocks spread over the
corpus axis with `shard_map` building blocks in `distributed/collectives`)
execute the identical per-block programs and the identical fixed-order
combine, so a sharded offline build is **bit-identical** to the single-device
build (tested under the 8-fake-device harness in tests/test_sharded_build.py).

Why not `psum` for the centroid sums: float addition is non-associative and a
psum's reduction tree is backend-defined.  The sharded path instead
all-gathers the per-block partial sums (one collective per Lloyd iteration)
and reduces them locally in canonical block order — the same `(n_blocks, k,
d)` → `(k, d)` reduction the single-device path runs.

A host-side *balanced* assignment pass is provided as a beyond-paper option:
PIR-RAG's downlink cost is `max_cluster_bytes`, so capping cluster occupancy
directly shrinks the dominant cost of the paper's own architecture.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

#: Canonical number of corpus row blocks used by the offline build.  Bit
#: identity between single-device and sharded builds holds whenever both use
#: the same block count; `PirRagSystem.build` picks ``lcm(BUILD_BLOCKS, S)``
#: for S shards, so every mesh width dividing BUILD_BLOCKS (1, 2, 4, 8)
#: reproduces the unsharded build exactly.
BUILD_BLOCKS = 8


class KMeansResult(NamedTuple):
    centroids: jax.Array      # (k, d) f32
    assignment: jax.Array     # (N,) i32
    inertia: jax.Array        # () f32, final mean squared distance


def pairwise_sqdist(x: jax.Array, c: jax.Array) -> jax.Array:
    """||x_i - c_j||² as a GEMM.  x: (N, d) f32, c: (k, d) f32 → (N, k) f32.

    Full f32 precision (the TPU's default f32 matmul rounds its inputs to
    bf16): the client's cluster pick must agree with the build's assignment
    kernel, or a document near a cluster boundary becomes unreachable.
    """
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    c2 = jnp.sum(c * c, axis=1)[None, :]
    return x2 - 2.0 * jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST
                                 ) + c2


def resolve_mesh_axes(mesh, mesh_axes=None) -> tuple[tuple[str, ...], int]:
    """(axes, shard count) for a mesh — the one axis-defaulting rule.

    Every sharded entry point (kmeans fit, assignment/distance sweeps, the
    build facade, PIRServer) resolves ``mesh_axes=None`` to all mesh axes
    through here, so axis defaulting and shard counting cannot drift apart
    between the build stages that must agree on the row layout.
    """
    axes = (tuple(mesh_axes) if mesh_axes is not None
            else tuple(mesh.axis_names))
    shards = 1
    for a in axes:
        shards *= mesh.shape[a]
    return axes, shards


def _pad_rows_np(x: np.ndarray, mult: int) -> tuple[np.ndarray, np.ndarray]:
    """Host-side row pad to a multiple of ``mult``; (padded, valid mask)."""
    n = x.shape[0]
    pad = (-n) % mult
    xp = np.zeros((n + pad, x.shape[1]), np.float32)
    xp[:n] = np.asarray(x, np.float32)
    return xp, np.arange(n + pad) < n


# ---------------------------------------------------------------------------
# Block-canonical core (shared verbatim by the host and shard_map paths)
# ---------------------------------------------------------------------------

def _flat_axis_index(axis) -> jax.Array:
    """Row-major flat shard index across the (possibly tuple) mesh axes."""
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    idx = jnp.int32(0)
    for a in names:
        idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    return idx


def _gather_blocks(v: jax.Array, axis) -> jax.Array:
    """Identity on the host path; tiled all-gather along axis 0 on the mesh.

    Per-shard stacks of block partials concatenate in shard order, which is
    exactly canonical block order (each shard owns a contiguous block range).
    """
    if axis is None:
        return v
    return jax.lax.all_gather(v, axis, axis=0, tiled=True)


def _fetch_row(x: jax.Array, idx: jax.Array, axis) -> jax.Array:
    """Global row fetch x[idx].  Sharded: masked local gather + one psum.

    The psum adds exactly one non-zero contribution to all-zero ones, so it
    is exact in any reduction order — safe for the bit-identity contract.
    """
    if axis is None:
        return x[idx]
    rows = x.shape[0]
    lo = _flat_axis_index(axis) * rows
    li = idx - lo
    ok = (li >= 0) & (li < rows)
    row = jnp.where(ok, x[jnp.clip(li, 0, rows - 1)], 0.0)
    return jax.lax.psum(row, axis)


def _kmeanspp(key: jax.Array, x: jax.Array, valid: jax.Array, k: int,
              n: int, axis) -> jax.Array:
    """D²-weighted seeding (Arthur & Vassilvitskii) over the caller's rows.

    x: (rows, d) f32 — the full (padded) corpus on the host path, this
    shard's contiguous row slice under shard_map.  valid: (rows,) bool masks
    padding rows out of the D² distribution.  The categorical draw needs the
    global D² vector, so the sharded path all-gathers it once per step and
    every shard samples the identical index from the replicated key.
    """
    k0, key = jax.random.split(key)
    first = _fetch_row(x, jax.random.randint(k0, (), 0, n), axis)
    mind2 = jnp.sum((x - first) ** 2, axis=1)
    cents = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(first)

    def body(i, state):
        cents, mind2, key = state
        key, kc = jax.random.split(key)
        g = _gather_blocks(mind2, axis)
        gv = _gather_blocks(valid, axis)
        logits = jnp.where(gv, jnp.log(g + 1e-12), -jnp.inf)
        idx = jax.random.categorical(kc, logits)
        c_new = _fetch_row(x, idx, axis)
        cents = cents.at[i].set(c_new)
        mind2 = jnp.minimum(mind2, jnp.sum((x - c_new) ** 2, axis=1))
        return cents, mind2, key

    cents, _, _ = jax.lax.fori_loop(1, k, body, (cents, mind2, key))
    return cents


def _block_stats(xb: jax.Array, vb: jax.Array, cents: jax.Array, k: int,
                 impl: str):
    """One block's Lloyd partials: (sums (k, d), counts (k,), Σ min-d²).

    Assignment goes through `kernels.ops.kmeans_assign`, so the fused Pallas
    distance+argmin kernel serves both the host and the sharded build when
    ``impl`` routes to it.  Padding rows land in an overflow segment k that
    is sliced off, so they contribute nothing.
    """
    assign, mind2 = ops.kmeans_assign(xb, cents, impl=impl)
    seg = jnp.where(vb, assign, k)
    ones = jnp.where(vb, 1.0, 0.0).astype(xb.dtype)
    sums = jax.ops.segment_sum(xb, seg, num_segments=k + 1)[:k]
    cnts = jax.ops.segment_sum(ones, seg, num_segments=k + 1)[:k]
    w = jnp.sum(jnp.where(vb, mind2, 0.0))
    return sums, cnts, w


def _kmeans_core(key: jax.Array, x: jax.Array, valid: jax.Array, *, k: int,
                 iters: int, blocks: int, n: int, impl: str, axis=None):
    """kmeans++ then `iters` Lloyd steps over this caller's row slice.

    x: (rows, d) f32 with rows divisible by ``blocks`` (the LOCAL block
    count); valid: (rows,) bool.  ``axis`` names the shard_map corpus axis
    (None on the host path).  Returns (centroids (k, d) — identical on every
    shard, local assignment (rows,) i32, inertia ()).
    """
    rows, d = x.shape
    xb = x.reshape(blocks, rows // blocks, d)
    vb = valid.reshape(blocks, rows // blocks)
    cents0 = _kmeanspp(key, x, valid, k, n, axis)

    def lloyd(cents, _):
        sums, cnts, w = jax.lax.map(
            lambda t: _block_stats(t[0], t[1], cents, k, impl), (xb, vb))
        sums = _gather_blocks(sums, axis)      # (n_blocks, k, d) global order
        cnts = _gather_blocks(cnts, axis)
        w = _gather_blocks(w, axis)
        tot, cnt = jnp.sum(sums, axis=0), jnp.sum(cnts, axis=0)
        new = jnp.where(cnt[:, None] > 0,
                        tot / jnp.maximum(cnt, 1)[:, None], cents)
        return new, jnp.sum(w) / n

    cents, inertias = jax.lax.scan(lloyd, cents0, None, length=iters)
    assign = jax.lax.map(
        lambda b: ops.kmeans_assign(b, cents, impl=impl)[0], xb)
    return cents, assign.reshape(rows).astype(jnp.int32), inertias[-1]


@functools.partial(jax.jit,
                   static_argnames=("k", "iters", "blocks", "n", "impl"))
def _kmeans_fit_host(key, x, valid, *, k, iters, blocks, n, impl):
    return _kmeans_core(key, x, valid, k=k, iters=iters, blocks=blocks,
                        n=n, impl=impl, axis=None)


def _pad_rows(x: np.ndarray | jax.Array, mult: int):
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.pad(jnp.asarray(x), ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return jnp.asarray(x), jnp.arange(n + pad) < n


def kmeans_fit(key: jax.Array, x: jax.Array, *, k: int, iters: int = 25,
               n_blocks: int = 1, impl: str = "auto") -> KMeansResult:
    """kmeans++ init then `iters` Lloyd steps.  Empty clusters keep centroids.

    x: (N, d) f32.  ``n_blocks`` picks the canonical reduction granularity
    (see module docstring); any fixed value gives a deterministic result, and
    matching `kmeans_fit_sharded`'s block count reproduces the sharded fit
    bit-for-bit.  ``impl`` dispatches the assignment kernel
    (`ops.kmeans_assign`): "auto" is the fused Pallas kernel on TPU and the
    XLA oracle elsewhere.
    """
    xp, valid = _pad_rows(jnp.asarray(x, jnp.float32), n_blocks)
    cents, assign, inertia = _kmeans_fit_host(
        key, xp, valid, k=k, iters=iters, blocks=n_blocks,
        n=x.shape[0], impl=impl)
    return KMeansResult(cents, assign[: x.shape[0]], inertia)


def kmeans_fit_sharded(key: jax.Array, x: np.ndarray, *, k: int,
                       iters: int = 25, mesh, mesh_axes=None,
                       n_blocks: int | None = None,
                       impl: str = "auto") -> KMeansResult:
    """`kmeans_fit` with the corpus row-sharded over a device mesh.

    x: (N, d) f32 (host or device); rows are padded and placed P(axes, None)
    so each device owns a contiguous run of canonical blocks.  One
    all-gather of the per-block partials per Lloyd iteration (plus one per
    kmeans++ step) — see `distributed.collectives.corpus_shard_kmeans`.
    ``n_blocks`` defaults to ``lcm(BUILD_BLOCKS, shards)`` and must be a
    multiple of the shard count.  Bit-identical to
    ``kmeans_fit(..., n_blocks=same)`` on one device.
    """
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.distributed import collectives

    axes, shards = resolve_mesh_axes(mesh, mesh_axes)
    if n_blocks is None:
        n_blocks = math.lcm(BUILD_BLOCKS, shards)
    if n_blocks % shards:
        raise ValueError(f"n_blocks {n_blocks} not divisible by {shards} shards")

    n = x.shape[0]
    xp, valid = _pad_rows_np(x, n_blocks)
    xs = jax.device_put(xp, NamedSharding(mesh, PartitionSpec(axes, None)))
    vs = jax.device_put(valid, NamedSharding(mesh, PartitionSpec(axes)))
    fit = collectives.corpus_shard_kmeans(mesh, axes, k=k, iters=iters,
                                          n_blocks=n_blocks, n=n, impl=impl)
    cents, assign, inertia = fit(key, xs, vs)
    return KMeansResult(cents, assign[:n], inertia)


# ---------------------------------------------------------------------------
# Assignment sweeps
# ---------------------------------------------------------------------------

def assign_to_centroids(x: jax.Array, cents: jax.Array, *, impl: str = "auto",
                        mesh=None, mesh_axes=None) -> jax.Array:
    """Nearest-centroid assignment (the client-side cluster pick).  (N,) i32.

    On TPU (or impl="pallas") this is the fused distance+argmin kernel
    (kernels/kmeans_assign.py) — on TPU it avoids materializing the (N, K)
    distance matrix in HBM for corpus-scale assignment sweeps.  ``mesh=``
    row-shards the sweep (x P(axes, None), centroids replicated, zero
    collectives) through `collectives.row_shard_assign`, routing the same
    kernel per shard; assignment is row-local, so the result is bit-identical
    to the single-device sweep.
    """
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.distributed import collectives
        axes, shards = resolve_mesh_axes(mesh, mesh_axes)
        n = x.shape[0]
        xp, _ = _pad_rows(jnp.asarray(x, jnp.float32), shards)
        xs = jax.device_put(xp, NamedSharding(mesh, PartitionSpec(axes, None)))
        cr = jax.device_put(jnp.asarray(cents, jnp.float32),
                            NamedSharding(mesh, PartitionSpec()))
        fn = collectives.row_shard_assign(mesh, axes, impl=impl)
        return fn(xs, cr)[0][:n]
    return ops.kmeans_assign(x, cents, impl=impl)[0]


@functools.partial(jax.jit, static_argnames=("blocks",))
def _blocked_sqdist_host(x, cents, *, blocks):
    rows, d = x.shape
    xb = x.reshape(blocks, rows // blocks, d)
    return jax.lax.map(lambda b: pairwise_sqdist(b, cents), xb
                       ).reshape(rows, cents.shape[0])


def blocked_sqdist(x: np.ndarray, cents: np.ndarray, *,
                   n_blocks: int = BUILD_BLOCKS, mesh=None,
                   mesh_axes=None) -> jax.Array:
    """(N, k) f32 squared distances in canonical block order.

    The GEMM runs one (rows/n_blocks, d)·(d, k) block at a time, so the
    result is identical whether the blocks execute on one device (lax.map)
    or spread over a mesh (`collectives.row_shard_sqdist`) — the distance
    input `balanced_assign` needs to stay bit-stable across build layouts.
    """
    n = x.shape[0]
    if mesh is None:
        xp, _ = _pad_rows(jnp.asarray(x, jnp.float32), n_blocks)
        return _blocked_sqdist_host(xp, jnp.asarray(cents, jnp.float32),
                                    blocks=n_blocks)[:n]
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.distributed import collectives
    axes, shards = resolve_mesh_axes(mesh, mesh_axes)
    if n_blocks % shards:
        raise ValueError(f"n_blocks {n_blocks} not divisible by {shards} shards")
    xp, _ = _pad_rows_np(x, n_blocks)
    xs = jax.device_put(xp, NamedSharding(mesh, PartitionSpec(axes, None)))
    cr = jax.device_put(jnp.asarray(cents, jnp.float32),
                        NamedSharding(mesh, PartitionSpec()))
    fn = collectives.row_shard_sqdist(mesh, axes, n_blocks=n_blocks)
    return fn(xs, cr)[:n]


def balanced_assign(x: np.ndarray, cents: np.ndarray, cap: int,
                    batch: int = 65536, *,
                    d2: np.ndarray | None = None) -> np.ndarray:
    """Greedy capacity-capped assignment (host-side, offline).  (N,) i32.

    Docs are visited in order of confidence (margin to their best centroid);
    a doc whose best cluster is full spills to the nearest non-full one.
    Bounds `max_cluster_bytes`, the PIR-RAG downlink driver.

    ``d2`` (N, k) f32 overrides the internal batched numpy distance pass —
    the offline build supplies `blocked_sqdist` output here so the greedy
    walk sees bit-identical distances on every mesh layout (the walk itself
    is a deterministic function of d2 and input order).
    """
    n, k = x.shape[0], cents.shape[0]
    if cap * k < n:
        raise ValueError(f"cap {cap} × k {k} < N {n}")
    if d2 is None:
        # distances in batches to bound memory
        d2 = np.empty((n, k), np.float32)
        for s in range(0, n, batch):
            xb = x[s:s + batch]
            d2[s:s + batch] = (
                (xb * xb).sum(1, keepdims=True) - 2 * xb @ cents.T
                + (cents * cents).sum(1)[None, :])
    else:
        d2 = np.asarray(d2, np.float32)
        assert d2.shape == (n, k), (d2.shape, (n, k))
    best = d2.min(axis=1)
    order = np.argsort(best)          # most-confident docs claim slots first
    # Deferred acceptance with the confidence order as every cluster's
    # common priority: all free docs propose at once, each cluster keeps
    # its `cap` best-priority holders, losers re-propose next round.
    # Under a common strict priority this converges to EXACTLY the
    # sequential walk's assignment (serial dictatorship ≡ deferred
    # acceptance; regression-pinned against `_balanced_assign_walk` in
    # tests/test_clustering.py).  The walk's preference lists never
    # materialize: a full cluster's worst-held rank `thr[c]` only tightens
    # over rounds, so the set of clusters that could still accept rank r is
    # exactly {c : thr[c] >= r} — past rejectors are excluded for free —
    # and "first viable preference" is a masked argmin over d2.  That
    # replaces the old walk's full (N, k) argsort (its single most
    # expensive op) and its Python loop over N docs with one vectorized
    # argmin per round over the shrinking free set.  Exact distance ties
    # break lowest-cluster-first (argmin's first-occurrence rule — the
    # stable preference order).
    d2r = d2[order]                   # rank-major distances (rank r = row r)
    free = np.arange(n)               # ranks still proposing (all, initially)
    held: list[np.ndarray] = [np.empty(0, np.int64) for _ in range(k)]
    thr = np.full(k, n, np.int64)     # full cluster's worst held rank
    while free.size:
        sub = d2r if free.size == n else d2r[free]
        if (thr == n).all():          # nothing full yet (always round 1)
            props = sub.argmin(1)
        else:
            masked = np.where(thr[None, :] >= free[:, None], sub, np.inf)
            props = masked.argmin(1)
            # cap·k ≥ n ⇒ some cluster is below cap (thr = n) and viable
            assert np.isfinite(
                masked[np.arange(free.size), props]).all()
        srt = np.lexsort((free, props))       # by cluster, then priority
        f, p = free[srt], props[srt]
        bounds = np.flatnonzero(np.diff(p)) + 1
        rejected: list[np.ndarray] = []
        for c, g in zip(p[np.concatenate(([0], bounds))],
                        np.split(f, bounds)):
            merged = np.sort(np.concatenate((held[c], g)))
            held[c] = merged[:cap]
            if merged.size >= cap:
                thr[c] = held[c][-1]
            if merged.size > cap:
                rejected.append(merged[cap:])
        free = (np.concatenate(rejected) if rejected
                else np.empty(0, np.int64))
    out = np.full(n, -1, np.int32)
    for c in range(k):
        out[order[held[c]]] = c
    assert (out >= 0).all()
    return out


def _balanced_assign_walk(x: np.ndarray, cents: np.ndarray, cap: int,
                          batch: int = 65536, *,
                          d2: np.ndarray | None = None) -> np.ndarray:
    """The original O(N·k) sequential greedy walk `balanced_assign` replaced.

    Kept as the behavioural reference: the vectorized deferred-acceptance
    implementation must produce identical assignments on identical inputs
    (the equality regression in tests/test_clustering.py), since packed
    columns — and therefore hints, queries and answers — depend on it
    byte-for-byte.
    """
    n, k = x.shape[0], cents.shape[0]
    if cap * k < n:
        raise ValueError(f"cap {cap} × k {k} < N {n}")
    if d2 is None:
        d2 = np.empty((n, k), np.float32)
        for s in range(0, n, batch):
            xb = x[s:s + batch]
            d2[s:s + batch] = (
                (xb * xb).sum(1, keepdims=True) - 2 * xb @ cents.T
                + (cents * cents).sum(1)[None, :])
    else:
        d2 = np.asarray(d2, np.float32)
        assert d2.shape == (n, k), (d2.shape, (n, k))
    best = d2.min(axis=1)
    order = np.argsort(best)
    # kind="stable" pins the preference order on exact distance ties to
    # lowest-cluster-first — the tie-break argmin gives for free — where the
    # original quicksort left it unspecified (and numpy-version-dependent).
    pref = np.argsort(d2, axis=1, kind="stable")
    counts = np.zeros(k, np.int64)
    out = np.full(n, -1, np.int32)
    for i in order:
        for j in pref[i]:
            if counts[j] < cap:
                out[i] = j
                counts[j] += 1
                break
    assert (out >= 0).all()
    return out
