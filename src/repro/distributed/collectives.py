"""Explicit shard_map building blocks for the model-parallel hot paths.

pjit+constraints handles most of the framework; these are the places where
we want the communication pattern pinned down rather than inferred:

  * ``sharded_embedding_lookup`` — row-sharded tables: local masked gather +
    one psum (the classic model-parallel embedding; avoids XLA materializing
    an all-gathered table).
  * ``split_s_decode_attention`` — flash-decoding: KV cache sharded along
    sequence; per-shard online-softmax partials combined with pmax/psum.
  * ``ring_psum`` — reduce via collective_permute ring, used by the gradient
    compression path so the wire format stays int8 end-to-end.
  * ``row_shard_gemm`` / ``row_shard_delta_gemm`` / ``row_shard_scatter``
    — the PIR server's one path (`sharding.pir_rules`): the packed database
    row-shards over the mesh, a single chip being a one-device mesh,
    queries replicate, every shard answers its own row slice.  ZERO
    collectives on the hot path — the modular GEMM's contraction dim (the
    cluster axis) is never split, so per-shard answers are already final.
  * ``corpus_shard_kmeans`` / ``row_shard_assign`` / ``row_shard_sqdist`` —
    the sharded OFFLINE build: the corpus row-shards over the same mesh the
    serving DB uses.  K-means runs the block-canonical core from
    `core.clustering` per shard with one tiled all-gather of the per-block
    partial sums per Lloyd iteration (gather + fixed-order local reduce, not
    psum, so the float combine order is pinned and the fit is bit-identical
    to the single-device build); assignment/distance sweeps are row-local
    and collective-free like the serving GEMM.

Each has an 8-device subprocess test (tests/test_sharded.py /
tests/test_sharded_pir.py) asserting bitwise/allclose equality with the
single-device reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def sharded_embedding_lookup(mesh: Mesh, axis: str):
    """Returns lookup(table, idx) with table row-sharded over `axis`.

    table: (V, d) sharded P(axis, None); idx: (B,) replicated → (B, d)
    replicated.  Each shard gathers only its local rows; one psum combines.
    """
    def local(table_shard, idx):
        size = table_shard.shape[0]
        lo = jax.lax.axis_index(axis) * size
        local_idx = idx - lo
        ok = (local_idx >= 0) & (local_idx < size)
        safe = jnp.clip(local_idx, 0, size - 1)
        rows = jnp.take(table_shard, safe, axis=0)
        rows = jnp.where(ok[:, None], rows, 0)
        return jax.lax.psum(rows, axis)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(axis, None), P()),
                         out_specs=P())


def split_s_decode_attention(mesh: Mesh, axis: str, *, scale: float):
    """Returns attn(q, k, v, lengths) with K/V sharded on the seq axis.

    q: (B, H, hd) replicated; k/v: (B, T, H, hd) sharded P(None, axis);
    lengths: (B,) replicated.  Per-shard online softmax partials (m, l, o)
    are combined with pmax/psum — numerically identical to global softmax.
    """
    def local(q, k_shard, v_shard, lengths):
        t_local = k_shard.shape[1]
        lo = jax.lax.axis_index(axis) * t_local
        s = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32),
                       k_shard.astype(jnp.float32)) * scale
        tpos = lo + jnp.arange(t_local)
        mask = tpos[None, None, :] < lengths[:, None, None]
        s = jnp.where(mask, s, -1e30)
        m_loc = jnp.max(s, axis=-1)                          # (B, H)
        m_glob = jax.lax.pmax(m_loc, axis)
        p = jnp.exp(s - m_glob[..., None])
        l_loc = jnp.sum(p, axis=-1)
        o_loc = jnp.einsum("bht,bthd->bhd", p,
                           v_shard.astype(jnp.float32))
        l_glob = jax.lax.psum(l_loc, axis)
        o_glob = jax.lax.psum(o_loc, axis)
        return o_glob / jnp.maximum(l_glob[..., None], 1e-30)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(), P(None, axis), P(None, axis), P()),
                         out_specs=P())


@functools.lru_cache(maxsize=None)
def row_shard_gemm(mesh: Mesh, axes: tuple[str, ...], *, impl: str = "auto",
                   q_switch: int | None = None):
    """Returns ans(db, q): the row-sharded modular GEMM  D·q  (mod 2^32).

    db: (m, n') uint8 sharded P(axes, None) — each device holds a row slice
    D_s; n' is n or `ops.kernel_db`'s padded width, to which q's rows pad.
    q: (n, b) uint32 replicated.  Returns (m, b) sharded P(axes, None)
    (uint16 when ``q_switch`` ≤ 2^16 — the modulus switch runs shard-local
    too, so the downlink leaves each shard already compressed).  One
    program, ``jit_local`` in a device trace, on every layout `PIRServer`
    serves: a single chip is a one-device mesh.

    Row sharding never splits the contraction dim, so each shard's answer
    slice  ans_s = D_s·q  is final: no psum, no all-gather — the compiled
    HLO contains no collective ops at all (asserted in tests).  This is the
    whole-system serving strategy argued in ``sharding.pir_rules``:
    replicating the query batch (n·b·4 bytes) is a trivial broadcast next
    to streaming the per-shard DB bytes, and it keeps per-device arithmetic
    intensity at the full-batch 4·b ops/byte.
    """
    from repro.core import lwe
    from repro.kernels import ops

    def local(db_shard, q):
        if q.shape[0] != db_shard.shape[1]:
            q = jnp.pad(q, ((0, db_shard.shape[1] - q.shape[0]), (0, 0)))
        ans = ops.modmatmul(db_shard, q, impl=impl)
        if q_switch is not None:
            ans = lwe.switch_modulus(ans, q_switch)
        return ans

    return jax.jit(jax.shard_map(local, mesh=mesh,
                                 in_specs=(P(axes, None), P()),
                                 out_specs=P(axes, None)))


@functools.lru_cache(maxsize=None)
def row_shard_delta_gemm(mesh: Mesh, axes: tuple[str, ...], *,
                         impl: str = "auto"):
    """Returns delta(new, old, a_j): row-sharded ΔH = (new−old)·A_J.

    new/old: (m, J) uint8 sharded P(axes, None); a_j: (J, k) uint32
    replicated.  Each shard patches only its own hint rows — the live-index
    delta never leaves the shard that owns those DB rows, so mutation
    commits are collective-free exactly like the answer path.
    """
    from repro.kernels import ops

    def local(new_shard, old_shard, a_j):
        return ops.delta_gemm(new_shard, old_shard, a_j, impl=impl)

    return jax.jit(jax.shard_map(local, mesh=mesh,
                                 in_specs=(P(axes, None), P(axes, None), P()),
                                 out_specs=P(axes, None)))


@functools.lru_cache(maxsize=None)
def row_shard_scatter(mesh: Mesh, axes: tuple[str, ...], *,
                      donate: bool = False):
    """Returns scatter(db, cols, new_cols): row-sharded column replacement.

    db: (m, n) uint8 sharded P(axes, None); cols: (J,) int replicated;
    new_cols: (m, J) uint8 sharded P(axes, None).  Each shard swaps the
    touched columns of its own row slice — the column axis is never split,
    so, like every other op on the PIR serving path, there are zero
    collectives and the result is bit-identical to the single-device
    scatter.

    ``donate=True`` donates the DB operand so XLA writes the J touched
    columns into the live buffer instead of copying all m·n bytes per epoch
    commit — the in-place half of the shadow-epoch commit path.  Callers
    must treat the input array as consumed.
    """
    def local(db_shard, cols, new_shard):
        return db_shard.at[:, cols].set(new_shard)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(axes, None), P(), P(axes, None)),
                       out_specs=P(axes, None))
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


@functools.lru_cache(maxsize=None)
def bucket_shard_gemm(mesh: Mesh, axes: tuple[str, ...]):
    """Returns ans(stack, qs): bucket-sharded batch-PIR GEMM (mod 2^32).

    stack: (B, m, W) uint8 sharded P(axes, None, None) — buckets spread
    across devices, each device owning B/shards whole sub-DBs.  qs:
    (B, W, C) uint32 sharded the same way (a bucket's queries live with its
    sub-DB).  Returns (B, m, C) uint32 sharded P(axes, None, None).

    Bucket-parallel, not row-parallel: every bucket's GEMM is complete on
    its owning device, so — like ``row_shard_gemm`` — there are zero
    collectives.  The local op is the plain u32 batched matmul (XLA integer
    matmul wraps mod 2^32, the same oracle `kernels.ref` uses), bitwise
    equal to the per-bucket loop in ``ops.bucketed_modmatmul``.
    """
    def local(stack_shard, q_shard):
        return jnp.einsum("bmw,bwc->bmc", stack_shard.astype(jnp.uint32),
                          q_shard.astype(jnp.uint32))

    spec = P(axes, None, None)
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(spec, spec),
                                 out_specs=spec))


def balanced_bucket_order(heights, n_shards: int) -> "np.ndarray":
    """LPT bucket→device packing for `bucket_shard_gemm`; returns an order.

    heights: per-bucket useful row counts (sub-DB heights before the stack
    pads them to a common m').  The bucket count pads up to a multiple of
    ``n_shards`` with zero-height virtual buckets, then buckets assign
    longest-first to the least-loaded device, each device taking exactly
    B'/n_shards buckets.  The result is a (B',) int64 permutation laid out
    device-major: stacking ``dbs`` in this order makes the contiguous
    per-device slices carry near-equal useful-row totals, so a skewed
    height distribution no longer parks most of the real work on one
    device while the rest multiply zero padding.

    Deterministic and permutation-stable: ties break by (height desc,
    bucket index asc) and by lowest device id, and the per-device load
    totals depend only on the sorted height sequence — permuting the
    input heights permutes the assignment but reproduces the same load
    multiset.  Reordering the bucket axis never changes any bucket's GEMM
    (each answer is complete on its owning device), so callers that index
    answers through the inverse permutation stay bit-identical to the
    unsorted layout.
    """
    import numpy as np
    h = np.asarray(heights, np.int64)
    b_pad = (-len(h)) % n_shards
    if b_pad:
        h = np.concatenate([h, np.zeros(b_pad, np.int64)])
    cap = len(h) // n_shards
    by_h = np.lexsort((np.arange(len(h)), -h))      # height desc, index asc
    loads = np.zeros(n_shards, np.int64)
    counts = np.zeros(n_shards, np.int64)
    slots: list[list[int]] = [[] for _ in range(n_shards)]
    for b in by_h:
        open_devs = np.nonzero(counts < cap)[0]
        dev = int(open_devs[np.argmin(loads[open_devs])])
        slots[dev].append(int(b))
        loads[dev] += h[b]
        counts[dev] += 1
    return np.concatenate([np.asarray(s, np.int64) for s in slots])


def shard_row_loads(heights, n_shards: int, order=None) -> "np.ndarray":
    """Per-device useful-row totals of a bucket stack layout.

    With ``order=None`` this scores the sequential (unsorted) layout
    `ops.stack_buckets` produces by default; passing the permutation from
    `balanced_bucket_order` scores the height-aware layout.  The
    max/mean of the returned (n_shards,) vector is the imbalance metric
    the recsys benchmark reports.
    """
    import numpy as np
    h = np.asarray(heights, np.int64)
    b_pad = (-len(h)) % n_shards
    if b_pad:
        h = np.concatenate([h, np.zeros(b_pad, np.int64)])
    if order is not None:
        h = h[np.asarray(order)]
    return h.reshape(n_shards, -1).sum(axis=1)


def _shard_count(mesh: Mesh, axes: tuple[str, ...]) -> int:
    """Shard count via the one shared axis rule (`resolve_mesh_axes`)."""
    from repro.core import clustering
    return clustering.resolve_mesh_axes(mesh, axes)[1]


@functools.lru_cache(maxsize=None)
def corpus_shard_kmeans(mesh: Mesh, axes: tuple[str, ...], *, k: int,
                        iters: int, n_blocks: int, n: int,
                        impl: str = "auto"):
    """Returns fit(key, x, valid): the corpus-sharded K-means fit.

    x: (N_pad, d) f32 sharded P(axes, None) — N_pad a multiple of
    ``n_blocks``, which is a multiple of the shard count, so every device
    owns a contiguous run of canonical blocks.  valid: (N_pad,) bool sharded
    P(axes) masks padding rows; ``n`` is the true corpus size.  key is
    replicated.  Returns (centroids (k, d) replicated, assignment (N_pad,)
    i32 sharded P(axes), inertia () replicated).

    Each device runs `clustering._kmeans_core` on its row slice: kmeans++
    draws sample from the all-gathered global D² vector with the replicated
    key (every shard picks the identical index; the chosen row travels via
    an exact masked-gather psum), and each Lloyd iteration all-gathers the
    per-block partial sums/counts and reduces them locally in canonical
    block order — the bit-identity contract with the single-device
    `clustering.kmeans_fit(..., n_blocks=n_blocks)`.
    """
    from repro.core import clustering

    shards = _shard_count(mesh, axes)
    assert n_blocks % shards == 0, (n_blocks, shards)

    def local(key, x_shard, valid_shard):
        return clustering._kmeans_core(
            key, x_shard, valid_shard, k=k, iters=iters,
            blocks=n_blocks // shards, n=n, impl=impl, axis=axes)

    return jax.jit(jax.shard_map(local, mesh=mesh,
                                 in_specs=(P(), P(axes, None), P(axes)),
                                 out_specs=(P(), P(axes), P()),
                                 check_vma=False))


@functools.lru_cache(maxsize=None)
def row_shard_assign(mesh: Mesh, axes: tuple[str, ...], *,
                     impl: str = "auto"):
    """Returns assign(x, cents): the row-sharded nearest-centroid sweep.

    x: (N_pad, d) f32 sharded P(axes, None); cents: (k, d) f32 replicated.
    Returns (assignment (N_pad,) i32, min-d² (N_pad,) f32) sharded P(axes).
    Assignment is row-local, so there are zero collectives, and each shard
    dispatches `kernels.ops.kmeans_assign` — the fused Pallas distance+
    argmin kernel when ``impl`` routes to it — over its own slice.
    """
    from repro.kernels import ops

    def local(x_shard, cents):
        return ops.kmeans_assign(x_shard, cents, impl=impl)

    return jax.jit(jax.shard_map(local, mesh=mesh,
                                 in_specs=(P(axes, None), P()),
                                 out_specs=(P(axes), P(axes))))


@functools.lru_cache(maxsize=None)
def row_shard_sqdist(mesh: Mesh, axes: tuple[str, ...], *, n_blocks: int):
    """Returns d2(x, cents): row-sharded block-canonical squared distances.

    x: (N_pad, d) f32 sharded P(axes, None), N_pad a multiple of
    ``n_blocks``; cents: (k, d) f32 replicated.  Returns (N_pad, k) f32
    sharded P(axes, None).  Each shard runs the same per-block GEMM the
    host path uses (`clustering._blocked_sqdist_host` body), zero
    collectives — the distances `balanced_assign` consumes are bit-stable
    across mesh layouts.
    """
    from repro.core import clustering

    shards = _shard_count(mesh, axes)
    assert n_blocks % shards == 0, (n_blocks, shards)

    def local(x_shard, cents):
        rows, d = x_shard.shape
        blocks = n_blocks // shards
        xb = x_shard.reshape(blocks, rows // blocks, d)
        return jax.lax.map(
            lambda b: clustering.pairwise_sqdist(b, cents), xb
        ).reshape(rows, cents.shape[0])

    return jax.jit(jax.shard_map(local, mesh=mesh,
                                 in_specs=(P(axes, None), P()),
                                 out_specs=P(axes, None)))


def ring_psum(mesh: Mesh, axis: str):
    """All-reduce built from collective_permute (explicit ring; int-friendly).

    x sharded P(axis, ...) — each device's block is its contribution; every
    device ends with the elementwise sum of all blocks.
    """
    n = mesh.shape[axis]

    def local(x):
        def body(i, val):
            acc, buf = val
            buf = jax.lax.ppermute(
                buf, axis, [(j, (j + 1) % n) for j in range(n)])
            return acc + buf, buf
        acc, _ = jax.lax.fori_loop(0, n - 1, body, (x, x))
        return acc

    return jax.shard_map(local, mesh=mesh, in_specs=P(axis, None),
                         out_specs=P(axis, None), check_vma=False)


def row_shard_health_check(faults, n_devices: int) -> list[tuple[int, int]]:
    """Guard the "collectives.row_shard.loss" fault site for a fleet tick.

    The replica layer (repro.fleet.replica) calls this once per tick in
    place of a real per-device heartbeat RPC; `faults` is a duck-typed
    injector (or None) whose due events name the device losing its
    row-shard cells.  Returns [(device, down_ticks), ...] — empty on every
    un-faulted tick, at the cost of one counter increment, so the no-fault
    health check adds no clock reads or collectives to the serving path.
    """
    if faults is None:
        return []
    return [(ev.device % n_devices, ev.down_ticks)
            for ev in faults.fire("collectives.row_shard.loss")]
