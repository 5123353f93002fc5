"""Public jit'd wrappers around the modular-GEMM kernels.

``modmatmul`` is the single entry point used by the PIR protocol (online
answer, offline hint GEMM) and by the Tiptoe-style baseline (private scoring).
It handles shape padding, implementation dispatch and matvec convenience:

  impl="pallas"  — the Pallas TPU kernel (interpret=True off-TPU, for tests)
  impl="xla"     — the exact uint32 XLA matmul (production CPU path; oracle)
  impl="auto"    — pallas on TPU, xla elsewhere
"""
from __future__ import annotations

import collections
import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.modmatmul import modmatmul_pallas

U32 = jnp.uint32

#: Default (bm, bn, bb) tile of the modmatmul kernel.  A DB of any row count
#: whose column count is a multiple of bn is used in place (the kernel's
#: last row tile may be partial); other widths cost a padded device copy.
BLOCK = (256, 512, 128)


#: jit'd oracle: fuses the u8→u32 widening into the GEMM instead of
#: materializing a 4× DB copy per call (measured ~40× on large matvecs).
_modmatmul_ref_jit = jax.jit(ref.modmatmul_ref)


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


class _LayoutCache:
    """Identity-keyed memo for device-resident kernel layouts.

    The Pallas path pads (and thereby copies) a database operand whose width
    is not whole column tiles on every call; in the serving hot loop the
    database is the SAME array
    object tick after tick, so the padded layout is cached and reused until a
    commit swaps the array.  Keys carry ``id()`` plus shape/block, and every
    entry pins the source array(s) so an entry can only be returned while its
    key identity still refers to the array it was built from (a recycled
    ``id()`` after GC can never alias: the pinned source keeps the id alive).
    Bounded FIFO so retired epochs' layouts fall out on their own.  The
    capacity stays small because only the live epoch's layout (plus, on the
    serving path, at most one in-flight predecessor and the transient
    delta-GEMM operands of a commit) can ever hit again — anything older is
    a full-size padded copy pinning dead memory.
    """

    def __init__(self, capacity: int = 4):
        self.capacity = capacity
        self._slots: collections.OrderedDict = collections.OrderedDict()
        self.hits = 0
        self.misses = 0

    def get(self, key: tuple, srcs: tuple, build: Callable[[], jax.Array]
            ) -> jax.Array:
        ent = self._slots.get(key)
        if ent is not None and all(a is b for a, b in zip(ent[0], srcs)):
            self.hits += 1
            self._slots.move_to_end(key)
            return ent[1]
        self.misses += 1
        val = build()
        self._slots[key] = (srcs, val)
        if len(self._slots) > self.capacity:
            self._slots.popitem(last=False)
        return val

    def clear(self):
        self._slots.clear()
        self.hits = 0
        self.misses = 0


_db_pad_cache = _LayoutCache()
_bucket_stack_cache = _LayoutCache()


# ---------------------------------------------------------------------------
# In-place column patching (epoch commits)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_cols_donated(db, cols, new_cols):
    return db.at[:, cols].set(new_cols)


@jax.jit
def _scatter_cols(db, cols, new_cols):
    return db.at[:, cols].set(new_cols)


def scatter_columns(db: jax.Array, cols: jax.Array, new_cols: jax.Array, *,
                    donate: bool = False) -> jax.Array:
    """db with columns ``cols`` replaced by ``new_cols`` (fresh array).

    donate=True donates the input buffer to XLA so the scatter writes the
    touched columns in place instead of copying the whole (m, n) database
    per epoch commit.  The caller must guarantee no OTHER pending Python-side
    use of ``db`` exists (already-dispatched computations are safe — the
    runtime keeps their operand buffers alive); the shadow-epoch committer is
    the only donating caller.
    """
    fn = _scatter_cols_donated if donate else _scatter_cols
    return fn(db, cols, new_cols)


@functools.partial(jax.jit, donate_argnums=(0,))
def _add_into(delta, hint):
    return hint + delta


def add_delta(hint: jax.Array, delta: jax.Array) -> jax.Array:
    """hint + delta (exact mod 2^32) writing into ``delta``'s buffer.

    The hint delta ΔH is transient — it exists only to be folded into the
    hint — so donating IT (never the hint, which client-side snapshots may
    still reference) lets every epoch commit reuse the ΔH allocation for the
    patched hint instead of allocating a third (m, k) u32 array.
    """
    return _add_into(delta, hint)


def modmatmul(db: jax.Array, q: jax.Array, *, impl: str = "auto",
              block: tuple[int, int, int] = BLOCK) -> jax.Array:
    """Exact (db @ q) mod 2^32.

    db: (m, n) uint8 (entries < plaintext modulus p ≤ 256).
    q:  (n,) or (n, b) uint32.
    Returns uint32 of shape (m,) or (m, b).
    """
    if db.dtype != jnp.uint8:
        raise TypeError(f"db must be uint8, got {db.dtype}")
    if q.dtype != U32:
        raise TypeError(f"q must be uint32, got {q.dtype}")

    was_vec = q.ndim == 1
    q2 = q[:, None] if was_vec else q

    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"

    if impl == "xla":
        out = _modmatmul_ref_jit(db, q2)
    elif impl == "pallas":
        bm, bn, bb = block
        # Hot-loop reuse: the serving DB is the same array object across
        # ticks, so its column-padded device layout is cached instead of
        # re-padded per call.  Queries change every call — pad inline.
        # Under a trace (shard_map, jit) the operand is a tracer: nothing to
        # reuse, and a tracer must not outlive its trace in the cache.
        pad = lambda: _pad_to(db, 1, bn)  # noqa: E731
        dbp = (pad() if isinstance(db, jax.core.Tracer) else
               _db_pad_cache.get((id(db), db.shape, bn), (db,), pad))
        qp = _pad_to(_pad_to(q2, 0, bn), 1, bb)
        interpret = jax.default_backend() != "tpu"
        out = modmatmul_pallas(dbp, qp, bm=bm, bn=bn, bb=bb,
                               interpret=interpret)
        out = out[:, :q2.shape[1]]
    else:
        raise ValueError(f"unknown impl {impl!r}")

    return out[:, 0] if was_vec else out


def hint_gemm(db: jax.Array, a_mat: jax.Array, *, impl: str = "auto",
              block: tuple[int, int, int] = BLOCK) -> jax.Array:
    """Offline hint H = D · A (mod 2^32); same kernel, many query columns."""
    return modmatmul(db, a_mat, impl=impl, block=block)


def delta_gemm(new_cols: jax.Array, old_cols: jax.Array, a_j: jax.Array, *,
               impl: str = "auto") -> jax.Array:
    """Sparse hint delta ΔH = (new − old)·A_J, exact mod 2^32.

    The live-index hot path (PIRServer.update_columns).  The difference
    ΔD isn't u8-representable (entries ∈ [−255, 255] wrap to u32), so:

      xla    — ONE u32 GEMM on the wrapped difference (ref path accepts
               u32; halves the work vs subtracting two products)
      pallas — two u8 limb GEMMs on the MXU, subtracted afterwards (the
               MXU kernel needs u8 inputs, and on TPU the GEMMs are cheap)

    new_cols/old_cols: (m, J) uint8.  a_j: (J, k) uint32.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        diff = new_cols.astype(U32) - old_cols.astype(U32)
        return ref.modmatmul_ref(diff, a_j)
    return (modmatmul(new_cols, a_j, impl=impl)
            - modmatmul(old_cols, a_j, impl=impl))


@jax.jit
def _matvec_u32(d: jax.Array, q: jax.Array) -> jax.Array:
    """u8 × u32 2-D product — the one u32 GEMM shape XLA-CPU executes fast.

    q may be (W,) or (W, C); every output column is an exact mod-2^32 dot.
    """
    return jnp.matmul(d.astype(U32), q)


def stack_buckets(dbs: Sequence[jax.Array], n_shards: int = 1,
                  order: Sequence[int] | None = None) -> jax.Array:
    """Zero-pad bucket sub-DBs to a common height and stack: (B', m', W).

    The bucket count pads up to a multiple of ``n_shards`` with all-zero
    buckets (their answers are zero and are never sliced out), so the stack
    divides evenly over a mesh for the sharded batch-PIR path.

    ``order`` — a permutation of the padded bucket axis, e.g. from
    `distributed.collectives.balanced_bucket_order` — reorders the stack so
    skewed bucket heights pack evenly across devices.  Callers must route
    queries and answers through the same permutation (queries reorder, the
    answer slices index via the inverse); every bucket's GEMM is complete
    on its own leading-axis slice, so the reordered layout is bit-identical
    to the sequential one.
    """
    m_pad = max(d.shape[0] for d in dbs)
    b_pad = (-len(dbs)) % n_shards
    padded = [jnp.pad(d, ((0, m_pad - d.shape[0]), (0, 0))) for d in dbs]
    if b_pad:
        zero = jnp.zeros((m_pad, dbs[0].shape[1]), jnp.uint8)
        padded += [zero] * b_pad
    if order is not None:
        assert len(order) == len(padded), (len(order), len(padded))
        padded = [padded[int(b)] for b in order]
    return jnp.stack(padded)


def bucketed_modmatmul_sharded(stack: jax.Array, qs: jax.Array, mesh,
                               mesh_axes: tuple[str, ...]) -> jax.Array:
    """Bucket-sharded batch-PIR GEMM: buckets spread across the mesh.

    stack: (B', m', W) uint8 from `stack_buckets` (B' a multiple of the
    mesh's shard count); qs: (B', W, C) uint32.  Both shard on the bucket
    axis — each device answers its own whole buckets, zero collectives —
    and the result (B', m', C) uint32 is bit-identical to the per-bucket
    loop (exact mod-2^32 arithmetic either way).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed import collectives
    spec = NamedSharding(mesh, P(tuple(mesh_axes), None, None))
    fn = collectives.bucket_shard_gemm(mesh, tuple(mesh_axes))
    return fn(jax.device_put(stack, spec), jax.device_put(qs, spec))


def bucketed_modmatmul(dbs: Sequence[jax.Array], qs: jax.Array, *,
                       impl: str = "auto",
                       block: tuple[int, int, int] = BLOCK
                       ) -> list[jax.Array]:
    """Per-bucket exact (D_b @ q_b) mod 2^32 — the batch-PIR server op.

    dbs: B uint8 sub-DBs (m_b, W) sharing one padded width W (rows may
         differ per bucket: each bucket is row-truncated to its tallest
         member cluster).
    qs:  (B, W) or (B, W, C) uint32 — one query (or C stacked client
         queries) per bucket.
    Returns a list of B uint32 arrays, (m_b,) or (m_b, C).

    This is ONE public entry point, not B ad-hoc dispatches, but the two
    implementations deliberately diverge in execution shape:

      pallas — buckets are row-padded to a shared height, stacked, and the
               limb-decomposed MXU kernel is vmapped over the bucket axis:
               one fused dispatch whose grid covers every bucket (the
               MXU-shaped form the TPU wants).  The stacked layout is
               cached on the sub-DB identities, so hot-loop serving calls
               skip the restack until a commit swaps a bucket.
      xla    — a loop of 2-D (m_b, W) @ (W, C) products.  Measured on CPU,
               XLA's 2-D u32 matmul is ~15× faster per MAC than any 3-D
               batched dot_general form (which lowers to a naive loop
               nest), so the "one big dispatch" shape would be a large
               pessimization here — but all C client columns of a bucket
               DO share one 2-D call (bitwise equal to per-column matvecs:
               each output column is the same exact mod-2^32 dot).  The
               loop reuses one traced callee, so compile cost is O(1) in B.
    """
    if qs.dtype != U32:
        raise TypeError(f"qs must be uint32, got {qs.dtype}")
    n_b = len(dbs)
    if qs.shape[0] != n_b:
        raise ValueError(f"{n_b} buckets but qs has leading dim {qs.shape[0]}")
    was_vec = qs.ndim == 2
    q3 = qs[:, :, None] if was_vec else qs
    width = q3.shape[1]
    for d in dbs:
        if d.dtype != jnp.uint8:
            raise TypeError(f"bucket sub-DBs must be uint8, got {d.dtype}")
        if d.shape[1] != width:
            raise ValueError(f"bucket width {d.shape[1]} != query width {width}")

    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"

    if impl == "xla":
        # one (m_b, W) @ (W, C) call per bucket — C stacked client columns
        # share the dispatch, each output column the same exact u32 dot as
        # the old per-column matvec loop (parity-tested bitwise)
        out = [_matvec_u32(d, q3[b]) for b, d in enumerate(dbs)]
    elif impl == "pallas":
        bm, bn, bb = block
        m_pad = max(d.shape[0] for d in dbs)
        m_pad += (-m_pad) % bm
        restack = lambda: jnp.stack(  # noqa: E731
            [_pad_to(jnp.pad(d, ((0, m_pad - d.shape[0]), (0, 0))), 1, bn)
             for d in dbs])
        stack = (restack() if any(isinstance(d, jax.core.Tracer) for d in dbs)
                 else _bucket_stack_cache.get(
                     (tuple(id(d) for d in dbs),
                      tuple(d.shape for d in dbs), bm, bn),
                     tuple(dbs), restack))
        qp = _pad_to(_pad_to(q3, 1, bn), 2, bb)
        interpret = jax.default_backend() != "tpu"
        full = jax.vmap(lambda d, q: modmatmul_pallas(
            d, q, bm=bm, bn=bn, bb=bb, interpret=interpret))(stack, qp)
        out = [full[b, :d.shape[0], :q3.shape[2]] for b, d in enumerate(dbs)]
    else:
        raise ValueError(f"unknown impl {impl!r}")

    return [o[:, 0] for o in out] if was_vec else out


def kmeans_assign(x: jax.Array, c: jax.Array, *, impl: str = "auto",
                  block: tuple[int, int] = (256, 512)):
    """Fused nearest-centroid assignment: (assign (N,) i32, min_d2 (N,)).

    x: (N, d) f32 points; c: (k, d) f32 centroids.  impl="pallas" fuses
    distance + argmin on the MXU without materializing the (N, k) distance
    matrix in HBM; "xla" is the unfused oracle (identical results).

    This is the assignment kernel of the offline build's K-means: the
    block-canonical Lloyd core (`core.clustering._block_stats`) calls it
    per corpus block, both on the host path and inside the `shard_map`'d
    sharded build (`collectives.corpus_shard_kmeans` /
    `row_shard_assign`), so the same fused kernel serves every layout —
    one call sees only its (rows_local/blocks, d) slice either way.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        return ref.kmeans_assign_ref(x, c)
    from repro.kernels.kmeans_assign import kmeans_assign_pallas
    bn, bk = block
    n, k = x.shape[0], c.shape[0]
    xp = _pad_to(x, 0, bn)
    cp = _pad_to(c, 0, bk)
    if cp.shape[0] != k:
        # padded centroids must never win the argmin
        pad = cp.shape[0] - k
        cp = cp.at[k:].set(jnp.full((pad, c.shape[1]), 1e30, c.dtype))
    interpret = jax.default_backend() != "tpu"
    assign, d2 = kmeans_assign_pallas(xp, cp, bn=bn, bk=bk,
                                      interpret=interpret)
    return assign[:n], d2[:n]
