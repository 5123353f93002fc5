"""Pallas TPU kernel: fused pairwise-distance + argmin (k-means assignment).

The offline clustering hot loop: every Lloyd iteration assigns N embeddings
to K centroids.  Unfused, XLA materializes the (N, K) distance matrix in HBM
(N=5.7M docs × K=4096 f32 = 93 GB per iteration).  This kernel fuses
  d²(x,c) = |x|² − 2·x·c + |c|²   →   running (min, argmin)
so the (bn, bk) score tile lives only in VMEM; HBM traffic is X once per
K-tile sweep + C once — the same blocking logic as the PIR GEMM, reused for
the paper's *other* offline stage.

Grid (i, k): i over N tiles (parallel), k over K tiles (arbitrary,
running-min accumulation in the output refs).  Tie-break: strict `<` keeps
the earliest centroid index, matching jnp.argmin.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, c_ref, best_d_ref, best_i_ref, *, bk: int):
    k = pl.program_id(1)
    x = x_ref[...]                                  # (bn, d) f32
    c = c_ref[...]                                  # (bk, d) f32
    x2 = jnp.sum(x * x, axis=1, keepdims=True)      # (bn, 1)
    c2 = jnp.sum(c * c, axis=1)[None, :]            # (1, bk)
    scores = x2 - 2.0 * jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32) + c2    # (bn, bk)

    local_i = jnp.argmin(scores, axis=1)            # (bn,)
    local_d = jnp.min(scores, axis=1)
    global_i = (k * bk + local_i).astype(jnp.int32)

    @pl.when(k == 0)
    def _init():
        best_d_ref[...] = local_d[:, None]
        best_i_ref[...] = global_i[:, None]

    @pl.when(k > 0)
    def _update():
        prev_d = best_d_ref[..., 0]
        better = local_d < prev_d                   # strict: earliest wins
        best_d_ref[...] = jnp.where(better, local_d, prev_d)[:, None]
        best_i_ref[...] = jnp.where(better, global_i,
                                    best_i_ref[..., 0])[:, None]


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def kmeans_assign_pallas(x: jax.Array, c: jax.Array, *, bn: int = 256,
                         bk: int = 512, interpret: bool = False):
    """x: (N, d) f32; c: (K, d) f32 → (assign (N,) i32, min_d2 (N,) f32).

    N % bn == 0 and K % bk == 0 (ops.py pads; padded centroids are +inf'd
    by the wrapper so they never win)."""
    n, d = x.shape
    k_total, d2 = c.shape
    assert d == d2 and n % bn == 0 and k_total % bk == 0

    # row-local like its input slice when called inside a shard_map
    vma = jax.typeof(x).vma | jax.typeof(c).vma
    best_d, best_i = pl.pallas_call(
        functools.partial(_kernel, bk=bk),
        grid=(n // bn, k_total // bk),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, k: (i, 0)),
            pl.BlockSpec((bk, d), lambda i, k: (k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, k: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((n, 1), jnp.int32, vma=vma),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(x, c)
    return best_i[:, 0], best_d[:, 0]
