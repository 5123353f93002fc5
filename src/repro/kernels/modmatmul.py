"""Pallas TPU kernel: exact mod-2^32 GEMM between a u8 database and u32 queries.

This is the PIR-RAG server hot loop (`ans = D · qu mod 2^32`) and, with more
query columns, the offline hint GEMM (`H = D · A`).  The TPU has no scalar
u32 multiply path worth using — the MXU is an int8×int8→int32 systolic array —
so we adapt the computation instead of porting it:

  * The DB entry fits one 8-bit limb (plaintext modulus p ≤ 256).
  * Each u32 query word is split into 4 × 8-bit limbs:
        qu = Σ_l limb_l · 2^(8l)
    ⇒   D·qu mod 2^32 = Σ_l (D · limb_l) << 8l        (mod 2^32)
  * int32 accumulator overflow *wraps*, which is exactly mod-2^32 arithmetic —
    bits ≥ 32 are discarded by definition, so no carry tracking is needed.
  * Unsigned 8-bit limbs exceed int8 range; the MXU path is kept via the
    zero-point identity with X_u = X_s + 128·J, Y_u = Y_s + 128·J:
        X_u @ Y_u = X_s@Y_s + 128·rowsum(X_s)⊕ + 128·colsum(Y_s)⊕ + 128²·n
    where the rank-1 corrections are cheap VPU work.
  * The 4 limb GEMMs are fused into ONE MXU call by stacking limbs along the
    output-column axis: (bm,bn)@(bn,4·bb), then combined with shifts.

Blocking: D streams HBM→VMEM in (bm, bn) u8 tiles; queries are small and
VMEM-resident per (j,k) block; the u32 accumulator is the output block itself,
revisited across the contraction grid axis.  Default tile (256, 512, 128) ⇒
~1.2 MiB VMEM working set, MXU-aligned (multiples of 32×128 int8 tiling).

Arithmetic intensity of the online op is 4·b int8-MACs per DB byte: HBM-bound
for small query batches (SimplePIR's "PIR at memory bandwidth" reappears on
TPU), compute-bound for b ≳ 60.

Validated bitwise (integer exact, not allclose) against ref.modmatmul_ref in
interpret mode — see tests/test_kernels_modmatmul.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

U32 = jnp.uint32
I32 = jnp.int32
I8 = jnp.int8
N_LIMBS = 4
_ZP = 128  # zero point for u8 → i8


def _kernel(d_ref, q_ref, o_ref, *, bn: int):
    """Grid (i, j, k) = (m-tile, b-tile, n-tile); k is the contraction axis."""
    k = pl.program_id(2)

    # ---- load & center the DB tile: u8 → i8 around zero-point 128 ----------
    d_u = d_ref[...].astype(I32)                      # (bm, bn) in [0, 256)
    d_s = (d_u - _ZP).astype(I8)                      # [-128, 128)

    # ---- split the u32 query tile into 4 stacked 8-bit limbs ---------------
    q_u32 = q_ref[...]                                # (bn, bb) u32
    bb = q_u32.shape[1]
    limbs = [((q_u32 >> jnp.uint32(8 * l)) & jnp.uint32(0xFF)).astype(I32)
             for l in range(N_LIMBS)]
    q_u = jnp.concatenate(limbs, axis=1)              # (bn, 4*bb) in [0,256)
    q_s = (q_u - _ZP).astype(I8)

    # ---- one MXU int8 GEMM for all four limbs -------------------------------
    prod = jax.lax.dot_general(
        d_s, q_s, (((1,), (0,)), ((), ())), preferred_element_type=I32)

    # ---- zero-point corrections (rank-1, VPU) --------------------------------
    rs_d = jnp.sum(d_s.astype(I32), axis=1, keepdims=True)     # (bm, 1)
    cs_q = jnp.sum(q_s.astype(I32), axis=0, keepdims=True)     # (1, 4*bb)
    full = prod + _ZP * (rs_d + cs_q) + (_ZP * _ZP) * bn       # int32, wraps ok

    # ---- recombine limbs with shifts, mod 2^32 -------------------------------
    full = full.astype(U32).reshape(full.shape[0], N_LIMBS, bb)
    acc = full[:, 0, :]
    for l in range(1, N_LIMBS):
        acc = acc + (full[:, l, :] << jnp.uint32(8 * l))

    # ---- accumulate over contraction grid axis ------------------------------
    @pl.when(k == 0)
    def _init():
        o_ref[...] = acc

    @pl.when(k > 0)
    def _acc():
        o_ref[...] = o_ref[...] + acc


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bb", "interpret"))
def modmatmul_pallas(db: jax.Array, q: jax.Array, *, bm: int = 256,
                     bn: int = 512, bb: int = 128,
                     interpret: bool = False) -> jax.Array:
    """(db @ q) mod 2^32 via the limb-decomposed MXU kernel.

    db: (m, n) uint8 — n must be a multiple of bn (ops.py pads); m is
        free: the last row tile may run past m.  Its out-of-range rows read
        unspecified values and their output rows are dropped, which is exact
        because every output row depends on its own DB row only.
    q:  (n, b) uint32 — b must be a multiple of bb.
    returns (m, b) uint32, bitwise equal to ref.modmatmul_ref.
    """
    m, n = db.shape
    n2, b = q.shape
    assert n == n2, (db.shape, q.shape)
    assert n % bn == 0 and b % bb == 0, (db.shape, q.shape, (bm, bn, bb))
    grid = (pl.cdiv(m, bm), b // bb, n // bn)

    # Inside a shard_map that checks varying manual axes the output must say
    # how it varies: like the DB row slice it is computed from.
    vma = jax.typeof(db).vma | jax.typeof(q).vma
    return pl.pallas_call(
        functools.partial(_kernel, bn=bn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bb), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bb), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, b), U32, vma=vma),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(db, q)
