"""Pallas TPU kernel: exact mod-2^32 GEMM between a u8 database and u32 queries.

This is the PIR-RAG server hot loop (`ans = D · qu mod 2^32`) and, with more
query columns, the offline hint GEMM (`H = D · A`).  The TPU has no scalar
u32 multiply path worth using — the MXU is an int8×int8→int32 systolic array —
so we adapt the computation instead of porting it:

  * The DB entry fits one 8-bit limb (plaintext modulus p ≤ 256).
  * Each u32 query word is recoded into 4 signed 8-bit digits,
        qu ≡ Σ_l s_l · 2^(8l)  (mod 2^32),   s_l ∈ [-128, 128),
    so every query limb is an int8 as it stands.
  * The DB byte is centred on the MXU's int8 range, D_s = D_u − 128 (one
    XOR of the top bit), hence
        D_u·qu ≡ Σ_l (D_s·s_l) << 8l  +  128·colsum(qu)        (mod 2^32).
    The correction is one u32 per query column: no DB row sums at all.
  * int32 accumulator overflow *wraps*, which is exactly mod-2^32 arithmetic —
    bits ≥ 32 are discarded by definition, so no carry tracking is needed.
  * The 4 limb GEMMs are fused into ONE MXU call by stacking limbs along the
    output-column axis: (bm,bn)@(bn,4·bb) per step.

So a contraction step does only the DB tile's work: load it, flip its top
bits, one int8 MXU dot into an i32 accumulator.  Everything that does not
depend on the DB tile happens once: the digit split and the column
correction once per call, in the same jitted program as the ``pallas_call``
(the device trace names the whole answer ``jit_modmatmul_pallas``), and the
limb recombination once per output tile, after the last contraction step.

Blocking: D streams HBM→VMEM in (bm, bn) u8 tiles; the digit operand is
VMEM-resident per (j, k) block; the accumulator is a VMEM scratch.  A column
tile of ``bb ≤ 32`` query columns packs its 4·bb limbs into one 128-lane
MXU operand, and the limbs are brought together with lane rotations;
wider tiles are whole multiples of 128 lanes.

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
LANES = 128
_ZP = 128  # zero point for u8 → i8
_TOP_BITS = 0x80808080  # XOR with it centres four packed u8 on zero


def signed_digits(q: jax.Array) -> jax.Array:
    """(n, b) u32 → (n, 4, b) i8 digits s_l with q ≡ Σ_l s_l·2^(8l) mod 2^32.

    Balanced base-256 recoding: s_l = ((r_l + 128) mod 256) − 128 and
    r_(l+1) = (r_l − s_l) / 256; the subtraction is exact in u32 because
    r_l − s_l is a multiple of 256, and the last carry is ≡ 0 mod 2^32.
    """
    digits = []
    r = q
    for _ in range(N_LIMBS):
        s = ((r + jnp.uint32(_ZP)) & jnp.uint32(0xFF)).astype(I32) - _ZP
        digits.append(s.astype(I8))
        r = (r - s.astype(U32)) >> jnp.uint32(8)
    return jnp.stack(digits, axis=1)


def _kernel(d_ref, q_ref, c_ref, o_ref, *scratch, bb: int, nk: int):
    """Grid (i, j, k) = (m-tile, b-tile, n-tile); k is the contraction axis."""
    # ---- centre the DB tile: u8 → i8, x − 128 = x XOR 0x80, 4 bytes a word
    d_s = pltpu.bitcast(pltpu.bitcast(d_ref[...], U32) ^ U32(_TOP_BITS), I8)

    # ---- one MXU int8 GEMM for all four limbs ------------------------------
    prod = jax.lax.dot_general(
        d_s, q_ref[...], (((1,), (0,)), ((), ())), preferred_element_type=I32)

    def _finish(acc):
        # limbs stacked on lanes: limb l of column c at lane l·bb + c
        acc = acc.astype(U32)
        if bb % LANES == 0:
            out = acc[:, :bb]
            for l in range(1, N_LIMBS):
                out = out + (acc[:, l * bb:(l + 1) * bb] << U32(8 * l))
            o_ref[...] = out + c_ref[:, :bb]
            return
        out = acc + c_ref[...]
        for l in range(1, N_LIMBS):
            out = out + (pltpu.roll(acc, LANES - l * bb, 1) << U32(8 * l))
        # a narrow tile leaves transposed: its rows are the lanes of (bb, m)
        o_ref[...] = out.T[:bb]

    if nk == 1:
        _finish(prod)
        return

    (acc_ref,) = scratch
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = prod

    @pl.when(k > 0)
    def _acc():
        acc_ref[...] += prod

    @pl.when(k == nk - 1)
    def _out():
        _finish(acc_ref[...])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bb", "interpret"))
def modmatmul_pallas(db: jax.Array, q: jax.Array, *, bm: int = 256,
                     bn: int = 512, bb: int = 128,
                     interpret: bool = False) -> jax.Array:
    """(db @ q) mod 2^32 via the limb-decomposed MXU kernel.

    db: (m, n) uint8 — n must be a multiple of bn (ops.py pads); m is
        free: the last row tile may run past m.  Its out-of-range rows read
        unspecified values and their output rows are dropped, which is exact
        because every output row depends on its own DB row only.
    q:  (n, b) uint32 — any b: its columns pad to whole tiles of bb here,
        and the result is cut back to b, in this one program.
    bb: query columns per tile; a multiple of 128, or at most 32 (its four
        limbs share one 128-lane MXU operand).
    returns (m, b) uint32, bitwise equal to ref.modmatmul_ref.
    """
    m, n = db.shape
    n2, b = q.shape
    assert n == n2, (db.shape, q.shape)
    assert n % bn == 0, (db.shape, (bm, bn, bb))
    assert bb % LANES == 0 or N_LIMBS * bb <= LANES, bb
    nb = pl.cdiv(b, bb)
    narrow = bb % LANES != 0
    qp = jnp.pad(q, ((0, 0), (0, nb * bb - b))) if nb * bb != b else q
    # once per call: the signed digits, limb-major in each column tile and
    # padded to whole lanes, and the per-column correction 128·colsum(q) on
    # the first bb lanes of each tile
    width = max(N_LIMBS * bb, LANES)
    digits = signed_digits(qp).reshape(n, N_LIMBS, nb, bb)
    digits = digits.transpose(0, 2, 1, 3).reshape(n, nb, N_LIMBS * bb)
    corr = (jnp.sum(qp, axis=0, dtype=U32) * U32(_ZP)).reshape(1, nb, bb)
    digits = _pad_last(digits, width).reshape(n, nb * width)
    corr = _pad_last(corr, width).reshape(1, nb * width)
    nk = n // bn

    # Inside a shard_map that checks varying manual axes the output must say
    # how it varies: like the DB row slice it is computed from.
    vma = jax.typeof(db).vma | jax.typeof(q).vma
    if narrow:      # (b, m): lane-dense, the layout XLA gives an (m, b≤32)
        out_spec = pl.BlockSpec((bb, bm), lambda i, j, k: (j, i))
        out_shape = (nb * bb, m)
    else:
        out_spec = pl.BlockSpec((bm, bb), lambda i, j, k: (i, j))
        out_shape = (m, nb * bb)
    out = pl.pallas_call(
        functools.partial(_kernel, bb=bb, nk=nk),
        grid=(pl.cdiv(m, bm), nb, nk),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, width), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, width), lambda i, j, k: (0, j)),
        ],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, U32, vma=vma),
        scratch_shapes=([pltpu.VMEM((bm, width), I32)] if nk > 1 else []),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(db, digits, corr)
    if narrow:
        out = out.T
    return out[:, :b] if nb * bb != b else out


def _pad_last(x: jax.Array, width: int) -> jax.Array:
    pad = width - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x
