"""Work of one answer, counted from shapes (not from the kernel).

The answer is ``(D · Q) mod 2^32`` for a u8 database ``D`` of shape (m, n)
and ``b`` u32 query columns.  Its logical work is that of an int8 GEMM over
the four 8-bit limbs of each query word: ``8·m·n·b`` integer operations
(2 per multiply-add, 4 limbs), as ``core/pir.server_flops`` counts it.  Its
least traffic is the database streamed once plus the queries read and the
u32 products written.  Only the real query columns count: padding that an
implementation adds is not work the answer needs.

On a row-sharded server every chip answers its own row slice at once, so
one chip's least time is that of an answer over the rows it holds
(``m`` on one chip), against that chip's peaks.
"""
from __future__ import annotations

LIMBS = 4
#: The answer program as the device trace names it: the Pallas kernel's
#: own jit on one chip, and the jit of ``collectives.row_shard_gemm``'s
#: ``shard_map`` (its per-shard function is ``local``) on a row-sharded DB.
ANSWER_MODULES = ("jit_modmatmul_pallas", "jit_local")


def answer_ops(m: int, n: int, b: int) -> int:
    """Integer operations of one answer over ``b`` real query columns."""
    return 2 * LIMBS * m * n * b


def answer_bytes(m: int, n: int, b: int) -> int:
    """Bytes one answer must move: DB once, queries in, products out."""
    return m * n + 4 * n * b + 4 * m * b


def least_seconds(m: int, n: int, b: int, peak_ops: float,
                  peak_bytes: float) -> tuple[float, str]:
    """(least time of one answer on a chip, the bound: "hbm" or "mxu")."""
    t_mem = answer_bytes(m, n, b) / peak_bytes
    t_ops = answer_ops(m, n, b) / peak_ops
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "mxu")
