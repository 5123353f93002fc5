"""Pure-jnp oracles for the modular-GEMM kernels.

XLA integer arithmetic is modular (wraparound), so a plain uint32 matmul *is*
the exact mod-2^32 product — verified bitwise against uint64 numpy in tests.
These oracles are also the production CPU path (`impl="xla"` in ops.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

U32 = jnp.uint32


def modmatmul_ref(db: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """Exact (db @ q) mod 2^32.

    db: (m, n) unsigned integer (u8/u16/u32) plaintext database.
    q:  (n,) or (n, b) uint32 ciphertext queries.
    returns uint32 (m,) or (m, b).
    """
    return jnp.matmul(db.astype(U32), q.astype(U32))


def modmatvec_ref(db: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    return modmatmul_ref(db, q)


def kmeans_assign_ref(x: jnp.ndarray, c: jnp.ndarray):
    """Unfused oracle for the k-means assignment kernel."""
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    c2 = jnp.sum(c * c, axis=1)[None, :]
    d2 = x2 - 2.0 * jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST
                               ) + c2
    return jnp.argmin(d2, axis=1).astype(jnp.int32), jnp.min(d2, axis=1)
