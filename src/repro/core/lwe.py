"""Regev-style LWE linearly-homomorphic encryption over Z_{2^32}.

This is the lattice primitive underneath PIR-RAG's SimplePIR-style protocol
(Henzinger et al., USENIX Sec'23).  All ciphertext arithmetic is uint32 with
wraparound, i.e. the ciphertext modulus is q = 2^32 *implicitly* — XLA integer
ops are modular, so ``jnp.matmul`` on uint32 computes exactly mod q (verified
bitwise in tests/test_lwe.py).

Scheme (secret dim k, plaintext modulus p, Δ = q // p, error σ):

    A  ~ U(Z_q^{n×k})            public, derived from a shared seed
    s  ~ U(Z_q^k)                secret
    e  ~ round(N(0, σ²))^n       fresh per query
    ct = A·s + e + Δ·msg         (n,) uint32, msg ∈ Z_p^n

The server's homomorphic op is a plaintext matrix product D·ct which the
client strips with the hint H = D·A:

    D·ct − H·s = D·e + Δ·(D·msg)      → round to recover D·msg  (mod p)

Security point (k=1024, q=2^32, σ=6.4) is the standard ≈128-bit SimplePIR /
Tiptoe parameterization; we take it as given rather than re-running a lattice
estimator.  Correctness margins ARE re-derived here (`noise_budget_ok`).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

U32 = jnp.uint32
Q_BITS = 32
Q = 1 << Q_BITS  # ciphertext modulus (implicit via uint32 wraparound)


@dataclasses.dataclass(frozen=True)
class LWEParams:
    """Parameters of the LWE scheme.

    k:        secret dimension.
    p:        plaintext modulus (DB entries live in Z_p; p ≤ 2^16).
    sigma:    gaussian error std-dev.
    z_tail:   tail factor for correctness bound (≈ erfc⁻¹ based; 6 ⇒ ~2^-29
              per-coefficient failure).
    q_switch: response modulus for downlink modulus switching (None = off).
              2^16 halves the response vs raw q = 2^32.
    """

    k: int = 1024
    p: int = 256
    sigma: float = 6.4
    z_tail: float = 6.0
    q_switch: int | None = 1 << 16

    @property
    def delta(self) -> int:
        return Q // self.p

    @property
    def plaintext_bits(self) -> int:
        return int(math.log2(self.p))

    def __post_init__(self):
        if self.p & (self.p - 1):
            raise ValueError("p must be a power of two")
        if self.p > (1 << 16):
            raise ValueError("p > 2^16 unsupported (limb decomposition)")
        if self.q_switch is not None and self.q_switch & (self.q_switch - 1):
            raise ValueError("q_switch must be a power of two")


def noise_bound(params: LWEParams, n_inner: int) -> float:
    """High-probability bound on |<db_row, e>| for db entries in [0, p).

    Each of the n_inner error coords is N(0, σ²); the inner product with a
    row of entries ≤ p−1 has std ≤ σ·(p−1)·√n_inner.
    """
    return params.z_tail * params.sigma * (params.p - 1) * math.sqrt(n_inner)


def noise_budget_ok(params: LWEParams, n_inner: int) -> bool:
    """True iff decoding succeeds whp for a DB with n_inner columns."""
    budget = params.delta / 2.0
    slack = 0.0
    if params.q_switch is not None:
        # Two roundings (answer + hint·s), each ≤ 0.5 in q_switch units,
        # i.e. ≤ q / (2·q_switch) in q units — plus one for safety.
        slack = 3.0 * Q / (2.0 * params.q_switch)
    return noise_bound(params, n_inner) + slack < budget


def choose_params(n_inner: int, *, want_p: int = 256,
                  q_switch: int | None = 1 << 16) -> LWEParams:
    """Largest safe plaintext modulus ≤ want_p for an n_inner-column DB."""
    p = want_p
    while p >= 2:
        params = LWEParams(p=p, q_switch=q_switch)
        if noise_budget_ok(params, n_inner):
            return params
        p >>= 1
    raise ValueError(f"no safe plaintext modulus for n_inner={n_inner}")


# ---------------------------------------------------------------------------
# Sampling
# ---------------------------------------------------------------------------

def gen_public_matrix(seed: int, n: int, k: int) -> jax.Array:
    """Public LWE matrix A ∈ Z_q^{n×k}, derived from a shared seed."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0x5157)
    return jax.random.bits(key, (n, k), dtype=U32)


def keygen(key: jax.Array, params: LWEParams) -> jax.Array:
    """Uniform secret s ∈ Z_q^k (Regev; hint subtraction is exact)."""
    return jax.random.bits(key, (params.k,), dtype=U32)


def sample_error(key: jax.Array, shape, sigma: float) -> jax.Array:
    """Rounded-gaussian error, represented mod q (negatives wrap)."""
    e = jnp.round(sigma * jax.random.normal(key, shape, dtype=jnp.float32))
    return e.astype(jnp.int32).astype(U32)


# ---------------------------------------------------------------------------
# Encrypt / decrypt
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=())
def encrypt_vector(key: jax.Array, s: jax.Array, a_mat: jax.Array,
                   msg: jax.Array, delta: jnp.uint32,
                   sigma: float) -> jax.Array:
    """ct = A·s + e + Δ·msg   (all uint32 wraparound).

    msg entries are plaintext residues (for PIR: a one-hot selector).
    """
    e = sample_error(key, (a_mat.shape[0],), sigma)
    mask = jnp.matmul(a_mat, s.astype(U32))  # exact mod 2^32
    return mask + e + jnp.uint32(delta) * msg.astype(U32)


@jax.jit
def encrypt_onehots(key: jax.Array, a_mat: jax.Array, indices: jax.Array,
                    delta: jnp.uint32,
                    sigma: float) -> tuple[jax.Array, jax.Array]:
    """C one-hot encryptions in one program: (cts (n, C), secrets (k, C)).

    Column i is the one-hot of ``indices[i]`` under ``fold_in(key, i)``,
    split into a `keygen` key and an `encrypt_vector` error key, so it is
    bit-identical to that per-query chain: each key's random bits do not
    depend on the batch, and the (n, k)·(k, C) product is exact mod 2^32.
    """
    n, k = a_mat.shape

    def secret_and_error(i):
        k_sec, k_err = jax.random.split(jax.random.fold_in(key, i))
        return (jax.random.bits(k_sec, (k,), dtype=U32),     # as `keygen`
                sample_error(k_err, (n,), sigma))

    s, e = jax.vmap(secret_and_error, out_axes=1)(
        jnp.arange(indices.shape[0]))
    onehots = (jnp.arange(n)[:, None] == indices[None, :]).astype(U32)
    return jnp.matmul(a_mat, s) + e + jnp.uint32(delta) * onehots, s


def hint_strip(ans: jax.Array, hint: jax.Array, s: jax.Array) -> jax.Array:
    """ans − H·s (mod q): leaves Δ·(D·msg) + D·e."""
    return ans - jnp.matmul(hint, s.astype(U32))


def decode(rec: jax.Array, params: LWEParams) -> jax.Array:
    """Round Δ·x + noise → x ∈ Z_p (wrapping add handles negative noise)."""
    half = jnp.uint32(params.delta // 2)
    return ((rec + half) >> jnp.uint32(Q_BITS - params.plaintext_bits)).astype(
        U32) % jnp.uint32(params.p)


# ---------------------------------------------------------------------------
# Modulus switching (downlink compression — beyond-paper optimization)
# ---------------------------------------------------------------------------

def switch_modulus(x: jax.Array, q_switch: int) -> jax.Array:
    """Round x from Z_{2^32} to Z_{q_switch} (power of two)."""
    shift = Q_BITS - int(math.log2(q_switch))
    half = jnp.uint32(1 << (shift - 1))
    return ((x + half) >> jnp.uint32(shift)).astype(
        jnp.uint16 if q_switch <= 1 << 16 else U32)


def decode_switched(ans_sw: jax.Array, hint: jax.Array, s: jax.Array,
                    params: LWEParams) -> jax.Array:
    """Decode a modulus-switched answer.

    The client computes H·s exactly in Z_q, switches it to q_switch, and
    subtracts there; Δ maps to Δ·q_switch/q.
    """
    qs = params.q_switch
    assert qs is not None
    log_qs = int(math.log2(qs))
    hs_sw = switch_modulus(jnp.matmul(hint, s.astype(U32)), qs).astype(U32)
    rec = (ans_sw.astype(U32) - hs_sw) % jnp.uint32(qs)
    delta_sw = qs // params.p
    half = jnp.uint32(delta_sw // 2)
    return ((rec + half) % jnp.uint32(qs) >> jnp.uint32(
        log_qs - params.plaintext_bits)) % jnp.uint32(params.p)
