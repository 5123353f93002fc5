"""Pallas modmatmul kernel: bitwise-exact vs the pure-jnp u32 oracle.

Integer crypto ⇒ exact equality, not allclose.  Sweeps shapes (aligned and
ragged), batch sizes, and block configurations, in interpret mode on CPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.modmatmul import modmatmul_pallas, signed_digits


def _rand_db_q(seed, m, n, b):
    rng = np.random.default_rng(seed)
    db = rng.integers(0, 256, (m, n), dtype=np.uint8)
    q = rng.integers(0, 2**32, (n, b), dtype=np.uint32)
    return jnp.asarray(db), jnp.asarray(q)


@pytest.mark.parametrize("m,n,b", [
    (256, 512, 128),          # exactly one block
    (512, 1024, 128),         # multi-block contraction
    (256, 512, 256),          # multi-block batch
    (768, 1536, 128),         # 3x3 grid
])
def test_kernel_exact_aligned(m, n, b):
    db, q = _rand_db_q(0, m, n, b)
    got = modmatmul_pallas(db, q, interpret=True)
    want = ref.modmatmul_ref(db, q)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# 4608 = 9·512: the default contraction tile is 1536, three steps of it
_N_STEPS = 4608


@pytest.mark.parametrize("m,n,b", [
    (100, 300, 1),            # ragged everything + matvec
    (257, 513, 3),
    (31, 1025, 129),
    # at the default tiles, over several contraction steps, every column
    # width the tile choice can take: one tile of b (b ≤ 32), tiles of 128
    # padded (33) or whole (128, and the hint GEMM's 1024)
    *[(40, _N_STEPS, b) for b in (1, 2, 7, 16, 17, 33, 128, 1024)],
])
def test_ops_wrapper_pads_ragged(m, n, b):
    db, q = _rand_db_q(1, m, n, b)
    qq = q[:, 0] if b == 1 else q
    got = ops.modmatmul(db, qq, impl="pallas")
    want = ref.modmatmul_ref(db, qq)
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("block", [(128, 256, 128), (256, 512, 128),
                                   (512, 512, 256)])
def test_block_configs(block):
    db, q = _rand_db_q(2, 512, 1024, 256)
    got = ops.modmatmul(db, q, impl="pallas", block=block)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.modmatmul_ref(db, q)))


def test_extreme_values_wraparound():
    """All-255 DB × all-(2^32−1) queries stresses every carry path."""
    m, n, b = 256, 512, 128
    db = jnp.full((m, n), 255, jnp.uint8)
    q = jnp.full((n, b), 0xFFFFFFFF, jnp.uint32)
    got = modmatmul_pallas(db, q, interpret=True)
    want = ref.modmatmul_ref(db, q)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_xla_impl_matches_numpy_u64():
    rng = np.random.default_rng(3)
    db = rng.integers(0, 256, (64, 96), dtype=np.uint8)
    q = rng.integers(0, 2**32, (96, 5), dtype=np.uint32)
    got = np.asarray(ops.modmatmul(jnp.asarray(db), jnp.asarray(q), impl="xla"))
    want = ((db.astype(np.uint64) @ q.astype(np.uint64)) & 0xFFFFFFFF)
    np.testing.assert_array_equal(got, want.astype(np.uint32))


@settings(max_examples=10, deadline=None)
@given(m=st.integers(1, 64), n=st.integers(1, 128), b=st.integers(1, 8),
       seed=st.integers(0, 2**31 - 1))
def test_property_pallas_equals_oracle(m, n, b, seed):
    db, q = _rand_db_q(seed, m, n, b)
    got = ops.modmatmul(db, q, impl="pallas", block=(32, 64, 32))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.modmatmul_ref(db, q)))


def test_dtype_guards():
    db, q = _rand_db_q(4, 8, 8, 1)
    with pytest.raises(TypeError):
        ops.modmatmul(db.astype(jnp.int32), q)
    with pytest.raises(TypeError):
        ops.modmatmul(db, q.astype(jnp.int64))


def test_bucketed_xla_stacked_columns_bitwise():
    """The xla bucketed path now sends all C client columns of a bucket
    through ONE (m_b, W) @ (W, C) call — bitwise equal to the per-column
    matvec loop it replaced and to the u64 numpy oracle."""
    rng = np.random.default_rng(9)
    dbs = [jnp.asarray(rng.integers(0, 256, (rows, 64), dtype=np.uint8))
           for rows in (128, 384, 256)]
    qs = jnp.asarray(rng.integers(0, 2**32, (3, 64, 5), dtype=np.uint32))
    got = ops.bucketed_modmatmul(dbs, qs, impl="xla")
    for b, d in enumerate(dbs):
        d64 = np.asarray(d).astype(np.uint64)
        for c in range(5):
            want = (d64 @ np.asarray(qs[b, :, c]).astype(np.uint64)
                    ) % (1 << 32)
            np.testing.assert_array_equal(np.asarray(got[b][:, c]),
                                          want.astype(np.uint32))
    # (B, W) vector form still returns per-bucket vectors
    got_vec = ops.bucketed_modmatmul(dbs, qs[:, :, 0], impl="xla")
    for b in range(3):
        np.testing.assert_array_equal(np.asarray(got_vec[b]),
                                      np.asarray(got[b][:, 0]))


def test_pallas_pad_cache_reuses_and_invalidates():
    """Hot-loop calls with the SAME db array hit the padded-layout cache;
    a functionally updated db (new array object) misses and recomputes."""
    db, q = _rand_db_q(4, 100, 300, 2)
    ops._db_pad_cache.clear()
    h0, m0 = ops._db_pad_cache.hits, ops._db_pad_cache.misses
    a1 = ops.modmatmul(db, q, impl="pallas")
    a2 = ops.modmatmul(db, q, impl="pallas")
    assert ops._db_pad_cache.misses == m0 + 1
    assert ops._db_pad_cache.hits == h0 + 1
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    db2 = db.at[0, 0].set(7)          # new array object → cache miss
    a3 = ops.modmatmul(db2, q, impl="pallas")
    assert ops._db_pad_cache.misses == m0 + 2
    np.testing.assert_array_equal(np.asarray(a3),
                                  np.asarray(ref.modmatmul_ref(db2, q)))


def test_bucket_stack_cache_keyed_on_identity():
    """The pallas bucket stack is cached across calls and rebuilt when any
    sub-DB is swapped (as an epoch commit does)."""
    rng = np.random.default_rng(10)
    dbs = [jnp.asarray(rng.integers(0, 256, (128, 64), dtype=np.uint8))
           for _ in range(2)]
    qs = jnp.asarray(rng.integers(0, 2**32, (2, 64), dtype=np.uint32))
    ops._bucket_stack_cache.clear()
    ops.bucketed_modmatmul(dbs, qs, impl="pallas")
    ops.bucketed_modmatmul(dbs, qs, impl="pallas")
    assert ops._bucket_stack_cache.hits >= 1
    assert ops._bucket_stack_cache.misses == 1
    dbs2 = [dbs[0], dbs[1].at[3, 3].set(1)]
    got = ops.bucketed_modmatmul(dbs2, qs, impl="pallas")
    assert ops._bucket_stack_cache.misses == 2
    want = ops.bucketed_modmatmul(dbs2, qs, impl="xla")
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_scatter_and_add_helpers_exact():
    """scatter_columns / add_delta (the donated commit primitives) match
    their functional equivalents bitwise; donation consumes the operand."""
    rng = np.random.default_rng(11)
    db = jnp.asarray(rng.integers(0, 256, (64, 16), dtype=np.uint8))
    cols = jnp.asarray([3, 9])
    new = jnp.asarray(rng.integers(0, 256, (64, 2), dtype=np.uint8))
    want = np.asarray(db.at[:, cols].set(new))
    got = ops.scatter_columns(db, cols, new, donate=False)
    np.testing.assert_array_equal(np.asarray(got), want)
    got_don = ops.scatter_columns(db, cols, new, donate=True)
    np.testing.assert_array_equal(np.asarray(got_don), want)
    with pytest.raises(RuntimeError):
        np.asarray(db)                 # donated buffer is consumed

    hint = jnp.asarray(rng.integers(0, 2**32, (64, 8), dtype=np.uint32))
    delta = jnp.asarray(rng.integers(0, 2**32, (64, 8), dtype=np.uint32))
    want_h = np.asarray(hint) + np.asarray(delta)      # u32 wraparound
    got_h = ops.add_delta(hint, delta)
    np.testing.assert_array_equal(np.asarray(got_h), want_h)
    np.asarray(hint)                   # the HINT is never donated
    with pytest.raises(RuntimeError):
        np.asarray(delta)              # the transient delta is


# --- the default tiles, picked from shapes (ops.pick_block) ----------------

@pytest.mark.parametrize("word", [0xFFFFFFFF,   # digits (−1, 0, 0, 0)
                                  0x7F7F7F80,   # every digit −128
                                  0x7F7F7F7F])  # every digit +127
@pytest.mark.parametrize("b", [7, 128])
def test_extreme_values_at_default_tiles(word, b):
    """All-255 DB × extreme query words: i32 wraparound across the
    contraction and the once-only column correction."""
    db = jnp.full((40, _N_STEPS), 255, jnp.uint8)
    q = jnp.full((_N_STEPS, b), word, jnp.uint32)
    got = ops.modmatmul(db, q, impl="pallas")
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.modmatmul_ref(db, q)))


def test_signed_digits_recompose_each_word():
    words = np.array([0, 1, 127, 128, 255, 256, 0x7F7F7F7F, 0x7F7F7F80,
                      0x80808080, 0xFFFFFFFF], np.uint32)
    words = np.concatenate([words, np.random.default_rng(6).integers(
        0, 2**32, 1000, dtype=np.uint32)])[:, None]
    digits = np.asarray(signed_digits(jnp.asarray(words))).astype(np.int64)
    assert digits.shape == (len(words), 4, 1)
    assert digits.min() >= -128 and digits.max() <= 127
    back = sum(digits[:, l] << (8 * l) for l in range(4)) % (1 << 32)
    np.testing.assert_array_equal(back, words)


@pytest.mark.parametrize("n,b,block", [
    (4096, 1, (512, 4096, 1)),        # a serving batch: one tile of b
    (4096, 16, (512, 4096, 16)),
    (4096, 32, (512, 4096, 32)),
    (4096, 33, (512, 4096, 128)),     # past 32: tiles of 128
    (4096, 1024, (512, 4096, 128)),   # the hint GEMM keeps the wide tile
    (300, 8, (512, 512, 8)),          # a narrow DB pads to 512 columns
    (1024, 8, (512, 1024, 8)),        # a bucket's width is used in place
    (_N_STEPS, 7, (512, 1536, 7)),    # three contraction steps
])
def test_tile_is_picked_from_shapes(n, b, block):
    assert ops.pick_block(n, b) == block
    cols = ops.kernel_cols(b, "pallas")
    assert cols == (b if b <= 32 else -(-b // 128) * 128)
    assert ops.kernel_cols(b, "xla") == b
