"""Height-aware bucket→device placement: LPT properties + bitwise parity.

`collectives.balanced_bucket_order` reorders the bucket stack so skewed
per-bucket heights spread evenly across devices.  The placement is pure
bookkeeping — each bucket's GEMM is complete on its owning device — so the
contract is twofold: the load balance properties hold on any height
profile, and the reorder is INVISIBLE to callers (answers bit-identical to
the 1-device / unsorted layout).  The multi-device parity case runs under
the 8-fake-device subprocess harness and is slow-marked; the pure
host-side properties run in tier-1.
"""
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from _mesh_harness import run_sub
from repro.distributed import collectives


def _loads(heights, n_shards, order):
    return collectives.shard_row_loads(heights, n_shards, order=order)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n_shards=st.sampled_from([2, 4, 8]),
       n_buckets=st.integers(2, 96))
def test_lpt_order_is_balanced_capacity_exact_permutation(seed, n_shards,
                                                          n_buckets):
    """The order is a proper permutation, fills every device with exactly
    B'/S buckets, and never loses to the sequential layout on max-load."""
    rng = np.random.default_rng(seed)
    heights = np.maximum(1, (rng.lognormal(0.0, 0.8, n_buckets)
                             * 1024)).astype(np.int64)
    order = collectives.balanced_bucket_order(heights, n_shards)
    b_pad = -(-n_buckets // n_shards) * n_shards
    assert sorted(order) == list(range(b_pad))         # permutation incl. pads
    lpt, seq = _loads(heights, n_shards, order), _loads(heights, n_shards,
                                                        None)
    assert lpt.sum() == seq.sum() == heights.sum()     # no rows lost
    assert lpt.max() <= seq.max()                      # never worse than seq


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_lpt_order_permutation_stable(seed):
    """Permuting the input heights permutes the assignment but reproduces
    the same per-device load MULTISET — placement depends on the height
    set, not on bucket numbering."""
    rng = np.random.default_rng(seed)
    n_shards = int(rng.choice([2, 4, 8]))
    heights = rng.integers(1, 10_000, int(n_shards * rng.integers(1, 12)))
    base = np.sort(_loads(heights, n_shards,
                          collectives.balanced_bucket_order(heights,
                                                            n_shards)))
    perm = rng.permutation(len(heights))
    shuf = np.sort(_loads(heights[perm], n_shards,
                          collectives.balanced_bucket_order(heights[perm],
                                                            n_shards)))
    np.testing.assert_array_equal(base, shuf)


def test_lpt_reduces_imbalance_on_skewed_heights():
    """On a heavy-tailed profile the win is material, not epsilon: the
    benchmark-reported max/mean metric must drop."""
    rng = np.random.default_rng(0)
    heights = np.maximum(128, (rng.lognormal(0.0, 0.6, 48)
                               * 8192)).astype(np.int64)
    seq, lpt = (_loads(heights, 8, None),
                _loads(heights, 8, collectives.balanced_bucket_order(
                    heights, 8)))
    assert lpt.max() / lpt.mean() < 0.9 * (seq.max() / seq.mean())


@pytest.mark.slow
def test_sharded_keyed_answers_bit_identical_across_layouts():
    """8-device height-aware stack ≡ 1-device layout, bit for bit, through
    a mutation epoch — with a genuinely non-identity LPT permutation."""
    out = run_sub("""
from repro.update import LiveIndex

rng = np.random.default_rng(4)
table = rng.standard_normal((600, 8)).astype(np.float32)
mesh = make_mesh((8,), ("chunks",))
live1 = LiveIndex.build_keyed(table, kappa=8, impl="xla", seed=0)
live8 = LiveIndex.build_keyed(table, kappa=8, impl="xla", seed=0, mesh=mesh)
sys1, sys8 = live1.system, live8.system
assert sys8.batch.server.mesh is not None

ids = ((rng.zipf(1.2, size=8) - 1) % 600).astype(np.int64)
r1, _ = sys1.lookup(ids, key=jax.random.PRNGKey(2))
r8, _ = sys8.lookup(ids, key=jax.random.PRNGKey(2))
np.testing.assert_array_equal(r1, table[ids])
np.testing.assert_array_equal(r1, r8)

# the placement must actually be exercised: skewed keyed heights (the
# short last group plus granule rounding) or padding must move buckets
srv = sys8.batch.server
assert srv._order is not None
print("ORDER_NONTRIVIAL",
      bool((srv._order != np.arange(len(srv._order))).any()))

# mutation epoch patches the stack through the slot indirection
new = rng.standard_normal((2, 8)).astype(np.float32)
for live in (live1, live8):
    live.replace_row(int(ids[0]), new[0])
    live.replace_row(599, new[1])
    live.commit()
table[int(ids[0])], table[599] = new[0], new[1]
ask = np.concatenate([ids, [599]])
p1, _ = live1.lookup(ask, epoch=live1.epoch, key=jax.random.PRNGKey(3))
p8, _ = live8.lookup(ask, epoch=live8.epoch, key=jax.random.PRNGKey(3))
np.testing.assert_array_equal(p1, table[ask])
np.testing.assert_array_equal(p1, p8)
print("OK")
""")
    assert "ORDER_NONTRIVIAL True" in out, out
    assert "OK" in out
