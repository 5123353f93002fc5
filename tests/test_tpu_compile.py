"""Compile rehearsals of the serve path's kernels for a described TPU v5e.

Nothing runs: each test compiles one program of the main path at real
widths for a v5e that is described, not attached, and asserts the Pallas
kernel is in it (``tpu_custom_call``) — what the chip's compiler would
refuse (misaligned blocks, VMEM over-use, a kernel that cannot be
partitioned) fails here at no chip time.  ``ops`` picks its kernel from
``jax.default_backend()``, which still says "cpu" here, so each test steers
that check to "tpu" with monkeypatch.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import lwe
from repro.distributed import collectives
from repro.kernels import ops
from repro.launch.mesh import make_chunk_mesh

COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m,n,b", [
    (65536, 4096, 128),       # online answer, one query tile
    (65536, 4096, 1024),      # offline hint GEMM, H = D·A at LWE k = 1024
    (65536 + 192, 4096, 128),  # a row count ending in a partial row tile
    # msmarco.steady's DB: serving batches of 1, 8 and 16 (one narrow column
    # tile each), then its hint GEMM at LWE k = 1024
    (1_559_040, 4096, 1),
    (1_559_040, 4096, 8),
    (1_559_040, 4096, 16),
    (1_559_040, 4096, 1024),
])
def test_modmatmul_compiles_to_kernel(one_chip, on_tpu, m, n, b):
    text = _compile(ops.modmatmul, _sds((m, n), jnp.uint8, one_chip),
                    _sds((n, b), jnp.uint32, one_chip))
    assert "tpu_custom_call" in text


def test_bucketed_modmatmul_compiles_to_vmapped_kernel(one_chip, on_tpu):
    """κ = 4 batch-PIR over 4096 clusters: 12 buckets of width 1024."""
    heights = [4096 - 256 * (b % 3) for b in range(12)]
    dbs = [_sds((h, 1024), jnp.uint8, one_chip) for h in heights]
    qs = _sds((12, 1024, 16), jnp.uint32, one_chip)
    text = _compile(lambda d, q: ops.bucketed_modmatmul(d, q), dbs, qs)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("d", [64, 768])
def test_kmeans_assign_compiles_to_kernel(one_chip, on_tpu, d):
    text = _compile(ops.kmeans_assign, _sds((65536, d), jnp.float32, one_chip),
                    _sds((4096, d), jnp.float32, one_chip))
    assert "tpu_custom_call" in text


def test_client_decode_compiles(one_chip):
    """The client's hint strip is a u32 (m, k)·(k, C) matmul on the chip."""
    params = lwe.LWEParams()
    text = _compile(lambda a, h, s: lwe.decode_switched(a, h, s, params),
                    _sds((65536, 16), jnp.uint16, one_chip),
                    _sds((65536, params.k), jnp.uint32, one_chip),
                    _sds((params.k, 16), jnp.uint32, one_chip))
    assert "ENTRY" in text


@pytest.mark.parametrize("c", [1, 16])
def test_client_batched_encrypt_compiles(one_chip, c):
    """A serving batch's C encrypts at 4096 clusters, LWE k = 1024: one
    program with one u32 (n, k)·(k, C) matmul."""
    text = _compile(lwe.encrypt_onehots, _sds((2,), jnp.uint32, one_chip),
                    _sds((4096, 1024), jnp.uint32, one_chip),
                    _sds((c,), jnp.int32, one_chip),
                    _sds((), jnp.uint32, one_chip),
                    _sds((), jnp.float32, one_chip))
    assert "ENTRY" in text


def _row_shard_answer(topo, rows: int, b: int) -> str:
    """The compiled row-sharded answer over four chips of ``rows`` each."""
    mesh = make_chunk_mesh(4, topo.devices)
    fn = collectives.row_shard_gemm(mesh, ("chunks",), q_switch=1 << 16)
    return fn.lower(
        _sds((4 * rows, 4096), jnp.uint8,
             NamedSharding(mesh, P("chunks", None))),
        _sds((4096, b), jnp.uint32, NamedSharding(mesh, P()))
    ).compile().as_text()


def test_row_shard_gemm_compiles_without_collectives(topo, on_tpu):
    """Each chip's row slice ends in a partial kernel row tile, as a packed
    DB's m/4 usually does: the kernel takes it in place."""
    text = _row_shard_answer(topo, 65536 + 192, 128)
    assert "tpu_custom_call" in text
    assert not [c for c in COLLECTIVES if c in text]


@pytest.mark.parametrize("b", [1, 16])
def test_row_shard_gemm_at_the_sharded_cells_rows(topo, on_tpu, b):
    """dpr-wiki.sharded4's answer: 266,304 DB rows a chip, a serving batch
    of b queries copied to every chip; the kernel, and no collective."""
    text = _row_shard_answer(topo, 266_304, b)
    assert "tpu_custom_call" in text
    assert not [c for c in COLLECTIVES if c in text]
