"""Production mesh builders.

Functions, not module-level constants — importing this module never touches
jax device state (smoke tests must keep seeing 1 CPU device).

Every mesh here states its axis types as ``Auto``: the sharding code in this
repo leaves propagation to the compiler (``with_sharding_constraint`` plus
explicit ``shard_map`` blocks), which is what ``jax.make_mesh`` meant before
its default became ``Explicit``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """``jax.make_mesh`` with every axis ``Auto``-typed."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 chips per pod; the multi-pod mesh adds a leading pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly fake) local devices exist."""
    return make_mesh((data, model), ("data", "model"))


def make_chunk_mesh(n_shards: int, devices=None):
    """The ``("chunks",)`` row-shard mesh the sharded build and server use,
    over the first ``n_shards`` local devices (or the given ones)."""
    devices = list(jax.devices())[:n_shards] if devices is None else devices
    assert len(devices) == n_shards, (len(devices), n_shards)
    return make_mesh((n_shards,), ("chunks",), devices=devices)


def make_replica_meshes(n_replicas: int, n_shards: int, devices=None):
    """Per-rank row-shard meshes over DISJOINT device sets.

    Rank r of the replica fleet gets devices [r·S, (r+1)·S) as its own
    ("chunks",) mesh — the same axis convention the sharded build uses —
    so one lost device takes out exactly one rank's shard cell and never
    touches the sibling replica (the placement invariant
    `repro.fleet.replica` fails over on).  Requires R·S ≤ len(devices).
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    assert n_replicas * n_shards <= len(devices), (
        n_replicas, n_shards, len(devices))
    return [make_chunk_mesh(n_shards,
                            devices[r * n_shards:(r + 1) * n_shards])
            for r in range(n_replicas)]
