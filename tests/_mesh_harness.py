"""Reusable multi-fake-device subprocess harness for sharding tests.

XLA pins the host-platform device count at first jax init, so a test that
needs N devices cannot run in the pytest process (which already initialised
jax with 1 CPU device).  Every multi-device case instead runs in a child
interpreter whose environment sets
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* jax imports.

Usage (from any test module):

    from _mesh_harness import run_sub

    def test_something_on_8_devices():
        run_sub('''
    mesh = make_mesh((8,), ("model",))
    ...
    print("OK")
    ''')

The prelude the body runs under imports jax/jnp/np, the sharding names
(`Mesh`, `NamedSharding`, `P`) and `make_mesh` (`repro.launch.mesh`: every
axis ``Auto``, as the code under test expects) and asserts the device
count, so bodies can use them directly.  `run_sub` asserts a zero exit
status and returns the child's stdout for content assertions.
"""
from __future__ import annotations

import subprocess
import sys

ENV_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
assert jax.device_count() == {n}, jax.device_count()
"""


def run_sub(body: str, *, n_devices: int = 8, timeout: float = 600) -> str:
    """Run `body` in a child interpreter with n_devices fake CPU devices."""
    prelude = ENV_PRELUDE.format(n=n_devices)
    proc = subprocess.run(
        [sys.executable, "-c", prelude + body],
        capture_output=True, text=True, timeout=timeout,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    return proc.stdout
