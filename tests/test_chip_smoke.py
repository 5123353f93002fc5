"""chip_smoke.py rehearsed on CPU: its build → serve → check body at a tiny
size, its refusal to report a result without a TPU, and the compile-cache
placement rule its entry points share."""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest
from _mesh_harness import run_sub

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

TINY = dataclasses.replace(
    chip_smoke.ONE_CHIP, n_docs=600, n_clusters=16, emb_dim=32,
    text_len=(64, 256), requests=12, mutate_every=4, max_batch=4,
    mp_docs=200, mp_requests=4, answer_rows=64, hint_rows=16,
    clusters_checked=4, min_db_bytes_per_chip=4096)


def _env(**extra):
    env = {"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
           "HOME": os.path.expanduser("~"), "JAX_PLATFORMS": "cpu"}
    env.update(extra)
    return env


def test_one_chip_body_checks_pass_at_tiny_size():
    checks = chip_smoke.run_one_chip(TINY)
    # the CPU runs the XLA oracle, so only the kernel-presence check differs
    assert checks.pop("answer_program_has_tpu_custom_call") is False
    assert checks and all(checks.values()), checks


def test_four_chip_body_checks_pass_on_four_cpu_devices():
    """The --chips 4 path (row-sharded build + serve vs the host reference)
    on four virtual devices."""
    out = run_sub(f"""
import dataclasses, json, sys
sys.path.insert(0, {str(ROOT)!r})
import chip_smoke
size = dataclasses.replace(
    chip_smoke.FOUR_CHIPS, n_docs=600, n_clusters=16, emb_dim=32,
    text_len=(64, 256), requests=8, max_batch=4, answer_rows=64,
    hint_rows=16, clusters_checked=4, min_db_bytes_per_chip=1024)
print(json.dumps(chip_smoke.run_four_chips(size, 4)))
""", n_devices=4)
    checks = json.loads(out.splitlines()[-1])
    assert checks.pop("answer_program_has_tpu_custom_call") is False
    assert checks["answer_program_has_no_collective"], checks
    assert all(checks.values()), checks


def test_script_refuses_without_tpu():
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, timeout=300,
                          env=_env(), cwd=ROOT)
    assert proc.returncode != 0
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    assert '"ok": true' not in last
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_dir_rule(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins; otherwise <checkout>/.jax_cache."""
    extra = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if env_dir else {}
    code = ("import json, jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "got = enable_compile_cache()\n"
            "print(json.dumps([got, jax.config.jax_compilation_cache_dir]))")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120, env=_env(**extra))
    assert proc.returncode == 0, proc.stderr
    got, configured = json.loads(proc.stdout.splitlines()[-1])
    want = str(tmp_path) if env_dir else str(ROOT / ".jax_cache")
    assert got == configured == want
