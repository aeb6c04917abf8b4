"""Entry-point plumbing: where the compile cache lives, and a chip smoke
that refuses to run anywhere but on a TPU."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

_PROBE = (
    "import jax\n"
    "from repro.launch import use_compile_cache\n"
    "used = use_compile_cache()\n"
    "jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones(8)).block_until_ready()\n"
    "print(used)\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def _run(args, env_extra, unset=()):
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src"), "JAX_PLATFORMS": "cpu",
           **env_extra}
    for k in unset:
        env.pop(k, None)
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True, env=env,
        timeout=300, cwd=str(ROOT),
    )


def test_compile_cache_defaults_to_the_checkout():
    proc = _run(["-c", _PROBE], {}, unset=("JAX_COMPILATION_CACHE_DIR",))
    assert proc.returncode == 0, proc.stderr[-2000:]
    used, configured = proc.stdout.split()[-2:]
    assert used == configured == str(ROOT / ".jax_cache")


def test_compile_cache_env_dir_stands_and_is_written(tmp_path):
    cache = tmp_path / "cache"
    proc = _run(["-c", _PROBE], {
        "JAX_COMPILATION_CACHE_DIR": str(cache),
        # cache even this tiny compile, so the write is observable
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
    })
    assert proc.returncode == 0, proc.stderr[-2000:]
    used, configured = proc.stdout.split()[-2:]
    assert used == configured == str(cache)
    assert any(cache.iterdir())


def test_chip_smoke_refuses_the_cpu():
    proc = _run([str(ROOT / "chip_smoke.py")], {})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        assert not json.loads(line).get("ok"), line
