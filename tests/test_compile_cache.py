"""Where the persistent compilation cache goes (repro.launch.compile_cache).

Each case runs in a child process: the cache directory is process-wide JAX
configuration, and turning it on in a test worker would change every later
compile in that worker."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.launch.compile_cache import DEFAULT_CACHE_DIR

REPO = Path(__file__).resolve().parents[1]

CHILD = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
jax.jit(lambda x: jnp.sin(x) * 2)(jnp.arange(8.0)).block_until_ready()
"""


def _run_child(env_dir):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    # cache every compile, however quick, so the child always writes one
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", CHILD], capture_output=True, text=True, env=env,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_env_dir_stands_and_receives_the_cache(tmp_path):
    cache = tmp_path / "cache"
    assert _run_child(cache) == str(cache)
    assert any(cache.iterdir())


def test_default_dir_is_fixed_in_checkout_and_ignored():
    assert DEFAULT_CACHE_DIR == REPO / ".jax_cache"
    assert _run_child(None) == str(DEFAULT_CACHE_DIR)
    assert any(DEFAULT_CACHE_DIR.iterdir())
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("n", [1, 2])
def test_host_devices_selects_the_cpu(n):
    """--host-devices is a CPU simulation on every machine."""
    code = (
        "import os; from repro.launch.train import simulate_host_devices;"
        f"simulate_host_devices({n}); import jax;"
        "print(os.environ['JAX_PLATFORMS'], jax.devices()[0].platform, len(jax.devices()))"
    )
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["cpu", "cpu", str(n)]
