"""Persistent-compilation-cache wiring (the cache itself is exercised on
the chip by ``chip_smoke.py``; these cover the configuration contract)."""

import os
import subprocess
import sys

import jax
import pytest

from orion_tpu.utils import jit_cache
from orion_tpu.utils.jit_cache import enable_persistent_compilation_cache


@pytest.fixture
def no_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_existing_jax_config_wins():
    # conftest enabled the cache before anything else ran; enable() must
    # honor it rather than redirect.
    configured = jax.config.jax_compilation_cache_dir
    assert configured
    assert enable_persistent_compilation_cache() == configured


def test_default_dir_is_the_checkout(no_cache_dir, monkeypatch, repo_root):
    monkeypatch.delenv("ORION_TPU_JIT_CACHE", raising=False)
    out = enable_persistent_compilation_cache()
    assert out == os.path.join(repo_root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == out
    assert os.path.isdir(out)


@pytest.mark.parametrize("value", ["off", "0", "false", "no"])
def test_off_switch(no_cache_dir, monkeypatch, value):
    monkeypatch.setenv("ORION_TPU_JIT_CACHE", value)
    assert enable_persistent_compilation_cache() is None
    assert not jax.config.jax_compilation_cache_dir


def test_path_values_are_not_a_location(no_cache_dir, monkeypatch, tmp_path):
    """The old path form of ORION_TPU_JIT_CACHE is gone: any value but an
    off switch keeps the checkout location."""
    monkeypatch.setenv("ORION_TPU_JIT_CACHE", str(tmp_path / "elsewhere"))
    assert enable_persistent_compilation_cache() == jit_cache.CHECKOUT_CACHE_DIR
    assert not (tmp_path / "elsewhere").exists()


def test_env_dir_is_the_cache_in_a_fresh_process(tmp_path, repo_root):
    """JAX_COMPILATION_CACHE_DIR set: it is the cache, in the conftest-free
    production path (create_algo) too."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"))
    code = (
        "import jax\n"
        "from orion_tpu.algo.base import create_algo\n"
        "from orion_tpu.space.dsl import build_space\n"
        "create_algo(build_space({'x': 'uniform(0, 1)'}), 'random', seed=0)\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=repo_root,
        capture_output=True, text=True, timeout=120, check=True,
    )
    assert out.stdout.strip().splitlines()[-1] == str(tmp_path / "jc")
