"""Test harness configuration.

All tests run on a virtual 8-device CPU mesh so multi-chip sharding logic
(`orion_tpu.parallel`) is exercised hermetically without TPU hardware.  The
chip run is `python chip_smoke.py` (README "Benchmarks").  The env vars must
be set before the first backend initialization anywhere in the test process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
# Persistent compilation cache: the suite's wall time is dominated by jit
# compiles repeated identically across test processes/sessions.  Same
# rules as every other process (JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache).
from orion_tpu.utils.jit_cache import enable_persistent_compilation_cache

enable_persistent_compilation_cache()

import numpy as np
import pytest

# THE repo-root discovery — shared by every test that shells out to repo
# files (bench.py, __graft_entry__.py, docs/commands.md) and by the lint
# self-test, replacing the per-file dirname/dirname/dirname chains that
# silently break when a test file moves one directory deeper.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="session")
def repo_root():
    """Absolute path of the repository root (the directory holding
    ``bench.py``/``orion_tpu``/``docs``)."""
    return _REPO_ROOT


@pytest.fixture(autouse=True)
def _tsan_marked_tests(request):
    """The tsan pytest plugin: a test marked ``@pytest.mark.tsan`` runs
    under the runtime concurrency sanitizer (instrumented lock/event shims,
    vector-clock race detection, the seeded interleaving explorer) and
    FAILS if the run observed any data race or lock-order cycle — the
    tier-1 dynamic leg of the static LCK rules.  Marked tests must not
    enable/disable the sanitizer themselves (the fixture owns it); tests
    that exercise the sanitizer's own machinery stay unmarked."""
    marker = request.node.get_closest_marker("tsan")
    if marker is None:
        yield
        return
    from orion_tpu.analysis.sanitizer import TSAN

    if TSAN.enabled:
        # The whole pytest process is already instrumented (`orion-tpu
        # tsan -- pytest ...`): the outer owner collects and reports at
        # exit; enabling again would raise and unpatching mid-run would
        # blind it.
        yield
        return
    TSAN.enable(seed=int(marker.kwargs.get("seed", 0)))
    try:
        yield
    finally:
        report = TSAN.disable()
    assert report.violation_count() == 0, (
        "tsan violations in a tsan-marked test:\n" + report.format_human()
    )


@pytest.fixture(autouse=True)
def _isolate_user_config(tmp_path, monkeypatch):
    """Tests must never inherit the developer's ~/.config/orion_tpu."""
    monkeypatch.setenv("XDG_CONFIG_HOME", str(tmp_path / "xdg-isolated"))


@pytest.fixture
def rng_seed():
    """Pin numpy global RNG for legacy-style deterministic tests."""
    np.random.seed(42)
    return 42


@pytest.fixture
def tmp_storage(tmp_path):
    """A fresh file-locked storage instance in a temp dir."""
    from orion_tpu.storage import create_storage

    return create_storage({"type": "pickled", "path": str(tmp_path / "db.pkl")})
