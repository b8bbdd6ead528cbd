"""Pallas fused-gram kernel: numerical parity with the XLA path.

Runs in pallas interpret mode (the CPU test mesh has no Mosaic); the real
lowering is compiled for a described v5e in `test_tpu_compile.py` and run
on the chip by `chip_smoke.py`.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.algo.gp.kernels import cross_kernel_matrix, kernel_matrix
from orion_tpu.ops.gram import fused_gram, pallas_available


@pytest.mark.parametrize("kind", ["matern52", "rbf"])
@pytest.mark.parametrize(
    "m,n,d",
    [
        (300, 70, 6),    # ragged: every axis off the tile grid
        (256, 256, 4),   # exact tiles
        (513, 129, 130), # just past tile boundaries incl. feature axis
    ],
)
def test_fused_gram_matches_xla(kind, m, n, d):
    rng = np.random.default_rng(0)
    xa = jnp.asarray(rng.uniform(size=(m, d)), jnp.float32)
    xb = jnp.asarray(rng.uniform(size=(n, d)), jnp.float32)
    ils = jnp.asarray(rng.uniform(0.5, 3.0, size=(d,)), jnp.float32)
    amp = jnp.asarray(1.7, jnp.float32)
    ref = kernel_matrix(kind, xa, xb, ils, amp)
    got = fused_gram(xa, xb, ils, amp, kind=kind, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_fused_gram_diagonal_is_amplitude():
    """k(x, x) must equal the amplitude exactly-ish — the cancellation bug
    the full-precision cross matmul exists to prevent."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.uniform(size=(64, 8)), jnp.float32)
    ils = jnp.asarray(rng.uniform(0.5, 3.0, size=(8,)), jnp.float32)
    amp = jnp.asarray(2.5, jnp.float32)
    g = fused_gram(x, x, ils, amp, kind="matern52", interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.diagonal(g)), 2.5, atol=1e-4)


@pytest.mark.parametrize("m", [1024, 1030], ids=["divides", "padded"])
def test_fused_gram_splits_rows_over_the_ambient_mesh(m):
    """Under a multi-device ambient mesh the kernel runs per device on its
    rows (XLA cannot partition a Mosaic kernel): the values are those of
    the unsplit kernel, also when the device count does not divide m."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("rows",))
    rng = np.random.default_rng(2)
    xa = jnp.asarray(rng.uniform(size=(m, 6)), jnp.float32)
    xb = jnp.asarray(rng.uniform(size=(256, 6)), jnp.float32)
    ils = jnp.asarray(rng.uniform(0.5, 3.0, size=(6,)), jnp.float32)
    amp = jnp.asarray(1.3, jnp.float32)

    @jax.jit
    def split(xa, xb, ils, amp):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fused_gram(xa, xb, ils, amp, kind="matern52", interpret=True)

    text = split.lower(xa, xb, ils, amp).as_text()
    assert "shard_map" in text or "manual" in text.lower()
    got = split(jax.device_put(xa, NamedSharding(mesh, PartitionSpec())), xb, ils, amp)
    want = fused_gram(xa, xb, ils, amp, kind="matern52", interpret=True)
    assert got.shape == (m, 256)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_cross_kernel_matrix_small_shapes_stay_on_xla():
    """Below the crossover the dispatcher must not pay pallas overhead —
    and on the CPU test mesh pallas_available() is False anyway, so the
    result must be identical to the plain path."""
    rng = np.random.default_rng(2)
    xa = jnp.asarray(rng.uniform(size=(32, 3)), jnp.float32)
    xb = jnp.asarray(rng.uniform(size=(16, 3)), jnp.float32)
    ils = jnp.ones((3,), jnp.float32)
    amp = jnp.asarray(1.0, jnp.float32)
    out = cross_kernel_matrix("matern52", xa, xb, ils, amp)
    ref = kernel_matrix("matern52", xa, xb, ils, amp)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_pallas_available_env_override(monkeypatch):
    pallas_available.cache_clear()
    monkeypatch.setenv("ORION_TPU_PALLAS", "0")
    assert pallas_available() is False
    pallas_available.cache_clear()
    monkeypatch.setenv("ORION_TPU_PALLAS", "1")
    assert pallas_available() is True
    pallas_available.cache_clear()


def test_pallas_dispatch_policy(monkeypatch):
    """Dispatch follows the compile/run probe (auto-enable where the fused
    kernel measured 1.1-1.4x, docs/performance.md): ORION_TPU_PALLAS=0
    disables, and =1 cannot force dispatch where the probe says no.  The
    probe is stubbed both ways."""
    import orion_tpu.ops.gram as gram

    def reset():
        gram.pallas_enabled.cache_clear()
        gram.pallas_available.cache_clear()

    for probe_ok in (False, True):
        monkeypatch.setattr(gram, "_probe", lambda ok=probe_ok: ok)
        reset()
        monkeypatch.delenv("ORION_TPU_PALLAS", raising=False)
        assert gram.pallas_enabled() is probe_ok  # auto-follows the probe
        reset()
        monkeypatch.setenv("ORION_TPU_PALLAS", "0")
        assert gram.pallas_enabled() is False  # explicit opt-out always wins
        reset()
        monkeypatch.setenv("ORION_TPU_PALLAS", "1")
        assert gram.pallas_enabled() is probe_ok  # cannot force past the probe
        assert gram.pallas_available() is True  # ...though tests may override
        reset()


def test_probe_runs_for_real_inside_a_trace(monkeypatch):
    """The fused step's trace is usually the probe's first caller: the probe
    must still run the kernel and read its result back (on the chip this
    raised a TracerArrayConversionError, which the old probe swallowed into
    a silent XLA fallback).  Interpret mode stands in for the chip."""
    import functools

    import jax

    import orion_tpu.ops.gram as gram

    monkeypatch.setattr(gram.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        gram, "fused_gram", functools.partial(gram.fused_gram, interpret=True)
    )
    gram._probe.cache_clear()
    try:
        out = jax.jit(lambda x: x + float(gram._probe()))(jnp.float32(1.0))
    finally:
        gram._probe.cache_clear()
    assert float(out) == 2.0


@pytest.mark.parametrize("failure", ["raise", "non_finite"])
def test_probe_failure_on_tpu_raises(monkeypatch, failure):
    """On a TPU backend a broken kernel must surface, never quietly give
    way to XLA: the probe raises instead of answering False."""
    import orion_tpu.ops.gram as gram

    def broken(*args, **kwargs):
        if failure == "raise":
            raise RuntimeError("Mosaic lowering failed")
        return jnp.full((8, 8), jnp.nan)

    monkeypatch.setattr(gram.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(gram, "fused_gram", broken)
    gram._probe.cache_clear()
    try:
        with pytest.raises(RuntimeError):
            gram._probe()
    finally:
        gram._probe.cache_clear()
