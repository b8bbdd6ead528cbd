"""The GP fit on dense fit sets: the trust region's local fit set packs
tightly around its incumbent, far from the origin, where the uncentred
``|a|^2 + |b|^2 - 2ab`` distance rounds near-neighbour distances to noise in
float32 and the kernel matrix stops being positive definite.  Checked
against the plain float64 reference (``benchmark/reference/gp.py``), and
the fit's count of non-finite steps through its health field and its
telemetry counters."""

import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from benchmark.reference import gp as ref
from benchmark.reference.objectives import hartmann6
from orion_tpu import telemetry as tel
from orion_tpu.algo.base import create_algo
from orion_tpu.algo.gp.gp import GPHypers, _masked_kernel, fit_gp
from orion_tpu.algo.gp.kernels import sq_dists
from orion_tpu.health import DEVICE_HEALTH_FIELDS
from orion_tpu.space.dsl import build_space

NAMES6 = [f"x{i}" for i in range(6)]


def _dense_set(seed=0, n=256, d=6, radius=1e-3):
    """``n`` points in a ball of ``radius`` around a random point of the
    unit cube, and Hartmann6 at them."""
    rng = np.random.default_rng(seed)
    centre = rng.uniform(size=d)
    step = rng.normal(size=(n, d))
    step *= radius * rng.uniform(size=(n, 1)) ** (1 / d) / np.linalg.norm(step, axis=1,
                                                                          keepdims=True)
    x = (centre + step).astype(np.float32)
    return x, hartmann6(x).astype(np.float32)


def _theta(hypers):
    return np.concatenate([np.asarray(hypers.log_lengthscales, np.float64),
                           [float(hypers.log_amplitude), float(hypers.log_noise)]])


def test_dense_fit_set_kernel_matches_reference_and_factors():
    """Lengthscales near the clip floor (1e-3) on a ball of radius 1e-3:
    the float32 masked K matches the float64 reference and its Cholesky is
    finite."""
    x, _ = _dense_set()
    mask = np.ones(x.shape[0], np.float32)
    hypers = GPHypers(
        log_lengthscales=jnp.full(6, np.log(2e-3), jnp.float32),
        log_amplitude=jnp.asarray(0.0, jnp.float32),
        log_noise=jnp.asarray(np.log(1e-4), jnp.float32),
    )
    k = np.asarray(_masked_kernel("matern52", jnp.asarray(x), jnp.asarray(mask), hypers))
    k_ref = ref.masked_kernel(_theta(hypers), x, mask)[0]
    np.testing.assert_allclose(k, k_ref, rtol=0, atol=2e-5)
    assert np.all(np.isfinite(np.asarray(jnp.linalg.cholesky(jnp.asarray(k)))))


def test_dense_fit_set_fit_is_finite_and_moves():
    x, y = _dense_set(seed=1)
    mask = np.ones(x.shape[0], np.float32)
    warm = GPHypers(
        log_lengthscales=jnp.full(6, np.log(2e-3), jnp.float32),
        log_amplitude=jnp.asarray(0.0, jnp.float32),
        log_noise=jnp.asarray(np.log(1e-3), jnp.float32),
    )
    state = fit_gp(jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask), n_steps=40,
                   init=warm, y_transform="copula")
    assert np.isfinite(float(state.mll))
    assert int(state.fit_nonfinite) == 0
    assert not np.allclose(_theta(state.hypers), _theta(warm))


def test_dense_fit_nonfinite_steps_are_counted():
    """A NaN target poisons every step's loss: each is counted (and zeroed,
    so the fit stays at its warm start)."""
    x, y = _dense_set(seed=2, n=64)
    y[5] = np.nan
    state = fit_gp(jnp.asarray(x), jnp.asarray(y), jnp.ones(64), n_steps=7)
    assert int(state.fit_nonfinite) == 7


@pytest.mark.parametrize("seed", [0, 1])
def test_regular_fit_set_distances_unchanged(seed):
    """A Rosenbrock-shaped fit set (256 uniform points of [0, 1]^20, fitted
    lengthscales 0.2-2): the centred distances equal the uncentred
    expansion's to float32 rounding, and neither is further from float64."""
    rng = np.random.default_rng(seed)
    x = rng.uniform(size=(256, 20)).astype(np.float32)
    inv_ls = (1.0 / rng.uniform(0.2, 2.0, size=20)).astype(np.float32)
    got = np.asarray(sq_dists(jnp.asarray(x), jnp.asarray(x), jnp.asarray(inv_ls)))
    a = jnp.asarray(x * inv_ls)
    aa = jnp.sum(a * a, axis=-1)
    uncentred = np.asarray(jnp.maximum(
        aa[:, None] + aa[None, :]
        - 2.0 * jnp.matmul(a, a.T, precision=jax.lax.Precision.HIGHEST), 0.0))
    a64 = x.astype(np.float64) * inv_ls
    exact = np.sum((a64[:, None, :] - a64[None, :, :]) ** 2, axis=-1)
    rounding = 8 * np.finfo(np.float32).eps * float(np.max(aa))
    np.testing.assert_allclose(got, uncentred, rtol=0, atol=rounding)
    assert np.max(np.abs(got - exact)) <= np.max(np.abs(uncentred - exact))


def _observe(algo, x, values):
    algo.observe([dict(zip(NAMES6, row)) for row in x.tolist()],
                 [{"objective": float(v)} for v in values])


def test_hartmann6_q1024_rounds_stay_finite(repo_root):
    """The hartmann6-q1024 deployment, 130 seeded points then four rounds of
    1024: the uncentred distance made round 3's fit NaN on this seed (CPU)
    and froze its hyper-parameters.  Every round's fit is finite now."""
    with open(os.path.join(repo_root, "benchmark", "configs", "hartmann6-q1024.json")) as f:
        config = json.load(f)
    space = build_space({n: "uniform(0, 1)" for n in NAMES6})
    algo = create_algo(space, config["algorithm"], seed=2)
    x0 = np.random.default_rng(2).random((config["seed_observations"], 6))
    _observe(algo, x0, hartmann6(x0))
    nonfinite = DEVICE_HEALTH_FIELDS.index("gp_fit_nonfinite")
    for _ in range(4):
        batch = algo.suggest_batch(config["q"])
        x = np.column_stack([batch.params.column(n) for n in NAMES6])
        _observe(algo, x, hartmann6(x))
        state = algo._gp_state
        assert np.isfinite(float(state.mll))
        assert float(np.asarray(state.health)[nonfinite]) == 0.0


@pytest.fixture(params=[True, False], ids=["telemetry_on", "telemetry_off"])
def telemetry(request):
    """The process-wide registry, on or off and emptied, restored after."""
    before = tel.TELEMETRY.enabled
    (tel.TELEMETRY.enable if request.param else tel.TELEMETRY.disable)()
    tel.TELEMETRY.reset()
    try:
        yield tel.TELEMETRY
    finally:
        (tel.TELEMETRY.enable if before else tel.TELEMETRY.disable)()
        tel.TELEMETRY.reset()


def _small_algo():
    names = NAMES6[:3]
    algo = create_algo(build_space({n: "uniform(0, 1)" for n in names}),
                       {"tpu_bo": dict(n_init=8, n_candidates=256, fit_steps=5,
                                       prewarm=False)}, seed=3)
    x = np.random.default_rng(4).random((16, 3))
    algo.observe([dict(zip(names, row)) for row in x.tolist()],
                 [{"objective": float(v)} for v in np.sum((x - 0.3) ** 2, axis=1)])
    return algo


def _plant_nan_warm_start(algo):
    """The next refit starts from a NaN noise level: every step's loss is
    NaN.  (A NaN objective cannot plant it: observe stores the worst value
    in its place.)"""
    state = algo._gp_state
    algo._gp_state = state._replace(
        hypers=state.hypers._replace(log_noise=jnp.asarray(np.nan, jnp.float32)))


@pytest.mark.parametrize("plant_nan", [False, True])
def test_fit_counters_count_steps_and_nonfinite_steps(telemetry, plant_nan):
    """Two rounds of 5 fit steps; the second starts from a NaN noise level
    when planted.  The counters move only with telemetry on; the health
    field carries the count either way."""
    algo = _small_algo()
    algo.suggest_batch(8)
    if plant_nan:
        _plant_nan_warm_start(algo)
    algo.suggest_batch(8)
    nonfinite = 5 if plant_nan else 0
    on = telemetry.enabled
    assert telemetry.counter_value("gp.fit.steps") == (10 if on else 0)
    assert telemetry.counter_value("gp.fit.nonfinite_steps") == (nonfinite if on else 0)
    assert algo._fit_steps_uncounted is None
    assert algo.health_record()["gp_fit_nonfinite"] == nonfinite
