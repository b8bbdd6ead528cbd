"""The plain reference against the program at small sizes on the CPU: the
same arithmetic where both are exact enough, the same random numbers, and
the selection check's order logic."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import compare, draws, gp, objectives, thompson


def _fit_set(n=40, pad=64, d=3, seed=0):
    rng = np.random.default_rng(seed)
    x = np.zeros((pad, d), np.float32)
    x[:n] = rng.random((n, d))
    y = np.zeros(pad, np.float32)
    y[:n] = objectives.hartmann6(np.pad(x[:n], ((0, 0), (0, 6 - d)), constant_values=0.5))
    mask = np.zeros(pad, np.float32)
    mask[:n] = 1.0
    return x, y, mask


def test_objectives_match_the_programs():
    from orion_tpu.benchmarks import functions

    u = np.random.default_rng(1).random((64, 20))
    np.testing.assert_allclose(objectives.hartmann6(u[:, :6]), functions.hartmann6(u[:, :6]),
                               rtol=1e-5)
    np.testing.assert_allclose(objectives.rosenbrock(u), functions.rosenbrock(u), rtol=1e-5)
    np.testing.assert_allclose(objectives.ackley(u), functions.ackley(u), rtol=1e-5)


def test_gradient_matches_finite_differences():
    x, y, mask = _fit_set()
    yn, _, _ = gp.normalize(gp.copula(y, mask), mask)
    theta = gp.init_hypers(3) + np.array([0.2, -0.1, 0.3, 0.1, 1.0])
    _, g = gp.neg_mll(theta, x.astype(np.float64), yn, mask.astype(np.float64))
    for i in range(theta.size):
        e = np.zeros_like(theta)
        e[i] = 1e-6
        up = gp.neg_mll(theta + e, x, yn, mask, grad=False)[0]
        down = gp.neg_mll(theta - e, x, yn, mask, grad=False)[0]
        assert g[i] == pytest.approx((up - down) / 2e-6, rel=1e-4, abs=1e-7)


def test_fit_matches_the_program():
    from orion_tpu.algo.gp.gp import fit_gp

    x, y, mask = _fit_set()
    state = fit_gp(jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask), kind="matern52",
                   n_steps=10, y_transform="copula")
    yt = gp.copula(y, mask)
    np.testing.assert_allclose(np.asarray(state.y), yt, atol=1e-5)
    yn, _, _ = gp.normalize(yt, mask)
    theta = gp.fit(gp.init_hypers(3), x, yn, mask.astype(np.float64), 10)
    prog = np.concatenate([np.asarray(state.hypers.log_lengthscales),
                           [float(state.hypers.log_amplitude), float(state.hypers.log_noise)]])
    np.testing.assert_allclose(prog, theta, atol=2e-3)
    assert float(state.mll) == pytest.approx(gp.mll(prog, x, yn, mask.astype(np.float64)),
                                             abs=1e-3)
    assert gp.backward_error(prog, x, yn, mask.astype(np.float64), state.alpha) < 1e-5


def test_global_pool_is_the_programs():
    from orion_tpu.algo import tpu_bo

    key = jax.random.PRNGKey(7)
    n, d, frac = 512, 5, 0.3
    ls = jnp.full((d,), 0.4)
    chol = jnp.eye(d) * 0.1
    cand = tpu_bo._make_tr_candidates(key, n, d, jnp.full((d,), 0.5), jnp.asarray(0.8), ls,
                                      frac, chol, jnp.full((d,), 0.4))
    n_global = n - int(n * frac)
    np.testing.assert_array_equal(np.asarray(cand[:n_global]),
                                  np.asarray(draws.global_pool(key, d, n_global)))


def test_thompson_picks_match_the_program():
    from orion_tpu.algo.gp.acquisition import rff_thompson
    from orion_tpu.algo.gp.gp import fit_gp

    x, y, mask = _fit_set(d=4)
    state = fit_gp(jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask), kind="matern52",
                   n_steps=5, y_transform="copula")
    cand = np.random.default_rng(3).random((2048, 4)).astype(np.float32)
    key = jax.random.PRNGKey(11)
    q = 64
    picks = np.asarray(rff_thompson(key, state, jnp.asarray(cand), q))
    raw = jax.device_get(draws.thompson_noise(key, 4, q))
    theta = np.concatenate([np.asarray(state.hypers.log_lengthscales),
                            [float(state.hypers.log_amplitude), float(state.hypers.log_noise)]])
    yn = (np.asarray(state.y) - float(state.y_mean)) / float(state.y_std) * mask
    draw = thompson.posterior_draws(raw, theta, x.astype(np.float64), yn, mask)
    values = thompson.scores(cand.astype(np.float64), draw)
    gaps = values[picks, np.arange(q)] - values.min(0)
    assert gaps.max() < 1e-3
    assert np.mean(picks == values.argmin(0)) > 0.9


def test_order_gap():
    # Rows: exploit, then the picks of draws 0..2 in order; draw 3 repeats row 1.
    values = np.array([[5.0, 5.0, 5.0, 5.0],
                       [0.0, 3.0, 3.0, 0.0],
                       [3.0, 0.0, 3.0, 3.0],
                       [3.0, 3.0, 0.0, 3.0]])
    best = np.zeros(4)
    assert compare.order_gap(values, best, 1) == 0.0
    swapped = values[[0, 2, 1, 3]]
    assert compare.order_gap(swapped, best, 1) == 3.0
    near = values.copy()
    near[2, 0] = 1e-6  # a near-tie costs only its own gap
    assert compare.order_gap(near, best, 1) == 0.0
