"""GP engine numeric tests: posterior correctness vs direct numpy algebra,
padding invariance, acquisition sanity, TPUBO integration."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from orion_tpu.algo.gp.gp import GPHypers, fit_gp, init_hypers, posterior, posterior_norm
from orion_tpu.algo.gp.kernels import kernel_matrix, matern52, rbf
from orion_tpu.algo.gp.acquisition import (
    expected_improvement,
    rff_thompson,
    upper_confidence_bound,
)


def _toy_state(n=20, n_pad=32, d=3, seed=0, n_steps=30):
    rng = np.random.default_rng(seed)
    X = rng.uniform(size=(n, d)).astype(np.float32)
    y = np.sin(3 * X[:, 0]) + 0.5 * X[:, 1] ** 2
    x = np.zeros((n_pad, d), np.float32)
    yy = np.zeros(n_pad, np.float32)
    mask = np.zeros(n_pad, np.float32)
    x[:n], yy[:n], mask[:n] = X, y, 1.0
    state = fit_gp(jnp.asarray(x), jnp.asarray(yy), jnp.asarray(mask), n_steps=n_steps)
    return X, y, state


def test_kernels_psd_and_diag():
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.uniform(size=(50, 4)).astype(np.float32))
    for kern in (rbf, matern52):
        K = np.asarray(kern(X, X, jnp.ones(4) * 2.0, 1.5))
        assert np.allclose(np.diag(K), 1.5, atol=1e-4)  # k(x,x) = amplitude
        assert np.allclose(K, K.T, atol=1e-5)
        eigs = np.linalg.eigvalsh(K + 1e-4 * np.eye(50))
        assert eigs.min() > 0


def test_posterior_matches_direct_numpy():
    """Masked padded posterior == dense numpy GP on the real rows."""
    X, y, state = _toy_state()
    rng = np.random.default_rng(1)
    Xq = rng.uniform(size=(7, 3)).astype(np.float32)
    mean, std = posterior(state, jnp.asarray(Xq))

    # Direct computation with the same hypers on unpadded data.
    ls = np.exp(np.asarray(state.hypers.log_lengthscales))
    amp = float(jnp.exp(state.hypers.log_amplitude))
    noise = float(jnp.exp(state.hypers.log_noise))
    y_mean, y_std = float(state.y_mean), float(state.y_std)

    def k(a, b):
        return np.asarray(
            kernel_matrix("matern52", jnp.asarray(a), jnp.asarray(b), jnp.asarray(1 / ls), amp)
        )

    Kxx = k(X, X) + (noise + 1e-5) * np.eye(len(X))
    Kqx = k(Xq, X)
    y_norm = (y - y_mean) / y_std
    alpha = np.linalg.solve(Kxx, y_norm)
    mean_direct = Kqx @ alpha * y_std + y_mean
    cov_direct = amp - np.sum(Kqx * np.linalg.solve(Kxx, Kqx.T).T, axis=1)
    std_direct = np.sqrt(np.maximum(cov_direct, 1e-10)) * y_std

    np.testing.assert_allclose(np.asarray(mean), mean_direct, rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(std), std_direct, rtol=5e-2, atol=5e-2)


def test_padding_invariance():
    """Doubling the padded buffer must not change the posterior."""
    rng = np.random.default_rng(2)
    n, d = 10, 2
    X = rng.uniform(size=(n, d)).astype(np.float32)
    y = (X**2).sum(1).astype(np.float32)
    hypers = init_hypers(d)
    states = []
    for n_pad in (16, 64):
        x = np.zeros((n_pad, d), np.float32)
        yy = np.zeros(n_pad, np.float32)
        mask = np.zeros(n_pad, np.float32)
        x[:n], yy[:n], mask[:n] = X, y, 1.0
        states.append(
            fit_gp(jnp.asarray(x), jnp.asarray(yy), jnp.asarray(mask), n_steps=5, init=hypers)
        )
    Xq = jnp.asarray(rng.uniform(size=(5, d)).astype(np.float32))
    m1, s1 = posterior(states[0], Xq)
    m2, s2 = posterior(states[1], Xq)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-2, atol=1e-3)


def test_fit_interpolates_training_data():
    X, y, state = _toy_state(n_steps=60)
    mean, _ = posterior(state, jnp.asarray(X))
    resid = np.abs(np.asarray(mean) - y)
    assert resid.mean() < 0.1 * (y.std() + 1e-9)


def test_expected_improvement_formula():
    mean = jnp.asarray([0.0, 1.0, -1.0])
    std = jnp.asarray([1.0, 1.0, 1e-6])
    ei = np.asarray(expected_improvement(mean, std, best=0.0))
    assert ei[0] == pytest.approx(0.3989, abs=1e-3)  # std * pdf(0)
    assert ei[1] < ei[0]  # worse mean -> less improvement
    assert ei[2] == pytest.approx(1.0, abs=1e-3)  # certain improvement of 1
    ucb = np.asarray(upper_confidence_bound(mean, std, beta=2.0))
    assert ucb[0] == pytest.approx(2.0, abs=1e-5)


def test_rff_thompson_selects_low_posterior_mean():
    X, y, state = _toy_state(n=40, n_pad=64, n_steps=60)
    rng = np.random.default_rng(3)
    cands = jnp.asarray(rng.uniform(size=(2048, 3)).astype(np.float32))
    idx = np.asarray(rff_thompson(jax.random.PRNGKey(0), state, cands, 32))
    # Selected candidates should skew toward low predicted mean.  (Draws MAY
    # collapse to few points when the posterior is confident — batch
    # uniqueness is guaranteed one level up, by the fused step dedup.)
    mean_all, _ = posterior_norm(state, cands)
    sel_mean = np.asarray(mean_all)[idx].mean()
    assert sel_mean < float(np.asarray(mean_all).mean())


def test_tpu_bo_batches_are_unique_even_when_confident():
    from orion_tpu.algo.base import create_algo
    from orion_tpu.space.dsl import build_space

    space = build_space({f"x{i}": "uniform(0, 1)" for i in range(3)})
    algo = create_algo(
        space, {"tpu_bo": {"n_init": 4, "n_candidates": 512, "fit_steps": 30}}, seed=0
    )
    rng = np.random.default_rng(0)
    # Smooth easy function -> confident model -> TS draws collapse.
    for _ in range(3):
        params = algo.suggest(8)
        keys = [tuple(p.values()) for p in params]
        assert len(set(keys)) == 8  # all suggestions distinct
        ys = [sum(v * v for v in p.values()) for p in params]
        algo.observe(params, [{"objective": float(v)} for v in ys])


def test_tpu_bo_improves_on_branin():
    from orion_tpu.algo.base import create_algo
    from orion_tpu.benchmarks.functions import branin
    from orion_tpu.space.dsl import build_space

    space = build_space({"x0": "uniform(0, 1)", "x1": "uniform(0, 1)"})
    algo = create_algo(
        space,
        {"tpu_bo": {"n_init": 8, "n_candidates": 1024, "fit_steps": 25}},
        seed=0,
    )
    best = np.inf
    for _ in range(8):
        params = algo.suggest(8)
        cube = np.array([[p["x0"], p["x1"]] for p in params])
        ys = np.asarray(branin(jnp.asarray(cube)))
        best = min(best, float(ys.min()))
        algo.observe(params, [{"objective": float(v)} for v in ys])
    assert best < 1.5  # optimum 0.398; random search at 64 evals is ~2-4


def test_tpu_bo_state_roundtrip_and_deepcopy():
    import copy

    from orion_tpu.algo.base import create_algo
    from orion_tpu.space.dsl import build_space

    space = build_space({"x": "uniform(0, 1)"})
    algo = create_algo(space, {"tpu_bo": {"n_init": 2}}, seed=1)
    params = algo.suggest(3)
    algo.observe(params, [{"objective": float(i)} for i in range(3)])
    clone = copy.deepcopy(algo)  # what the producer does every round
    assert clone._x.shape == algo._x.shape

    fresh = create_algo(space, {"tpu_bo": {"n_init": 2}}, seed=99)
    fresh.set_state(algo.state_dict())
    assert fresh._x.shape == algo._x.shape
    np.testing.assert_allclose(fresh._y, algo._y)
    # Same rng state -> same next suggestion.
    a = algo.suggest(2)
    b = fresh.suggest(2)
    assert [p["x"] for p in a] == [p["x"] for p in b]


def test_rff_thompson_scores_candidates_at_full_precision():
    """Both candidate-scoring matmuls ask for HIGHEST precision: at the
    TPU's default one the sharded and one-device programs scored the same
    candidates up to 6e-3 apart and picked different rows (PERF.md)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.uniform(size=(32, 3)), jnp.float32)
    state = fit_gp(x, jnp.sin(x).sum(1), jnp.ones(32), kind="matern52", n_steps=2,
                   init=init_hypers(3))
    cands = jnp.asarray(rng.uniform(size=(1000, 3)), jnp.float32)
    text = jax.jit(rff_thompson, static_argnames=("q",)).lower(
        jax.random.PRNGKey(0), state, cands, q=7).as_text()
    scoring = [line for line in text.splitlines()
               if "dot_general" in line and "-> tensor<1000x" in line]
    assert len(scoring) == 2, scoring
    assert all("precision = [HIGHEST, HIGHEST]" in line for line in scoring), scoring
