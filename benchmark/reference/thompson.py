"""Plain reference of the scoring and selection layers: Thompson draws by
random Fourier features of the Matern-5/2 kernel, scored over candidates,
and the gap by which a chosen candidate lies above a draw's best.

The random numbers come from ``benchmark.reference.draws``; everything
computed from them here is NumPy at the given precision.
"""

import numpy as np
from scipy import linalg

from benchmark.reference.precision import F64

N_FEATURES = 512
RIDGE_FLOOR = 1e-3


def posterior_draws(raw, theta, x, y_norm, mask, p=F64):
    """Feature map and ``q`` weight draws of the Bayesian linear regression
    on the fit set: ``(w, b, scale, thetas)``, thetas of shape (F, q)."""
    d = x.shape[1]
    ls = np.exp(np.asarray(theta[:d], np.float64))
    amp = np.exp(float(theta[d]))
    noise = np.exp(float(theta[d + 1]))
    # Matern-5/2 spectral density: a Student-t with 5 degrees of freedom.
    z = np.asarray(raw["z"], np.float64) * np.sqrt(5.0 / np.asarray(raw["g"], np.float64))
    w = p(z / ls[None, :])
    b = p(raw["b"])
    scale = np.sqrt(2.0 * amp / N_FEATURES)
    phi = features(x, w, b, scale, p) * np.asarray(mask)[:, None]
    ridge = noise + RIDGE_FLOOR
    a = p.mm(phi.T, phi) + ridge * np.eye(N_FEATURES, dtype=p.dtype)
    chol = linalg.cholesky(a, lower=True)
    theta_mean = linalg.cho_solve((chol, True), p.mm(phi.T, y_norm))
    delta = linalg.solve_triangular(chol.T, p(raw["eps"]), lower=False)
    thetas = p(theta_mean[:, None] + np.sqrt(ridge) * delta)
    return w, b, scale, thetas


def features(x, w, b, scale, p=F64):
    return p(scale * np.cos(p.mm(x, w.T) + b[None, :]))


def scores(x, draw, p=F64, chunk=4096):
    """(len(x), q) draw values at the points ``x`` (lower is better)."""
    w, b, scale, thetas = draw
    out = [p.mm(features(x[i:i + chunk], w, b, scale, p), thetas)
           for i in range(0, x.shape[0], chunk)]
    return np.concatenate(out, axis=0)


def draw_gaps(best_values, chosen_values):
    """Per draw: how far the chosen candidate's value lies above the best."""
    return np.asarray(chosen_values) - np.asarray(best_values)
