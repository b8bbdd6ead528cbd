"""Plain reference of the GP fit: copula transform of the targets, their
normalisation, the masked Matern-5/2 kernel, the marginal likelihood with
its analytic gradient, and the Adam hyper-parameter fit.

It follows the algorithm the program states (``tpu_bo``: a GP on the
fit set, hyper-parameters by ``fit_steps`` Adam steps of learning rate
0.08 from the warm start, clipped to fixed ranges, jitter
``1e-5 * (1 + amplitude)``), written from that description in NumPy.
Hyper-parameters are one vector: ``d`` log lengthscales, then the log
amplitude, then the log noise.
"""

import numpy as np
from scipy import linalg, special

from benchmark.reference.precision import F64

JITTER = 1e-5
LEARNING_RATE = 0.08
SQRT5 = np.sqrt(5.0)
LOG_LS = (np.log(1e-3), np.log(1e2))
LOG_AMP = (np.log(0.05), np.log(5.0))
LOG_NOISE = (np.log(1e-4), np.log(1.0))


def init_hypers(d):
    """The cold start: lengthscales 0.3, amplitude 1, noise 1e-3."""
    return np.concatenate([np.full(d, np.log(0.3)), [0.0, np.log(1e-3)]])


def copula(y, mask, p=F64):
    """Rank -> normal quantile over the real rows; first occurrence first
    among equal values; padded rows give 0."""
    real = mask > 0
    n = max(float(mask.sum()), 1.0)
    keyed = np.where(real, np.asarray(y, np.float64), np.inf)
    rank = np.argsort(np.argsort(keyed, kind="stable"), kind="stable")
    q = np.clip((rank + 0.5) / n, 1e-7, 1.0 - 1e-7)
    return p(np.where(real, special.ndtri(q), 0.0))


def normalize(y, mask, p=F64):
    """(y_norm, mean, std) over the real rows; padded rows give 0."""
    y = p(y)
    n = max(float(mask.sum()), 1.0)
    mean = np.sum(y * mask) / n
    std = np.sqrt(max(np.sum((y - mean) ** 2 * mask) / n, 1e-12))
    return p((y - mean) * mask / std), p(mean), p(std)


def _split(theta, d):
    return np.exp(theta[:d]), np.exp(theta[d]), np.exp(theta[d + 1])


def masked_kernel(theta, x, mask, p=F64):
    """Real block K + (noise + jitter) I; padded rows and columns identity.
    Distances by direct differences, so no cancellation."""
    d = x.shape[1]
    ls, amp, noise = _split(p(theta), d)
    a = p(np.asarray(x, np.float64) / ls)
    diff = a[:, None, :] - a[None, :, :]
    r2 = np.sum(diff * diff, axis=-1)
    r = np.sqrt(r2)
    k = amp * (1.0 + SQRT5 * r + (5.0 / 3.0) * r2) * np.exp(-SQRT5 * r)
    outer = mask[:, None] * mask[None, :]
    eye = np.eye(x.shape[0], dtype=k.dtype)
    big = k * outer + eye * (noise + JITTER * (1.0 + amp)) * mask + eye * (1.0 - mask)
    return big, k, diff, r, outer


def neg_mll(theta, x, y_norm, mask, p=F64, grad=True):
    """Negative marginal log-likelihood per real row and its gradient."""
    d = x.shape[1]
    big, k, diff, r, outer = masked_kernel(theta, x, mask, p)
    chol = linalg.cholesky(big, lower=True)
    alpha = linalg.cho_solve((chol, True), y_norm)
    n = max(float(mask.sum()), 1.0)
    loss = 0.5 * (y_norm @ alpha + 2.0 * np.sum(np.log(np.diag(chol)) * mask)) / n
    if not grad:
        return loss, None
    _, amp, noise = _split(p(theta), d)
    w = linalg.cho_solve((chol, True), np.eye(x.shape[0], dtype=big.dtype))
    w -= np.outer(alpha, alpha)
    # dK/dlog ls_k = amp (5/3) (1 + sqrt5 r) exp(-sqrt5 r) D_k^2 on the real block.
    common = amp * (5.0 / 3.0) * (1.0 + SQRT5 * r) * np.exp(-SQRT5 * r) * outer
    g_ls = 0.5 / n * np.einsum("ij,ijk->k", w * common, diff * diff)
    g_amp = 0.5 / n * (np.sum(w * k * outer) + JITTER * amp * np.sum(np.diag(w) * mask))
    g_noise = 0.5 / n * noise * np.sum(np.diag(w) * mask)
    return loss, np.concatenate([g_ls, [g_amp, g_noise]])


def clip_hypers(theta, d):
    out = np.array(theta, copy=True)
    out[:d] = np.clip(out[:d], *LOG_LS)
    out[d] = np.clip(out[d], *LOG_AMP)
    out[d + 1] = np.clip(out[d + 1], *LOG_NOISE)
    return out


def fit(theta0, x, y_norm, mask, steps, p=F64):
    """``steps`` Adam steps (optax defaults, learning rate 0.08) on the
    negative marginal likelihood, clipped after each step."""
    d = x.shape[1]
    theta = p(theta0)
    m = np.zeros_like(theta)
    v = np.zeros_like(theta)
    for t in range(1, steps + 1):
        _, g = neg_mll(theta, x, y_norm, mask, p)
        g = p(np.nan_to_num(g))
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        step = (m / (1.0 - 0.9**t)) / (np.sqrt(v / (1.0 - 0.999**t)) + 1e-8)
        theta = p(clip_hypers(theta - LEARNING_RATE * step, d))
    return theta


def solve(theta, x, y_norm, mask, p=F64):
    """alpha = K^-1 y_norm at the hyper-parameters ``theta``."""
    big = masked_kernel(theta, x, mask, p)[0]
    return linalg.cho_solve((linalg.cholesky(big, lower=True), True), y_norm)


def backward_error(theta, x, y_norm, mask, alpha):
    """Normwise backward error of ``alpha`` as a solution of K alpha = y
    under the float64 kernel at ``theta``: |K a - y| / (|K| |a| + |y|)."""
    big = masked_kernel(theta, x, mask)[0]
    a = np.asarray(alpha, np.float64)
    resid = np.linalg.norm(big @ a - y_norm)
    return float(resid / (np.linalg.norm(big) * np.linalg.norm(a) + np.linalg.norm(y_norm)))


def mll(theta, x, y_norm, mask, p=F64):
    """Marginal log-likelihood per real row."""
    return -neg_mll(theta, x, y_norm, mask, p, grad=False)[0]
