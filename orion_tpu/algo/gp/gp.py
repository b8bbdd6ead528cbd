"""Device-resident GP: masked static-shape buffers, Cholesky posterior,
marginal-likelihood fitting by a fixed (jit-friendly) number of adam steps.

Design notes (TPU-first):
- Trial history grows dynamically but jit needs static shapes: observations
  live in power-of-2 padded buffers with a validity mask.  Padded rows are
  made inert in the Cholesky by pinning their diagonal to 1 and off-diagonals
  to 0, and their targets to 0 — they then contribute nothing to the solve,
  the quad form, or the logdet (log 1 = 0).
- Everything is float32: the MXU path.  A jitter floor keeps Cholesky stable
  at that precision for histories in the thousands.
- Fitting is `lax.scan` over a fixed number of optimizer steps, so one
  compiled computation per buffer size, no Python-loop retrace.
"""

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from orion_tpu.algo.gp.kernels import cross_kernel_matrix, kernel_matrix
from orion_tpu.algo.sampling import masked_copula_transform

JITTER = 1e-5


class GPHypers(NamedTuple):
    log_lengthscales: jnp.ndarray  # (d,)
    log_amplitude: jnp.ndarray  # ()
    log_noise: jnp.ndarray  # ()


class GPState(NamedTuple):
    x: jnp.ndarray  # (n_pad, d) in the unit cube
    y: jnp.ndarray  # (n_pad,) raw objectives
    mask: jnp.ndarray  # (n_pad,) 1.0 for real rows
    hypers: GPHypers
    chol: jnp.ndarray  # (n_pad, n_pad) lower Cholesky of masked K + noise
    alpha: jnp.ndarray  # (n_pad,) chol^-T chol^-1 y_norm
    y_mean: jnp.ndarray  # ()
    y_std: jnp.ndarray  # ()
    # Optimization-health extras (orion_tpu.health): () marginal
    # log-likelihood per observation of the final fit, the count of its
    # Adam steps whose loss or gradient was non-finite, and the packed
    # per-round DEVICE_HEALTH_FIELDS vector the fused suggest step attaches
    # via _replace.  Optional (None) so ad-hoc constructions stay valid.
    mll: Optional[jnp.ndarray] = None  # ()
    health: Optional[jnp.ndarray] = None  # (len(DEVICE_HEALTH_FIELDS),)
    fit_nonfinite: Optional[jnp.ndarray] = None  # () int32


def init_hypers(n_dims):
    return GPHypers(
        log_lengthscales=jnp.zeros(n_dims, dtype=jnp.float32) + jnp.log(0.3),
        log_amplitude=jnp.asarray(0.0, dtype=jnp.float32),
        log_noise=jnp.asarray(jnp.log(1e-3), dtype=jnp.float32),
    )


def _normalize_y(y, mask):
    n = jnp.maximum(jnp.sum(mask), 1.0)
    mean = jnp.sum(y * mask) / n
    var = jnp.sum(((y - mean) ** 2) * mask) / n
    std = jnp.sqrt(jnp.maximum(var, 1e-12))
    return (y - mean) * mask / std, mean, std


def _masked_kernel(kind, x, mask, hypers):
    inv_ls = jnp.exp(-hypers.log_lengthscales)
    amp = jnp.exp(hypers.log_amplitude)
    noise = jnp.exp(hypers.log_noise)
    k = kernel_matrix(kind, x, x, inv_ls, amp)
    outer = mask[:, None] * mask[None, :]
    eye = jnp.eye(x.shape[0], dtype=x.dtype)
    # Real block keeps K + noise*I; padded rows/cols become identity.  The
    # jitter scales with the amplitude: long-lengthscale fits make K nearly
    # rank-1 at magnitude `amp`, and an absolute 1e-5 is then below f32
    # resolution — the Cholesky NaNs.
    return k * outer + eye * (noise + JITTER * (1.0 + amp)) * mask + eye * (1.0 - mask)


def _neg_mll(hypers, kind, x, y_norm, mask):
    k = _masked_kernel(kind, x, mask, hypers)
    chol = jnp.linalg.cholesky(k)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y_norm)
    quad = jnp.dot(y_norm, alpha)
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(chol)) * mask)
    n = jnp.maximum(jnp.sum(mask), 1.0)
    return 0.5 * (quad + logdet) / n


@partial(jax.jit, static_argnames=("kind", "n_steps", "y_transform"))
def fit_gp(x, y, mask, kind="matern52", n_steps=50, lr=0.08, init=None,
           y_transform="none"):
    """Fit hyperparameters by adam on the marginal likelihood; returns GPState
    with the posterior factorization cached (Cholesky + alpha).

    ``y_transform="copula"`` rank-Gaussianizes the masked targets ON DEVICE
    before normalization (see ``sampling.masked_copula_transform``); the
    returned ``GPState.y`` then holds the transformed targets, exactly as
    when callers pre-transformed on host."""
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    mask = mask.astype(jnp.float32)
    if y_transform == "copula":
        y = masked_copula_transform(y, mask)
    y_norm, y_mean, y_std = _normalize_y(y, mask)
    hypers = init if init is not None else init_hypers(x.shape[1])

    optimizer = optax.adam(lr)
    opt_state = optimizer.init(hypers)
    loss_grad = jax.value_and_grad(_neg_mll)

    def step(carry, _):
        hyp, opt, nonfinite = carry
        loss, grads = loss_grad(hyp, kind, x, y_norm, mask)
        finite = jnp.isfinite(loss)
        for g in jax.tree.leaves(grads):
            finite &= jnp.all(jnp.isfinite(g))
        nonfinite += (~finite).astype(jnp.int32)
        # A transiently ill-conditioned Cholesky must not poison the whole
        # fit; `nonfinite` counts the steps this zeroes (health field
        # gp_fit_nonfinite).
        grads = jax.tree.map(jnp.nan_to_num, grads)
        updates, opt = optimizer.update(grads, opt)
        hyp = optax.apply_updates(hyp, updates)
        # Keep hypers in sane ranges (lengthscale in cube units, noise floor).
        hyp = GPHypers(
            log_lengthscales=jnp.clip(hyp.log_lengthscales, jnp.log(1e-3), jnp.log(1e2)),
            # Targets are normalized to unit variance; amplitudes far above 1
            # are the flat-function degeneracy (huge amp + huge lengthscale).
            log_amplitude=jnp.clip(hyp.log_amplitude, jnp.log(0.05), jnp.log(5.0)),
            # Noise floor 1e-4: duplicate-x rows (collapsed batches, lies)
            # otherwise drive noise to 0 and the f32 Cholesky off a cliff.
            log_noise=jnp.clip(hyp.log_noise, jnp.log(1e-4), jnp.log(1.0)),
        )
        return (hyp, opt, nonfinite), loss

    (hypers, _, nonfinite), _losses = jax.lax.scan(
        step, (hypers, opt_state, jnp.zeros((), jnp.int32)), None, length=n_steps
    )

    k = _masked_kernel(kind, x, mask, hypers)
    chol = jnp.linalg.cholesky(k)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y_norm)
    # Fit health for free: the final factorization already yields the
    # marginal likelihood terms (quad form + logdet) — a couple of vector
    # reductions, no extra Cholesky (orion_tpu.health, `gp_mll`).
    n = jnp.maximum(jnp.sum(mask), 1.0)
    quad = jnp.dot(y_norm, alpha)
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(chol)) * mask)
    mll = -0.5 * (quad + logdet) / n
    return GPState(
        x=x, y=y, mask=mask, hypers=hypers, chol=chol, alpha=alpha,
        y_mean=y_mean, y_std=y_std, mll=mll, fit_nonfinite=nonfinite,
    )


def posterior(state, xq, kind="matern52"):
    """Predictive mean/std at query points ``xq`` (m, d) — vmap-free batched
    linear algebra: one (m, n) kernel matmul + one triangular solve."""
    inv_ls = jnp.exp(-state.hypers.log_lengthscales)
    amp = jnp.exp(state.hypers.log_amplitude)
    kqx = cross_kernel_matrix(kind, xq.astype(jnp.float32), state.x, inv_ls, amp)
    kqx = kqx * state.mask[None, :]
    mean_norm = kqx @ state.alpha
    v = jax.scipy.linalg.solve_triangular(state.chol, kqx.T, lower=True)
    var_norm = jnp.maximum(amp - jnp.sum(v * v, axis=0), 1e-10)
    mean = mean_norm * state.y_std + state.y_mean
    std = jnp.sqrt(var_norm) * state.y_std
    return mean, std


def posterior_norm(state, xq, kind="matern52"):
    """Predictive mean/std in normalized target units (for acquisitions)."""
    inv_ls = jnp.exp(-state.hypers.log_lengthscales)
    amp = jnp.exp(state.hypers.log_amplitude)
    kqx = cross_kernel_matrix(kind, xq.astype(jnp.float32), state.x, inv_ls, amp)
    kqx = kqx * state.mask[None, :]
    mean = kqx @ state.alpha
    v = jax.scipy.linalg.solve_triangular(state.chol, kqx.T, lower=True)
    var = jnp.maximum(amp - jnp.sum(v * v, axis=0), 1e-10)
    return mean, jnp.sqrt(var)
