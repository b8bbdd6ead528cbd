"""GP kernels with ARD lengthscales, written for the MXU.

Pairwise distances are computed via the ||a-b||^2 = ||a||^2 + ||b||^2 - 2ab
expansion so the dominant cost is one (n, d) x (d, m) matmul that XLA tiles
onto the systolic array, instead of an O(n*m*d) broadcast-subtract that would
be HBM-bandwidth-bound.  Both inputs are first shifted by one common centre
(the mean of ``xb``'s rows): the expansion's float32 rounding error scales
with ||a||^2, not with the distance, so on a fit set packed tightly far from
the origin (a trust region closing on its incumbent) the uncentred form
rounds near-neighbour distances to noise and K stops being positive
definite.  Centred, ||a||^2 is the set's own spread.
"""

import jax
import jax.numpy as jnp


def sq_dists(xa, xb, inv_lengthscales):
    """Squared scaled euclidean distances, matmul-dominant.

    The rows are centred on ``xb``'s mean first (module docstring): the
    shift leaves every distance as it is and keeps the cancellation in
    aa+bb-2ab at the scale of the points' spread.

    The cross term MUST run at full f32 precision: TPU's default bf16 matmul
    loses ~0.4% relative, which after the aa+bb-2ab cancellation shows up as
    k(x,x) != amplitude and an indefinite kernel matrix.
    """
    centre = jnp.mean(xb, axis=0)
    a = (xa - centre) * inv_lengthscales
    b = (xb - centre) * inv_lengthscales
    aa = jnp.sum(a * a, axis=-1)[:, None]
    bb = jnp.sum(b * b, axis=-1)[None, :]
    cross = jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(aa + bb - 2.0 * cross, 0.0)


def rbf(xa, xb, inv_lengthscales, amplitude):
    return amplitude * jnp.exp(-0.5 * sq_dists(xa, xb, inv_lengthscales))


def matern52(xa, xb, inv_lengthscales, amplitude):
    r2 = sq_dists(xa, xb, inv_lengthscales)
    # Double-where keeps d(sqrt)/d(r2) finite at r2=0 (the diagonal): without
    # it the 1/(2 sqrt(r2)) gradient is inf there and one MLL step NaNs every
    # hyperparameter.
    positive = r2 > 1e-12
    r = jnp.where(positive, jnp.sqrt(jnp.where(positive, r2, 1.0)), 0.0)
    sqrt5_r = jnp.sqrt(5.0) * r
    return amplitude * (1.0 + sqrt5_r + (5.0 / 3.0) * r2) * jnp.exp(-sqrt5_r)


_KERNELS = {"rbf": rbf, "matern52": matern52}

# Measured head-to-head on a chip (2026-07, through the old remote device
# path) with the two-chain-length method (`python -m
# orion_tpu.benchmarks.runner --op gram`: per-op time =
# (t_1032ops - t_8ops)/1024 per dispatch, cancelling the per-dispatch cost;
# gram consumed by a matvec + elementwise-square reduction like the
# production posterior; table in docs/performance.md): the fused pallas
# gram won 1.1-1.4x over XLA on every production shape, including the
# smallest (m=4096, n=256, d=8 -> work 8.4e6).  Not re-measured on the
# local chip yet.  The threshold covers every shape measured to win;
# below it the dispatch is untested and XLA is kept.
_PALLAS_MIN_WORK = 8 * 10**6


def kernel_matrix(kind, xa, xb, inv_lengthscales, amplitude):
    return _KERNELS[kind](xa, xb, inv_lengthscales, amplitude)


def cross_kernel_matrix(kind, xa, xb, inv_lengthscales, amplitude):
    """Forward-only gram for candidate scoring: dispatches to the pallas
    fused kernel (`orion_tpu.ops.fused_gram`) on measured-to-win shapes
    when the runtime's compile/run probe passes (ORION_TPU_PALLAS=0 opts
    out — see _PALLAS_MIN_WORK note).  Never use under `jax.grad` — the
    pallas path defines no autodiff rule (the MLL fit's (n, n) kernel
    stays on `kernel_matrix`).  Under a multi-device mesh the op splits
    its own rows over the devices (`fused_gram`)."""
    m, d = xa.shape
    n = xb.shape[0]
    if m * n * max(d, 1) >= _PALLAS_MIN_WORK:
        from orion_tpu.ops import pallas_enabled

        if pallas_enabled():
            from orion_tpu.ops import fused_gram

            return fused_gram(xa, xb, inv_lengthscales, amplitude, kind=kind)
    return _KERNELS[kind](xa, xb, inv_lengthscales, amplitude)
