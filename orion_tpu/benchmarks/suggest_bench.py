"""Device-time / MFU measurement of the fused GP-BO suggest step.

Separates the three components of a suggest round at the headline shapes:

- ``device_ms``   — pure device execution time of the compiled step, via the
  repo's two-chain-length subtraction (gram_bench.py): K iterations of the
  step chained *inside one jit* (data-dependent, so XLA cannot elide them),
  per-step time = (t_hi - t_lo) / (K_hi - K_lo).  The constant per-dispatch
  cost cancels exactly.
- ``wall_ms``     — one dispatch of the same compiled step, forced to
  completion by the result transfer, i.e. device_ms + dispatch + the
  (q, d) result transfer a production round also pays.
- ``public_ms``   — one round through the public ``algo.suggest`` API
  (adds host-side copula transform, codec decode, param-dict construction).

FLOPs come from XLA's own cost model on the compiled executable via the
compiler plane's shared analysis path (``orion_tpu.compiler_plane`` — the
same ``lower().compile()`` + cost/memory extraction the CompileRegistry
runs for the runtime), not hand arithmetic; achieved
FLOP/s = flops / device_s, and MFU is quoted against the chip's bf16 peak
from ``PEAK_BF16_FLOPS`` (the GP path runs f32, whose MXU peak is lower,
so the bf16-denominated MFU is a strict lower bound on MXU utilization).
A device kind the table does not know gets no MFU (None).

Run: ``python -m orion_tpu.benchmarks.runner --op suggest``
One JSON line per headline shape.
"""

import json
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from orion_tpu.algo.gp.gp import init_hypers
from orion_tpu.algo.tpu_bo import _suggest_step
from orion_tpu.compiler_plane import (
    device_hbm_capacity,
    lowered_analysis_fn,
    predict_hbm_bound_q,
)

#: Published bf16 peak FLOP/s of one chip, keyed by ``device_kind`` as JAX
#: reports it.  Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s).
PEAK_BF16_FLOPS = {"TPU v5 lite": 1.97e14}


def peak_bf16_flops(device=None):
    """The bf16 peak of ``device`` (default: the first), or None for a
    device kind the table does not know — never another chip's peak."""
    device = device if device is not None else jax.devices()[0]
    return PEAK_BF16_FLOPS.get(device.device_kind)

# The three headline shapes (VERDICT r4 next-1).  n_obs is the steady-state
# fit-buffer size: hartmann6's history pads to 256 through the whole timed
# bench.py loop; the trust-region presets cap the fit set at tr_local_m.
# fit_steps is the steady-state (warm-refit) count each preset actually runs.
SHAPES = {
    "hartmann6-q1024": dict(
        d=6, n_obs=192, q=1024, n_candidates=16384, fit_steps=40,
        fixed_tail_cols=0, rounds_per_run=None,
    ),
    "rosenbrock20-q256": dict(
        d=20, n_obs=256, q=256, n_candidates=16384, fit_steps=30,
        fixed_tail_cols=0, rounds_per_run=4,
    ),
    "ackley50-q512": dict(
        # asha_bo: 50 free dims + 1 pinned fidelity-context column.
        d=51, n_obs=512, q=512, n_candidates=8192, fit_steps=10,
        fixed_tail_cols=1, rounds_per_run=7,
    ),
}

_K_LO = 1
_K_HI = 17  # 16-step delta: >=80 ms of device signal at ~5 ms/step


def _step_kwargs(cfg, kernel="matern52"):
    return dict(
        q=cfg["q"],
        n_candidates=cfg["n_candidates"],
        kernel=kernel,
        acq="thompson",
        fit_steps=cfg["fit_steps"],
        local_frac=0.5,
        local_sigma=0.1,
        beta=2.0,
        trust_region=True,
        tr_perturb_dims=20,
        fixed_tail_cols=cfg["fixed_tail_cols"],
        mesh=None,
    )


def _make_args(cfg, rng):
    n, d = cfg["n_obs"], cfg["d"]
    n_pad = 1 << (n - 1).bit_length()
    x = np.zeros((n_pad, d), dtype=np.float32)
    y = np.zeros((n_pad,), dtype=np.float32)
    mask = np.zeros((n_pad,), dtype=np.float32)
    x[:n] = rng.uniform(size=(n, d))
    y[:n] = rng.normal(size=n)
    mask[:n] = 1.0
    best_x = x[int(np.argmin(y[:n]))]
    key = jax.random.PRNGKey(0)
    return (
        key,
        jnp.asarray(x),
        jnp.asarray(y),
        jnp.asarray(mask),
        jnp.asarray(best_x),
        init_hypers(d),
        jnp.float32(0.8),
    )


def _chained(k_iters, **step_kw):
    """k data-dependent suggest steps under ONE jit (see module docstring)."""

    @jax.jit
    def many(key, x, y, mask, best_x, warm, tr_len):
        def body(i, carry):
            x_cur, acc = carry
            rows, _ = _suggest_step(
                jax.random.fold_in(key, i), x_cur, y, mask, best_x, warm,
                tr_len, **step_kw,
            )
            acc = acc + jnp.sum(rows)
            # ~1e-30 perturbation: forces iteration i+1 to depend on i's
            # output without changing what is computed.
            return x + acc * 1e-30, acc

        _, acc = jax.lax.fori_loop(0, k_iters, body, (x, jnp.float32(0.0)))
        return acc

    return many


def _time_fn(fn, args, reps=8, warmup=2):
    """Best-of-reps wall seconds of one call.  Every call gets a DISTINCT
    PRNG key (a byte-identical dispatch must not be answered from any
    cache), and completion is forced by the HOST TRANSFER (np.asarray)
    that every production round also pays (the producer reads the rows
    back)."""
    rest = args[1:]
    counter = [0]

    def call():
        counter[0] += 1
        return np.asarray(fn(jax.random.PRNGKey(1000 + counter[0]), *rest))

    for _ in range(warmup):
        call()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        call()
        best = min(best, time.perf_counter() - t0)
    return best


def _public_round_ms(name, cfg, reps=5):
    """One observe+suggest round through the public algorithm API at the
    same steady-state shape (hartmann6's is bench.py's timed loop)."""
    from orion_tpu.algo.base import create_algo
    from orion_tpu.space.dsl import build_space

    if cfg["fixed_tail_cols"]:
        return None  # asha_bo's public round is rung-scheduled, not shape-stable
    d = cfg["d"]
    rng = np.random.default_rng(0)
    space = build_space({f"x{i:02d}": "uniform(0, 1)" for i in range(d)})
    algo = create_algo(
        space,
        {"tpu_bo": {"n_init": 16, "n_candidates": cfg["n_candidates"],
                     "fit_steps": cfg["fit_steps"]}},
        seed=0,
    )
    n0 = cfg["n_obs"] - 32
    X = rng.uniform(size=(n0, d)).astype(np.float32)
    names = sorted(p for p in space.keys())

    def observe(Xb):
        params = [dict(zip(names, map(float, row))) for row in Xb]
        algo.observe(params, [{"objective": float(v)} for v in rng.normal(size=len(Xb))])

    observe(X)
    algo.suggest(cfg["q"])  # compile
    best = float("inf")
    for _ in range(reps):
        observe(rng.uniform(size=(16, d)).astype(np.float32))  # mark GP stale
        t0 = time.perf_counter()
        algo.suggest(cfg["q"])
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def device_seconds(shape, reps=8, k_hi=_K_HI, kernel="matern52"):
    """Pure device seconds per fused suggest step at a SHAPES entry, by
    two-chain subtraction — the ONE instrument, shared with bench.py's
    per-round decomposition."""
    cfg = SHAPES[shape]
    step_kw = _step_kwargs(cfg, kernel=kernel)
    args = _make_args(cfg, np.random.default_rng(0))
    t_lo = _time_fn(_chained(_K_LO, **step_kw), args, reps=reps)
    t_hi = _time_fn(_chained(k_hi, **step_kw), args, reps=reps)
    return max(t_hi - t_lo, 0.0) / (k_hi - _K_LO)


def run_suggest_bench(reps=8, shapes=None, kernel="matern52"):
    rng = np.random.default_rng(0)
    rows = []
    for name, cfg in SHAPES.items():
        if shapes and name not in shapes:
            continue
        step_kw = _step_kwargs(cfg, kernel=kernel)
        args = _make_args(cfg, rng)
        # The compiler plane's shared analysis closure (the exact code path
        # CompileRegistry.analyze_all runs for the runtime) — a bench IS a
        # declared cold path, so the AOT second compile is fine here.
        analysis = lowered_analysis_fn(_suggest_step, args, step_kw)() or {}
        flops = analysis.get("flops")
        flops = float("nan") if flops is None else flops
        hbm_bytes = analysis.get("hbm_bytes")

        one = jax.jit(partial(_suggest_step, **step_kw))
        wall_s = _time_fn(lambda *a: one(*a)[0], args, reps=reps)
        device_s = device_seconds(name, reps=reps, kernel=kernel)
        public_ms = _public_round_ms(name, cfg)

        achieved = flops / device_s if device_s > 0 else float("nan")
        peak = peak_bf16_flops()
        row = {
            "shape": name,
            "n_obs": cfg["n_obs"],
            "q": cfg["q"],
            "n_candidates": cfg["n_candidates"],
            "fit_steps": cfg["fit_steps"],
            "device_ms": round(device_s * 1e3, 3),
            "wall_ms": round(wall_s * 1e3, 2),
            "dispatch_ms": round((wall_s - device_s) * 1e3, 2),
            "public_api_ms": round(public_ms, 2) if public_ms else None,
            "gflops_per_call": round(flops / 1e9, 3),
            "achieved_tflops": round(achieved / 1e12, 3),
            "mfu_vs_bf16_peak": round(achieved / peak, 5) if peak else None,
            # Per-plan HBM footprint + predicted HBM-bound q (ROADMAP item
            # 1's open tail) — from the same analysis pass as the FLOPs.
            "plan_hbm_bytes": hbm_bytes,
            "hbm_bound_q": predict_hbm_bound_q(
                {"q": cfg["q"]}, hbm_bytes, device_hbm_capacity()
            ),
            "backend": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


if __name__ == "__main__":
    run_suggest_bench()
