#!/usr/bin/env python
"""Bring-up smoke of the suggest path on a TPU: ``python chip_smoke.py``.

One process drives the system's main path once through the entry points a
user calls, at the repo's headline deployment size (6-D Hartmann6, a
16384-candidate pool, 40 fit steps, history padded to 256, q=1024):

- ``library``: ``tpu_bo`` through the public ``observe``/``suggest`` API;
- ``cli``: ``orion-tpu hunt`` + ``info`` + ``status``, in-process, over a
  pickled store, with a NumPy Hartmann6 user script;
- ``gateway``: an in-process ``GatewayServer`` whose ``tpu_bo`` tenants
  suggest through ``GatewayClient``s, with coalesced dispatches.

``--chips 4`` runs only what exists across chips, each against its
one-device comparison: ``tpu_bo`` with ``use_mesh=True`` (candidate axis
sharded) and one tenant-sharded coalesced dispatch.

Each phase prints one JSON line: per-round wall ms, compile seconds and
persistent-cache hits/misses (``jax.monitoring``), and what it checked.
The last line is ``{"ok": true, "device": {...}}``.  There is no CPU
fallback: without a TPU (or with fewer chips than asked) the script exits
1 and prints no result.  It starts no child process that loads JAX (the
hunt's trial scripts are NumPy only).
"""

import argparse
import contextlib
import io
import json
import os
import shutil
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

D = 6
Q = 1024
N_CANDIDATES = 16384
FIT_STEPS = 40
N_HIST = 130  # pads the fit buffer to 256 and keeps it there
N_EVAL = 16  # rows of each suggested batch evaluated and observed
ROUNDS = 4  # round 0 compiles; rounds 1.. are steady state
SEED = 0
H6_MIN = -3.32237
#: The headline deployment (bench.py's ``_make_algo``).
ALGO = {"tpu_bo": {"n_init": 16, "n_candidates": N_CANDIDATES,
                   "fit_steps": FIT_STEPS, "local_frac": 0.3,
                   "prewarm": False}}

_H6_ALPHA = np.array([1.0, 1.2, 3.0, 3.2])
_H6_A = np.array([
    [10, 3, 17, 3.5, 1.7, 8],
    [0.05, 10, 17, 0.1, 8, 14],
    [3, 3.5, 1.7, 10, 17, 8],
    [17, 8, 0.05, 10, 0.1, 14],
])
_H6_P = 1e-4 * np.array([
    [1312, 1696, 5569, 124, 8283, 5886],
    [2329, 4135, 8307, 3736, 1004, 9991],
    [2348, 1451, 3522, 2883, 3047, 6650],
    [4047, 8828, 8732, 5743, 1091, 381],
])


def hartmann6(u):
    """NumPy Hartmann-6 on [0, 1]^6 (rows of ``u``); global min -3.32237."""
    u = np.atleast_2d(np.asarray(u, dtype=np.float64))
    inner = np.sum(_H6_A[None] * (u[:, None, :] - _H6_P[None]) ** 2, axis=-1)
    return -np.sum(_H6_ALPHA[None] * np.exp(-inner), axis=-1)


#: The hunt's user script: NumPy only, so a trial never loads JAX.
USER_SCRIPT = f"""\
import argparse, json, os, sys
sys.path.insert(0, {HERE!r})
from chip_smoke import hartmann6
parser = argparse.ArgumentParser()
for i in range({D}):
    parser.add_argument(f"--x{{i}}", type=float, required=True)
args = parser.parse_args()
value = float(hartmann6([[getattr(args, f"x{{i}}") for i in range({D})]])[0])
with open(os.environ["ORION_RESULTS_PATH"], "w") as handle:
    json.dump([{{"name": "objective", "type": "objective", "value": value}}], handle)
"""


class SmokeFailure(Exception):
    pass


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


class CompileLog:
    """Backend compile seconds and persistent-cache hits/misses, from
    ``jax.monitoring`` events; ``take()`` returns what accrued since the
    last call."""

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self._acc = {"compile_s": 0.0, "compiles": 0, "cache_hits": 0,
                     "cache_misses": 0}

        def on_duration(event, seconds, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                with self._lock:
                    self._acc["compile_s"] += seconds
                    self._acc["compiles"] += 1

        def on_event(event, **_):
            key = {"/jax/compilation_cache/cache_hits": "cache_hits",
                   "/jax/compilation_cache/cache_misses": "cache_misses"}.get(event)
            if key:
                with self._lock:
                    self._acc[key] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def take(self):
        with self._lock:
            out = dict(self._acc, compile_s=round(self._acc["compile_s"], 3))
            for key in self._acc:
                self._acc[key] = 0 if key != "compile_s" else 0.0
        return out


def _space():
    from orion_tpu.space.dsl import build_space

    return build_space({f"x{i}": "uniform(0, 1)" for i in range(D)})


def _rows(params):
    return np.asarray([[p[f"x{i}"] for i in range(D)] for p in params])


def _observe(algo, X):
    X = np.asarray(X)
    algo.observe(
        [{f"x{i}": float(row[i]) for i in range(D)} for row in X],
        [{"objective": float(v)} for v in hartmann6(X)],
    )


def _check_batch(params, q, what):
    rows = _rows(params)
    check(rows.shape == (q, D), f"{what}: shape {rows.shape} != {(q, D)}")
    check(np.isfinite(rows).all(), f"{what}: non-finite rows")
    check(((rows >= 0.0) & (rows <= 1.0)).all(), f"{what}: rows outside [0, 1]^6")
    unique = len({tuple(r) for r in rows.tolist()})
    check(unique == q, f"{what}: {unique} unique rows of {q}")
    return rows


def phase_library(log):
    """tpu_bo through the public API at the headline size, plus proof from
    the compiled step's text that the fused Pallas gram is inside it."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.algo.base import create_algo
    from orion_tpu.algo.tpu_bo import _suggest_step
    from orion_tpu.benchmarks.functions import hartmann6 as hartmann6_ref
    from orion_tpu.compiler_plane import lowered_analysis_fn

    rng = np.random.default_rng(SEED)
    probe = rng.uniform(size=(8, D))
    ref = np.asarray(hartmann6_ref(jnp.asarray(probe, jnp.float32)))
    check(np.allclose(ref, hartmann6(probe), rtol=1e-4, atol=1e-5),
          "NumPy hartmann6 disagrees with orion_tpu.benchmarks.functions")

    algo = create_algo(_space(), ALGO, seed=SEED)
    X = rng.uniform(size=(N_HIST, D))
    _observe(algo, X)
    best = float(hartmann6(X).min())
    round_ms = []
    for r in range(ROUNDS):
        t0 = time.perf_counter()
        params = algo.suggest(Q)
        round_ms.append((time.perf_counter() - t0) * 1e3)
        rows = _check_batch(params, Q, f"library round {r}")
        _observe(algo, rows[:N_EVAL])
        best = min(best, float(hartmann6(rows[:N_EVAL]).min()))
        if r == 0:
            first = log.take()
    regret = best - H6_MIN
    check(np.isfinite(regret), f"hartmann6 regret not finite: {regret}")
    steady = log.take()
    # The compiled step of this deployment, by the compiler plane's own
    # cold path: its text must hold the Pallas kernel (a tpu_custom_call).
    plan = algo.fused_step_plan(Q)
    analysis = lowered_analysis_fn(_suggest_step, plan.arrays, plan.statics)()
    check(analysis["tpu_custom_calls"], "no fused Pallas gram in the compiled step")
    return {
        "phase": "library",
        "q": Q, "n_candidates": N_CANDIDATES, "fit_pad": int(plan.arrays[1].shape[0]),
        "round_ms": [round(ms, 3) for ms in round_ms],
        "first_round": first, "steady_rounds": steady,
        "regret": regret, "n_obs": N_HIST + ROUNDS * N_EVAL,
        "tpu_custom_calls": analysis["tpu_custom_calls"],
        "step_hbm_bytes": analysis["hbm_bytes"],
        "analysis_compile": log.take(),
        "device": str(jax.devices()[0]),
    }


def phase_cli(log):
    """`orion-tpu hunt` with tpu_bo past its random init, then info/status,
    all through orion_tpu.cli.main in this process."""
    import yaml

    from orion_tpu.cli import main as cli_main

    work = os.path.join(OUT_DIR, "cli")
    os.makedirs(work)
    script = os.path.join(work, "hartmann6_script.py")
    with open(script, "w") as handle:
        handle.write(USER_SCRIPT)
    conf = os.path.join(work, "conf.yaml")
    n_init, max_trials, pool = 16, 48, 8
    with open(conf, "w") as handle:
        yaml.safe_dump({"algorithms": {"tpu_bo": {
            "n_init": n_init, "n_candidates": N_CANDIDATES,
            "fit_steps": FIT_STEPS}}}, handle)
    db = ["--storage-path", os.path.join(work, "db.pkl")]
    argv = ["hunt", "-n", "chip-smoke", "-c", conf, *db,
            "--max-trials", str(max_trials), "--worker-trials", str(max_trials),
            "--pool-size", str(pool), "--working-dir", os.path.join(work, "trials"),
            script, *[f"--x{i}~uniform(0, 1)" for i in range(D)]]
    out = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(out):
        rc = cli_main(argv)
    wall_s = time.perf_counter() - t0
    hunt_log = out.getvalue()
    check(rc == 0, f"hunt exited {rc}:\n{hunt_log[-2000:]}")
    compiles = log.take()
    reports = {}
    for command in ("info", "status"):
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = cli_main([command, "-n", "chip-smoke", *db])
        check(rc == 0, f"{command} exited {rc}")
        reports[command] = out.getvalue()
    with open(os.path.join(work, "cli_output.txt"), "w") as handle:
        handle.write(hunt_log + "\n".join(reports.values()))

    from orion_tpu.storage import create_storage

    storage = create_storage({"type": "pickled", "path": db[1]})
    [exp] = storage.fetch_experiments({"name": "chip-smoke"})
    done = [t for t in storage.fetch_trials(uid=exp["_id"]) if t.status == "completed"]
    values = np.array([t.objective.value for t in done])
    check(len(done) >= max_trials, f"{len(done)} of {max_trials} trials completed")
    check(np.isfinite(values).all(), "non-finite objective in the store")
    check("completed" in reports["status"], "status does not list completed trials")
    check("chip-smoke" in reports["info"], "info does not name the experiment")
    return {
        "phase": "cli",
        "trials": len(done), "n_init": n_init, "pool_size": pool,
        "wall_s": round(wall_s, 3),
        "ms_per_trial": round(wall_s * 1e3 / len(done), 3),
        "best": float(values.min()), "regret": float(values.min()) - H6_MIN,
        "compile": compiles,
    }


def phase_gateway(log, n_tenants=3, rounds=3):
    """tpu_bo tenants behind one in-process gateway; barrier-synced rounds
    so their same-signature steps coalesce into shared dispatches."""
    from orion_tpu.serve.client import GatewayClient, RemoteAlgorithm
    from orion_tpu.serve.gateway import GatewayServer

    priors = {f"x{i}": "uniform(0, 1)" for i in range(D)}
    server = GatewayServer(window=0.25, max_width=4)
    host, port = server.serve_background()
    clients = []
    try:
        algos = []
        for t in range(n_tenants):
            client = GatewayClient(host=host, port=port, timeout=600.0,
                                   retry={"max_attempts": 1, "deadline": 600.0})
            clients.append(client)
            algo = RemoteAlgorithm(_space(), priors, ALGO, client,
                                   f"smoke-{t}", seed=SEED + t)
            _observe(algo, np.random.default_rng(SEED + t).uniform(size=(N_HIST, D)))
            algos.append(algo)
        barrier = threading.Barrier(n_tenants)
        round_ms = [[] for _ in range(n_tenants)]
        errors = []

        def drive(t):
            try:
                for r in range(rounds):
                    barrier.wait(timeout=900)
                    t0 = time.perf_counter()
                    params = algos[t].suggest(Q)
                    round_ms[t].append(round((time.perf_counter() - t0) * 1e3, 3))
                    rows = _check_batch(params, Q, f"tenant {t} round {r}")
                    _observe(algos[t], rows[:N_EVAL])
            except Exception as exc:
                errors.append(exc)
                barrier.abort()

        threads = [threading.Thread(target=drive, args=(t,)) for t in range(n_tenants)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=1200)
        check(not any(thread.is_alive() for thread in threads), "gateway rounds hung")
        if errors:
            raise errors[0]
        stats = server.stats_snapshot()
    finally:
        # Close the connections first: a handler thread still blocked on
        # one when the interpreter exits aborts the process.
        for client in clients:
            client.close()
        server.shutdown()
        server.server_close()
    check(stats["coalesced_dispatches"] >= 1, f"no coalesced dispatch: {stats}")
    check(stats["max_width"] >= 2, f"coalesce width < 2: {stats}")
    return {
        "phase": "gateway",
        "tenants": n_tenants, "q": Q,
        "round_ms": round_ms,
        "suggests": stats["suggests"], "dispatches": stats["dispatches"],
        "coalesced_dispatches": stats["coalesced_dispatches"],
        "max_width": stats["max_width"],
        "compile": log.take(),
    }


MESH_ROUNDS = 3  # round 0 cold; rounds 1.. warm, as a user's rounds run


def _rounds(log, cfg):
    """A fresh algorithm's MESH_ROUNDS public rounds: rows, wall ms, the GP
    state each round left, and what it compiled."""
    from orion_tpu.algo.base import create_algo

    algo = create_algo(_space(), {"tpu_bo": cfg}, seed=SEED)
    _observe(algo, np.random.default_rng(SEED).uniform(size=(N_HIST, D)))
    rounds = []
    for _ in range(MESH_ROUNDS):
        t0 = time.perf_counter()
        rows = np.asarray(algo._suggest_cube(Q))
        rounds.append({"rows": rows, "ms": round((time.perf_counter() - t0) * 1e3, 3),
                       "state": algo._gp_state, "compile": log.take()})
        _observe(algo, rows[:N_EVAL])
    return algo, rounds


def phase_mesh(log, n_devices):
    """Candidate-axis sharding (use_mesh=True) at q=1024 over a 16384 pool,
    against the same seed on one device, every round: the same selection,
    rows within ROWS_ATOL and GP state within STATE_RTOL.  The sharded
    compiled step must hold the fused Pallas gram."""
    from orion_tpu.algo.sharding import (
        compare_rows, compare_states, placement_fractions, shard_candidates)
    from orion_tpu.algo.tpu_bo import _suggest_step
    from orion_tpu.compiler_plane import lowered_analysis_fn

    mesh_cfg = dict(ALGO["tpu_bo"], use_mesh=True, n_devices=n_devices)
    sharded_algo, sharded = _rounds(log, mesh_cfg)
    _, single = _rounds(log, ALGO["tpu_bo"])
    parity = [dict(compare_rows(s["rows"], o["rows"]),
                   state=compare_states(s["state"], o["state"]))
              for s, o in zip(sharded, single)]
    health = sharded_algo.health_record()
    plan = sharded_algo.fused_step_plan(Q)
    analysis = lowered_analysis_fn(_suggest_step, plan.arrays, plan.statics)()
    pool = shard_candidates(np.zeros((N_CANDIDATES, D), np.float32), sharded_algo._mesh)
    fractions = placement_fractions(pool)
    result = {
        "phase": "mesh",
        "devices": n_devices, "q": Q, "n_candidates": N_CANDIDATES,
        "parity": parity,
        "sharded_ms": [r["ms"] for r in sharded], "single_ms": [r["ms"] for r in single],
        "sharded_compile": sharded[0]["compile"], "single_compile": single[0]["compile"],
        "tpu_custom_calls": analysis["tpu_custom_calls"],
        "mesh_devices": health.get("mesh_devices"),
        "placement": {str(k): v for k, v in sorted(fractions.items())},
        "analysis_compile": log.take(),
    }
    print(json.dumps(result), flush=True)
    for r, p in enumerate(parity):
        check(p["ok"] and p["state"]["ok"], f"sharded round {r} drifts from one device: {p}")
    check(analysis["tpu_custom_calls"], "no fused Pallas gram in the sharded compiled step")
    check(health.get("mesh_devices") == n_devices, f"mesh has {health.get('mesh_devices')} devices")
    check(len(fractions) == n_devices and all(
        abs(f - 1.0 / n_devices) < 1e-6 for f in fractions.values()),
        f"candidate pool not a quarter per device: {fractions}")
    return None


def phase_tenant_sharded(log, n_devices):
    """One coalesced dispatch with the tenant axis sharded over the chips
    (shard_map), against each plan's standalone step."""
    from orion_tpu.algo.base import create_algo
    from orion_tpu.algo.sharding import compare_rows, compare_states
    from orion_tpu.algo.tpu_bo import run_fused_plan
    from orion_tpu.serve.coalesce import LAST_STACK_PLACEMENT, run_coalesced_plans

    plans = []
    for lane in range(n_devices):
        algo = create_algo(
            _space(), {"tpu_bo": dict(ALGO["tpu_bo"], use_mesh=True, n_devices=n_devices)},
            seed=SEED + lane)
        _observe(algo, np.random.default_rng(SEED + lane).uniform(size=(N_HIST, D)))
        plans.append(algo.fused_step_plan(Q))
    t0 = time.perf_counter()
    want = [run_fused_plan(plan) for plan in plans]
    standalone_ms = (time.perf_counter() - t0) * 1e3
    standalone = log.take()
    t0 = time.perf_counter()
    got = run_coalesced_plans(plans)
    coalesced_ms = (time.perf_counter() - t0) * 1e3
    parity = [dict(compare_rows(g_rows, w_rows), state=compare_states(g_state, w_state))
              for (g_rows, g_state), (w_rows, w_state) in zip(got, want)]
    placement = dict(LAST_STACK_PLACEMENT)
    result = {
        "phase": "tenant_sharded",
        "lanes": n_devices, "q": Q, "n_candidates": N_CANDIDATES,
        "parity": parity,
        "standalone_ms": round(standalone_ms, 3), "standalone_compile": standalone,
        "coalesced_ms": round(coalesced_ms, 3), "coalesced_compile": log.take(),
        "placement": placement,
    }
    print(json.dumps(result), flush=True)
    check(all(p["ok"] and p["state"]["ok"] for p in parity),
          f"coalesced lanes drift from standalone: {parity}")
    check(placement.get("tenant_parallel") is True, f"not tenant-parallel: {placement}")
    check(placement.get("devices") == n_devices, f"placement: {placement}")
    # Tenant-sharded inputs: every chip holds one lane, a quarter each.
    check(abs(placement["util_min_frac"] - 1.0 / n_devices) < 1e-6
          and abs(placement["util_max_frac"] - 1.0 / n_devices) < 1e-6,
          f"lanes not a quarter per device: {placement}")
    return None


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="1: library, CLI and gateway phases on one chip; "
                        "4: only the sharded paths and their comparisons")
    args = parser.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "orion_tpu")):
        print("chip_smoke: orion_tpu/ is not beside this script; run it from "
              "a checkout of the repository", file=sys.stderr)
        return 1
    sys.path.insert(0, HERE)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {devices[0].platform!r}); "
              "this smoke never runs on the CPU", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)} "
              "device(s)", file=sys.stderr)
        return 1

    from orion_tpu.utils.jit_cache import enable_persistent_compilation_cache

    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    log = CompileLog()
    print(json.dumps({"cache_dir": enable_persistent_compilation_cache(),
                      "devices": [str(d) for d in devices]}), flush=True)
    if args.chips == 1:
        phases = (phase_library, phase_cli, phase_gateway)
    else:
        phases = (lambda lg: phase_mesh(lg, args.chips),
                  lambda lg: phase_tenant_sharded(lg, args.chips))
    for phase in phases:
        t0 = time.perf_counter()
        try:
            result = phase(log)
        except Exception as exc:
            import traceback

            traceback.print_exc()
            print(f"chip_smoke: phase failed: {type(exc).__name__}: {exc}",
                  file=sys.stderr)
            return 1
        if result is not None:
            result["phase_s"] = round(time.perf_counter() - t0, 3)
            print(json.dumps(result), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
