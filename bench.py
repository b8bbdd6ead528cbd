#!/usr/bin/env python
"""Benchmark: the BASELINE.json north-star configuration.

Measures **suggestions/sec at q=1024 on Hartmann6** for the TPU-native
batched GP-BO engine (`tpu_bo`) **through the public algorithm API**
(`BaseAlgorithm.suggest`/`observe`, including unit-cube decode and host
param-dict construction), against the skopt-style anchor: a sequential CPU
GP-EI loop (sklearn GaussianProcessRegressor with a Matern-5/2 kernel and
MLL refit per suggestion + EI argmax — which is what skopt's `gp_minimize`
does internally; skopt itself is not installed in this image).

Also verifies simple-regret parity (the other half of the north star): both
optimizers run from the same 16-point initial design to an equal
192-evaluation budget, and the engine's simple regret must not exceed the
anchor's by more than the tolerance.  The check is a hard assert AND both
regrets are printed in the JSON line.

Prints ONE json line:
{"metric", "value", "unit", "vs_baseline", "regret", "anchor_regret",
 "wall_ms_per_round", "device_ms_per_round", "breakdown_ms"} — the last is
the per-stage host/device split of one steady-state round (encode, upload,
dispatch, wait_transfer, decode, dict_build, doc_build; see
bench_breakdown).  The steady-state host tax is gated against device time
(_check_host_budget: 1.25x factor from orion_tpu.hostbudget — the same
knob the doctor's DX004 rule and `orion-tpu top` read;
ORION_TPU_HOST_BUDGET_FACTOR overrides — hard SystemExit in --smoke,
warning on full runs).
"""

import json
import time
import warnings

import numpy as np


Q = 1024
N_INIT = 16
PARITY_BUDGET = 192
PARITY_Q = 16
REGRET_TOL = 0.10  # ours may trail the anchor's regret by at most 10%
GLOBAL_MIN = -3.32237  # Hartmann6
SEED = 0

#: Version of the emitted JSON payload (and of the compact
#: ``BENCH_history.jsonl`` records derived from it).  Bump when a payload
#: key is renamed/removed so cross-run consumers (the doctor's future
#: perf-trajectory rules, trend dashboards) can join records honestly.
BENCH_SCHEMA_VERSION = 2


def _hartmann6_np(u):
    import jax.numpy as jnp

    import orion_tpu.benchmarks.functions as f

    return np.asarray(f.hartmann6(jnp.asarray(u)))


def _make_algo(seed=SEED, n_candidates=16384, fit_steps=40, prewarm=False):
    from orion_tpu.algo.base import create_algo
    from orion_tpu.space.dsl import build_space

    space = build_space({f"x{i}": "uniform(0, 1)" for i in range(6)})
    return create_algo(
        space,
        # local_frac 0.3 = the measured setting for smooth multimodal
        # landscapes (runner.py's hartmann6 preset comment has the A/B).
        # prewarm defaults OFF here (the production default is on): the
        # timed phases must not have a background XLA compile competing
        # for cores mid-measurement; bench_prewarm opts in explicitly to
        # measure the boundary-crossing contract itself.
        {"tpu_bo": {"n_init": N_INIT, "n_candidates": n_candidates,
                     "fit_steps": fit_steps, "local_frac": 0.3,
                     "prewarm": prewarm}},
        seed=seed,
    )


def _params_to_x(params_list):
    return np.asarray(
        [[p[f"x{i}"] for i in range(6)] for p in params_list], dtype=np.float32
    )


def _observe(algo, X, y):
    params = [{f"x{i}": float(row[i]) for i in range(6)} for row in np.asarray(X)]
    algo.observe(params, [{"objective": float(v)} for v in np.asarray(y)])


def bench_throughput():
    """suggestions/sec at q=1024 through the public suggest/observe API.

    Each timed round first observes a fresh batch (marking the GP stale), so
    the measured suggest() includes the full honest cycle: encode + refit +
    candidate generation + acquisition + dedup + decode to param dicts.
    History starts at 130 points so the padded GP size (256) is stable across
    rounds — no recompilation inside the timing loop.
    """
    rng = np.random.default_rng(SEED)
    algo = _make_algo()
    X = rng.uniform(size=(130, 6)).astype(np.float32)
    _observe(algo, X, _hartmann6_np(X))

    algo.suggest(Q)  # compile (fit at pad 256 + acquire at q=1024)
    times = []
    for _ in range(5):
        Xn = rng.uniform(size=(16, 6)).astype(np.float32)
        _observe(algo, Xn, _hartmann6_np(Xn))  # marks the GP stale
        t0 = time.perf_counter()
        out = algo.suggest(Q)
        times.append(time.perf_counter() - t0)
    assert len(out) == Q and set(out[0]) == {f"x{i}" for i in range(6)}
    return Q / float(np.median(times))


#: Seeds of the multi-seed regret-trajectory gate (seed 0 doubles as the
#: anchor-parity run).  Five seeds span both modes of Hartmann6's bimodal
#: seed distribution (BENCH_REGRET_BASELINE.json's justification).
GATE_SEEDS = (0, 1, 2, 3, 4)
REGRET_BASELINE_PATH = "BENCH_REGRET_BASELINE.json"


def run_regret_curve(seed, budget=PARITY_BUDGET, q=PARITY_Q, algo_kwargs=None):
    """One seeded bench regret trajectory: ``(curve, health_records)``.

    ``curve`` is the incumbent simple regret after the initial design and
    after every q-round; ``health_records`` one ``algo.health_record()``
    dict per GP round (regret stamped in) — the optimization-health series
    the gate and the emitted ``health`` payload are built from.  Seed
    ``SEED`` with default kwargs reproduces the historical single-seed
    regret number exactly."""
    rng = np.random.default_rng(seed)
    X0 = rng.uniform(size=(N_INIT, 6)).astype(np.float32)
    y0 = _hartmann6_np(X0)
    algo = _make_algo(seed=seed, **(algo_kwargs or {}))
    _observe(algo, X0, y0)
    best = float(np.min(y0))
    n_evals = len(y0)
    curve = [best - GLOBAL_MIN]
    health_records = []
    while n_evals < budget:
        step_q = min(q, budget - n_evals)
        params = algo.suggest(step_q)
        Xn = _params_to_x(params)
        yn = _hartmann6_np(Xn)
        algo.observe(params, [{"objective": float(v)} for v in yn])
        best = min(best, float(np.min(yn)))
        n_evals += step_q
        curve.append(best - GLOBAL_MIN)
        record = algo.health_record() or {}
        record["regret"] = best - GLOBAL_MIN
        record["round"] = len(health_records) + 1
        health_records.append(record)
    return curve, health_records


def _health_payload(curve, health_records):
    """The emitted ``health`` block: the per-round regret curve plus the
    GP/TR health series and the last full record (schema-pinned by
    tests/unit/test_bench_smoke.py)."""
    return {
        "regret_curve": [round(float(v), 6) for v in curve],
        "rounds": len(health_records),
        "gp_mll": [
            round(r["gp_mll"], 4) for r in health_records if r.get("gp_mll") is not None
        ],
        "tr_length": [
            round(r["tr_length"], 4)
            for r in health_records
            if r.get("tr_length") is not None
        ],
        "last": health_records[-1] if health_records else None,
    }


def _baseline_curves(baseline_path=REGRET_BASELINE_PATH):
    """Committed baseline curves, resolved next to this file when the cwd
    differs (the smoke test runs bench.py from the repo root either way)."""
    import os

    from orion_tpu.benchmarks.regret_gate import load_baseline

    path = baseline_path
    if not os.path.exists(path):
        path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), baseline_path
        )
    return load_baseline(path)


def bench_regret_gate(curves, baseline_path=REGRET_BASELINE_PATH):
    """Evaluate the multi-seed statistical gate against the committed
    baseline (orion_tpu.benchmarks.regret_gate); returns the verdict dict
    with the measured per-seed finals attached."""
    from orion_tpu.benchmarks.regret_gate import evaluate_regret_gate

    baseline = _baseline_curves(baseline_path)
    verdict = evaluate_regret_gate(curves, baseline)
    verdict["current_final"] = [round(float(c[-1]), 6) for c in curves]
    verdict["baseline_final"] = [round(float(c[-1]), 6) for c in baseline]
    return verdict


def run_anchor_regret(X0, y0):
    """Sequential skopt-style GP-EI on CPU from the same initial design.

    Returns (simple_regret, per-suggest times at history >= 128) so the
    anchor's suggestions/sec is measured at a history size comparable to the
    throughput bench (130+).
    """
    from scipy.stats import norm
    from sklearn.gaussian_process import GaussianProcessRegressor
    from sklearn.gaussian_process.kernels import (
        ConstantKernel,
        Matern,
        WhiteKernel,
    )

    rng = np.random.default_rng(SEED + 1)
    X = np.asarray(X0, dtype=np.float64)
    y = np.asarray(y0, dtype=np.float64)
    times = []
    while len(y) < PARITY_BUDGET:
        t0 = time.perf_counter()
        kernel = (
            ConstantKernel(1.0) * Matern(length_scale=np.ones(6), nu=2.5)
            + WhiteKernel(1e-4)
        )
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            gpr = GaussianProcessRegressor(
                kernel=kernel,
                normalize_y=True,
                n_restarts_optimizer=1,
                random_state=SEED,
            )
            gpr.fit(X, y)
            cands = rng.uniform(size=(1000, 6))
            mu, std = gpr.predict(cands, return_std=True)
        z = (y.min() - mu) / np.maximum(std, 1e-12)
        ei = std * (z * norm.cdf(z) + norm.pdf(z))
        xn = cands[np.argmax(ei)]
        dt = time.perf_counter() - t0
        if len(y) >= 128:
            times.append(dt)
        yn = _hartmann6_np(xn[None].astype(np.float32))
        X = np.vstack([X, xn[None]])
        y = np.append(y, yn)
    return float(y.min()) - GLOBAL_MIN, times


def bench_storage(q=Q, rounds=3):
    """The storage edge of one producer round: register a q-trial batch
    through ``DocumentStorage.register_trials`` on the two backends that
    matter at scale — sqlite (the durable local default) and network (an
    in-process loopback server) — measuring wall ms per round AND the
    backend-level operation count (SQLite transactions / wire round
    trips).  The batched write path commits the whole round as ONE
    transaction / ONE wire request, so ``storage_ops_per_round`` must stay
    O(1) regardless of q; a regression back to per-trial commits shows up
    here as q, not 1.

    Returns ``(storage_ms, storage_ops_per_round)`` dicts keyed by
    backend."""
    import os
    import tempfile

    from orion_tpu.core.trial import Trial
    from orion_tpu.storage.base import DocumentStorage
    from orion_tpu.storage.netdb import DBServer, NetworkDB
    from orion_tpu.storage.sqlitedb import SQLiteDB

    rng = np.random.default_rng(SEED + 3)
    storage_ms, storage_ops = {}, {}

    def _run(name, db, ops_counter):
        storage = DocumentStorage(db)
        exp = storage.create_experiment(
            {"name": "bench-storage", "metadata": {"user": "bench"}}
        )
        times = []
        ops_before = ops_counter()
        for _ in range(rounds):
            trials = [
                Trial(
                    experiment=exp["_id"],
                    params={f"x{i}": float(v) for i, v in enumerate(row)},
                )
                for row in rng.uniform(size=(q, 6))
            ]
            t0 = time.perf_counter()
            outcomes = storage.register_trials(trials)
            times.append(time.perf_counter() - t0)
            assert not any(isinstance(o, Exception) for o in outcomes)
        storage_ms[name] = round(1e3 * float(np.median(times)), 3)
        storage_ops[name] = int(round((ops_counter() - ops_before) / rounds))

    with tempfile.TemporaryDirectory(prefix="orion-bench-storage-") as tmpdir:
        sqlite_db = SQLiteDB(os.path.join(tmpdir, "bench.sqlite"))
        try:
            _run("sqlite", sqlite_db, lambda: sqlite_db.txn_count)
        finally:
            sqlite_db.close()

    server = DBServer(port=0)
    host, port = server.serve_background()
    net_db = NetworkDB(host=host, port=port)
    try:
        _run("network", net_db, lambda: net_db.wire_requests)
    finally:
        net_db.close()
        server.shutdown()
        server.server_close()
    return storage_ms, storage_ops


def bench_breakdown(rounds=4, q=Q, algo=None, n_hist=130):
    """Median per-round host/device breakdown of the q=1024 boundary at the
    steady-state shape, one stage at a time (the stages algo.observe +
    algo.suggest run internally, replayed through the same public codec and
    suggest-step entry points):

    - encode:        observe-side dict -> unit-cube rows (params_to_cube)
    - upload:        observe-side device append (the incremental
                     DeviceHistory write — the only O(batch) transfer; the
                     history itself stays resident)
    - dispatch:      host prep + async dispatch of the fused suggest jit
                     (the copula transform runs in-jit over the resident
                     device buffers — nothing history-sized is rebuilt on
                     host or uploaded here)
    - wait_transfer: blocking on the device result + the (q, d) transfer
                     (device execution + the host<->device transfer)
    - decode:        cube -> per-dim host arrays (decode_flat_np)
    - dict_build:    per-dim arrays -> the round's ParamBatch
                     (arrays_to_params: vectorized column build; the
                     per-trial dicts are LAZY — they materialize exactly
                     once, inside the doc_build stage's columnar pass,
                     instead of eagerly here — docs/performance.md
                     "Wall ≈ device")
    - doc_build:     the columnar trial-document pass (TrialBatch.prepare
                     + to_docs — ids and storage docs for the whole
                     q-round, what the producer's commit feeds apply_batch)
    - health:        one ``algo.health_record()`` read (the per-round
                     optimization-health record, orion_tpu.health) —
                     measured AFTER wait_transfer so it reads ready device
                     data; ``main``/``--smoke`` hard-assert it stays under
                     1% of the round

    Everything except wait_transfer is host boundary tax; regressions in
    any stage show up in the JSON line.  ``storage_ms`` (the sqlite commit
    of one q-batch registration, measured by :func:`bench_storage`) is
    merged into this dict by ``main`` — the host stage the pipelined
    producer commit overlaps with the next round's dispatch.

    The FIRST loop round is a discarded warmup: the big fused-step compile
    is covered by the pre-loop ``suggest``, but the first in-loop round
    still pays the batch-16 observe-append jit compile (measured
    ``wait_transfer≈3306ms`` at ``rounds=3`` on CPU) — a median over few
    rounds must not carry that one-time cost as a steady-state number."""
    rng = np.random.default_rng(SEED + 2)
    if algo is None:
        algo = _make_algo(seed=SEED + 2)
    space = algo.space
    X = rng.uniform(size=(n_hist, 6)).astype(np.float32)
    _observe(algo, X, _hartmann6_np(X))
    algo.suggest(q)  # compile

    from orion_tpu.core.trial import TrialBatch

    # Plan-prep cache accounting over the measured rounds only: per round,
    # which cache counters moved and the round's ``algo.plan`` span.
    plan_rounds = []

    stages = {k: [] for k in
              ("encode", "upload", "dispatch", "wait_transfer", "health",
               "decode", "dict_build", "doc_build")}
    for bench_round in range(rounds + 1):
        Xn = rng.uniform(size=(16, 6)).astype(np.float32)
        yn = _hartmann6_np(Xn)
        params = [{f"x{i}": float(r[i]) for i in range(6)} for r in Xn]
        t0 = time.perf_counter()
        cube = space.params_to_cube(params)
        t1 = time.perf_counter()
        algo.observe(params, [{"objective": float(v)} for v in yn], cube=cube)
        t2 = time.perf_counter()
        rows, plan = _counted_suggest_cube(algo, q)
        t3 = time.perf_counter()
        plan_rounds.append(plan)
        out = np.asarray(rows)
        t4 = time.perf_counter()
        algo.health_record()
        t_health = time.perf_counter()
        arrays = space.decode_flat_np(out)
        t5 = time.perf_counter()
        batch = space.arrays_to_params(arrays)
        t6 = time.perf_counter()
        TrialBatch(batch).prepare("bench-breakdown", submit_time=0.0).to_docs()
        t7 = time.perf_counter()
        if bench_round == 0:
            plan_rounds.clear()
            continue  # discarded warmup round (append-jit compiles)
        for key, dt in zip(stages, (t1 - t0, t2 - t1, t3 - t2, t4 - t3,
                                    t_health - t4, t5 - t_health,
                                    t6 - t5, t7 - t6)):
            stages[key].append(dt)
    out = {k: round(1e3 * float(np.median(v)), 3) for k, v in stages.items()}
    # SAVINGS reports, not stages: the dispatch medians above already
    # CONTAIN the cache-hit prep, so the saved µs must be excluded from
    # every host_ms sum (test_bench_smoke pins this).  prep: the µs the
    # per-signature cache saves (statics dict + signature + cold-hypers
    # rebuilt on a miss, reused on a hit); dispatch: the µs the
    # per-instance prep token saves on top (skipping the prep-key probe
    # entirely on the steady path).
    out["prep_us_saved"] = _prep_us_saved(plan_rounds, "plan.prep")
    out["dispatch_us_saved"] = _prep_us_saved(plan_rounds, "plan.token")
    return out


PREP_COUNTERS = ("plan.prep.hits", "plan.prep.misses",
                 "plan.token.hits", "plan.token.misses")


def _counted_suggest_cube(algo, q):
    """``algo._suggest_cube(q)`` with telemetry on for the call: the rows,
    and ``(counter deltas, algo.plan seconds)`` of the round."""
    from orion_tpu.telemetry import TELEMETRY

    was_enabled = TELEMETRY.enabled
    TELEMETRY.enable()
    try:
        TELEMETRY.drain_spans("bench.plan")
        before = [TELEMETRY.counter_value(c) for c in PREP_COUNTERS]
        rows = algo._suggest_cube(q)
        deltas = {c: TELEMETRY.counter_value(c) - b
                  for c, b in zip(PREP_COUNTERS, before)}
        plan_s = sum(s["dur"] for s in TELEMETRY.drain_spans("bench.plan")
                     if s["name"] == "algo.plan")
    finally:
        if not was_enabled:
            TELEMETRY.disable()
    return rows, (deltas, plan_s)


def _prep_us_saved(plan_rounds, cache):
    """µs a plan-prep cache (``plan.prep`` or ``plan.token`` counters)
    saved over the rounds: its hits times the mean ``algo.plan`` time of a
    miss round less that of a hit round."""
    hit = [s for deltas, s in plan_rounds if deltas[f"{cache}.hits"]]
    miss = [s for deltas, s in plan_rounds if deltas[f"{cache}.misses"]]
    hit_us = 1e6 * float(np.mean(hit)) if hit else 0.0
    miss_us = 1e6 * float(np.mean(miss)) if miss else 0.0
    return len(hit) * max(0.0, miss_us - hit_us)


def bench_id_hash(q=1024, reps=5):
    """Trial-identity cost at the bench batch size: the md5 path
    (per-trial repr assembly + md5, ``compute_batch_ids``) vs the
    ``cube_hash`` scheme (ONE vectorized pass over the canonical cube-row
    bytes, ``compute_scheme_ids``) — the ~6.4µs/trial repr+md5 floor was
    the last per-trial host line of the registration tail (ROADMAP item
    5).  Returns per-trial µs for both paths, the speedup, and a
    ``distinct_ok`` collision check over the q-batch; ``--smoke``
    hard-gates ``speedup >= 4`` at q=1024."""
    from orion_tpu.core.trial import compute_batch_ids, compute_scheme_ids
    from orion_tpu.space.dsl import build_space

    space = build_space({f"x{i}": "uniform(0, 1)" for i in range(6)})
    rng = np.random.default_rng(SEED + 3)
    cube = rng.uniform(size=(q, 6)).astype(np.float32)
    arrays = space.decode_flat_np(cube)
    params = space.arrays_to_params(arrays)
    exp_id = "bench-id-hash"
    md5_times, cube_times = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        md5_ids = compute_batch_ids(exp_id, params)
        md5_times.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        cube_ids = compute_scheme_ids(
            exp_id, params, id_scheme="cube_hash", space=space
        )
        cube_times.append(time.perf_counter() - t0)
    md5_us = float(np.median(md5_times)) / q * 1e6
    cube_us = float(np.median(cube_times)) / q * 1e6
    return {
        "q": q,
        "md5_us_per_trial": round(md5_us, 3),
        "cube_hash_us_per_trial": round(cube_us, 3),
        "speedup": round(md5_us / cube_us, 2) if cube_us else None,
        "distinct_ok": len(set(cube_ids)) == q and len(set(md5_ids)) == q,
    }


def bench_prewarm(q=16):
    """The pow-2 boundary-crossing contract, asserted on every bench run:
    grow a small history across a bucket boundary with prewarm enabled and
    measure (via telemetry) how many synchronous retraces the post-warm
    suggest rounds paid — MUST be zero (the background compile turned the
    crossing into a jit-cache hit; docs/performance.md, "The zero-reupload
    round").  Returns ``{"retraces_after_warm", "prewarms"}``; retrace
    introspection rides a private jax accessor, so the fields are None
    (skipped, not failed) when it is unavailable."""
    from orion_tpu import telemetry as tel
    from orion_tpu.algo.tpu_bo import _suggest_step

    if not hasattr(_suggest_step, "_cache_size"):
        return {"retraces_after_warm": None, "prewarms": None}
    was_enabled = tel.TELEMETRY.enabled
    tel.TELEMETRY.enable()
    try:
        rng = np.random.default_rng(SEED + 5)
        # Distinct static signature (n_candidates) from the other bench
        # phases so their compiled buckets cannot mask the measurement.
        algo = _make_algo(seed=SEED + 5, n_candidates=192, fit_steps=2,
                          prewarm=True)

        def observe(batch):
            Xn = rng.uniform(size=(batch, 6)).astype(np.float32)
            _observe(algo, Xn, _hartmann6_np(Xn))

        observe(40)          # bucket 64, under the fill threshold
        algo.suggest(q)      # compiles the 64-bucket, records the q bucket
        observe(16)          # count 56 >= 48: prewarm of bucket 128 starts
        algo._prewarmer.wait()
        base = tel.TELEMETRY.counter_value("jax.retraces")
        observe(16)          # count 72: crosses 64 -> 128
        algo.suggest(q)      # post-warm round — must be a cache hit
        return {
            "retraces_after_warm":
                tel.TELEMETRY.counter_value("jax.retraces") - base,
            "prewarms": tel.TELEMETRY.counter_value("jax.prewarms"),
        }
    finally:
        if not was_enabled:
            tel.TELEMETRY.disable()


def bench_serve(m_tenants=2, rounds=4, q=8, window=0.4, n_candidates=256,
                fit_steps=4, storage=None, algorithms=None, priors=None,
                name_prefix="bench-serve"):
    """The multi-tenant suggest gateway, full stack (orion_tpu.serve):
    M concurrent experiments — each a REAL producer/worker loop over one
    shared sqlite store, its algorithm a gateway-backed RemoteAlgorithm —
    drive one in-process GatewayServer with barrier-synchronized rounds so
    concurrent suggest traffic actually lands in the coalescing window.

    Hard asserts (the serving contract, ISSUE 8):

    - **coalescing happened**: at least one dispatch stacked >= 2 tenants
      (``max_width >= 2``), and device dispatches per suggest < 1 — M
      suggests cost fewer than M device calls;
    - **storage invariants hold**: `orion-tpu audit` is clean for every
      tenant experiment after the run (served rounds register/complete
      trials exactly like local ones).

    Returns the ``serve`` payload block: coalesce width stats, device
    dispatches per suggest, per-tenant request p50/p99 (from the gateway's
    per-tenant telemetry histograms), backpressure/eviction counts.

    ``algorithms``/``priors`` parametrize the tenants' experiments (default:
    6-dim Hartmann6 under tpu_bo) — the ``--serve --smoke`` asha_bo leg
    reuses this same harness with a fidelity dimension added; the objective
    is always Hartmann6 over the ``x*`` parameters, so a fidelity column
    simply rides along unscored."""
    import os
    import tempfile
    import threading

    from orion_tpu import telemetry as tel
    from orion_tpu.client.experiment import ExperimentClient
    from orion_tpu.core.experiment import build_experiment
    from orion_tpu.serve.gateway import GatewayServer
    from orion_tpu.storage.audit import audit_experiment
    from orion_tpu.storage.base import create_storage
    from orion_tpu.telemetry import histogram_percentile

    if priors is None:
        priors = {f"x{j}": "uniform(0, 1)" for j in range(6)}
    if algorithms is None:
        algorithms = {
            "tpu_bo": {
                "n_init": q,
                "n_candidates": n_candidates,
                "fit_steps": fit_steps,
            }
        }
    x_names = sorted(
        (k for k in priors if k.startswith("x")), key=lambda k: int(k[1:])
    )
    was_enabled = tel.TELEMETRY.enabled
    tel.TELEMETRY.enable()
    server = GatewayServer(window=window, max_width=max(2, m_tenants))
    host, port = server.serve_background()
    barrier = threading.Barrier(m_tenants)
    errors, reports = [], {}
    try:
        with tempfile.TemporaryDirectory(prefix="orion-bench-serve-") as tmp:
            if storage is None:
                storage = create_storage(
                    {"type": "sqlite", "path": os.path.join(tmp, "serve.sqlite")}
                )

            def run_tenant(index):
                try:
                    experiment = build_experiment(
                        storage,
                        f"{name_prefix}-{index}",
                        priors=priors,
                        algorithms=algorithms,
                        pool_size=q,
                        metadata={"user": "bench"},
                    )
                    experiment.serve_config = {"address": f"{host}:{port}"}
                    experiment.instantiate(seed=SEED + index)
                    client = ExperimentClient(experiment)
                    for _ in range(rounds):
                        # Round barrier: the gateway's coalescing window is
                        # small; the bench must present genuinely
                        # concurrent traffic, as M live workers would.
                        barrier.wait(timeout=120)
                        trials = client.suggest(q)
                        X = np.asarray(
                            [
                                [t.params[name] for name in x_names]
                                for t in trials
                            ],
                            dtype=np.float32,
                        )
                        client.observe_all(
                            trials, [float(v) for v in _hartmann6_np(X)]
                        )
                    reports[index] = audit_experiment(storage, experiment)
                except Exception as exc:  # surfaced after join
                    errors.append(exc)
                    barrier.abort()

            threads = [
                threading.Thread(target=run_tenant, args=(i,), daemon=True)
                for i in range(m_tenants)
            ]
            for thread in threads:
                thread.start()
            for thread in threads:
                thread.join(timeout=500)
            assert not errors, f"serve bench tenant failed: {errors[0]!r}"
            stats = server.stats_snapshot()
            snapshot = tel.TELEMETRY.snapshot()
    finally:
        server.shutdown()
        server.server_close()
        if not was_enabled:
            tel.TELEMETRY.disable()

    assert all(r.ok for r in reports.values()), {
        i: r.summary() for i, r in reports.items() if not r.ok
    }
    assert stats["max_width"] >= 2, (
        f"no coalescing happened: width stats {stats['widths']}"
    )
    ratio = stats["dispatches_per_suggest"]
    assert ratio is not None and ratio < 1.0, (
        f"device dispatches per suggest = {ratio} (must be < 1 for "
        f"M={m_tenants} tenants): {stats}"
    )
    per_tenant = {}
    for name, hist in (snapshot.get("histograms") or {}).items():
        prefix, suffix = "serve.tenant.", ".request"
        if name.startswith(prefix) and name.endswith(suffix) and hist.get("count"):
            tenant = name[len(prefix):-len(suffix)]
            per_tenant[tenant] = {
                "requests": hist["count"],
                "p50_ms": round(histogram_percentile(hist, 50) * 1e3, 3),
                "p99_ms": round(histogram_percentile(hist, 99) * 1e3, 3),
            }
    return {
        "tenants": m_tenants,
        "rounds": rounds,
        "q": q,
        "suggests": stats["suggests"],
        "device_dispatches": stats["dispatches"],
        "dispatches_per_suggest": ratio,
        "coalesced_dispatches": stats["coalesced_dispatches"],
        "coalesce_max_width": stats["max_width"],
        "coalesce_widths": stats["widths"],
        "backpressure": stats["backpressure"],
        "evictions": stats["evictions"],
        "per_tenant": per_tenant,
        "audit_violations": sum(
            len(r.violations) for r in reports.values()
        ),
    }


def bench_serve_fleet(m_gateways=3, n_tenants=6, rounds=4, q=8, window=0.4,
                      n_candidates=256, fit_steps=4, priors=None,
                      algorithms=None, name_prefix="bench-fleet"):
    """The gateway FLEET leg (ISSUE 19): K tenants ring-routed over M
    gateway processes with one member killed mid-stream.

    Two passes with identical seeds: a single-gateway reference run, then
    the fleet run — M members sharing a per-tenant snapshot store, every
    client routing by the consistent-hash ring (``serve.addresses``), and
    the member owning the MOST tenants killed (simulated crash, no
    farewell snapshot) at the mid-stream round barrier while suggests are
    in flight.  Hard gates (SystemExit, not assert — must hold under
    ``python -O``):

    - **bit-identical**: every tenant's suggestion stream matches its
      single-gateway reference exactly — failover + store restore +
      replay never fork a trajectory;
    - **zero lost observations**: each tenant's gateway-side count equals
      ``rounds * q`` on whichever surviving member hosts it;
    - **fleet-wide amortization**: total device dispatches / total
      suggests < 1 across ALL members — the per-process coalescing win
      survives the scale-out (ring co-residents still stack);
    - **the kill bit**: at least one client failover actually happened,
      and every tenant experiment passes ``orion-tpu audit``.

    Returns the ``serve_fleet`` payload block."""
    import os
    import socket
    import tempfile
    import threading

    from orion_tpu import telemetry as tel
    from orion_tpu.client.experiment import ExperimentClient
    from orion_tpu.core.experiment import build_experiment
    from orion_tpu.serve.fleet import FleetState, ring_key
    from orion_tpu.serve.gateway import GatewayServer
    from orion_tpu.storage.audit import audit_experiment
    from orion_tpu.storage.base import create_storage

    if priors is None:
        priors = {f"x{j}": "uniform(0, 1)" for j in range(6)}
    if algorithms is None:
        algorithms = {
            "tpu_bo": {
                "n_init": q,
                "n_candidates": n_candidates,
                "fit_steps": fit_steps,
            }
        }
    x_names = sorted(
        (k for k in priors if k.startswith("x")), key=lambda k: int(k[1:])
    )

    def objective_values(X):
        # Hartmann6 over the x* columns; narrower spaces ride zero-padded.
        if X.shape[1] < 6:
            X = np.concatenate(
                [X, np.zeros((len(X), 6 - X.shape[1]), dtype=X.dtype)], axis=1
            )
        return [float(v) for v in _hartmann6_np(X)]

    def run_pass(serve_config, storage, barrier, controller=None):
        """Drive every tenant for ``rounds`` barrier-synchronized rounds.
        ``controller`` (fleet pass) participates in the same barrier from
        the calling thread — that is what lets it kill a member while the
        round's suggests are genuinely in flight."""
        streams, reports, errors = {}, {}, []

        def run_tenant(index):
            try:
                experiment = build_experiment(
                    storage,
                    f"{name_prefix}-{index}",
                    priors=priors,
                    algorithms=algorithms,
                    pool_size=q,
                    metadata={"user": "bench"},
                )
                experiment.serve_config = dict(serve_config)
                experiment.instantiate(seed=SEED + index)
                client = ExperimentClient(experiment)
                stream = []
                for _ in range(rounds):
                    barrier.wait(timeout=300)
                    trials = client.suggest(q)
                    X = np.asarray(
                        [
                            [t.params[name] for name in x_names]
                            for t in trials
                        ],
                        dtype=np.float32,
                    )
                    stream.append(X.tolist())
                    client.observe_all(trials, objective_values(X))
                # The Producer pushes completed trials to the gateway on
                # the NEXT suggest; flush so the last round's batch is
                # gateway-side before the zero-loss gate counts it.
                client.producer.update()
                streams[index] = stream
                reports[index] = audit_experiment(storage, experiment)
            except Exception as exc:  # surfaced after join
                errors.append(exc)
                barrier.abort()

        threads = [
            threading.Thread(target=run_tenant, args=(i,), daemon=True)
            for i in range(n_tenants)
        ]
        for thread in threads:
            thread.start()
        if controller is not None:
            controller()
        for thread in threads:
            thread.join(timeout=500)
        if errors:
            raise SystemExit(f"fleet bench tenant failed: {errors[0]!r}")
        return streams, reports

    was_enabled = tel.TELEMETRY.enabled
    tel.TELEMETRY.enable()
    failovers_before = int(tel.TELEMETRY.counter_value("serve.client.failovers"))
    try:
        with tempfile.TemporaryDirectory(prefix="orion-bench-fleet-") as tmp:
            # --- reference pass: the same tenants through ONE gateway ----
            ref_server = GatewayServer(
                window=window, max_width=max(2, n_tenants)
            )
            ref_host, ref_port = ref_server.serve_background()
            try:
                ref_streams, _ = run_pass(
                    {"address": f"{ref_host}:{ref_port}"},
                    create_storage(
                        {"type": "sqlite",
                         "path": os.path.join(tmp, "ref.sqlite")}
                    ),
                    threading.Barrier(n_tenants),
                )
            finally:
                ref_server.shutdown()
                ref_server.server_close()

            # --- fleet pass: M members, shared store, mid-stream kill ----
            def free_port():
                sock = socket.socket()
                sock.bind(("127.0.0.1", 0))
                port = sock.getsockname()[1]
                sock.close()
                return port

            members = [f"127.0.0.1:{free_port()}" for _ in range(m_gateways)]
            store = os.path.join(tmp, "tenant-store")
            gateways = [
                GatewayServer(
                    host="127.0.0.1",
                    port=int(member.rsplit(":", 1)[1]),
                    window=window,
                    max_width=max(2, n_tenants),
                    fleet=members,
                    advertise=member,
                    persist=store,
                )
                for member in members
            ]
            for gateway in gateways:
                gateway.serve_background()

            # Ring placement is known before any traffic (same HashRing on
            # every client); kill the member owning the MOST tenants so
            # the handoff path carries real load.
            fleet_state = FleetState(members)
            worker = f"{socket.gethostname()}:{os.getpid()}"
            tenant_names = [
                f"{name_prefix}-{index}-v1@{worker}"
                for index in range(n_tenants)
            ]
            placement = {member: 0 for member in members}
            for tenant in tenant_names:
                placement[fleet_state.owner(ring_key(tenant))] += 1
            victim_addr = max(placement, key=placement.get)
            victim = gateways[members.index(victim_addr)]
            survivors = [g for g in gateways if g is not victim]
            kill_round = rounds // 2
            barrier = threading.Barrier(n_tenants + 1)

            def controller():
                for round_index in range(rounds):
                    try:
                        barrier.wait(timeout=300)
                    except threading.BrokenBarrierError:
                        return
                    if round_index == kill_round:
                        # Simulated crash while the round's suggests are
                        # in flight: no farewell snapshot — durability
                        # must come from the sync persist-before-reply
                        # path alone.
                        victim.kill()

            try:
                fleet_streams, reports = run_pass(
                    {"addresses": list(members)},
                    create_storage(
                        {"type": "sqlite",
                         "path": os.path.join(tmp, "fleet.sqlite")}
                    ),
                    barrier,
                    controller=controller,
                )
            finally:
                for gateway in survivors:
                    gateway.shutdown()
                    gateway.server_close()
            # Stats survive shutdown (counters on the server object);
            # the victim's froze at the kill.
            stats = [gateway.stats_snapshot() for gateway in gateways]
    finally:
        if not was_enabled:
            tel.TELEMETRY.disable()
    failovers = (
        int(tel.TELEMETRY.counter_value("serve.client.failovers"))
        - failovers_before
    )

    # --- the gates (SystemExit: must hold under `python -O`) -------------
    for index in range(n_tenants):
        if fleet_streams.get(index) != ref_streams.get(index):
            raise SystemExit(
                f"fleet stream FORKED for tenant {index}: the killed-"
                "member run diverged from its single-gateway reference"
            )
    survivor_stats = [
        s for s, g in zip(stats, gateways) if g is not victim
    ]
    lost = {}
    for tenant in tenant_names:
        observed = max(
            (s["per_tenant"].get(tenant, {}).get("n_observed", 0)
             for s in survivor_stats),
            default=0,
        )
        if observed != rounds * q:
            lost[tenant] = observed
    if lost:
        raise SystemExit(
            f"fleet run LOST observations (want {rounds * q} each): {lost}"
        )
    total_suggests = sum(s["suggests"] for s in stats)
    total_dispatches = sum(s["dispatches"] for s in stats)
    ratio = (
        total_dispatches / total_suggests if total_suggests else None
    )
    if ratio is None or ratio >= 1.0:
        raise SystemExit(
            f"fleet-wide dispatches per suggest = {ratio} (must be < 1 "
            f"across {m_gateways} gateways): {stats}"
        )
    if failovers < 1:
        raise SystemExit(
            "the mid-stream kill never bit: no client failover happened "
            f"(victim {victim_addr} owned {placement[victim_addr]} tenants)"
        )
    audit_violations = sum(len(r.violations) for r in reports.values())
    if any(not r.ok for r in reports.values()):
        raise SystemExit(
            "fleet run audits dirty: "
            f"{ {i: r.summary() for i, r in reports.items() if not r.ok} }"
        )
    return {
        "gateways": m_gateways,
        "tenants": n_tenants,
        "rounds": rounds,
        "q": q,
        "killed": victim_addr,
        "kill_round": kill_round,
        "placement": placement,
        "suggests": total_suggests,
        "device_dispatches": total_dispatches,
        "dispatches_per_suggest": round(ratio, 4),
        "coalesce_max_width": max(s["max_width"] for s in stats),
        "failovers": failovers,
        "bit_identical": True,
        "lost_observations": 0,
        "audit_violations": audit_violations,
    }


def main_serve(m_tenants=4, rounds=6, q=16, smoke=False):
    """``bench.py --serve``: the gateway serving M concurrent experiments —
    prints ONE json line with the coalesce/latency/dispatch-amortization
    numbers (hard asserts inside bench_serve).

    ``--serve --smoke`` runs the tenants over a LOOPBACK NETDB store so
    every hop crosses a real wire, exports the merged distributed trace
    (``bench_serve_trace.json``), and hard-asserts the ISSUE-11 acceptance:
    a RemoteAlgorithm suggest, the gateway's coalesced dispatch (link),
    and the storage commit's server-side apply joined by trace_id, with
    cross-process flow events in the Perfetto file."""
    if not smoke:
        payload = {
            "metric": "serve gateway smoke",
            "serve": bench_serve(
                m_tenants=m_tenants, rounds=rounds, q=q, n_candidates=1024,
                fit_steps=8,
            ),
            # The fleet headline (ISSUE 19): M=3 gateways x K tenants with
            # a mid-stream member kill — bit-identical streams, zero lost
            # observations, fleet-wide dispatches/suggest < 1, all
            # SystemExit-gated inside.
            "serve_fleet": bench_serve_fleet(
                m_gateways=3, n_tenants=6, rounds=rounds, q=q,
                n_candidates=1024, fit_steps=8,
            ),
        }
        print(json.dumps(payload))
        return

    from orion_tpu import telemetry as tel
    from orion_tpu.storage.base import DocumentStorage
    from orion_tpu.storage.netdb import DBServer, NetworkDB
    from orion_tpu.tracing import SERVER_EXPERIMENT

    was_enabled = tel.TELEMETRY.enabled
    tel.TELEMETRY.enable()
    db_server = DBServer(port=0)
    host, port = db_server.serve_background()
    net_db = NetworkDB(host=host, port=port)
    try:
        serve_block = bench_serve(
            m_tenants=2, rounds=3, q=8, window=0.4, n_candidates=256,
            fit_steps=4, storage=DocumentStorage(net_db),
        )
        # asha_bo leg (host-tail endgame): two multi-fidelity tenants whose
        # fused-step signatures must still line up — promotions are consumed
        # host-side, only the FRESH points ride the device plan, and the
        # bucket-normalized shapes (q bucket, quantized local_sigma ladder)
        # keep both tenants coalescible.  bench_serve hard-asserts
        # max_width >= 2 inside.
        serve_asha_block = bench_serve(
            m_tenants=2, rounds=4, q=8, window=0.4, n_candidates=128,
            fit_steps=4, storage=DocumentStorage(net_db),
            algorithms={
                "asha_bo": {"n_init": 8, "n_candidates": 128, "fit_steps": 4}
            },
            priors={
                **{f"x{j}": "uniform(0, 1)" for j in range(6)},
                "epochs": "fidelity(1, 9, 3)",
            },
            name_prefix="bench-serve-asha",
        )
        db_server.flush_server_spans(force=True)
        server_spans = DocumentStorage(net_db).fetch_spans(SERVER_EXPERIMENT)
    finally:
        net_db.close()
        db_server.shutdown()
        db_server.server_close()
        if not was_enabled:
            tel.TELEMETRY.disable()
    # The 2-gateway fleet twin of the full run's M=3 leg: kill one member
    # mid-stream — zero lost, bit-identical streams, clean audits, all
    # SystemExit-gated inside bench_serve_fleet.
    serve_fleet_block = bench_serve_fleet(
        m_gateways=2, n_tenants=3, rounds=4, q=8, window=0.4,
        n_candidates=128, fit_steps=4,
    )
    spans = [s for s in tel.TELEMETRY.iter_spans() if s] + list(server_spans)
    trace_path = "bench_serve_trace.json"
    tel.write_chrome_trace(trace_path, spans)
    joined = assert_joined_serve_trace(spans)
    payload = {
        "metric": "serve gateway smoke (distributed trace)",
        "serve": serve_block,
        "serve_asha": serve_asha_block,
        "serve_fleet": serve_fleet_block,
        "serve_trace_file": trace_path,
        "trace": joined,
    }
    print(json.dumps(payload))


def assert_joined_serve_trace(spans):
    """The ISSUE-11 end-to-end join, hard-gated (SystemExit, not assert —
    must hold under ``python -O``): at least one trace_id carries BOTH the
    client's ``serve.client.suggest`` span and the netdb server's
    ``netdb.apply`` span AND is linked by a gateway ``serve.dispatch``
    span; the exported events contain >= 1 bound ``s``/``f`` flow pair."""
    from orion_tpu.telemetry import chrome_trace_events

    by_trace = {}
    for span in spans:
        trace_id = span.get("trace_id")
        if trace_id:
            by_trace.setdefault(trace_id, set()).add(span.get("name"))
    linked = set()
    for span in spans:
        if span.get("name") != "serve.dispatch":
            continue
        for link in span.get("links") or ():
            linked.add((link or {}).get("trace_id"))
    joined = [
        trace_id
        for trace_id, names in by_trace.items()
        if "serve.client.suggest" in names
        and "netdb.apply" in names
        and trace_id in linked
    ]
    if not joined:
        raise SystemExit(
            "distributed serve trace is NOT joined: no trace_id carries "
            "serve.client.suggest + netdb.apply + a serve.dispatch link "
            f"(traces seen: {len(by_trace)}, linked: {len(linked)})"
        )
    events = chrome_trace_events(spans)
    starts = {e["id"] for e in events if e.get("ph") == "s"}
    finishes = {e["id"] for e in events if e.get("ph") == "f"}
    flow_pairs = len(starts & finishes)
    if not flow_pairs:
        raise SystemExit("no cross-process flow events in the serve trace")
    return {"joined_traces": len(joined), "flow_pairs": flow_pairs}


def bench_trace(out_path, rounds=3, q=16):
    """Run a few REAL producer rounds (sqlite storage, speculation-safe
    random search) and one GP suggest pair with the unified telemetry
    registry enabled, then export the process's span ring as a Chrome
    trace-event JSON — the artifact every bench run leaves behind so the
    PR-2 pipelined commit is *visible*: in Perfetto the round's
    ``storage.commit`` span runs concurrently under the open
    ``device.dispatch`` window (speculative suggest in flight while the
    batched register writes).  The GP pair adds the
    ``jax.suggest_step.compile`` (first call, retrace) and
    ``jax.suggest_step.dispatch`` (second call, cache hit) spans.

    A DISTRIBUTED leg then runs the same producer rounds over a loopback
    netdb server, so every round's trace crosses a real wire: the server's
    adopted ``netdb.apply`` spans are fetched back through the
    ``__server__`` channel, merged by trace_id, and the exported file
    carries cross-process flow arrows.  The merged spans feed the
    critical-path attribution (``orion_tpu.tracing``): each round's wall
    time bucketed into client-host / wire / server-host / device — the
    ROADMAP item-2 burn-down number.  Returns ``(path, host_attribution)``.

    Telemetry is enabled ONLY inside this phase, so the timed benches above
    keep measuring the disabled-path cost (the production default)."""
    import os
    import tempfile

    from orion_tpu import telemetry as tel
    from orion_tpu.core.experiment import build_experiment
    from orion_tpu.core.producer import Producer
    from orion_tpu.storage.base import DocumentStorage, create_storage
    from orion_tpu.storage.netdb import DBServer, NetworkDB
    from orion_tpu.tracing import SERVER_EXPERIMENT, summarize_attribution

    def run_rounds(storage, name):
        experiment = build_experiment(
            storage,
            name,
            priors={f"x{i}": "uniform(0, 1)" for i in range(4)},
            algorithms={"random": {"seed": SEED}},
            metadata={"user": "bench"},
        )
        experiment.instantiate(seed=SEED)
        producer = Producer(experiment)
        for _ in range(rounds):
            producer.update()
            producer.produce(q)
        producer._flush_timings(force_metrics=True)

    was_enabled = tel.TELEMETRY.enabled
    tel.TELEMETRY.enable()
    phase_t0 = time.time()
    try:
        with tempfile.TemporaryDirectory(prefix="orion-bench-trace-") as tmpdir:
            storage = create_storage(
                {"type": "sqlite", "path": os.path.join(tmpdir, "trace.sqlite")}
            )
            run_rounds(storage, "bench-trace")
        # Distributed leg: loopback netdb — the round's storage commits
        # carry the trace context over the wire and come back joined.
        server = DBServer(port=0)
        host, port = server.serve_background()
        net_db = NetworkDB(host=host, port=port)
        try:
            run_rounds(DocumentStorage(net_db), "bench-trace-dist")
            server.flush_server_spans(force=True)
            server_spans = DocumentStorage(net_db).fetch_spans(SERVER_EXPERIMENT)
        finally:
            net_db.close()
            server.shutdown()
            server.server_close()
        algo = _make_algo(seed=SEED + 4, n_candidates=256, fit_steps=4)
        rng = np.random.default_rng(SEED + 4)
        X = rng.uniform(size=(16, 6)).astype(np.float32)
        _observe(algo, X, _hartmann6_np(X))
        algo.suggest(8)  # compile -> jax.suggest_step.compile span
        algo.suggest(8)  # cache hit -> jax.suggest_step.dispatch span
        spans = [s for s in tel.TELEMETRY.iter_spans() if s] + list(server_spans)
        # The exported FILE keeps everything the ring holds (earlier phases
        # like the serve leg included — their cross-track flows are part of
        # the artifact); the ATTRIBUTION covers only THIS phase's rounds, so
        # an earlier leg's deliberately-slow coalescing windows cannot skew
        # the round split.
        phase_spans = [s for s in spans if float(s.get("ts") or 0.0) >= phase_t0]
        attribution = summarize_attribution(phase_spans, root_name="producer.round")
        tel.write_chrome_trace(out_path, spans)
    finally:
        if not was_enabled:
            tel.TELEMETRY.disable()
    return out_path, attribution


def bench_device_decomposition():
    """Device time of one fused suggest round at the headline
    shape (two-chain-length subtraction; suggest_bench.py is the full
    instrument and docs/performance.md the published table)."""
    from orion_tpu.benchmarks.suggest_bench import device_seconds

    # Shorter chain/reps than the full instrument: bench.py runs every
    # round and only needs the order of magnitude next to the wall number.
    return device_seconds("hartmann6-q1024", reps=5, k_hi=9) * 1e3


def _json_payload(
    metric,
    value,
    vs_baseline,
    regret,
    anchor_regret,
    wall_ms_per_round,
    device_ms_per_round,
    breakdown_ms,
    storage_ms,
    storage_ops_per_round,
    prewarm=None,
    health=None,
    regret_gate=None,
    compiler=None,
    smoke=False,
):
    """THE output schema — built here for both the full run and --smoke, so
    the smoke test's key assertions actually cover what the full bench
    emits (two hand-built dicts would let drift ship silently)."""
    # Steady-state host tax of one round: every breakdown stage that runs
    # on host (wait_transfer is device execution + transfer; storage_ms is
    # tracked separately — the pipelined commit overlaps it with dispatch;
    # prep_us_saved and dispatch_us_saved are SAVINGS reports, not stages).
    host_ms_per_round = round(
        sum(
            v for k, v in breakdown_ms.items()
            if k not in ("wait_transfer", "storage_ms", "prep_us_saved",
                         "dispatch_us_saved")
            and v is not None
        ),
        3,
    )
    payload = {
        "schema_version": BENCH_SCHEMA_VERSION,
        "metric": metric,
        "value": value,
        "unit": "suggestions/sec",
        # Chrome trace-event JSON of a traced producer-round + GP-suggest
        # phase (bench_trace) — load in Perfetto.
        "trace_file": None,
        "vs_baseline": vs_baseline,
        "regret": regret,
        "anchor_regret": anchor_regret,
        # Decomposition of one q=1024 round (docs/performance.md):
        # wall = device compute + host<->device transfer + host-side
        # transform/decode.
        "wall_ms_per_round": wall_ms_per_round,
        "device_ms_per_round": device_ms_per_round,
        "host_ms_per_round": host_ms_per_round,
        # Per-stage host/device split of one steady-state round
        # (bench_breakdown docstring): everything except wait_transfer is
        # host boundary tax; storage_ms is the stage the pipelined
        # producer commit overlaps with device dispatch.
        "breakdown_ms": breakdown_ms,
        # The storage edge per backend (bench_storage): wall ms of one
        # q-batch registration, and how many backend-level operations
        # (transactions / wire round trips) it cost.  The batched write
        # path keeps ops O(1) regardless of q.
        "storage_ms": storage_ms,
        "storage_ops_per_round": storage_ops_per_round,
        # The pow-2 boundary-crossing contract (bench_prewarm):
        # retraces_after_warm must be 0 — a prewarmed bucket crossing is a
        # jit-cache hit, not a dispatch stall.  None = introspection
        # unavailable (private jax accessor).
        "prewarm": prewarm,
        # Optimization health (orion_tpu.health): per-round regret curve +
        # GP/TR health series of the seed-0 regret scenario.
        "health": health,
        # Multi-seed regret-trajectory gate verdict
        # (orion_tpu.benchmarks.regret_gate vs BENCH_REGRET_BASELINE.json).
        "regret_gate": regret_gate,
        # Compiler-plane digest (orion_tpu.compiler_plane, compiler_block):
        # every XLA compile this run paid with per-plan compile_ms / flops /
        # hbm_bytes / predicted HBM-bound q, and the retrace-attribution
        # totals the smoke gate pins (retraces_attributed == retraces).
        "compiler": compiler,
        # Distributed-trace critical-path split of the traced producer
        # rounds (orion_tpu.tracing, mean ms per round): client-host /
        # wire / server-host / device — stamped by bench_trace.
        "host_attribution": None,
        # Self-diagnosis verdict over the bench's own run (doctor_gate):
        # the summary block plus the hard-gated critical count (--smoke
        # SystemExits on any critical finding).
        "doctor": None,
        "doctor_critical": None,
    }
    if smoke:
        payload["smoke"] = True
    return payload


def bench_history_record(payload, now=None):
    """One payload -> the compact cross-run record appended to
    ``BENCH_history.jsonl``: the headline/trajectory numbers future doctor
    trend rules (and humans) join across runs, without the multi-KB curve
    and trace blocks."""
    gate = payload.get("regret_gate") or {}
    compiler = payload.get("compiler") or {}
    sharded = payload.get("sharded") or {}
    return {
        "schema_version": payload.get("schema_version"),
        "time": time.time() if now is None else now,
        "smoke": bool(payload.get("smoke")),
        "value": payload.get("value"),
        "vs_baseline": payload.get("vs_baseline"),
        "regret": payload.get("regret"),
        "wall_ms_per_round": payload.get("wall_ms_per_round"),
        "device_ms_per_round": payload.get("device_ms_per_round"),
        "host_ms_per_round": payload.get("host_ms_per_round"),
        "storage_ms": payload.get("storage_ms"),
        "regret_gate_pass": gate.get("pass"),
        "doctor_critical": payload.get("doctor_critical"),
        # Compiler-plane columns (orion_tpu.compiler_plane): total compile
        # wall ms, attribution coverage, and the worst plan's HBM footprint
        # — the trend the DX050/DX053 doctor rules will join across runs.
        # Present even when None (a backend without memory_analysis): the
        # smoke hook checks PRESENCE, the attribution gate checks equality.
        "compile_ms_total": compiler.get("compile_ms_total"),
        "retraces_attributed": compiler.get("retraces_attributed"),
        "plan_hbm_bytes_max": compiler.get("plan_hbm_bytes_max"),
        # Sharded q-walk columns (ISSUE 19 satellite): the predicted
        # HBM-bound q and the measured-vs-predicted headroom from the
        # --sharded leg — None on runs without it (or on backends whose
        # memory analysis is unknowable), present always.
        "sharded_hbm_bound_q": sharded.get("hbm_bound_q"),
        "sharded_hbm_headroom": sharded.get("hbm_headroom"),
        # Day-2 storage columns (ISSUE 20): the drained fraction of the
        # keyspace the drain leg moved, and the quorum leg's lost count
        # (ZERO by construction — trending a nonzero here is the alarm).
        # None on runs without the soak legs; smoke pins them non-null.
        "soak_drained_frac": (
            ((payload.get("drain_soak") or {}).get("drain") or {})
            .get("planned") or {}
        ).get("move_fraction"),
        "soak_quorum_lost": (payload.get("quorum_soak") or {}).get(
            "lost_observations"
        ),
    }


def append_bench_history(payload, path=None):
    """Append this run's compact record to the cross-run series.

    ``path`` resolution: explicit argument > ``ORION_TPU_BENCH_HISTORY``
    env > the checked-in ``BENCH_history.jsonl`` next to this file — for
    FULL runs only.  ``--smoke`` appends nowhere by default (tier-1 runs
    it constantly and must not dirty the committed series); point the env
    var somewhere to capture smoke records too.  Returns the path written,
    or None.  Never raises — a read-only checkout must not fail a bench."""
    import os

    if path is None:
        path = os.environ.get("ORION_TPU_BENCH_HISTORY", "").strip()
        if not path:
            if payload.get("smoke"):
                return None
            path = os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "BENCH_history.jsonl"
            )
    try:
        with open(path, "a") as handle:
            handle.write(json.dumps(bench_history_record(payload)) + "\n")
    except OSError:
        return None
    return path


def compiler_block(families=("fused_plan", "stacked"), limit=8):
    """The compiler-plane digest of THIS bench run (orion_tpu
    .compiler_plane): run the pending cost/memory analyses — each an AOT
    ``lower().compile()``, which is exactly why this only happens here, on
    the bench's declared cold path, bounded by ``limit`` with the skipped
    count reported — then return the registry summary with per-plan
    compile_ms / flops / hbm_bytes and the predicted HBM-bound q.  The
    ``retraces``/``retraces_attributed`` totals come from the PROCESS
    telemetry counters, not the registry's own bookkeeping (which is equal
    by construction): the gate's point is catching a jit call site that
    counts ``jax.retraces`` without going through the registry."""
    from orion_tpu import telemetry as tel
    from orion_tpu.compiler_plane import COMPILE_REGISTRY

    analysis = COMPILE_REGISTRY.analyze_all(families=families, limit=limit)
    summary = COMPILE_REGISTRY.summary()
    summary["analysis"] = analysis
    summary["retraces"] = int(tel.TELEMETRY.counter_value("jax.retraces"))
    summary["retraces_attributed"] = int(
        tel.TELEMETRY.counter_value("jax.retraces.attributed")
    )
    summary["retraces_prewarm_covered"] = int(
        tel.TELEMETRY.counter_value("jax.retraces.prewarm_covered")
    )
    return summary


def _check_retrace_attribution(compiler):
    """Every post-warm retrace must be attributed: a ``jax.retraces``
    sample without a ``CompileRegistry.record_retrace`` twin means some
    jit call site books stalls the flight `jax.retrace` event cannot
    explain — the self-diagnosing contract of the compiler plane."""
    retraces = compiler.get("retraces") or 0
    attributed = compiler.get("retraces_attributed") or 0
    if retraces != attributed:
        # Not an assert: the gate must hold under `python -O` too.
        raise SystemExit(
            f"retrace attribution gate failed: {retraces} jax.retraces vs "
            f"{attributed} attributed — a jit call site counts retraces "
            "outside the CompileRegistry (doctor rule DX051)"
        )


def doctor_gate(health_records, hard=False):
    """Self-diagnosis over the bench's own run (orion_tpu.diagnosis): the
    process registry's counters/gauges/histograms + the measured health
    series, run through the full doctor rule catalog.  ZERO critical
    findings is the bar — a bench that paid a retrace storm or exhausted
    a retry policy is not producing numbers worth recording.  ``--smoke``
    hard-fails (SystemExit, holds under ``python -O``); full runs warn.

    Runs BEFORE the seeded-chaos legs in --smoke: those legs inject
    faults on purpose, and a doctor reading them SHOULD complain."""
    import sys

    from orion_tpu.diagnosis import local_snapshot, run_rules

    report = run_rules(local_snapshot(health=health_records))
    if report.count("critical"):
        message = (
            "doctor found critical findings over the bench run:\n"
            + report.format_human()
        )
        if hard:
            # Not an assert: the gate must hold under `python -O` too.
            raise SystemExit("doctor gate failed: " + message)
        print("WARNING: " + message, file=sys.stderr)
    return report


def _assert_health_overhead(breakdown):
    """Health recording must stay under 1% of the steady-state round (the
    ISSUE-7 acceptance bar): one ready-data device read + a small dict."""
    health_ms = breakdown.get("health")
    round_ms = sum(
        v for k, v in breakdown.items()
        if k not in ("storage_ms", "prep_us_saved", "dispatch_us_saved")
        and v is not None
    )
    assert health_ms is not None and round_ms > 0
    assert health_ms <= 0.01 * round_ms, (
        f"health recording costs {health_ms}ms of a {round_ms:.3f}ms round "
        "(>1%) — the packed-GPState read contract is broken"
    )


def main(smoke=False, trace_out="bench_trace.json"):
    if smoke:
        return main_smoke(trace_out=trace_out)
    ours_sps = bench_throughput()
    breakdown = bench_breakdown()
    device_ms = bench_device_decomposition()
    storage_ms, storage_ops = bench_storage()
    breakdown["storage_ms"] = storage_ms["sqlite"]
    _assert_health_overhead(breakdown)
    prewarm = bench_prewarm()
    assert prewarm["retraces_after_warm"] in (None, 0), (
        f"pow-2 boundary crossing paid {prewarm['retraces_after_warm']} "
        "synchronous retrace(s) despite prewarm"
    )

    # Multi-seed regret trajectories: seed 0 replays the historical
    # anchor-parity run; the full set feeds the statistical gate.
    curves = []
    health_records = None
    for seed in GATE_SEEDS:
        curve, records = run_regret_curve(seed)
        curves.append(curve)
        if seed == SEED:
            health_records = records
    ours_regret = curves[GATE_SEEDS.index(SEED)][-1]
    gate = bench_regret_gate(curves)
    assert gate["pass"], (
        "regret gate failed: statistically significant regression vs "
        f"BENCH_REGRET_BASELINE.json — {gate}"
    )

    rng = np.random.default_rng(SEED)
    X0 = rng.uniform(size=(N_INIT, 6)).astype(np.float32)
    y0 = _hartmann6_np(X0)
    anchor_regret, anchor_times = run_anchor_regret(X0, y0)
    anchor_sps = 1.0 / float(np.median(anchor_times))

    assert ours_regret <= anchor_regret * (1.0 + REGRET_TOL) + 1e-9, (
        f"regret parity failed: ours={ours_regret:.6f} "
        f"anchor={anchor_regret:.6f} tol={REGRET_TOL}"
    )
    trace_file, host_attribution = bench_trace(trace_out)
    compiler = compiler_block()
    _check_retrace_attribution(compiler)
    payload = _json_payload(
        metric=(
            "suggestions/sec @ q=1024, Hartmann6 "
            "(public suggest/observe, refit per round)"
        ),
        value=round(ours_sps, 2),
        vs_baseline=round(ours_sps / anchor_sps, 2),
        regret=round(ours_regret, 6),
        anchor_regret=round(anchor_regret, 6),
        wall_ms_per_round=round(1e3 * Q / ours_sps, 2),
        device_ms_per_round=round(device_ms, 2),
        breakdown_ms=breakdown,
        storage_ms=storage_ms,
        storage_ops_per_round=storage_ops,
        prewarm=prewarm,
        health=_health_payload(curves[GATE_SEEDS.index(SEED)], health_records),
        regret_gate=gate,
        compiler=compiler,
    )
    payload["trace_file"] = trace_file
    payload["host_attribution"] = host_attribution
    payload["id_hash"] = bench_id_hash(q=1024)
    doctor_report = doctor_gate(health_records, hard=False)
    payload["doctor"] = doctor_report.summary()
    payload["doctor_critical"] = doctor_report.count("critical")
    _check_host_budget(payload)
    print(json.dumps(payload))
    append_bench_history(payload)


def _host_budget_factor():
    """The wall≈device bar: host tax may be at most FACTOR x device time
    (ROADMAP item 5 tightened the ISSUE-13 2x to 1.25x).  Delegates to
    ``orion_tpu.hostbudget`` — the SAME knob the doctor's DX004 rule and
    ``orion-tpu top``'s ratio column read, so the gates cannot drift;
    ORION_TPU_HOST_BUDGET_FACTOR overrides everywhere at once."""
    from orion_tpu.hostbudget import host_budget_factor

    return host_budget_factor()


def _check_host_budget(payload, hard=False):
    """ROADMAP item-2 gate: steady-state ``host_ms_per_round`` must stay
    within FACTOR x device time.

    Full runs WARN (never fail — the headline numbers still get recorded,
    and the attribution block says where the excess lives).  ``--smoke``
    hard-fails (SystemExit, so the gate holds under ``python -O``): the
    2x target was met by ISSUE 13's vectorized codec + columnar commit,
    and the host-tail endgame (prep token, byte-hash ids) tightened the
    bar to 1.25x; tier-1 must catch a host-tax regression before the
    next full bench run does.  In smoke (no device decomposition phase)
    the device reference is the breakdown's ``wait_transfer`` stage —
    device execution + result transfer of the same measured round."""
    import sys

    factor = _host_budget_factor()
    host = payload.get("host_ms_per_round")
    device = payload.get("device_ms_per_round")
    if not device:
        device = (payload.get("breakdown_ms") or {}).get("wait_transfer")
    if host is None or not device:
        return
    if host > factor * device:
        message = (
            f"host_ms_per_round={host} exceeds the ROADMAP item-5 target of "
            f"{factor}x device time ({device} ms; ORION_TPU_HOST_BUDGET_FACTOR "
            "overrides) — see breakdown_ms and the host_attribution block "
            "for the client-host/wire/server-host/device split"
        )
        if hard:
            # Not an assert: the gate must hold under `python -O` too.
            raise SystemExit("host budget gate failed: " + message)
        print("WARNING: " + message, file=sys.stderr)


def main_chaos(rounds=6, q=8, seed=11):
    """Chaos smoke: producer rounds against fault-injected storage.

    Runs ``rounds`` produce+complete rounds twice — once over a
    FaultyDB-wrapped SQLite store, once over a loopback network server
    behind the TCP fault proxy (with a mid-run connection drop) — under a
    seeded schedule covering every fault class, then prints ONE json line
    with per-round ``storage.retries``/``reconnects``/injected-fault
    counts and the invariant auditor's verdict.  Converging through the
    schedule with zero audit violations IS the check (hard asserts);
    the numbers trend the retry tax across BENCH_* files."""
    import os
    import tempfile

    from orion_tpu import telemetry as tel
    from orion_tpu.storage.base import DocumentStorage
    from orion_tpu.storage.faults import FaultProxy, FaultSchedule, FaultyDB
    from orion_tpu.storage.sqlitedb import SQLiteDB
    from orion_tpu.testing import drive_chaos_experiment

    retry = {"max_attempts": 6, "base_delay": 0.005, "max_delay": 0.05}

    def run_rounds(storage, name, proxy=None):
        # ONE chaos driver shared with tests/functional/test_chaos.py
        # (reserve -> complete with transient backoff, bounded by a
        # convergence deadline, sweep + audit epilogue) so the bench's
        # smoke and the suite's assertions cannot drift apart.
        _exp, report = drive_chaos_experiment(
            storage,
            name=f"bench-chaos-{name}",
            priors={f"x{i}": "uniform(0, 1)" for i in range(4)},
            max_trials=rounds * q,
            pool_size=q,
            seed=seed,
            proxy=proxy,
            drop_every=3 if proxy is not None else 0,
            deadline=180.0,
        )
        return report

    was_enabled = tel.TELEMETRY.enabled
    tel.TELEMETRY.enable()
    payload = {"metric": "chaos smoke", "rounds": rounds, "q": q, "backends": {}}
    try:
        with tempfile.TemporaryDirectory(prefix="orion-bench-chaos-") as tmpdir:
            # --- sqlite through FaultyDB -----------------------------------
            schedule = FaultSchedule(
                seed=seed,
                plan={3: "error", 7: "latency", 11: "reply_lost", 15: "kill"},
                rates={"error": 0.02, "latency": 0.02},
                latency=0.002,
                max_faults=12,
            )
            inner = SQLiteDB(os.path.join(tmpdir, "chaos.sqlite"))
            storage = DocumentStorage(FaultyDB(inner, schedule), retry=retry)
            before = tel.TELEMETRY.counter_value("storage.retries")
            report = run_rounds(storage, "sqlite")
            retries = tel.TELEMETRY.counter_value("storage.retries") - before
            assert report.ok, report.summary()
            assert retries > 0, "faults fired but nothing retried"
            payload["backends"]["sqlite"] = {
                "storage_retries_per_round": round(retries / rounds, 2),
                "faults_injected": dict(schedule.injected),
                "audit_violations": len(report.violations),
            }
            inner.close()

            # --- network through the fault proxy ---------------------------
            from orion_tpu.storage.netdb import DBServer, NetworkDB

            server = DBServer(port=0)
            server.db = FaultyDB(
                server.db,
                FaultSchedule(seed=seed + 1, rates={"error": 0.02}, max_faults=8),
            )
            host, port = server.serve_background()
            proxy = FaultProxy(host, port)
            phost, pport = proxy.serve_background()
            client = NetworkDB(host=phost, port=pport, timeout=10.0, idle_probe=0.05)
            net_storage = DocumentStorage(client, retry=retry)
            before = tel.TELEMETRY.counter_value("storage.retries")
            try:
                report = run_rounds(net_storage, "network", proxy=proxy)
                retries = tel.TELEMETRY.counter_value("storage.retries") - before
                assert report.ok, report.summary()
                payload["backends"]["network"] = {
                    "storage_retries_per_round": round(retries / rounds, 2),
                    "reconnects_per_round": round(client.reconnects / rounds, 2),
                    "faults_injected": dict(server.db.faults_injected),
                    "proxy_drops": proxy.connections_dropped,
                    "audit_violations": len(report.violations),
                }
                assert client.reconnects >= 1
            finally:
                client.close()
                proxy.stop()
                server.shutdown()
                server.server_close()
    finally:
        if not was_enabled:
            tel.TELEMETRY.disable()
    print(json.dumps(payload))


def run_soak(n_workers=1000, n_experiments=24, trials_per_worker=3,
             n_routers=32, replicas=2, periodic_chaos=True, deadline=600.0,
             kill_primary=True):
    """The sharded control-plane load harness (ROADMAP item 3): drive
    ``n_workers`` simulated workers through consistent-hash routers
    against an in-process 3-shard x ``replicas``-replica topology of REAL
    netdb servers, under fault-proxy reconnect storms/partitions, a
    scripted mid-run shard restart, a replica kill — and, with
    ``kill_primary`` (the ISSUE-14 promotion leg), a PERMANENT primary
    loss on shard 0 that the router fleet must heal by electing the
    caught-up replica itself.  Hard-asserts the pass bar (zero lost
    observations, clean audits through the router AND on every shard,
    chaos signals counted, >= 1 automatic promotion with no manual
    restart) and returns the summary block for the payload.  SystemExit,
    not assert: the gate must hold under ``python -O`` too."""
    import tempfile

    from orion_tpu import telemetry as tel
    from orion_tpu.storage.soak import SoakTopology, drive_soak

    was_enabled = tel.TELEMETRY.enabled
    tel.TELEMETRY.enable()
    try:
        with tempfile.TemporaryDirectory(prefix="orion-bench-soak-") as tmpdir:
            topo = SoakTopology(
                n_shards=3, replicas=replicas, persist_dir=tmpdir
            )

            def chaos_once(storages):
                from orion_tpu.storage.soak import busiest_shard

                # Kill the BUSIEST shard's primary (the one the ring gave
                # the most experiments): the promotion must heal a shard
                # under live write load, not an idle corner.
                victim = (
                    busiest_shard(topo, storages[0].db, n_experiments)
                    if kill_primary
                    else None
                )
                topo.drop_all()
                restart_index = next(
                    i for i in range(len(topo.shards)) if i != victim
                )
                topo.shards[restart_index].restart_primary()
                # Replica 0 of (nearly) every shard dies so the read
                # path's failover leg fires regardless of where the ring
                # placed the experiments; the victim keeps its replicas —
                # it is about to lose its PRIMARY instead.
                for shard in topo.shards:
                    if shard.index == victim:
                        continue
                    shard.kill_replica(0)
                if kill_primary:
                    # The promotion leg: wait until a replica holds the
                    # full position (replication is async), then kill the
                    # primary for good.  No restart — the routers must
                    # elect the survivor on their own.
                    topo.shards[victim].kill_primary()

            try:
                result = drive_soak(
                    topo,
                    n_workers=n_workers,
                    n_experiments=n_experiments,
                    trials_per_worker=trials_per_worker,
                    n_routers=n_routers,
                    chaos=periodic_chaos,
                    chaos_period=1.0,
                    mid_hook=chaos_once,
                    deadline=deadline,
                )
            finally:
                topo.stop()
    finally:
        if not was_enabled:
            tel.TELEMETRY.disable()
    summary = result.summary()
    if result.lost_observations != 0:
        raise SystemExit(f"soak LOST observations: {summary}")
    if not result.audits_clean:
        raise SystemExit(f"soak audits dirty: {summary}")
    if sum(result.completed_per_shard.values()) != result.completed:
        raise SystemExit(f"router view != sum of shards: {summary}")
    if result.restarts < 1 or result.failovers < 1 or result.reconnects < 1:
        raise SystemExit(f"soak chaos signals never fired: {summary}")
    if kill_primary and result.promotions < 1:
        raise SystemExit(
            f"primary killed but NO automatic promotion happened: {summary}"
        )
    summary["trials_per_second"] = (
        round(result.completed / result.duration_s, 1)
        if result.duration_s else None
    )
    return summary


def run_rebalance_soak(n_workers=200, n_experiments=16, trials_per_worker=3,
                       n_routers=8, deadline=300.0):
    """The rebalance-mid-soak leg (ISSUE 14): a live topology GROWS by one
    shard at the worker barrier, every router retargets in place, and
    ``db rebalance``'s migrator moves ~1/N of the experiments — byte-
    identical copies verified doc by doc, clean destination audits, an
    atomic placement flip, source deletion — before the workers resume
    and finish on the new ring.  Hard gates: >= 1 experiment moved, the
    moved fraction stays near 1/N, zero lost observations, clean audits
    on EVERY shard (source and destination included)."""
    import tempfile

    from orion_tpu import telemetry as tel
    from orion_tpu.storage.soak import (
        SoakTopology,
        drive_soak,
        grow_and_rebalance,
    )

    was_enabled = tel.TELEMETRY.enabled
    tel.TELEMETRY.enable()
    outcome = {}
    try:
        with tempfile.TemporaryDirectory(prefix="orion-bench-rebal-") as tmpdir:
            topo = SoakTopology(n_shards=3, replicas=1, persist_dir=tmpdir)

            def rebalance_hook(storages):
                outcome.update(grow_and_rebalance(topo, storages))

            try:
                result = drive_soak(
                    topo,
                    n_workers=n_workers,
                    n_experiments=n_experiments,
                    trials_per_worker=trials_per_worker,
                    n_routers=n_routers,
                    chaos=False,
                    mid_hook=rebalance_hook,
                    deadline=deadline,
                )
            finally:
                topo.stop()
    finally:
        if not was_enabled:
            tel.TELEMETRY.disable()
    summary = result.summary()
    summary["rebalance"] = outcome
    if not outcome.get("executed"):
        raise SystemExit(f"rebalance never executed: {summary}")
    planned = outcome["planned"]
    if planned["moves"] < 1:
        raise SystemExit(f"rebalance moved NOTHING: {summary}")
    n_shards = outcome["n_shards"]
    if planned["move_fraction"] > 2.5 / n_shards:
        raise SystemExit(
            f"rebalance moved far more than ~1/N of the keyspace: {summary}"
        )
    if result.lost_observations != 0:
        raise SystemExit(f"rebalance soak LOST observations: {summary}")
    if not result.audits_clean:
        raise SystemExit(f"rebalance soak audits dirty: {summary}")
    if sum(result.completed_per_shard.values()) != result.completed:
        raise SystemExit(f"router view != sum of shards: {summary}")
    return summary


def run_drain_soak(n_workers=200, n_experiments=16, trials_per_worker=3,
                   n_routers=8, deadline=300.0):
    """The drain-mid-soak leg (ISSUE 20): at the worker barrier the
    busiest shard is DRAINED — every resident experiment migrated to its
    post-removal ring home by the crash-resumable migrator
    (storage/drain.py), zero residents verified — then removed from every
    live router's topology and stopped; the workers resume and finish on
    the shrunk ring.  Hard gates: >= 1 experiment moved, the moved
    fraction within 2x of the drained shard's ring share, ZERO residents
    left, zero lost observations, clean audits on every surviving
    shard."""
    import tempfile

    from orion_tpu import telemetry as tel
    from orion_tpu.storage.soak import (
        SoakTopology,
        drain_and_remove,
        drive_soak,
    )

    was_enabled = tel.TELEMETRY.enabled
    tel.TELEMETRY.enable()
    outcome = {}
    try:
        with tempfile.TemporaryDirectory(prefix="orion-bench-drain-") as tmpdir:
            topo = SoakTopology(n_shards=3, replicas=1, persist_dir=tmpdir)

            def drain_hook(storages):
                outcome.update(drain_and_remove(topo, storages))

            try:
                result = drive_soak(
                    topo,
                    n_workers=n_workers,
                    n_experiments=n_experiments,
                    trials_per_worker=trials_per_worker,
                    n_routers=n_routers,
                    chaos=False,
                    mid_hook=drain_hook,
                    deadline=deadline,
                )
            finally:
                topo.stop()
    finally:
        if not was_enabled:
            tel.TELEMETRY.disable()
    summary = result.summary()
    summary["drain"] = outcome
    if not outcome.get("executed"):
        raise SystemExit(f"drain never executed: {summary}")
    planned = outcome["planned"]
    if planned["moves"] < 1:
        raise SystemExit(f"drain moved NOTHING: {summary}")
    share = outcome["ring_share"]
    if planned["move_fraction"] > 2.0 * share:
        raise SystemExit(
            f"drain moved {planned['move_fraction']:.1%} of the experiments "
            f"vs a {share:.1%} ring share (over the 2x bound): {summary}"
        )
    if outcome.get("residual"):
        raise SystemExit(
            f"drained shard still holds {outcome['residual']} "
            f"experiment(s): {summary}"
        )
    if result.lost_observations != 0:
        raise SystemExit(f"drain soak LOST observations: {summary}")
    if not result.audits_clean:
        raise SystemExit(f"drain soak audits dirty: {summary}")
    if sum(result.completed_per_shard.values()) != result.completed:
        raise SystemExit(f"router view != sum of shards: {summary}")
    return summary


def run_quorum_soak(n_workers=200, n_experiments=16, trials_per_worker=3,
                    n_routers=8, deadline=300.0):
    """The quorum kill -9 leg (ISSUE 20): a 3-shard x 2-replica topology
    serving with a quorum floor of 1 — synchronous collections
    (experiments/trials/placement) acknowledge only after a replica holds
    the write — takes a PERMANENT primary kill on the busiest shard with
    **no replica catch-up wait** (``wait_catchup=False`` — the exact wait
    the async contract needed to be lossless before this PR).  Routers
    elect the max-seq replica; because every acknowledged sync write was
    replica-acked first, the winner holds all of them: zero lost BY
    CONSTRUCTION, which is the hard gate."""
    import tempfile

    from orion_tpu import telemetry as tel
    from orion_tpu.storage.soak import (
        SoakTopology,
        busiest_shard,
        drive_soak,
    )

    was_enabled = tel.TELEMETRY.enabled
    tel.TELEMETRY.enable()
    try:
        with tempfile.TemporaryDirectory(prefix="orion-bench-quorum-") as tmpdir:
            # replicas=2 is load-bearing with quorum=1: promotion removes
            # one member from the replica set (the winner) and the old
            # primary is dead — the ONE remaining replica is what keeps
            # the promoted primary's sync writes able to meet the floor.
            topo = SoakTopology(
                n_shards=3, replicas=2, persist_dir=tmpdir, quorum=1
            )

            def kill_once(storages):
                victim = busiest_shard(topo, storages[0].db, n_experiments)
                topo.shards[victim].kill_primary(wait_catchup=False)

            try:
                result = drive_soak(
                    topo,
                    n_workers=n_workers,
                    n_experiments=n_experiments,
                    trials_per_worker=trials_per_worker,
                    n_routers=n_routers,
                    chaos=False,
                    mid_hook=kill_once,
                    deadline=deadline,
                )
            finally:
                topo.stop()
    finally:
        if not was_enabled:
            tel.TELEMETRY.disable()
    summary = result.summary()
    summary["quorum"] = 1
    summary["wait_catchup"] = False
    if result.primary_kills != 1:
        raise SystemExit(f"quorum soak never killed a primary: {summary}")
    if result.promotions < 1:
        raise SystemExit(
            f"primary killed but NO automatic promotion happened: {summary}"
        )
    if result.lost_observations != 0:
        raise SystemExit(
            f"quorum soak LOST observations despite the ack floor: {summary}"
        )
    if not result.audits_clean:
        raise SystemExit(f"quorum soak audits dirty: {summary}")
    if sum(result.completed_per_shard.values()) != result.completed:
        raise SystemExit(f"router view != sum of shards: {summary}")
    return summary


def main_soak(n_workers=1000):
    """``bench.py --soak [--workers N]``: the 1000-worker headline run +
    the rebalance-, drain- and quorum-mid-soak legs."""
    summary = run_soak(n_workers=n_workers)
    rebalance = run_rebalance_soak(n_workers=min(200, n_workers))
    drain = run_drain_soak(n_workers=min(200, n_workers))
    quorum = run_quorum_soak(n_workers=min(200, n_workers))
    payload = {
        "metric": (
            f"sharded soak: {n_workers} workers, 3 shards x 2 replicas, "
            "storms+partition+restart+kill-primary(promotion)+rebalance"
            "+drain+quorum-kill"
        ),
        "n_workers": n_workers,
        "soak": summary,
        "rebalance_soak": rebalance,
        "drain_soak": drain,
        "quorum_soak": quorum,
    }
    print(json.dumps(payload))


def bench_sharded(smoke=False):
    """``--sharded``: the multichip suggest data path, measured.

    Must run in a process whose backend already exposes the mesh devices
    (chips, or the virtual CPU mesh of ``--smoke``'s child, whose payload
    says ``platform: cpu``).  Three blocks, one JSON payload:

    - ``parity``: one fused round on the full mesh vs the SAME plan
      forced single-device — the same rows selected, equal to within
      ``sharding.ROWS_ATOL`` (``sharding.compare_rows``), and the GP
      state within ``sharding.STATE_RTOL`` (``sharding.compare_states``).
    - ``placement``: per-device byte fractions of a sharded candidate pool
      (``sharding.placement_fractions``) — every mesh device must hold a
      nonzero shard, or sharding has silently regressed to one chip.
    - ``q_curve``: suggestions/sec sharded vs single-device across growing
      q (the candidate pool scales with q).  On hosts without at least one
      core/chip per mesh device (the CPU virtual mesh: N devices on one
      core) the sharded/single ratio is reported but carries no speedup
      meaning — ``platform`` says which reading applies.
    """
    import jax

    from orion_tpu.algo.sharding import (
        compare_rows,
        compare_states,
        placement_fractions,
        shard_candidates,
    )
    from orion_tpu.algo.tpu_bo import FusedPlan, run_fused_plan
    from orion_tpu.space.dsl import build_space

    n_dev = jax.device_count()
    if n_dev < 2:
        raise SystemExit(
            "bench.py --sharded needs a multi-device backend "
            f"(this process has {n_dev} {jax.devices()[0].platform} device)"
        )
    d = 6
    if smoke:
        qs, n_candidates, fit_steps, n_hist = (8, 32), 512, 8, 24
    else:
        qs, n_candidates, fit_steps, n_hist = (1024, 4096, 16384), 16384, 40, 130
    space = build_space({f"x{i}": "uniform(0, 1)" for i in range(d)})
    rng = np.random.default_rng(SEED + 3)
    X = rng.uniform(size=(n_hist, d)).astype(np.float32)
    y = _hartmann6_np(X)

    def fresh_algo(use_mesh):
        from orion_tpu.algo.base import create_algo

        algo = create_algo(
            space,
            {"tpu_bo": {"n_init": N_INIT, "n_candidates": n_candidates,
                        "fit_steps": fit_steps, "prewarm": False,
                        "use_mesh": use_mesh}},
            seed=SEED + 3,
        )
        _observe(algo, X, y)
        return algo

    # --- parity leg -------------------------------------------------------
    q0 = qs[0]
    plan = fresh_algo(True).fused_step_plan(q0)
    rows_sharded, state_sharded = run_fused_plan(plan)
    single = FusedPlan(
        plan.signature, plan.arrays, dict(plan.statics, mesh=None), plan.num
    )
    rows_single, state_single = run_fused_plan(single)
    parity = compare_rows(rows_sharded, rows_single)
    parity["state"] = compare_states(state_sharded, state_single)
    parity["ok"] = parity["ok"] and parity["state"]["ok"]

    # --- placement leg ----------------------------------------------------
    mesh = plan.statics["mesh"]
    pool = shard_candidates(
        np.zeros((n_candidates, d), dtype=np.float32), mesh
    )
    fractions = placement_fractions(pool)
    placement = {str(dev): round(frac, 4) for dev, frac in sorted(fractions.items())}
    devices_holding = sum(1 for frac in fractions.values() if frac > 0)

    # --- q-scaling curve --------------------------------------------------
    def rounds_per_sec(algo, q, reps):
        algo._suggest_cube(q)  # compile + warm
        t0 = time.perf_counter()
        for _ in range(reps):
            np.asarray(algo._suggest_cube(q))
        return reps * q / (time.perf_counter() - t0)

    reps = 2 if smoke else 3
    sharded_algo, single_algo = fresh_algo(True), fresh_algo(False)
    q_curve = []
    for q in qs:
        sps_sharded = rounds_per_sec(sharded_algo, q, reps)
        sps_single = rounds_per_sec(single_algo, q, reps)
        q_curve.append({
            "q": q,
            "sharded_sps": round(sps_sharded, 1),
            "single_sps": round(sps_single, 1),
            "ratio": round(sps_sharded / sps_single, 3),
        })

    # --- q-walk toward the predicted HBM bound (ROADMAP item 1 tail) -----
    # Double q from the curve's floor until the NEXT doubling would cross
    # the compiler plane's predict_hbm_bound_q extrapolation (or an OOM
    # guard trips, or the step cap on unknown-capacity backends).  Each
    # step's footprint comes from the sanctioned lowered_analysis_fn path
    # — a bench IS a declared cold path, the AOT second compile is fine.
    from orion_tpu.algo.tpu_bo import _suggest_step
    from orion_tpu.compiler_plane import (
        device_hbm_capacity,
        lowered_analysis_fn,
        predict_hbm_bound_q,
    )

    capacity = device_hbm_capacity()
    walk_algo = fresh_algo(True)
    q_walk, bound_q = [], None
    walk_q = qs[0]
    for _ in range(3 if smoke else 6):
        plan = walk_algo.fused_step_plan(walk_q)
        analysis = (
            lowered_analysis_fn(_suggest_step, plan.arrays, plan.statics)()
            or {}
        )
        hbm_bytes = analysis.get("hbm_bytes")
        predicted = predict_hbm_bound_q({"q": walk_q}, hbm_bytes, capacity)
        try:
            t0 = time.perf_counter()
            np.asarray(run_fused_plan(plan)[0])
            wall_ms, oom = round((time.perf_counter() - t0) * 1e3, 2), False
        except Exception:  # the OOM guard: record the wall and stop
            wall_ms, oom = None, True
        q_walk.append({
            "q": walk_q,
            "plan_hbm_bytes": hbm_bytes,
            "predicted_hbm_bound_q": predicted,
            "wall_ms": wall_ms,
            "oom": oom,
        })
        if oom:
            break
        if predicted is not None:
            bound_q = predicted
            if 2 * walk_q >= predicted:
                break  # the next doubling would cross the predicted bound
        walk_q *= 2
    measured = [row["q"] for row in q_walk if not row["oom"]]
    walk_max_q = max(measured) if measured else None
    # Measured-vs-predicted headroom: how many x of q the device still has
    # before the plan footprint fills HBM (None when capacity or the
    # memory analysis is unknowable — CPU interop backends).
    hbm_headroom = (
        round(bound_q / walk_max_q, 2) if bound_q and walk_max_q else None
    )

    return {
        "metric": f"sharded suggest over {n_dev} devices"
                  + (" (SMOKE)" if smoke else ""),
        "devices": n_dev,
        # "cpu" = virtual devices sharing the host's cores: the q_curve
        # ratio then says nothing about chips, and nothing here is a
        # chip result.
        "platform": jax.devices()[0].platform,
        "parity": parity,
        "placement": placement,
        "devices_holding_shards": devices_holding,
        "q_curve": q_curve,
        "q_walk": q_walk,
        "q_walk_max_q": walk_max_q,
        "hbm_capacity_bytes": capacity,
        "hbm_bound_q": bound_q,
        "hbm_headroom": hbm_headroom,
        "smoke": smoke,
    }


def main_sharded(smoke=False):
    """``bench.py --sharded``: run :func:`bench_sharded` on this process's
    devices (it fails on a one-device backend — never a CPU stand-in)."""
    payload = bench_sharded(smoke=smoke)
    if smoke:
        _assert_sharded_smoke(payload)
    print(json.dumps(payload))


def _sharded_subprocess(smoke, n_devices=8, timeout=900.0):
    """Run ``bench.py --sharded`` in a child process under the virtual
    CPU mesh and return its parsed payload (``platform: cpu`` — a schema
    and parity check, never a chip result).  The ``--smoke`` sharded leg;
    hard-asserts are applied in the CHILD, where the arrays live."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    cmd = [sys.executable, os.path.abspath(__file__), "--sharded"]
    if smoke:
        cmd.append("--smoke")
    proc = subprocess.run(
        cmd, env=env, capture_output=True, text=True, timeout=timeout
    )
    if proc.returncode != 0:
        raise SystemExit(
            "sharded leg failed in the virtual-mesh child:\n"
            + proc.stdout[-2000:] + proc.stderr[-4000:]
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _assert_sharded_smoke(payload):
    """The --smoke sharded leg's hard gate: parity (same selection, rows
    within ``ROWS_ATOL``, GP state within ``STATE_RTOL``) or fail, and every virtual device holding a
    nonzero candidate shard.  SystemExit, not assert: the gate must hold
    under ``python -O`` too."""
    if not payload.get("parity", {}).get("ok"):
        raise SystemExit(
            "sharded smoke: sharded round drifts from single-device "
            f"— {payload.get('parity')}"
        )
    if payload.get("devices_holding_shards") != payload.get("devices"):
        raise SystemExit(
            "sharded smoke: candidate pool not spread over every device "
            f"— {payload.get('placement')}"
        )


def lint_preflight():
    """Self-lint the tree before timing anything: bench numbers taken on a
    contract-violating tree (a host sync inside the fused step, a storage
    op off the retry policy) are not numbers worth recording.  Hard-fails
    with the full findings list; returns the violation count (0 when the
    gate passes) for the emitted payload."""
    import os

    import orion_tpu
    from orion_tpu.analysis import format_human, run_lint

    paths = [
        os.path.dirname(os.path.abspath(orion_tpu.__file__)),
        os.path.abspath(__file__),
    ]
    diagnostics = run_lint(paths)
    if diagnostics:
        # Not an assert: the gate must hold under `python -O` too.
        raise SystemExit(
            "lint preflight failed — fix the tree before benching:\n"
            + format_human(diagnostics)
        )
    return len(diagnostics)


def main_smoke(trace_out="bench_trace.json"):
    """Tiny-n schema smoke: the same JSON line shape in seconds instead of
    minutes — no regret parity, no sklearn anchor, no device
    decomposition.  The tier-1 bench smoke test runs ``bench.py --smoke``
    and asserts the breakdown/storage keys AND the emitted trace file's
    span names, so bench schema drift (a renamed stage, a dropped counter,
    a broken trace export) is caught by the unit suite instead of the next
    full bench run."""
    lint_violations = lint_preflight()
    q = 32
    algo = _make_algo(seed=SEED + 2, n_candidates=512, fit_steps=8)
    # rounds=3, not 1: the hard host-budget gate below keys off these
    # stage MEDIANS, and a single measured round lets one scheduling
    # hiccup on a loaded machine fail the gate (observed ~1/6 runs with
    # rounds=1); three rounds vote the outlier out for ~2 rounds of
    # extra tiny-q work.
    breakdown = bench_breakdown(rounds=3, q=q, algo=algo, n_hist=20)
    storage_ms, storage_ops = bench_storage(q=64, rounds=1)
    breakdown["storage_ms"] = storage_ms["sqlite"]
    _assert_health_overhead(breakdown)
    # Trial-identity gate (host-tail endgame): the cube_hash scheme must
    # beat the per-trial repr+md5 path by >= 4x at the bench batch size,
    # and stay collision-free over the batch.
    id_hash = bench_id_hash(q=1024)
    if not id_hash["distinct_ok"] or id_hash["speedup"] < 4:
        # Not an assert: the gate must hold under `python -O` too.
        raise SystemExit(
            "id-hash gate failed: cube_hash must be >= 4x faster than md5 "
            f"at q={id_hash['q']} and collision-free — {id_hash}"
        )
    prewarm = bench_prewarm(q=8)
    assert prewarm["retraces_after_warm"] in (None, 0), (
        f"pow-2 boundary crossing paid {prewarm['retraces_after_warm']} "
        "synchronous retrace(s) despite prewarm"
    )
    # Tiny-n health payload: a real (if short) GP regret trajectory with
    # per-round health records — the schema the full bench emits.
    curve, health_records = run_regret_curve(
        SEED + 2,
        budget=48,
        q=16,
        algo_kwargs={"n_candidates": 512, "fit_steps": 8},
    )
    assert health_records and health_records[-1].get("gp_mll") is not None, (
        "smoke health records lost their GP fields"
    )
    # Gate machinery check at tiny n: the committed baseline must pass
    # against itself (the full bench compares real re-measured curves).
    gate = bench_regret_gate([list(c) for c in _baseline_curves()])
    gate["mode"] = "baseline-self"
    assert gate["pass"], f"committed regret baseline fails its own gate: {gate}"
    # Tiny serve leg (orion_tpu.serve): 2 tenants, full producer stack over
    # one in-process gateway — coalesce width >= 2, device dispatches per
    # suggest < 1, and clean audits are hard-asserted inside.  The leg runs
    # UNDER the runtime concurrency sanitizer (orion-tpu tsan): instrumented
    # lock/event shims + vector-clock race detection over the gateway's
    # annotated shared cells, with a seeded interleaving explorer perturbing
    # the dispatcher's schedules — the payload's `tsan_violations: 0` is a
    # hard assert, the dynamic twin of lint_preflight's static gate.
    from orion_tpu.analysis.sanitizer import TSAN

    # The whole bench may itself be running under `orion-tpu tsan` (env
    # instrumentation from process start): then the outer owner keeps the
    # patches and we assert on a snapshot instead of fighting over enable.
    tsan_owned = not TSAN.enabled
    if tsan_owned:
        TSAN.enable(seed=0)
    try:
        serve_block = bench_serve(
            m_tenants=2, rounds=4, q=8, window=0.4, n_candidates=128, fit_steps=4
        )
    finally:
        tsan_report = TSAN.disable() if tsan_owned else TSAN.snapshot_report()
    if tsan_report.violation_count():
        # Not an assert: the gate must hold under `python -O` too.
        raise SystemExit(
            "serve leg failed the concurrency sanitizer:\n"
            + tsan_report.format_human()
        )
    # Self-diagnosis gate, BEFORE the seeded-chaos legs below (they inject
    # faults by design — a doctor reading them should complain): zero
    # critical findings over the healthy phases' registry + health series.
    doctor_report = doctor_gate(health_records, hard=True)
    # Tiny sharded-soak leg (storage/shard.py + soak.py): 8 workers over a
    # real 3-shard x 1-replica topology with the scripted storm + shard
    # restart + replica kill + PERMANENT shard-0 primary kill — run_soak
    # hard-asserts zero lost observations, clean audits on every shard,
    # that the chaos signals (restart, failover, reconnects) actually
    # fired, and that >= 1 AUTOMATIC replica promotion healed the killed
    # shard with no human in the loop.
    soak_block = run_soak(
        n_workers=8, n_experiments=4, trials_per_worker=4, n_routers=2,
        replicas=1, periodic_chaos=False, deadline=120.0,
    )
    # Tiny rebalance-mid-soak leg: the topology grows by one shard at the
    # worker barrier, the migrator moves ~1/N of the experiments (byte-
    # identical, audited), workers finish on the new ring — zero lost.
    rebalance_block = run_rebalance_soak(
        n_workers=8, n_experiments=8, trials_per_worker=4, n_routers=2,
        deadline=120.0,
    )
    # Tiny drain-mid-soak leg (ISSUE 20): the busiest shard is emptied by
    # the crash-resumable migrator and removed mid-run — zero residents,
    # zero lost, moved fraction within 2x of its ring share.
    drain_block = run_drain_soak(
        n_workers=8, n_experiments=8, trials_per_worker=4, n_routers=2,
        deadline=120.0,
    )
    # Tiny quorum kill -9 leg (ISSUE 20): 2 replicas under a quorum floor
    # of 1, permanent busiest-primary kill with NO replica catch-up wait —
    # zero lost by construction.
    quorum_block = run_quorum_soak(
        n_workers=8, n_experiments=4, trials_per_worker=4, n_routers=2,
        deadline=120.0,
    )
    trace_file, host_attribution = bench_trace(trace_out)
    # Smoke's round decomposition: the breakdown's wait_transfer stage IS
    # the measured device window (execution + result transfer), and the
    # wall is the full stage sum — so the appended history record carries
    # real host/device/storage columns even for smoke runs, keeping the
    # host/device ratio trendable across the whole series.
    # Compiler-plane digest + hard attribution gate: every jax.retraces
    # sample this run counted must have a CompileRegistry attribution twin
    # (the analyze pass is the bench's declared cold path for the AOT
    # second compiles).
    compiler = compiler_block()
    _check_retrace_attribution(compiler)
    smoke_device_ms = round(breakdown["wait_transfer"], 3)
    smoke_wall_ms = round(
        sum(
            v for k, v in breakdown.items()
            if k not in ("storage_ms", "prep_us_saved", "dispatch_us_saved")
            and v is not None
        ),
        3,
    )
    payload = _json_payload(
        metric=(
            f"SMOKE (q={q}): schema check only — run without "
            "--smoke for the headline numbers"
        ),
        value=None,
        vs_baseline=None,
        regret=None,
        anchor_regret=None,
        wall_ms_per_round=smoke_wall_ms,
        device_ms_per_round=smoke_device_ms,
        breakdown_ms=breakdown,
        storage_ms=storage_ms,
        storage_ops_per_round=storage_ops,
        prewarm=prewarm,
        health=_health_payload(curve, health_records),
        regret_gate=gate,
        compiler=compiler,
        smoke=True,
    )
    payload["trace_file"] = trace_file
    payload["host_attribution"] = host_attribution
    payload["lint_violations"] = lint_violations
    payload["tsan_violations"] = tsan_report.violation_count()
    payload["serve"] = serve_block
    payload["soak"] = soak_block
    payload["rebalance_soak"] = rebalance_block
    payload["drain_soak"] = drain_block
    payload["quorum_soak"] = quorum_block
    payload["doctor"] = doctor_report.summary()
    payload["doctor_critical"] = doctor_report.count("critical")
    # Sharded leg (ISSUE 16): the multichip suggest path under the 8-way
    # virtual CPU mesh, in a CHILD process (XLA_FLAGS must precede backend
    # init; JAX_PLATFORMS=cpu keeps it off any chip this process holds).
    # Its payload says platform: cpu — a schema/parity check, not a chip
    # result.  The child hard-asserts parity vs single-device and a
    # nonzero candidate shard on EVERY virtual device before printing its
    # payload; re-check both here so a child drift fails THIS gate too.
    payload["sharded"] = _sharded_subprocess(smoke=True)
    _assert_sharded_smoke(payload["sharded"])
    payload["id_hash"] = id_hash
    # Hard wall-=-device gate (ISSUE 13, tightened to 1.25x by the
    # host-tail endgame): smoke fails loudly on host-tax regressions
    # instead of warning into a log nobody reads.
    _check_host_budget(payload, hard=True)
    # The cross-run record must carry the round decomposition: a smoke
    # run that silently dropped host/device/storage columns would leave
    # the BENCH_history series untrendable for the doctor's rules.
    record = bench_history_record(payload)
    missing = [
        k for k in ("host_ms_per_round", "device_ms_per_round", "storage_ms")
        if not record.get(k)
    ]
    # Compiler-plane columns: PRESENCE check (`in`), not truthiness — a
    # backend without memory_analysis legitimately reports None for the
    # HBM column, but the key itself going missing is schema drift.
    missing += [
        k
        for k in (
            "compile_ms_total", "retraces_attributed", "plan_hbm_bytes_max"
        )
        if k not in record
    ]
    # Day-2 soak columns: hard non-null (`is None`, not truthiness — the
    # quorum leg's lost count is LEGITIMATELY 0): a smoke run just ran
    # both legs, so a None here means the record builder lost the wiring.
    missing += [
        k
        for k in ("soak_drained_frac", "soak_quorum_lost")
        if record.get(k) is None
    ]
    if missing:
        # Not an assert: the gate must hold under `python -O` too.
        raise SystemExit(
            f"bench history record dropped round-decomposition fields: {missing}"
        )
    print(json.dumps(payload))
    append_bench_history(payload)


if __name__ == "__main__":
    import sys

    argv = sys.argv[1:]
    out = "bench_trace.json"
    if "--trace-out" in argv:
        at = argv.index("--trace-out")
        if at + 1 >= len(argv):
            sys.exit("bench.py: --trace-out requires a path argument")
        out = argv[at + 1]
    if "--chaos" in argv:
        main_chaos()
    elif "--soak" in argv:
        workers = 1000
        if "--workers" in argv:
            at = argv.index("--workers")
            if at + 1 >= len(argv):
                sys.exit("bench.py: --workers requires a count argument")
            workers = int(argv[at + 1])
        main_soak(n_workers=workers)
    elif "--serve" in argv:
        main_serve(smoke="--smoke" in argv)
    elif "--sharded" in argv:
        main_sharded(smoke="--smoke" in argv)
    else:
        main(smoke="--smoke" in argv, trace_out=out)
