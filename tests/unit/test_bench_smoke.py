"""bench.py schema smoke: ``python bench.py --smoke`` must emit one valid
JSON line carrying the per-stage breakdown (including the storage stage)
and the O(1) ``storage_ops_per_round`` counters — so bench schema drift (a
renamed stage, a dropped counter, a broken import in the storage bench) is
caught by tier-1 instead of by the next full bench run.  Every run also
writes a Chrome trace-event file whose top-level span names (producer
round, storage commit, async device-dispatch window, jax compile-vs-cached
dispatch) and commit/dispatch overlap are asserted here — the pipelined
producer commit's visibility contract.
"""

import json
import os
import subprocess
import sys

import pytest

BREAKDOWN_KEYS = (
    "encode",
    "upload",
    "dispatch",
    "wait_transfer",
    "health",
    "decode",
    "dict_build",
    "doc_build",
    "storage_ms",
    "prep_us_saved",
    "dispatch_us_saved",
)

#: Spans every bench trace must carry: the produce round, its batched
#: storage write, the async device window the write overlaps with, and the
#: fused GP step's dispatch.
TRACE_SPAN_NAMES = (
    "producer.round",
    "producer.suggest",
    "storage.commit",
    "device.dispatch",
    "jax.suggest_step.dispatch",
)


def _retrace_introspection_available():
    """The compile-vs-cached split rides jax's PRIVATE PjitFunction
    ``_cache_size`` accessor; product code degrades gracefully without it
    (everything reports as ``dispatch``), so the compile-span assertion
    must degrade the same way instead of failing on a jax upgrade."""
    from orion_tpu.algo.tpu_bo import _suggest_step

    return hasattr(_suggest_step, "_cache_size")


def test_bench_smoke_emits_valid_json_with_breakdown_keys(tmp_path, repo_root):
    trace_path = tmp_path / "trace.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(repo_root, "bench.py"),
            "--smoke",
            "--trace-out",
            str(trace_path),
        ],
        capture_output=True,
        text=True,
        timeout=560,
        env=env,
        cwd=repo_root,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    assert payload["smoke"] is True
    # Cross-run joinability (ISSUE 15): every payload carries its schema
    # version so BENCH_history.jsonl records can be joined honestly.
    assert payload["schema_version"] >= 2
    # The --smoke preflight self-lints the tree before timing anything:
    # bench numbers must never be taken on a contract-violating tree.
    assert payload["lint_violations"] == 0
    # The self-diagnosis gate (orion-tpu doctor over the bench's own
    # healthy phases): bench.py hard-asserts zero CRITICAL findings
    # (SystemExit) before the seeded-chaos legs; this pins the payload.
    assert payload["doctor_critical"] == 0
    assert payload["doctor"]["critical"] == 0
    assert payload["doctor"]["status"] in ("ok", "warn")
    # The serve leg ran under the runtime concurrency sanitizer (orion-tpu
    # tsan): zero observed data races and zero lock-order cycles is a hard
    # assert inside bench.py; this pins the payload field on top.
    assert payload["tsan_violations"] == 0
    # The emitted line itself must carry the breakdown + storage keys —
    # r05's recorded line lacked them, and only an assertion on the payload
    # (not just on values we happen to index) pins the schema.
    assert "breakdown_ms" in payload and "storage_ms" in payload
    breakdown = payload["breakdown_ms"]
    for key in BREAKDOWN_KEYS:
        assert key in breakdown, f"breakdown_ms lost its {key!r} stage"
    # Steady-state host tax, trackable across BENCH_* separately from
    # throughput: the sum of the host stages (everything except
    # wait_transfer, the separately-tracked storage_ms, and the
    # prep_us_saved / dispatch_us_saved savings reports).
    assert payload["host_ms_per_round"] == round(
        sum(v for k, v in breakdown.items()
            if k not in ("wait_transfer", "storage_ms", "prep_us_saved",
                         "dispatch_us_saved")),
        3,
    )
    # The plan-prep cache (ISSUE 16 satellite): after the first round every
    # fused-plan build must be a cache hit, and the breakdown reports the
    # saved host microseconds.
    assert breakdown["prep_us_saved"] >= 0
    # The dispatch-prep token (host-tail endgame): the steady path skips
    # re-validation / statics rebuild entirely and books its savings on
    # the same ledger.
    assert breakdown["dispatch_us_saved"] >= 0
    # The wall-=-device gate, tightened to 1.25x by the host-tail endgame:
    # bench.py --smoke hard-fails (SystemExit) when the steady-state host
    # tax exceeds the orion_tpu.hostbudget factor x device time; this pins
    # the payload relationship on top, with the smoke device reference
    # being the measured wait_transfer stage.
    from orion_tpu.hostbudget import host_budget_factor

    assert payload["host_ms_per_round"] <= (
        host_budget_factor() * breakdown["wait_transfer"]
    )
    # Smoke fills the round decomposition so the history record stays
    # trendable: device = the measured wait_transfer stage.
    assert payload["device_ms_per_round"] == round(
        breakdown["wait_transfer"], 3
    )
    assert payload["wall_ms_per_round"] is not None
    # The cube_hash identity gate (host-tail endgame): >= 4x over the
    # per-trial repr+md5 path at q=1024, collision-free — bench.py
    # SystemExits otherwise; pin the reported block here.
    id_hash = payload["id_hash"]
    assert id_hash["q"] == 1024
    assert id_hash["distinct_ok"] is True
    assert id_hash["speedup"] >= 4
    # Health recording stays under 1% of the steady-state round (bench.py
    # hard-asserts the same bar before emitting).
    round_ms = sum(
        v for k, v in breakdown.items()
        if k not in ("storage_ms", "prep_us_saved", "dispatch_us_saved")
    )
    assert breakdown["health"] <= 0.01 * round_ms
    # The optimization-health payload: a real per-round regret curve with
    # GP/TR health fields (orion_tpu.health).
    health = payload["health"]
    assert len(health["regret_curve"]) >= 2
    assert health["rounds"] >= 1 and health["gp_mll"]
    assert health["last"]["gp_mll"] is not None
    assert health["last"]["q_unique_frac"] is not None
    assert health["last"]["tr_length"] is not None
    # Monotone non-increasing incumbent regret.
    curve = health["regret_curve"]
    assert all(b <= a + 1e-12 for a, b in zip(curve, curve[1:]))
    # The statistical regret gate: smoke checks the machinery against the
    # committed baseline (self-comparison must pass; the synthetic-shift
    # failure case is pinned in tests/unit/test_regret_gate.py).
    gate = payload["regret_gate"]
    assert gate["pass"] is True and gate["mode"] == "baseline-self"
    assert gate["final"]["p_value"] is not None and gate["auc"] is not None
    # The pow-2 boundary-crossing contract: a prewarmed crossing costs a
    # jit-cache hit, not a synchronous retrace (None = jax introspection
    # unavailable — skipped, not failed; bench.py itself hard-asserts 0).
    assert payload["prewarm"]["retraces_after_warm"] in (None, 0)
    if payload["prewarm"]["retraces_after_warm"] == 0:
        assert payload["prewarm"]["prewarms"] >= 1
    # The serve-gateway leg (orion_tpu.serve): 2 tenants through one
    # in-process gateway — coalescing actually happened (width >= 2), the
    # device dispatches were amortized across tenants (< 1 per suggest),
    # and both tenant experiments audit clean (bench.py hard-asserts all
    # three before emitting; this pins the payload schema on top).
    serve = payload["serve"]
    assert serve["tenants"] == 2
    assert serve["coalesce_max_width"] >= 2
    assert serve["dispatches_per_suggest"] < 1.0
    assert serve["audit_violations"] == 0
    # The sharded-soak leg (storage/shard.py + soak.py): 8 workers over a
    # real 3-shard x 1-replica netdb topology with a scripted reconnect
    # storm, shard restart, and replica kill — bench.py hard-asserts zero
    # lost observations, clean audits on every shard, and the chaos
    # signals; this pins the payload schema on top.
    soak = payload["soak"]
    assert soak["lost_observations"] == 0
    assert soak["audits_clean"] is True
    assert soak["shard_restarts"] >= 1
    assert soak["failovers"] >= 1
    assert soak["reconnects"] >= 1
    assert sum(soak["completed_per_shard"].values()) == soak["completed"]
    # The ISSUE-14 promotion leg: a primary was killed for good and the
    # router fleet healed it by electing a replica — no manual restart.
    assert soak["primary_kills"] >= 1
    assert soak["promotions"] >= 1
    # The rebalance-mid-soak leg: the topology grew by >= 1 shard and the
    # migrator moved ~1/N of the experiments with zero lost observations.
    rebalance = payload["rebalance_soak"]
    assert rebalance["lost_observations"] == 0
    assert rebalance["audits_clean"] is True
    assert rebalance["rebalance"]["executed"] is True
    assert rebalance["rebalance"]["planned"]["moves"] >= 1
    assert sum(rebalance["completed_per_shard"].values()) == rebalance["completed"]
    # The drain-mid-soak leg (ISSUE 20): the busiest shard was drained and
    # REMOVED mid-run — zero residual, ~its ring share of the experiments
    # moved (2x bound), zero lost observations, audits clean; bench.py
    # hard-asserts (SystemExit) each of these before emitting.
    drain = payload["drain_soak"]
    assert drain["lost_observations"] == 0
    assert drain["audits_clean"] is True
    drained = drain["drain"]
    assert drained["executed"] is True
    assert drained["residual"] == 0
    assert drained["planned"]["moves"] >= 1
    assert drained["planned"]["move_fraction"] <= 2.0 * drained["ring_share"]
    assert sum(drain["completed_per_shard"].values()) == drain["completed"]
    # The quorum leg (ISSUE 20): quorum=1 writes, busiest primary killed
    # with NO replication catch-up wait — the ack floor alone is the
    # zero-loss mechanism.
    quorum = payload["quorum_soak"]
    assert quorum["lost_observations"] == 0
    assert quorum["audits_clean"] is True
    assert quorum["primary_kills"] >= 1
    assert quorum["promotions"] >= 1
    assert quorum["quorum"] == 1
    assert quorum["wait_catchup"] is False
    # The record-building pin: the BENCH_history columns for the two new
    # legs must come out non-null from THIS payload (`is not None`, not
    # truthiness — a quorum run losing zero observations is the point).
    sys.path.insert(0, repo_root)
    try:
        from bench import bench_history_record
    finally:
        sys.path.remove(repo_root)
    record = bench_history_record(payload)
    assert record["soak_drained_frac"] is not None
    assert record["soak_quorum_lost"] is not None
    assert record["soak_quorum_lost"] == 0
    assert serve["per_tenant"] and all(
        row["p99_ms"] > 0 for row in serve["per_tenant"].values()
    )
    # The sharded leg (ISSUE 16): run in a child under the 8-way virtual
    # CPU mesh, parity and full per-device placement hard-asserted by
    # bench.py (child AND parent); this pins the payload schema on top.
    sharded = payload["sharded"]
    assert sharded["devices"] == 8
    assert sharded["platform"] == "cpu"  # labelled: never a chip result
    assert sharded["parity"]["ok"] is True
    assert sharded["parity"]["same_selection"] is True
    assert sharded["devices_holding_shards"] == 8
    assert len(sharded["placement"]) == 8
    assert all(frac > 0 for frac in sharded["placement"].values())
    assert sharded["q_curve"] and all(
        row["sharded_sps"] > 0 and row["single_sps"] > 0 and row["ratio"] > 0
        for row in sharded["q_curve"]
    )
    for backend in ("sqlite", "network"):
        assert payload["storage_ms"][backend] > 0
        # The batched write path commits a whole q-round as ONE transaction
        # / wire request; a regression to per-trial commits shows up here
        # as q ops, not O(1).
        assert payload["storage_ops_per_round"][backend] <= 2, backend

    # --- distributed-trace critical-path attribution ---------------------
    # The traced rounds (incl. the loopback-netdb leg) bucket each round's
    # wall time into client-host / wire / server-host / device
    # (orion_tpu.tracing) — the ROADMAP item-2 burn-down measurement.
    attribution = payload["host_attribution"]
    assert attribution is not None and attribution["traces"] >= 1
    for key in (
        "total_ms", "client_host_ms", "wire_ms", "server_host_ms", "device_ms",
    ):
        assert attribution[key] is not None and attribution[key] >= 0, key
    # The netdb leg really crossed a wire: server-side host time was seen.
    assert attribution["server_host_ms"] > 0

    # --- the telemetry trace artifact ------------------------------------
    assert payload["trace_file"] == str(trace_path)
    with open(trace_path) as handle:
        trace = json.load(handle)
    events = trace["traceEvents"]
    names = {e["name"] for e in events}
    expected = TRACE_SPAN_NAMES
    if _retrace_introspection_available():
        expected += ("jax.suggest_step.compile",)
    for span in expected:
        assert span in names, f"bench trace lost its {span!r} span"
    # The PR-2 pipelined commit is visible as CONCURRENT spans: the round's
    # batched register (storage.commit) runs inside the open async
    # device-dispatch window.
    commits = [e for e in events if e["name"] == "storage.commit"]
    windows = [e for e in events if e["name"] == "device.dispatch"]
    assert any(
        w["ts"] < c["ts"] and c["ts"] + c["dur"] < w["ts"] + w["dur"]
        for c in commits
        for w in windows
    ), "storage.commit no longer overlaps the device.dispatch window"
    # Distributed tracing: the trace carries >= 1 CROSS-PROCESS flow pair
    # (bound s/f events on different synthetic tracks) — the serve leg's
    # client->gateway hops and the netdb leg's commit->apply hops both
    # produce them, and the serve-leg spans must be among the arrows'
    # endpoints (the coalesced-dispatch links / gateway request spans).
    starts = {e["id"]: e for e in events if e.get("ph") == "s"}
    finishes = {e["id"]: e for e in events if e.get("ph") == "f"}
    pairs = [(starts[i], finishes[i]) for i in set(starts) & set(finishes)]
    assert pairs, "bench trace lost its distributed flow events"
    assert any(s["pid"] != f["pid"] for s, f in pairs), (
        "no flow pair crosses process tracks"
    )
    serve_tracks = {
        e["pid"]
        for e in events
        if e.get("ph") == "M" and "gateway:" in str(e.get("args", {}).get("name", ""))
    }
    assert any(
        s["pid"] in serve_tracks or f["pid"] in serve_tracks for s, f in pairs
    ), "the serve leg contributed no cross-process flow link"


def test_bench_serve_fleet_smoke_leg_survives_member_kill(repo_root):
    """The 2-gateway fleet twin of the serve leg (ISSUE 19), run
    IN-PROCESS (the jit compiles amortize with the rest of tier-1): 3
    tenants ring-routed over 2 fleet members sharing a tenant snapshot
    store, the busier member killed at the mid-stream round barrier.
    bench_serve_fleet hard-asserts (SystemExit) bit-identical streams vs
    the single-gateway reference, zero lost observations on the
    survivor, fleet-wide dispatches/suggest < 1, and that the kill
    actually forced a failover; this pins the payload block on top."""
    sys.path.insert(0, repo_root)
    try:
        from bench import bench_serve_fleet
    finally:
        sys.path.remove(repo_root)

    block = bench_serve_fleet(
        m_gateways=2,
        n_tenants=3,
        rounds=3,
        q=4,
        window=0.2,
        n_candidates=64,
        fit_steps=4,
        priors={f"x{j}": "uniform(0, 1)" for j in range(3)},
    )
    assert block["gateways"] == 2 and block["tenants"] == 3
    assert block["bit_identical"] is True
    assert block["lost_observations"] == 0
    assert block["audit_violations"] == 0
    assert block["dispatches_per_suggest"] < 1.0
    assert block["failovers"] >= 1
    assert block["killed"] in block["placement"]
    # The victim is the busier member by construction, so the kill moved
    # at least one tenant through the takeover path.
    assert block["placement"][block["killed"]] >= 1


def test_bench_chaos_smoke_reports_retries_and_audits_clean(repo_root):
    """``bench.py --chaos``: the seeded fault schedules fire, the retry
    policy absorbs them (storage.retries > 0 on the faulted sqlite run,
    reconnects > 0 through the fault proxy), and the invariant auditor
    reports zero violations — bench.py hard-asserts all of it; this test
    pins the emitted schema on top."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo_root, "bench.py"), "--chaos"],
        capture_output=True,
        text=True,
        timeout=560,
        env=env,
        cwd=repo_root,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    assert payload["metric"] == "chaos smoke"
    sqlite = payload["backends"]["sqlite"]
    assert sqlite["storage_retries_per_round"] > 0
    assert sqlite["audit_violations"] == 0
    assert sum(sqlite["faults_injected"].values()) > 0
    network = payload["backends"]["network"]
    assert network["reconnects_per_round"] > 0
    assert network["audit_violations"] == 0


@pytest.mark.parametrize(
    "kind, peak", [("TPU v5 lite", 1.97e14), ("TPU v4", None), ("cpu", None)]
)
def test_mfu_peak_is_looked_up_by_device_kind(kind, peak):
    """MFU's denominator belongs to the device that ran: a kind the table
    does not know gets None, never the v5e peak."""
    from orion_tpu.benchmarks.suggest_bench import peak_bf16_flops

    device = type("Device", (), {"device_kind": kind})()
    assert peak_bf16_flops(device) == peak
