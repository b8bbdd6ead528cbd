"""Compiler-plane tests (orion_tpu.compiler_plane).

THE acceptance pin: a forced fit-bucket crossing through the REAL
``run_fused_plan`` dispatch emits a flight ``jax.retrace`` event naming
the exact changed static (``fit_bucket 64→128``).  Plus the registry unit
contract — signature capture on real tiny jits, nearest-prior diffs
(bucket crossings, warm/cold flips, cold start, identical-signature
fallback), prewarm-covered attribution, None-degrading cost/memory
analysis, lazy dedup'd ``analyze_all``, and zero work when telemetry is
disabled."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu import compiler_plane as cp
from orion_tpu import health
from orion_tpu import telemetry as tel
from orion_tpu.compiler_plane import (
    COMPILE_REGISTRY,
    CompileRegistry,
    analysis_from_compiled,
    diff_fields,
    fields_from_plan_signature,
    format_fields,
    jit_cache_size,
    lowered_analysis_fn,
    predict_hbm_bound_q,
    profiler_capture,
    signature_fields,
)


@pytest.fixture
def telemetry():
    """Telemetry + flight recorder on, every plane reset around the test
    (the registry is process-wide state, like the span ring)."""
    tel_before = tel.TELEMETRY.enabled
    flight_before = health.FLIGHT.enabled
    tel.TELEMETRY.enable()
    health.FLIGHT.enable()
    tel.TELEMETRY.reset()
    health.FLIGHT.clear()
    COMPILE_REGISTRY.reset()
    try:
        yield tel.TELEMETRY
    finally:
        if not tel_before:
            tel.TELEMETRY.disable()
        if not flight_before:
            health.FLIGHT.disable()
        tel.TELEMETRY.reset()
        health.FLIGHT.clear()
        COMPILE_REGISTRY.reset()


# --- signature fields and diffs ----------------------------------------------


def test_signature_fields_stringifies_exactly_like_plan_signatures():
    fields = signature_fields((64, 3), {"q": 8, "kernel": "matern52",
                                        "mesh": None})
    assert fields == {
        "fit_bucket": 64,
        "width": 3,
        "q": "8",
        "kernel": "matern52",
        "mesh": "None",
    }
    # The FusedPlan.signature shape: (shape tuple, sorted (k, str(v)) pairs).
    assert fields_from_plan_signature(
        ((64, 3), (("kernel", "matern52"), ("mesh", "None"), ("q", "8")))
    ) == fields


def test_diff_fields_orders_priority_fields_first():
    old = {"fit_bucket": 64, "q": "256", "kernel": "rbf"}
    new = {"fit_bucket": 128, "q": "512", "kernel": "matern52"}
    assert diff_fields(old, new) == [
        "fit_bucket 64→128",
        "q 256→512",
        "kernel rbf→matern52",
    ]
    assert diff_fields(old, dict(old)) == []


def test_format_fields_is_one_line_priority_first():
    line = format_fields({"kernel": "rbf", "fit_bucket": 64, "width": 3})
    assert line == "fit_bucket=64 width=3 kernel=rbf"


def test_predict_hbm_bound_q_degrades_to_none():
    assert predict_hbm_bound_q({"q": "256"}, 4e9, 16e9) == 1024
    assert predict_hbm_bound_q({}, 4e9, 16e9) is None  # no q field
    assert predict_hbm_bound_q({"q": "256"}, None, 16e9) is None
    assert predict_hbm_bound_q({"q": "256"}, 4e9, None) is None
    assert predict_hbm_bound_q({"q": "0"}, 4e9, 16e9) is None


# --- cost/memory analysis: None-degrading on every backend -------------------


class _FakeCompiled:
    def __init__(self, cost=None, raise_cost=False):
        self._cost = cost
        self._raise = raise_cost

    def cost_analysis(self):
        if self._raise:
            raise RuntimeError("backend without cost model")
        return self._cost

    def memory_analysis(self):
        raise RuntimeError("backend without memory analysis")


def test_analysis_from_compiled_degrades_every_field_to_none():
    out = analysis_from_compiled(_FakeCompiled(raise_cost=True))
    assert set(out) == {
        "flops", "bytes_accessed", "argument_bytes", "output_bytes",
        "temp_bytes", "generated_code_bytes", "hbm_bytes", "tpu_custom_calls",
    }
    assert all(v is None for v in out.values())


def test_analysis_from_compiled_reads_partial_cost_dicts():
    out = analysis_from_compiled(
        _FakeCompiled(cost={"flops": 12.0, "bytes accessed": 34.0})
    )
    assert out["flops"] == 12.0
    assert out["bytes_accessed"] == 34.0
    assert out["hbm_bytes"] is None  # memory_analysis raised — degrade


def test_analysis_from_compiled_handles_per_device_lists():
    out = analysis_from_compiled(_FakeCompiled(cost=[{"flops": 5.0}]))
    assert out["flops"] == 5.0


def test_lowered_analysis_fn_on_a_real_tiny_jit():
    @partial(jax.jit, static_argnames=("k",))
    def toy(a, *, k):
        return a * k

    probe = lowered_analysis_fn(toy, (jnp.ones((8,), jnp.float32),), {"k": 3})
    out = probe()
    assert set(out) >= {"flops", "hbm_bytes"}
    assert out.pop("tpu_custom_calls") == 0  # no Pallas kernel in a toy
    # CPU exposes a cost model; whatever it reports must be float or None.
    assert all(v is None or isinstance(v, float) for v in out.values())


def test_jit_cache_size_counts_real_compilations():
    @partial(jax.jit, static_argnames=("k",))
    def toy2(a, *, k):
        return a + k

    before = jit_cache_size(toy2)
    assert before == 0
    toy2(jnp.ones((4,), jnp.float32), k=1)
    toy2(jnp.ones((4,), jnp.float32), k=2)  # new static: second entry
    assert jit_cache_size(toy2) == 2
    assert jit_cache_size(object()) is None  # not a jitted fn — degrade


# --- the registry ------------------------------------------------------------


def test_record_compile_books_entry_counter_and_signed_span(telemetry):
    reg = CompileRegistry()
    entry = reg.record_compile(
        "fused_plan", {"fit_bucket": 64, "width": 3, "q": "8"}, seconds=0.25
    )
    assert entry is not None
    assert telemetry.counter_value("jax.compiles") == 1
    spans = [s for s in telemetry.drain_spans() if s["name"] == "jax.compile"]
    assert len(spans) == 1
    assert spans[0]["args"]["family"] == "fused_plan"
    assert spans[0]["args"]["kind"] == "compile"
    assert "fit_bucket=64" in spans[0]["args"]["signature"]
    summary = reg.summary()
    assert summary["compiles"] == 1
    assert summary["compile_ms_total"] == 250.0


def test_retrace_attribution_names_the_changed_statics(telemetry):
    reg = CompileRegistry()
    reg.record_compile("fused_plan", {"fit_bucket": 64, "q": "256"})
    attribution = reg.record_retrace(
        "fused_plan", {"fit_bucket": 128, "q": "256"}, seconds=0.1
    )
    assert attribution["changed"] == ["fit_bucket 64→128"]
    assert attribution["covered_by_prewarm"] is False
    assert attribution["against"] == {"fit_bucket": 64, "q": "256"}
    assert telemetry.counter_value("jax.retraces.attributed") == 1
    events = [
        e for e in health.FLIGHT.events() if e["kind"] == "jax.retrace"
    ]
    assert len(events) == 1
    assert events[0]["args"]["changed"] == "fit_bucket 64→128"


def test_retrace_attribution_warm_cold_flip(telemetry):
    reg = CompileRegistry()
    reg.record_compile("fused_plan", {"fit_bucket": 64, "warm": "True"})
    attribution = reg.record_retrace(
        "fused_plan", {"fit_bucket": 64, "warm": "False"}
    )
    assert attribution["changed"] == ["warm True→False"]


def test_retrace_attribution_picks_nearest_prior_not_just_latest(telemetry):
    reg = CompileRegistry()
    reg.record_compile("fused_plan", {"fit_bucket": 64, "q": "256"})
    # A later, more-different signature must not win the diff.
    reg.record_compile("fused_plan", {"fit_bucket": 32, "q": "512"})
    attribution = reg.record_retrace(
        "fused_plan", {"fit_bucket": 128, "q": "256"}
    )
    assert attribution["changed"] == ["fit_bucket 64→128"]


def test_retrace_attribution_cold_start_and_identical_fallbacks(telemetry):
    reg = CompileRegistry()
    first = reg.record_retrace("stacked", {"t_pad": "4"})
    assert first["changed"] == ["first stacked signature (cold start)"]
    again = reg.record_retrace("stacked", {"t_pad": "4"})
    assert again["changed"] == [
        "identical signature (jit cache evicted or bypassed)"
    ]
    # Families never cross-attribute: a fused_plan retrace after only
    # stacked history is still a cold start for its family.
    other = reg.record_retrace("fused_plan", {"fit_bucket": 64})
    assert other["changed"] == ["first fused_plan signature (cold start)"]


def test_prewarm_covered_retrace_is_counted_as_a_prewarm_bug(telemetry):
    reg = CompileRegistry()
    fields = {"fit_bucket": 64, "q": "256", "warm": "False"}
    reg.record_prewarm("fused_plan", fields, seconds=0.2)
    attribution = reg.record_retrace("fused_plan", dict(fields))
    assert attribution["covered_by_prewarm"] is True
    assert attribution["changed"] == [
        "identical signature (jit cache evicted or bypassed)"
    ]
    assert telemetry.counter_value("jax.retraces.prewarm_covered") == 1
    # A different signature is NOT covered.
    miss = reg.record_retrace("fused_plan", {**fields, "fit_bucket": 128})
    assert miss["covered_by_prewarm"] is False
    assert telemetry.counter_value("jax.retraces.prewarm_covered") == 1


def test_disabled_telemetry_records_nothing(telemetry):
    telemetry.disable()
    try:
        reg = CompileRegistry()
        assert reg.record_compile("fused_plan", {"fit_bucket": 64}) is None
        assert reg.record_prewarm("fused_plan", {"fit_bucket": 64}) is None
        assert reg.record_retrace("fused_plan", {"fit_bucket": 64}) is None
        assert reg.entries() == []
        summary = reg.summary()
        assert summary["compiles"] == 0
        assert summary["retraces"] == 0
    finally:
        telemetry.enable()
    assert telemetry.counter_value("jax.compiles") == 0


def test_analyze_all_dedups_caches_and_honors_the_limit(telemetry):
    reg = CompileRegistry()
    calls = []

    def probe(tag, result):
        def run():
            calls.append(tag)
            return result
        return run

    shared = {"fit_bucket": 64, "q": "256"}
    cost = {"flops": 10.0, "hbm_bytes": 4e9}
    reg.record_prewarm("fused_plan", shared, analysis_fn=probe("warm", cost))
    reg.record_retrace("fused_plan", dict(shared),
                       analysis_fn=probe("retrace", cost))
    reg.record_compile("append", {"fit_bucket": 64, "batch": "8"},
                       analysis_fn=probe("append", {"flops": 1.0}))

    # limit=0: everything pending is skipped, nothing runs.
    assert reg.analyze_all(limit=0) == {"analyzed": 0, "skipped": 2}
    assert calls == []

    # The prewarm and the retrace it failed to cover share ONE analysis.
    assert reg.analyze_all(families=("fused_plan",)) == {
        "analyzed": 1, "skipped": 0,
    }
    assert calls == ["warm"]
    assert all(
        e.cost == cost for e in reg.entries("fused_plan")
    )

    # Re-running is free: the signature cache remembers the result.
    assert reg.analyze_all() == {"analyzed": 1, "skipped": 0}
    assert calls == ["warm", "append"]


def test_summary_predicts_hbm_bound_q(telemetry, monkeypatch):
    monkeypatch.setattr(cp, "device_hbm_capacity",
                        lambda device=None: 16_000_000_000)
    reg = CompileRegistry()
    reg.record_compile(
        "fused_plan", {"fit_bucket": 64, "q": "256"},
        seconds=0.5, analysis_fn=lambda: {"flops": 1.0, "hbm_bytes": 4e9},
    )
    reg.analyze_all()
    summary = reg.summary()
    assert summary["plan_hbm_bytes_max"] == 4e9
    assert summary["hbm_capacity_bytes"] == 16_000_000_000
    assert summary["hbm_bound_q"] == 1024  # 256 * 16e9 / 4e9
    assert summary["per_plan"][0]["hbm_bytes"] == 4e9
    # publish_gauges mirrors the digest onto the compiler.* gauge plane.
    reg.publish_gauges()
    assert telemetry.gauge_value("compiler.hbm_bytes_max") == 4e9
    assert telemetry.gauge_value("compiler.hbm_bound_q") == 1024


def test_analysis_failure_degrades_without_breaking_the_sweep(telemetry):
    reg = CompileRegistry()

    def boom():
        raise RuntimeError("interop backend")

    reg.record_compile("fused_plan", {"fit_bucket": 64}, analysis_fn=boom)
    assert reg.analyze_all() == {"analyzed": 1, "skipped": 0}
    assert reg.entries("fused_plan")[0].cost is None
    assert reg.summary()["plan_hbm_bytes_max"] is None


# --- the acceptance pin: a real bucket-crossing retrace ----------------------

#: Deliberately unusual statics so THIS test owns its jit signatures even
#: when other tests in the same process already compiled the fused step.
_CROSSING_KW = dict(
    n_candidates=48,
    kernel="matern52",
    acq="thompson",
    fit_steps=1,
    local_frac=0.47,
    local_sigma=0.11,
    beta=2.0,
)


def _tiny_plan(rows):
    from orion_tpu.algo.tpu_bo import make_fused_plan

    d = 2
    rng = np.random.default_rng(0)
    x = np.zeros((rows, d), dtype=np.float32)
    y = np.zeros((rows,), dtype=np.float32)
    mask = np.zeros((rows,), dtype=np.float32)
    x[:6] = rng.uniform(size=(6, d))
    y[:6] = rng.normal(size=6)
    mask[:6] = 1.0
    return make_fused_plan(
        jax.random.PRNGKey(0),
        jnp.asarray(x),
        jnp.asarray(y),
        jnp.asarray(mask),
        jnp.asarray(x[0]),
        None,
        4,
        **_CROSSING_KW,
    )


def test_bucket_crossing_retrace_emits_attributed_flight_event(telemetry):
    """Dispatch the REAL fused step at fit buffer 64 then 128: the second
    compile must land as a flight ``jax.retrace`` event naming exactly
    ``fit_bucket 64→128`` — the self-diagnosing form of every
    ``retraces_after_warm == 0`` failure."""
    from orion_tpu.algo.tpu_bo import run_fused_plan

    rows, _ = run_fused_plan(_tiny_plan(64))
    assert np.asarray(rows).shape == (4, 2)
    rows, _ = run_fused_plan(_tiny_plan(128))
    assert np.asarray(rows).shape == (4, 2)

    assert telemetry.counter_value("jax.retraces") == 2
    assert telemetry.counter_value("jax.retraces.attributed") == 2
    events = [
        e for e in health.FLIGHT.events() if e["kind"] == "jax.retrace"
    ]
    assert len(events) == 2
    assert events[0]["args"]["changed"] == (
        "first fused_plan signature (cold start)"
    )
    assert events[1]["args"]["changed"] == "fit_bucket 64→128"
    assert events[1]["args"]["covered_by_prewarm"] is False
    families = {e.family for e in COMPILE_REGISTRY.entries()}
    assert "fused_plan" in families


def test_profiler_capture_prints_the_shared_artifact_line(tmp_path, capsys):
    directory = str(tmp_path / "trace")
    with profiler_capture(directory):
        jnp.ones((4,)).block_until_ready()
    err = capsys.readouterr().err
    assert f"jax profiler trace written to {directory}" in err
