"""Unified telemetry subsystem (orion_tpu.telemetry): disabled-path
overhead guard, ring-buffer wraparound, Chrome trace-event schema, metric
merging, and the cross-worker snapshot flush through the storage channel.
"""

import json
import threading

import pytest

from orion_tpu import telemetry as tel
from orion_tpu.storage.base import DocumentStorage
from orion_tpu.storage.documents import MemoryDB


# --- disabled path ----------------------------------------------------------
def test_disabled_span_is_shared_singleton_no_allocation():
    """The disabled hot path must not allocate or lock: span() returns ONE
    shared no-op context manager and every mutator is a no-op."""
    t = tel.Telemetry(enabled=False)
    a = t.span("producer.round")
    b = t.span("storage.commit", args={"backend": "sqlite"})
    assert a is b is tel._NULL_SPAN
    with a:
        pass
    # The registry lock is never touched when disabled: replace it with a
    # poison object whose acquisition would explode.
    class _Poison:
        def __enter__(self):
            raise AssertionError("disabled path took the registry lock")

        def __exit__(self, *exc):  # pragma: no cover
            return False

        def acquire(self, *a, **k):  # pragma: no cover
            raise AssertionError("disabled path took the registry lock")

    t._lock = _Poison()
    with t.span("x"):
        pass
    t.count("c")
    t.set_gauge("g", 1.0)
    t.observe("h", 0.5)
    t.record_span("s", duration=0.1)
    t._lock = threading.Lock()
    snap = t.snapshot()
    assert snap == {"counters": {}, "gauges": {}, "histograms": {}}
    assert t.drain_spans() == []


def test_enable_disable_toggle():
    t = tel.Telemetry(enabled=False)
    t.enable()
    with t.span("op"):
        pass
    t.disable()
    with t.span("op"):
        pass
    assert len(t.iter_spans()) == 1


# --- ring buffer ------------------------------------------------------------
def test_ring_buffer_wraparound_keeps_newest():
    t = tel.Telemetry(enabled=True, span_capacity=8)
    for i in range(20):
        t.record_span(f"s{i}", duration=0.001)
    spans = t.iter_spans()
    assert [s["name"] for s in spans] == [f"s{i}" for i in range(12, 20)]
    # Histograms saw every record even though the ring dropped the oldest.
    snap = t.snapshot()
    assert sum(h["count"] for h in snap["histograms"].values()) == 20


def test_drain_spans_returns_each_span_once_across_wraparound():
    t = tel.Telemetry(enabled=True, span_capacity=8)
    for i in range(5):
        t.record_span(f"a{i}", duration=0.001)
    first = t.drain_spans()
    assert [s["name"] for s in first] == [f"a{i}" for i in range(5)]
    assert t.drain_spans() == []
    # Overflow between drains: only the surviving newest come back, once.
    for i in range(12):
        t.record_span(f"b{i}", duration=0.001)
    second = t.drain_spans()
    assert [s["name"] for s in second] == [f"b{i}" for i in range(4, 12)]
    assert t.drain_spans() == []


# --- chrome trace schema ----------------------------------------------------
def test_chrome_trace_schema(tmp_path):
    import time

    t = tel.Telemetry(enabled=True)
    with t.span("producer.round", args={"q": 16}):
        # A duration-only record back-computes its start from "now", so
        # sleep past the inner duration to keep it nested in the outer span.
        time.sleep(0.005)
        t.record_span("storage.commit", duration=0.002)
    out = tmp_path / "trace.json"
    t.export_chrome_trace(str(out))
    with open(out) as handle:
        trace = json.load(handle)
    assert set(trace) == {"traceEvents", "displayTimeUnit"}
    events = trace["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]
    metas = [e for e in events if e["ph"] == "M"]
    assert {e["name"] for e in spans} == {"producer.round", "storage.commit"}
    for event in spans:
        # The complete-event schema Perfetto's importer requires.
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(event)
        assert isinstance(event["ts"], float) and isinstance(event["dur"], float)
        assert event["dur"] >= 0.0
    [outer] = [e for e in spans if e["name"] == "producer.round"]
    [inner] = [e for e in spans if e["name"] == "storage.commit"]
    # Nesting: the inner explicit span lies within the outer context span.
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1.0
    assert outer["args"] == {"q": 16}
    # One process_name metadata event per pid.
    assert [m["name"] for m in metas] == ["process_name"]


def test_jsonl_export(tmp_path):
    t = tel.Telemetry(enabled=True)
    t.record_span("op", duration=0.001)
    t.count("c", 3)
    out = tmp_path / "telemetry.jsonl"
    t.export_jsonl(str(out))
    lines = [json.loads(line) for line in open(out)]
    assert lines[0]["type"] == "span" and lines[0]["name"] == "op"
    assert lines[-1]["type"] == "metrics" and lines[-1]["counters"] == {"c": 3}


# --- metrics primitives -----------------------------------------------------
def test_histogram_percentiles_are_bucket_conservative():
    t = tel.Telemetry(enabled=True)
    for ms in (1, 1, 1, 1, 1, 1, 1, 1, 1, 100):
        t.observe("lat", ms / 1e3)
    hist = t.snapshot()["histograms"]["lat"]
    assert hist["count"] == 10
    p50 = tel.histogram_percentile(hist, 50)
    p99 = tel.histogram_percentile(hist, 99)
    # p50 within the 2x bucket holding 1ms; p99 capped at the true max.
    assert 1e-3 <= p50 <= 2.1e-3
    assert abs(p99 - 0.1) < 1e-9
    assert tel.histogram_percentile({"count": 0, "buckets": []}, 50) == 0.0


def test_external_counter_weakref_lifecycle():
    class Backend:
        txn_count = 0

    t = tel.Telemetry(enabled=True)
    db = Backend()
    db.txn_count = 7
    t.register_external_counter("storage.sqlite.txn_count", db, "txn_count")
    assert t.snapshot()["counters"]["storage.sqlite.txn_count"] == 7
    db.txn_count = 9
    assert t.snapshot()["counters"]["storage.sqlite.txn_count"] == 9
    del db
    assert "storage.sqlite.txn_count" not in t.snapshot()["counters"]


def test_merge_snapshots_sums_counters_and_buckets():
    t1 = tel.Telemetry(enabled=True)
    t2 = tel.Telemetry(enabled=True)
    t1.count("jax.retraces", 2)
    t2.count("jax.retraces", 3)
    t1.observe("storage.sqlite.commit", 0.004)
    t2.observe("storage.sqlite.commit", 0.004)
    t2.observe("storage.sqlite.commit", 4.0)
    t1.set_gauge("pacemaker.heartbeat_lag_s", 0.5)
    t2.set_gauge("pacemaker.heartbeat_lag_s", 0.1)
    merged = tel.merge_snapshots(
        [
            {**t1.snapshot(), "time": 1.0},
            {**t2.snapshot(), "time": 2.0},
        ]
    )
    assert merged["counters"]["jax.retraces"] == 5
    hist = merged["histograms"]["storage.sqlite.commit"]
    assert hist["count"] == 3
    assert hist["max"] == 4.0
    # Gauges merge by MAX: the stalled worker's risk signal must not be
    # masked by a healthier worker's fresher flush.
    assert merged["gauges"]["pacemaker.heartbeat_lag_s"] == 0.5


# --- cross-worker aggregation through the storage channel -------------------
def test_cross_worker_snapshot_aggregation_through_storage():
    """Two 'workers' (two registries, distinct worker ids) flush snapshots
    through DocumentStorage.record_metrics; fetch + merge must aggregate
    them, and a re-flush from one worker must UPSERT (supersede its prior
    doc), not double-count."""
    storage = DocumentStorage(MemoryDB())
    exp = storage.create_experiment(
        {"name": "tele", "metadata": {"user": "t"}}
    )
    w1 = tel.Telemetry(enabled=True)
    w2 = tel.Telemetry(enabled=True)
    w1.count("jax.retraces", 1)
    w1.observe("producer.suggest", 0.010)
    w2.count("jax.retraces", 4)
    w2.observe("producer.suggest", 0.020)
    storage.record_metrics(exp, w1.snapshot(), worker="hostA:1")
    storage.record_metrics(exp, w2.snapshot(), worker="hostB:2")
    docs = storage.fetch_metrics(exp)
    assert {d["worker"] for d in docs} == {"hostA:1", "hostB:2"}
    merged = tel.merge_snapshots(docs)
    assert merged["counters"]["jax.retraces"] == 5
    assert merged["histograms"]["producer.suggest"]["count"] == 2
    # Worker 1 keeps running and re-flushes its grown totals: the upsert
    # replaces its old doc, so the merge never double-counts a worker.
    w1.count("jax.retraces", 2)
    w1.observe("producer.suggest", 0.015)
    storage.record_metrics(exp, w1.snapshot(), worker="hostA:1")
    docs = storage.fetch_metrics(exp)
    assert len(docs) == 2
    merged = tel.merge_snapshots(docs)
    assert merged["counters"]["jax.retraces"] == 7
    assert merged["histograms"]["producer.suggest"]["count"] == 3


def test_span_flush_through_storage_channel_with_cap(monkeypatch):
    storage = DocumentStorage(MemoryDB())
    exp = storage.create_experiment(
        {"name": "tele-spans", "metadata": {"user": "t"}}
    )
    t = tel.Telemetry(enabled=True)
    for i in range(6):
        t.record_span("producer.round", duration=0.001)
    storage.record_spans(exp, t.drain_spans())
    docs = storage.fetch_spans(exp)
    assert len(docs) == 6
    assert all(d["name"] == "producer.round" for d in docs)
    assert all(d["worker"] for d in docs)
    # ts-ascending contract (what the chrome merge relies on).
    assert [d["ts"] for d in docs] == sorted(d["ts"] for d in docs)
    # Cap: pruning keeps the newest SPANS_CAP records.
    monkeypatch.setattr(DocumentStorage, "SPANS_CAP", 4)
    for i in range(3):
        t.record_span("late", duration=0.001)
    storage.record_spans(exp, t.drain_spans())
    docs = storage.fetch_spans(exp)
    assert len(docs) <= 4
    assert [d["name"] for d in docs][-3:] == ["late"] * 3


# --- end-to-end: producer rounds populate the channel -----------------------
@pytest.mark.filterwarnings("ignore")
def test_producer_rounds_flush_spans_and_metrics():
    from orion_tpu.core.experiment import build_experiment
    from orion_tpu.core.producer import Producer

    enabled_before = tel.TELEMETRY.enabled
    tel.TELEMETRY.enable()
    try:
        storage = DocumentStorage(MemoryDB())
        experiment = build_experiment(
            storage,
            "tele-e2e",
            priors={"x": "uniform(0, 1)"},
            algorithms={"random": {"seed": 0}},
            metadata={"user": "t"},
        )
        experiment.instantiate()
        producer = Producer(experiment)
        for _ in range(2):
            producer.update()
            producer.produce(4)
        producer._flush_timings(force_metrics=True)
        names = {d["name"] for d in storage.fetch_spans(experiment)}
        assert {"producer.round", "producer.suggest", "storage.commit"} <= names
        merged = tel.merge_snapshots(storage.fetch_metrics(experiment))
        assert merged["histograms"]["producer.round"]["count"] >= 2
        assert merged["histograms"]["storage.memory.register_trials"]["count"] >= 2
    finally:
        if not enabled_before:
            tel.TELEMETRY.disable()
        tel.TELEMETRY.reset()