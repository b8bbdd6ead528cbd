"""The program's spans on the profiler's clock (orion_tpu.telemetry): a
``with`` span writes a profiler annotation while telemetry is on and
nothing while it is off; each drain consumer has its own cursor; the
algorithm API, the producer and the fused step's named scopes say where
the work happens."""

import glob
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from orion_tpu import telemetry as tel
from orion_tpu.algo.base import create_algo
from orion_tpu.space.dsl import build_space
from orion_tpu.storage.base import DocumentStorage
from orion_tpu.storage.documents import MemoryDB


@pytest.fixture
def telemetry_on():
    """The process-wide registry on, with a fresh ring, for one test."""
    was = tel.TELEMETRY.enabled
    tel.TELEMETRY.reset()
    tel.TELEMETRY.enable()
    yield tel.TELEMETRY
    if not was:
        tel.TELEMETRY.disable()
    tel.TELEMETRY.reset()


def _host_events(trace_dir, name):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(path)
    return [ev for plane in data.planes if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events if ev.name == name]


# --- the shared clock -------------------------------------------------------
def test_with_span_is_on_the_profiler_host_plane(tmp_path):
    t = tel.Telemetry(enabled=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with t.span("test.on_clock"):
            time.sleep(0.05)
    finally:
        jax.profiler.stop_trace()
    (event,) = _host_events(str(tmp_path), "test.on_clock")
    (record,) = t.iter_spans()
    assert record["name"] == "test.on_clock"
    assert event.duration_ns / 1e9 == pytest.approx(record["dur"], rel=0.1)


def test_disabled_span_writes_no_annotation(monkeypatch):
    made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", lambda name: made.append(name))
    off = tel.Telemetry(enabled=False)
    span = off.span("test.off")
    assert span is tel._NULL_SPAN
    with span:
        pass
    span.rename("test.other")  # the null span takes the call and keeps nothing
    assert made == [] and off.iter_spans() == []


def test_renamed_span_keeps_its_annotation_name(monkeypatch):
    opened = []

    class Annotation:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    t = tel.Telemetry(enabled=True)
    with t.span("jax.suggest_step.dispatch") as span:
        span.rename("jax.suggest_step.compile")
    assert opened == ["jax.suggest_step.dispatch"]
    assert [s["name"] for s in t.iter_spans()] == ["jax.suggest_step.compile"]
    assert t.snapshot()["histograms"]["jax.suggest_step.compile"]["count"] == 1


def test_span_in_a_process_without_jax_imports_nothing():
    code = (
        "import sys\n"
        "from orion_tpu.telemetry import Telemetry\n"
        "t = Telemetry(enabled=True)\n"
        "with t.span('netdb.apply'):\n"
        "    pass\n"
        "assert [s['name'] for s in t.iter_spans()] == ['netdb.apply']\n"
        "assert 'jax' not in sys.modules, 'the span imported jax'\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    proc = subprocess.run([sys.executable, "-c", code], cwd=root, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


# --- drain cursors ----------------------------------------------------------
def test_each_consumer_drains_every_span_once():
    t = tel.Telemetry(enabled=True, span_capacity=16)
    for i in range(3):
        t.record_span(f"s{i}", duration=0.001)
    assert [s["name"] for s in t.drain_spans("storage")] == ["s0", "s1", "s2"]
    assert t.drain_spans("storage") == []
    t.record_span("s3", duration=0.001)
    # The default cursor lost nothing to the storage consumer's drains.
    assert [s["name"] for s in t.drain_spans()] == ["s0", "s1", "s2", "s3"]
    assert [s["name"] for s in t.drain_spans("storage")] == ["s3"]
    t.reset()
    t.record_span("s4", duration=0.001)
    assert [s["name"] for s in t.drain_spans()] == ["s4"]


@pytest.mark.filterwarnings("ignore")
def test_storage_flush_leaves_every_round_to_an_in_process_reader(telemetry_on):
    """Four suggest/observe rounds through the optimize loop's client: the
    producer flushes its spans to storage every round, and a reader of the
    default cursor still sees all four ``producer.round`` spans."""
    from orion_tpu.client.experiment import ExperimentClient
    from orion_tpu.core.experiment import build_experiment

    storage = DocumentStorage(MemoryDB())
    experiment = build_experiment(
        storage, "cursor", priors={"x": "uniform(0, 1)"},
        algorithms={"random": {"seed": 0}}, max_trials=64, pool_size=4,
        metadata={"user": "t"},
    ).instantiate(seed=0)
    client = ExperimentClient(experiment)
    telemetry_on.drain_spans()  # the window starts here
    for _ in range(4):
        trials = client.suggest(4)
        client.observe_all(trials, [float(t.params["x"]) for t in trials])
    seen = [s["name"] for s in telemetry_on.drain_spans()]
    stored = [d["name"] for d in storage.fetch_spans(experiment)]
    assert seen.count("producer.round") == 4
    assert stored.count("producer.round") >= 3
    assert {"producer.update", "producer.suggest", "producer.register",
            "storage.commit"} <= set(seen)


# --- producer ---------------------------------------------------------------
@pytest.mark.filterwarnings("ignore")
def test_duplicate_rows_make_a_counted_retry(telemetry_on):
    from orion_tpu.core.producer import Producer
    from orion_tpu.testing import DumbAlgo  # noqa: F401 (registers dumbalgo)
    from orion_tpu.core.experiment import build_experiment

    storage = DocumentStorage(MemoryDB())
    experiment = build_experiment(
        storage, "dups", priors={"x": "uniform(0, 1)"},
        algorithms={"dumbalgo": {"possible_values": [0.1, 0.1, 0.2, 0.3]}},
        metadata={"user": "t"},
    ).instantiate()
    producer = Producer(experiment)
    producer.update()
    assert producer.produce(2) == 2
    names = [s["name"] for s in telemetry_on.drain_spans()]
    assert names.count("producer.retry") == 1
    assert names.count("producer.round") == 1
    assert telemetry_on.counter_value("producer.duplicate_rows") == 1


# --- algorithm API ----------------------------------------------------------
def _tiny_algo():
    space = build_space({"a": "uniform(0, 1)", "b": "uniform(0, 1)"})
    algo = create_algo(space, {"tpu_bo": dict(
        n_init=4, n_candidates=64, fit_steps=2, trust_region=True, tr_local_m=8,
        prewarm=False)}, seed=0)
    x = np.random.default_rng(0).uniform(size=(8, 2))
    algo.observe([{"a": float(r[0]), "b": float(r[1])} for r in x],
                 [{"objective": float(v)} for v in x.sum(axis=1)])
    return algo


def test_suggest_and_observe_spans_nest_under_the_api(telemetry_on):
    algo = _tiny_algo()
    algo.suggest_batch(4)  # compiles the step: the round below dispatches
    telemetry_on.drain_spans()
    batch = algo.suggest_batch(4)
    algo.observe(batch.params, [{"objective": 1.0}] * 4)
    spans = telemetry_on.drain_spans()
    by_name = {s["name"]: s for s in spans}
    # Children close before their parent.
    assert [s["name"] for s in spans] == [
        "algo.plan", "jax.suggest_step.dispatch", "algo.fetch", "algo.decode",
        "algo.suggest",
        "algo.encode", "algo.encode", "algo.append", "algo.prewarm",
        "algo.trust_region", "algo.observe",
    ]
    outer = by_name["algo.suggest"]
    for child in ("algo.plan", "algo.fetch", "algo.decode"):
        inner = by_name[child]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6


def test_fused_step_carries_its_stage_scopes():
    from orion_tpu.algo.tpu_bo import _suggest_step

    plan = _tiny_algo().fused_step_plan(4)
    text = _suggest_step.lower(*plan.arrays, **plan.statics).as_text(debug_info=True)
    assert "jit__suggest_step" in text
    for scope in ("fit", "candidates", "acquire", "posterior", "select", "health"):
        assert f"/{scope}/" in text, f"no ops under the {scope!r} scope"
