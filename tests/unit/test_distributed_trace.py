"""Distributed tracing end to end: TraceContext propagation over both wire
surfaces, the cross-process merge, flow-event export, critical-path
attribution, and the netdb wire-compatibility (downgrade) pins.

The heavyweight legs:

- a TWO-PROCESS test — a subprocess worker produces rounds over a netdb
  server owned by this process; the client's ``storage.commit`` span and
  the server's ``netdb.apply`` span must share a trace_id WITH parent
  linkage after the ``--distributed`` merge (and the CLI renders it);
- the SERVE join — RemoteAlgorithm suggest, the gateway's coalesced
  dispatch (link), and the storage commit's server-side apply joined by
  trace_id with flow events (the ISSUE-11 acceptance path, in-process so
  it runs on tier-1 budget).
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from orion_tpu import telemetry as tel
from orion_tpu.telemetry import (
    TELEMETRY,
    Telemetry,
    TraceContext,
    chrome_trace_events,
    current_trace_context,
    set_trace_context,
    trace_scope,
)
from orion_tpu.tracing import (
    SERVER_EXPERIMENT,
    attribute_traces,
    collect_distributed_spans,
    summarize_attribution,
)


@pytest.fixture
def enabled_telemetry():
    """Enable the process registry for one test, restoring (and draining)
    afterwards so trace records never leak across tests."""
    was = TELEMETRY.enabled
    TELEMETRY.enable()
    yield TELEMETRY
    TELEMETRY.drain_spans()
    if not was:
        TELEMETRY.disable()
    set_trace_context(None)


# --- TraceContext unit behavior ---------------------------------------------
def test_trace_context_ids_and_child():
    ctx = TraceContext()
    assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
    child = ctx.child()
    assert child.trace_id == ctx.trace_id
    assert child.span_id != ctx.span_id
    wire = ctx.to_wire()
    back = TraceContext.from_wire(wire)
    assert back.trace_id == ctx.trace_id and back.span_id == ctx.span_id
    # Tolerant adoption: garbage never raises.
    assert TraceContext.from_wire(None) is None
    assert TraceContext.from_wire({"trace_id": 7}) is None
    assert TraceContext.from_wire("nope") is None


def test_root_span_starts_trace_and_children_nest():
    t = Telemetry(enabled=True)
    with t.span("round", root=True) as root:
        assert current_trace_context() is root.ctx
        with t.span("inner") as inner:
            assert inner.ctx.trace_id == root.ctx.trace_id
    assert current_trace_context() is None
    spans = {s["name"]: s for s in t.iter_spans()}
    assert spans["inner"]["parent_span_id"] == spans["round"]["span_id"]
    assert spans["inner"]["trace_id"] == spans["round"]["trace_id"]
    assert "parent_span_id" not in spans["round"]


def test_root_span_under_foreign_ambient_has_no_parent():
    """A root span STARTS its trace even when an embedder's unrelated
    ambient context is set: no parent_span_id into the foreign trace (the
    attribution root-finding depends on it)."""
    t = Telemetry(enabled=True)
    foreign = TraceContext()
    set_trace_context(foreign)
    try:
        with t.span("producer.round", root=True) as root:
            assert root.ctx.trace_id != foreign.trace_id
    finally:
        set_trace_context(None)
    record = t.iter_spans()[0]
    assert record["trace_id"] != foreign.trace_id
    assert "parent_span_id" not in record
    assert attribute_traces([record])  # the round still has a root


def test_null_span_exposes_ctx():
    """The enabled check and span() can race a concurrent disable(): the
    shared no-op span must answer .ctx like a real one, not AttributeError."""
    t = Telemetry(enabled=False)
    span = t.span("anything")
    with span as entered:
        assert entered.ctx is None


def test_spans_without_ambient_context_stay_untraced():
    t = Telemetry(enabled=True)
    with t.span("plain"):
        pass
    t.record_span("explicit", duration=0.001)
    for span in t.iter_spans():
        assert "trace_id" not in span and "span_id" not in span


def test_record_span_parent_ctx_and_links_and_track():
    t = Telemetry(enabled=True)
    parent = TraceContext()
    t.record_span("adopted", duration=0.001, parent_ctx=parent, track="netdb:x:1")
    t.record_span(
        "linked", duration=0.001, links=[parent, {"trace_id": "t", "span_id": "s"}]
    )
    adopted, linked = t.iter_spans()
    assert adopted["trace_id"] == parent.trace_id
    assert adopted["parent_span_id"] == parent.span_id
    assert adopted["worker"] == "netdb:x:1"
    assert len(adopted["span_id"]) == 16
    assert linked["links"][0]["span_id"] == parent.span_id
    assert linked["links"][1] == {"trace_id": "t", "span_id": "s"}


def test_trace_scope_adopts_and_restores():
    outer = TraceContext()
    set_trace_context(outer)
    try:
        inner = TraceContext()
        with trace_scope(inner):
            assert current_trace_context() is inner
        assert current_trace_context() is outer
        with trace_scope(None):
            assert current_trace_context() is outer
    finally:
        set_trace_context(None)


def test_chrome_flow_events_cross_track_and_links():
    parent = TraceContext()
    spans = [
        {
            "name": "client.op", "ts": 1.0, "dur": 0.5, "pid": 1, "tid": 1,
            "trace_id": parent.trace_id, "span_id": parent.span_id,
        },
        {
            "name": "server.apply", "ts": 1.1, "dur": 0.1, "pid": 9, "tid": 2,
            "worker": "netdb:h:9", "trace_id": parent.trace_id,
            "span_id": "s" * 16, "parent_span_id": parent.span_id,
        },
        # Same-track child: slice nesting, NO flow arrow.
        {
            "name": "client.child", "ts": 1.2, "dur": 0.1, "pid": 1, "tid": 1,
            "trace_id": parent.trace_id, "span_id": "c" * 16,
            "parent_span_id": parent.span_id,
        },
        # Links-only span (the gateway dispatch shape): arrow regardless.
        {
            "name": "serve.dispatch", "ts": 1.3, "dur": 0.2, "pid": 9,
            "tid": 3, "worker": "gateway:h:9",
            "links": [{"trace_id": parent.trace_id, "span_id": parent.span_id}],
        },
    ]
    events = chrome_trace_events(spans)
    flows = [e for e in events if e.get("cat") == "flow"]
    starts = [e for e in flows if e["ph"] == "s"]
    finishes = [e for e in flows if e["ph"] == "f"]
    assert len(starts) == 2 and len(finishes) == 2
    by_id = {e["id"]: e for e in starts}
    for finish in finishes:
        start = by_id[finish["id"]]
        assert start["pid"] != finish["pid"]  # every arrow crosses tracks
        assert start["args"]["trace_id"] == parent.trace_id


def test_attribution_buckets_and_summary():
    trace = "t" * 32
    spans = [
        {"name": "producer.round", "ts": 0.0, "dur": 0.1, "pid": 1, "tid": 1,
         "trace_id": trace, "span_id": "root000000000000"},
        {"name": "storage.commit", "ts": 0.01, "dur": 0.04, "pid": 1, "tid": 1,
         "trace_id": trace, "span_id": "commit0000000000",
         "parent_span_id": "root000000000000"},
        {"name": "netdb.apply", "ts": 0.02, "dur": 0.01, "pid": 9, "tid": 2,
         "worker": "netdb:h:9", "trace_id": trace, "span_id": "apply00000000000",
         "parent_span_id": "commit0000000000"},
        {"name": "device.dispatch", "ts": 0.05, "dur": 0.02, "pid": 1, "tid": 1,
         "trace_id": trace, "span_id": "dev0000000000000",
         "parent_span_id": "root000000000000"},
    ]
    buckets = attribute_traces(spans)[trace]
    assert buckets["root"] == "producer.round"
    assert buckets["total_ms"] == pytest.approx(100.0)
    assert buckets["server_host_ms"] == pytest.approx(10.0)
    # wire = client commit (40ms) - nested server apply (10ms).
    assert buckets["wire_ms"] == pytest.approx(30.0)
    assert buckets["device_ms"] == pytest.approx(20.0)
    assert buckets["client_host_ms"] == pytest.approx(40.0)
    summary = summarize_attribution(spans, root_name="producer.round")
    assert summary["traces"] == 1 and summary["total_ms"] == pytest.approx(100.0)
    # A rootless trace is skipped, not misattributed.
    assert attribute_traces(spans[1:2]) == {}


# --- netdb wire compatibility (downgrade pins) ------------------------------
def _pre_upgrade_server():
    """A minimal PRE-UPGRADE netdb server: newline-framed JSON dispatch
    reading ONLY op/args/kwargs — exactly the old handler's key accesses —
    so a ctx-bearing request exercises the 'unknown top-level key is
    ignored' contract for real."""
    import socketserver

    from orion_tpu.storage.documents import MemoryDB
    from orion_tpu.storage.netdb import _dumps, _read_line

    class Handler(socketserver.StreamRequestHandler):
        def handle(self):
            while True:
                try:
                    request = _read_line(self.rfile)
                except Exception:
                    return
                if request is None:
                    return
                op = request.get("op")
                if op == "ping":
                    self.wfile.write(_dumps({"ok": True, "result": "pong"}))
                    continue
                try:
                    method = getattr(self.server.db, op)
                    result = method(
                        *request.get("args", []), **request.get("kwargs", {})
                    )
                    self.wfile.write(_dumps({"ok": True, "result": result}))
                except Exception as exc:
                    self.wfile.write(
                        _dumps(
                            {"ok": False, "error": type(exc).__name__,
                             "message": str(exc)}
                        )
                    )

    class Server(socketserver.ThreadingTCPServer):
        allow_reuse_address = True
        daemon_threads = True

    server = Server(("127.0.0.1", 0), Handler)
    server.db = MemoryDB()
    import threading

    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server


def test_new_client_with_ctx_against_pre_upgrade_server(enabled_telemetry):
    from orion_tpu.storage.netdb import NetworkDB

    server = _pre_upgrade_server()
    host, port = server.server_address[:2]
    db = NetworkDB(host=host, port=port)
    try:
        set_trace_context(TraceContext())  # the client WILL inject ctx
        assert db.write("things", {"a": 1}) == 1
        assert db.read("things", {"a": 1})[0]["a"] == 1
        assert db.count("things") == 1
        # The injected field really was on the wire for the write path.
        ctx = current_trace_context()
        assert ctx is not None and db._wire_request("write", [], {}).get("ctx")
    finally:
        set_trace_context(None)
        db.close()
        server.shutdown()
        server.server_close()


def test_pre_upgrade_client_without_ctx_against_new_server(enabled_telemetry):
    from orion_tpu.storage.netdb import DBServer, NetworkDB

    server = DBServer(port=0)
    host, port = server.serve_background()
    db = NetworkDB(host=host, port=port)
    try:
        # No ambient context = the exact envelope a pre-upgrade client
        # sends (no ctx key): everything works, the server adopts nothing.
        assert current_trace_context() is None
        assert "ctx" not in db._wire_request("write", [], {})
        assert db.write("things", {"b": 2}) == 1
        assert db.read("things", {"b": 2})[0]["b"] == 2
        assert server._span_tel.iter_spans() == []
    finally:
        db.close()
        server.shutdown()
        server.server_close()


def test_ctx_field_does_not_leak_into_db_ops(enabled_telemetry):
    """The server must pass ONLY args/kwargs to the backend — the ctx
    field is transport metadata, never document data."""
    from orion_tpu.storage.netdb import DBServer, NetworkDB

    server = DBServer(port=0)
    host, port = server.serve_background()
    db = NetworkDB(host=host, port=port)
    try:
        set_trace_context(TraceContext())
        db.write("things", {"c": 3})
        docs = db.read("things", {"c": 3})
        assert docs and "ctx" not in docs[0]
        # And the adoption DID happen: the server recorded an apply span.
        server.flush_server_spans(force=True)
        spans = server.db.read("spans", {"experiment": SERVER_EXPERIMENT})
        assert any(s["name"] == "netdb.apply" for s in spans)
    finally:
        set_trace_context(None)
        db.close()
        server.shutdown()
        server.server_close()


def test_server_span_channel_is_capped(enabled_telemetry):
    """The __server__ span channel must not grow forever: past the cap the
    flush prunes the oldest down to 90% (hysteresis)."""
    from orion_tpu.storage.netdb import DBServer

    server = DBServer(port=0)
    server.serve_background()
    server.SERVER_SPANS_CAP = 50
    try:
        ctx = TraceContext()
        for index in range(80):
            server._span_tel.record_span(
                "netdb.apply", duration=0.001, parent_ctx=ctx
            )
            if index % 20 == 19:
                server.flush_server_spans(force=True)
        server.flush_server_spans(force=True)
        remaining = server.db.count("spans", {"experiment": SERVER_EXPERIMENT})
        assert remaining <= 50
        assert remaining >= 40  # hysteresis keeps ~90%, never over-prunes
    finally:
        server.shutdown()
        server.server_close()


# --- the two-process distributed trace --------------------------------------
_WORKER_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    from orion_tpu.core.experiment import build_experiment
    from orion_tpu.core.producer import Producer
    from orion_tpu.storage.base import DocumentStorage
    from orion_tpu.storage.netdb import NetworkDB

    host, port = os.environ["NETDB_ADDR"].split(":")
    storage = DocumentStorage(NetworkDB(host=host, port=int(port)))
    experiment = build_experiment(
        storage,
        "dist-trace",
        priors={"x0": "uniform(0, 1)", "x1": "uniform(0, 1)"},
        algorithms={"random": {"seed": 0}},
        metadata={"user": "u"},
    )
    experiment.instantiate(seed=0)
    producer = Producer(experiment)
    for _ in range(2):
        producer.update()
        producer.produce(8)
    producer._flush_timings(force_metrics=True)
    print("WORKER_OK")
    """
)


def test_two_process_distributed_trace_merge(enabled_telemetry, tmp_path):
    """A subprocess worker produces over THIS process's netdb server; the
    merged trace joins the worker's storage.commit to the server's
    netdb.apply with exact parent linkage, and the trace CLI renders the
    distributed file with flow events."""
    from orion_tpu.core.experiment import build_experiment
    from orion_tpu.storage.base import DocumentStorage
    from orion_tpu.storage.netdb import DBServer, NetworkDB

    server = DBServer(port=0)
    host, port = server.serve_background()
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        ORION_TPU_TELEMETRY="1",
        NETDB_ADDR=f"{host}:{port}",
    )
    proc = subprocess.run(
        [sys.executable, "-c", _WORKER_SCRIPT],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "WORKER_OK" in proc.stdout
    server.flush_server_spans(force=True)

    db = NetworkDB(host=host, port=port)
    storage = DocumentStorage(db)
    try:
        experiment = build_experiment(storage, "dist-trace")
        spans = collect_distributed_spans(storage, experiment)
        commits = [
            s for s in spans
            if s["name"] == "storage.commit" and s.get("trace_id")
        ]
        applies = [s for s in spans if s["name"] == "netdb.apply"]
        assert commits and applies
        # Distinct processes really met in one trace:
        assert {s["worker"] for s in applies} != {s["worker"] for s in commits}
        by_id = {s.get("span_id"): s for s in spans if s.get("span_id")}
        joined = [
            (commit, apply)
            for apply in applies
            for commit in [by_id.get(apply.get("parent_span_id"))]
            if commit is not None
            and commit["name"].startswith("storage.")
            and commit["trace_id"] == apply["trace_id"]
        ]
        assert joined, "no netdb.apply parented at a client storage op span"
        # And the producer.round root exists for attribution.
        summary = summarize_attribution(spans, root_name="producer.round")
        assert summary["traces"] >= 1
        assert summary["wire_ms"] >= 0 and summary["server_host_ms"] > 0
    finally:
        db.close()

    # The CLI end of it: --distributed writes a Perfetto file with flows.
    config = tmp_path / "net.yaml"
    config.write_text(
        f"database:\n  type: network\n  host: {host}\n  port: {port}\n"
    )
    out = tmp_path / "dist.json"
    from orion_tpu.cli import main as cli_main

    rc = cli_main(
        [
            "trace", "-n", "dist-trace", "-c", str(config),
            "--distributed", "--out", str(out),
        ]
    )
    assert rc == 0
    events = json.load(open(out))["traceEvents"]
    assert any(e["name"] == "netdb.apply" for e in events)
    starts = {e["id"] for e in events if e.get("ph") == "s"}
    finishes = {e["id"] for e in events if e.get("ph") == "f"}
    assert starts & finishes, "no flow arrows in the distributed trace"
    # --attribute prints the table AND still writes the file (a scripted
    # pipeline passing --out must always find its artifact).
    attr_out = tmp_path / "attr.json"
    rc = cli_main(
        [
            "trace", "-n", "dist-trace", "-c", str(config),
            "--attribute", "--out", str(attr_out),
        ]
    )
    assert rc == 0
    assert attr_out.exists()
    server.shutdown()
    server.server_close()


# --- the serve join (ISSUE-11 acceptance, in-process) -----------------------
def test_serve_distributed_trace_joins_suggest_dispatch_apply(
    enabled_telemetry,
):
    """RemoteAlgorithm suggest + gateway coalesced-dispatch link + netdb
    server-side apply share one trace, with >= 1 flow pair — the exact
    gate `bench.py --serve --smoke` hard-asserts, run here on the tier-1
    budget (small fused shapes, one tenant stream)."""
    import jax.numpy as jnp

    import orion_tpu.benchmarks.functions as bench_fns
    from bench import assert_joined_serve_trace
    from orion_tpu.client.experiment import ExperimentClient
    from orion_tpu.core.experiment import build_experiment
    from orion_tpu.serve.gateway import GatewayServer
    from orion_tpu.storage.base import DocumentStorage
    from orion_tpu.storage.netdb import DBServer, NetworkDB

    db_server = DBServer(port=0)
    host, port = db_server.serve_background()
    net_db = NetworkDB(host=host, port=port)
    storage = DocumentStorage(net_db)
    gateway = GatewayServer(window=0.05, max_width=2)
    ghost, gport = gateway.serve_background()
    try:
        experiment = build_experiment(
            storage,
            "serve-trace",
            priors={f"x{j}": "uniform(0, 1)" for j in range(3)},
            algorithms={
                "tpu_bo": {"n_init": 4, "n_candidates": 64, "fit_steps": 2}
            },
            pool_size=4,
            metadata={"user": "u"},
        )
        experiment.serve_config = {"address": f"{ghost}:{gport}"}
        experiment.instantiate(seed=0)
        client = ExperimentClient(experiment)
        for _ in range(3):
            trials = client.suggest(4)
            rows = np.asarray(
                [[t.params[f"x{j}"] for j in range(3)] for t in trials],
                dtype=np.float32,
            )
            padded = jnp.concatenate(
                [jnp.asarray(rows), jnp.zeros((len(trials), 3))], axis=1
            )
            objectives = [float(v) for v in np.asarray(bench_fns.hartmann6(padded))]
            client.observe_all(trials, objectives)
        db_server.flush_server_spans(force=True)
        server_spans = storage.fetch_spans(SERVER_EXPERIMENT)
        spans = [s for s in tel.TELEMETRY.iter_spans() if s] + list(server_spans)
        joined = assert_joined_serve_trace(spans)
        assert joined["joined_traces"] >= 1 and joined["flow_pairs"] >= 1
    finally:
        gateway.shutdown()
        gateway.server_close()
        net_db.close()
        db_server.shutdown()
        db_server.server_close()
