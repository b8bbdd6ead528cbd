"""Storage contract tests, run over both backends.

Parity model: reference tests/unittests/storage/test_storage.py (protocol
contract under OrionState) + core/test_ephemeraldb.py / test_pickleddb.py.
"""

import multiprocessing
import time

import pytest

from orion_tpu.core.trial import Trial
from orion_tpu.storage import MemoryDB, PickledDB, create_storage
from orion_tpu.storage.base import BaseStorage, DocumentStorage, ReadOnlyStorage
from orion_tpu.utils.exceptions import DuplicateKeyError, FailedUpdate


@pytest.fixture(params=["memory", "pickled", "sqlite", "network"])
def storage(request, tmp_path):
    if request.param == "memory":
        yield create_storage({"type": "memory"})
        return
    if request.param == "pickled":
        yield create_storage({"type": "pickled", "path": str(tmp_path / "db.pkl")})
        return
    if request.param == "sqlite":
        yield create_storage({"type": "sqlite", "path": str(tmp_path / "db.sqlite")})
        return
    from orion_tpu.storage import DBServer

    # The contract suite runs the network backend AUTHENTICATED, so every
    # protocol op is exercised through the HMAC handshake path.
    server = DBServer(port=0, secret="contract-secret")
    host, port = server.serve_background()
    yield create_storage(
        {"type": "network", "host": host, "port": port, "secret": "contract-secret"}
    )
    server.shutdown()
    server.server_close()


def new_trial(i=0, experiment="exp-id", **kw):
    return Trial(experiment=experiment, params={"x": float(i)}, **kw)


# --- document DB semantics -------------------------------------------------


def test_db_write_read_count_remove():
    db = MemoryDB()
    db.write("c", {"a": 1, "b": {"c": 2}})
    db.write("c", {"a": 2, "b": {"c": 3}})
    assert db.count("c") == 2
    assert db.count("c", {"a": 1}) == 1
    assert db.read("c", {"b.c": {"$gte": 3}})[0]["a"] == 2
    assert db.read("c", {"a": {"$in": [2, 5]}})[0]["a"] == 2
    assert db.read("c", {"a": {"$ne": 2}})[0]["a"] == 1
    db.remove("c", {"a": 1})
    assert db.count("c") == 1


def test_db_update_with_query():
    db = MemoryDB()
    db.write("c", {"a": 1, "st": "new"})
    db.write("c", {"a": 2, "st": "new"})
    n = db.write("c", {"st": "old"}, query={"st": "new"})
    assert n == 2
    assert db.count("c", {"st": "old"}) == 2


def test_update_many_contract(storage):
    """Batched per-document updates (`db upgrade`'s migration path): every
    backend applies the pairs in order, returns the total matched count,
    and pays one lock/transaction/round-trip for the whole batch.
    (Mid-batch FAILURE state is deliberately backend-dependent — memory
    keeps the prefix, pickled/SQLite discard the batch, network drains
    everything; see MemoryDB.update_many's docstring — callers re-run
    idempotently.)"""
    db = storage.db
    ids = db.write("c", [{"k": i, "v": "old"} for i in range(4)])
    n = db.update_many(
        "c",
        [({"_id": ids[i]}, {"v": f"new{i}"}) for i in range(3)]
        + [({"k": 99}, {"v": "none"})],  # no match: counts 0, not an error
    )
    assert n == 3
    docs = {d["k"]: d["v"] for d in db.read("c")}
    assert docs == {0: "new0", 1: "new1", 2: "new2", 3: "old"}
    assert db.update_many("c", []) == 0


def test_db_projection():
    db = MemoryDB()
    db.write("c", {"a": 1, "b": {"c": 2, "d": 3}})
    out = db.read("c", projection={"b.c": 1})
    assert out[0]["b"] == {"c": 2}
    assert "a" not in out[0]
    assert "_id" in out[0]


def test_db_unique_index():
    db = MemoryDB()
    db.ensure_index("c", ["name", "version"], unique=True)
    db.write("c", {"name": "n", "version": 1})
    with pytest.raises(DuplicateKeyError):
        db.write("c", {"name": "n", "version": 1})
    db.write("c", {"name": "n", "version": 2})


def test_db_index_redefined_non_unique_stops_enforcing():
    db = MemoryDB()
    db.ensure_index("c", ["name"], unique=True)
    db.ensure_index("c", ["name"], unique=False)
    db.write("c", {"name": "n"})
    db.write("c", {"name": "n"})  # must not raise
    assert db.count("c", {"name": "n"}) == 2


def test_db_read_and_write_atomic_semantics():
    db = MemoryDB()
    db.write("c", {"a": 1, "st": "new"})
    doc = db.read_and_write("c", {"st": "new"}, {"st": "go"})
    assert doc["st"] == "go"
    assert db.read_and_write("c", {"st": "new"}, {"st": "go"}) is None


def test_pickled_persists_across_instances(tmp_path):
    path = str(tmp_path / "db.pkl")
    db1 = PickledDB(path)
    db1.write("c", {"a": 1})
    db2 = PickledDB(path)
    assert db2.count("c") == 1


# --- storage protocol ------------------------------------------------------


def test_experiment_unique_name_version(storage):
    storage.create_experiment({"name": "n", "version": 1})
    with pytest.raises(DuplicateKeyError):
        storage.create_experiment({"name": "n", "version": 1})
    storage.create_experiment({"name": "n", "version": 2})
    assert len(storage.fetch_experiments({"name": "n"})) == 2


def test_register_and_fetch_trials(storage):
    for i in range(3):
        storage.register_trial(new_trial(i))
    trials = storage.fetch_trials(uid="exp-id")
    assert len(trials) == 3
    assert all(t.status == "new" for t in trials)
    assert all(t.submit_time is not None for t in trials)


def test_register_duplicate_trial_raises(storage):
    storage.register_trial(new_trial(1))
    with pytest.raises(DuplicateKeyError):
        storage.register_trial(new_trial(1))


def test_reserve_trial_claims_each_once(storage):
    for i in range(2):
        storage.register_trial(new_trial(i))
    t1 = storage.reserve_trial("exp-id")
    t2 = storage.reserve_trial("exp-id")
    t3 = storage.reserve_trial("exp-id")
    assert t1.status == t2.status == "reserved"
    assert {t1.id, t2.id} == {t.id for t in storage.fetch_trials(uid="exp-id")}
    assert t3 is None


def test_cas_status_update(storage):
    trial = storage.register_trial(new_trial())
    storage.set_trial_status(trial, "reserved", was="new")
    with pytest.raises(FailedUpdate):
        storage.set_trial_status(trial, "completed", was="new")
    storage.set_trial_status(trial, "completed", was="reserved")
    assert storage.get_trial(uid=trial.id).status == "completed"
    assert storage.get_trial(uid=trial.id).end_time is not None


def test_heartbeat_and_lost_trials(storage):
    trial = storage.register_trial(new_trial())
    reserved = storage.reserve_trial("exp-id")
    assert storage.fetch_lost_trials("exp-id", timeout=1000.0) == []
    # Backdate the heartbeat directly to simulate a dead worker.
    storage.db.write("trials", {"heartbeat": time.time() - 9999}, {"_id": trial.id})
    lost = storage.fetch_lost_trials("exp-id", timeout=120.0)
    assert [t.id for t in lost] == [reserved.id]
    storage.update_heartbeat(reserved)
    assert storage.fetch_lost_trials("exp-id", timeout=120.0) == []


def test_heartbeat_fails_on_unreserved(storage):
    trial = storage.register_trial(new_trial())
    with pytest.raises(FailedUpdate):
        storage.update_heartbeat(trial)


def test_update_completed_trial(storage):
    from orion_tpu.core.trial import Result

    storage.register_trial(new_trial())
    trial = storage.reserve_trial("exp-id")
    storage.update_completed_trial(trial, [Result("loss", "objective", 0.5)])
    stored = storage.get_trial(uid=trial.id)
    assert stored.status == "completed"
    assert stored.objective.value == 0.5
    assert storage.count_completed_trials("exp-id") == 1


def test_lies_are_separate(storage):
    lie = new_trial(results=[{"name": "o", "type": "lie", "value": 1.0}])
    storage.register_lie(lie)
    assert storage.fetch_trials(uid="exp-id") == []
    lies = storage.fetch_lies("exp-id")
    assert len(lies) == 1
    assert lies[0].lie.value == 1.0


def test_counts_and_noncompleted(storage):
    for i in range(3):
        storage.register_trial(new_trial(i))
    t = storage.reserve_trial("exp-id")
    storage.set_trial_status(t, "broken", was="reserved")
    assert storage.count_broken_trials("exp-id") == 1
    assert storage.count_completed_trials("exp-id") == 0
    assert len(storage.fetch_noncompleted_trials("exp-id")) == 3


def test_readonly_storage_blocks_writes(storage):
    ro = ReadOnlyStorage(storage)
    assert ro.fetch_trials(uid="exp-id") == []
    with pytest.raises(AttributeError):
        ro.register_trial(new_trial())


# --- multiprocess safety ---------------------------------------------------


def _worker_reserve(config, out_queue):
    storage = create_storage(config)
    claimed = []
    while True:
        trial = storage.reserve_trial("exp-id")
        if trial is None:
            break
        claimed.append(trial.id)
    out_queue.put(claimed)


@pytest.mark.parametrize("db_type", ["pickled", "sqlite"])
def test_concurrent_reservation_no_double_claims(tmp_path, db_type):
    """N processes hammer reserve_trial; every trial is claimed exactly once."""
    config = {"type": db_type, "path": str(tmp_path / f"db.{db_type}")}
    storage = create_storage(config)
    all_ids = set()
    for i in range(20):
        t = new_trial(i)
        storage.register_trial(t)
        all_ids.add(t.id)

    ctx = multiprocessing.get_context("spawn")
    queue = ctx.Queue()
    procs = [ctx.Process(target=_worker_reserve, args=(config, queue)) for _ in range(4)]
    for p in procs:
        p.start()
    results = [queue.get(timeout=60) for _ in procs]
    for p in procs:
        p.join(timeout=60)

    flat = [tid for chunk in results for tid in chunk]
    assert len(flat) == 20
    assert set(flat) == all_ids


# --- regression tests from review findings ---------------------------------


def test_update_preserves_dotted_document_keys():
    db = MemoryDB()
    db.write("c", {"_id": "t", "params": {"opt.lr": 1}, "status": "new"})
    db.read_and_write("c", {"_id": "t"}, {"status": "reserved"})
    doc = db.read("c", {"_id": "t"})[0]
    assert doc["params"] == {"opt.lr": 1}


def test_update_dotted_key_over_scalar_parent():
    db = MemoryDB()
    db.write("c", {"_id": "t", "worker": 5})
    db.read_and_write("c", {"_id": "t"}, {"worker.pid": 1})
    assert db.read("c", {"_id": "t"})[0]["worker"] == {"pid": 1}


def test_update_experiment_requires_selector(storage):
    from orion_tpu.utils.exceptions import DatabaseError

    with pytest.raises(DatabaseError):
        storage.update_experiment(status="done")


def test_set_trial_status_guards_by_default(storage):
    trial = storage.register_trial(new_trial())
    other_view = storage.get_trial(uid=trial.id)
    storage.set_trial_status(trial, "reserved")  # guard = in-memory "new"
    with pytest.raises(FailedUpdate):
        storage.set_trial_status(other_view, "completed")  # stale view: still "new"


def test_projection_preserves_dotted_keys_and_id_only():
    db = MemoryDB()
    db.write("c", {"_id": "t", "params": {"opt.lr": 1}, "other": 2})
    out = db.read("c", projection={"params": 1})
    assert out[0]["params"] == {"opt.lr": 1}
    only_id = db.read("c", projection={"_id": 1})
    assert only_id == [{"_id": "t"}]

# --- network backend (reference MongoDB driver parity) ----------------------


def _net_worker_reserve(host, port, out_queue):
    storage = create_storage(
        {"type": "network", "host": host, "port": port, "secret": "mp-secret"}
    )
    claimed = []
    while True:
        trial = storage.reserve_trial("exp-id")
        if trial is None:
            break
        claimed.append(trial.id)
    out_queue.put(claimed)


def _run_network_reservation_race(worker_fn):
    """Shared driver: 4 client processes against one AUTHENTICATED server
    must claim the 20 trials exactly once between them — the multi-node
    equivalent of the pickled flock test, HMAC handshake in every process."""
    from orion_tpu.storage import DBServer

    server = DBServer(port=0, secret="mp-secret")
    host, port = server.serve_background()
    try:
        storage = create_storage(
            {"type": "network", "host": host, "port": port, "secret": "mp-secret"}
        )
        all_ids = set()
        for i in range(20):
            t = new_trial(i)
            storage.register_trial(t)
            all_ids.add(t.id)

        ctx = multiprocessing.get_context("spawn")
        queue = ctx.Queue()
        procs = [
            ctx.Process(target=worker_fn, args=(host, port, queue))
            for _ in range(4)
        ]
        for p in procs:
            p.start()
        results = [queue.get(timeout=60) for _ in procs]
        for p in procs:
            p.join(timeout=60)

        flat = [tid for chunk in results for tid in chunk]
        assert len(flat) == 20, "a trial was double-claimed or lost"
        assert set(flat) == all_ids
    finally:
        server.shutdown()
        server.server_close()


def test_network_concurrent_reservation_across_processes():
    _run_network_reservation_race(_net_worker_reserve)


def test_network_server_persistence_across_restarts(tmp_path):
    """--persist lets the server restart without losing the experiment."""
    from orion_tpu.storage import DBServer

    snapshot = str(tmp_path / "snap.pkl")
    server = DBServer(port=0, persist=snapshot)
    host, port = server.serve_background()
    storage = create_storage({"type": "network", "host": host, "port": port})
    trial = new_trial(1)
    storage.register_trial(trial)
    server.shutdown()
    server.server_close()

    server2 = DBServer(port=0, persist=snapshot)
    host2, port2 = server2.serve_background()
    try:
        storage2 = create_storage({"type": "network", "host": host2, "port": port2})
        fetched = storage2.fetch_trials(uid="exp-id")
        assert [t.id for t in fetched] == [trial.id]
    finally:
        server2.shutdown()
        server2.server_close()


def test_network_duplicate_key_crosses_the_wire():
    from orion_tpu.storage import DBServer

    server = DBServer(port=0)
    host, port = server.serve_background()
    try:
        storage = create_storage({"type": "network", "host": host, "port": port})
        trial = new_trial(3)
        storage.register_trial(trial)
        with pytest.raises(DuplicateKeyError):
            storage.register_trial(new_trial(3))
    finally:
        server.shutdown()
        server.server_close()


def test_network_client_reconnects_after_server_restart(tmp_path):
    """Reconnection re-runs the auth handshake transparently."""
    from orion_tpu.storage import DBServer, NetworkDB

    snapshot = str(tmp_path / "snap.pkl")
    server = DBServer(port=0, persist=snapshot, secret="s3cret")
    host, port = server.serve_background()
    db = NetworkDB(host=host, port=port, secret="s3cret")
    db.write("c", {"_id": 1, "v": 1})
    server.shutdown()
    server.server_close()

    # Restart on the SAME port so the same client handle keeps working.
    server2 = DBServer(host=host, port=port, persist=snapshot, secret="s3cret")
    server2.serve_background()
    try:
        assert db.read("c", {"_id": 1})[0]["v"] == 1
    finally:
        server2.shutdown()
        server2.server_close()


def test_network_auth_rejects_wrong_and_missing_secret():
    """A wrong-secret client gets a clean AuthenticationError (not a
    traceback or a hang); a no-secret client is rejected on its first op;
    ping stays open for health checks."""
    from orion_tpu.storage import DBServer, NetworkDB
    from orion_tpu.utils.exceptions import AuthenticationError

    server = DBServer(port=0, secret="right-secret")
    host, port = server.serve_background()
    try:
        wrong = NetworkDB(host=host, port=port, secret="wrong-secret")
        with pytest.raises(AuthenticationError):
            wrong.read("c")
        missing = NetworkDB(host=host, port=port)
        assert missing.ping()  # health checks need no credentials
        with pytest.raises(AuthenticationError):
            missing.read("c")
        # The right secret works on the very same server afterwards.
        good = NetworkDB(host=host, port=port, secret="right-secret")
        good.write("c", {"_id": 1, "v": 1})
        assert good.read("c", {"_id": 1})[0]["v"] == 1
    finally:
        server.shutdown()
        server.server_close()


def test_network_auth_mismatched_secrets_fail_cleanly():
    """Client and server with different secrets: clean AuthenticationError
    at the handshake (client proves first, so the server rejects)."""
    from orion_tpu.storage import DBServer, NetworkDB
    from orion_tpu.utils.exceptions import AuthenticationError

    server = DBServer(port=0, secret="server-side-secret")
    host, port = server.serve_background()
    try:
        client = NetworkDB(host=host, port=port, secret="client-side-secret")
        with pytest.raises(AuthenticationError, match="bad credentials"):
            client.read("c")
    finally:
        server.shutdown()
        server.server_close()


def test_network_auth_client_refuses_open_server_downgrade():
    """A secret-configured client must NOT silently proceed against a
    server that claims no auth (DNS hijack / typoed port would otherwise
    hand all experiment data to whoever answered)."""
    from orion_tpu.storage import DBServer, NetworkDB
    from orion_tpu.utils.exceptions import AuthenticationError

    server = DBServer(port=0)  # open server
    host, port = server.serve_background()
    try:
        client = NetworkDB(host=host, port=port, secret="my-secret")
        with pytest.raises(AuthenticationError, match="does not require"):
            client.read("c")
    finally:
        server.shutdown()
        server.server_close()


def test_network_address_forms():
    from orion_tpu.storage.base import _parse_network_address
    from orion_tpu.utils.exceptions import DatabaseError as DBErr

    assert _parse_network_address({"address": "hostA:9000"}) == ("hostA", 9000)
    assert _parse_network_address({"address": "hostA"}) == ("hostA", 8765)
    assert _parse_network_address({"host": "h", "port": 1234}) == ("h", 1234)
    with pytest.raises(DBErr):
        _parse_network_address({"address": "hostA:"})


def test_network_mutation_succeeds_after_idle_restart(tmp_path):
    """A mutation on a connection that idled across a server restart must be
    probed-and-reconnected, not failed (the restart-while-idle case)."""
    from orion_tpu.storage import DBServer, NetworkDB

    snapshot = str(tmp_path / "snap.pkl")
    server = DBServer(port=0, persist=snapshot)
    host, port = server.serve_background()
    db = NetworkDB(host=host, port=port, idle_probe=0.05)
    db.write("c", {"_id": 1, "v": 1})
    server.shutdown()
    server.server_close()

    server2 = DBServer(host=host, port=port, persist=snapshot)
    server2.serve_background()
    try:
        time.sleep(0.1)  # idle past the probe threshold
        db.write("c", {"_id": 2, "v": 2})  # mutation, not a read
        assert db.count("c") == 2
    finally:
        server2.shutdown()
        server2.server_close()


def test_network_server_flushes_snapshot_on_shutdown(tmp_path):
    import pickle

    from orion_tpu.storage import DBServer, NetworkDB

    snapshot = str(tmp_path / "snap.pkl")
    server = DBServer(port=0, persist=snapshot, persist_interval=60.0)
    host, port = server.serve_background()
    NetworkDB(host=host, port=port).write("c", {"_id": 1})
    # Interval is 60s so only the shutdown flush can have written it.
    server.shutdown()
    server.server_close()
    with open(snapshot, "rb") as fh:
        assert pickle.load(fh).count("c") == 1


def test_env_address_overrides_config_host(monkeypatch):
    from orion_tpu.config import _env_config, merge_configs

    monkeypatch.setenv("ORION_DB_TYPE", "network")
    monkeypatch.setenv("ORION_DB_ADDRESS", "hostA:9100")
    merged = merge_configs(
        {"storage": {"type": "network", "host": "127.0.0.1", "port": 8765}},
        _env_config(),
    )
    assert merged["storage"]["host"] == "hostA"
    assert merged["storage"]["port"] == 9100


def test_telemetry_batched_write_and_cap(storage):
    storage.TELEMETRY_CAP = 50
    for batch in range(6):
        storage.record_timings(
            "exp-id", [("suggest", 0.01 * batch + i * 1e-4, 1) for i in range(10)]
        )
    docs = storage.fetch_timings("exp-id")
    assert len(docs) <= 50
    # The newest samples survive the prune.
    assert docs[-1]["duration"] >= 0.05


def test_unpickling_pre_index_db_rebuilds_unique_maps(tmp_path):
    """DB files written before the hash-index rewrite must keep loading."""
    import pickle

    from orion_tpu.storage.documents import Collection

    col = Collection()
    col.ensure_index(["name", "version"], unique=True)
    col.insert({"name": "n", "version": 1})
    # Simulate an old-version pickle: strip the new attribute.
    state = dict(col.__dict__)
    del state["_unique_maps"]
    old = pickle.loads(pickle.dumps(col))
    old.__dict__.clear()
    old.__setstate__(state)

    with pytest.raises(DuplicateKeyError):
        old.insert({"name": "n", "version": 1})  # index still enforced
    old.insert({"name": "n", "version": 2})


def test_sqlite_persists_across_instances(tmp_path):
    from orion_tpu.storage.sqlitedb import SQLiteDB

    path = str(tmp_path / "db.sqlite")
    db = SQLiteDB(path)
    db.ensure_index("c", ["name"], unique=True)
    db.write("c", {"name": "n", "v": [1, 2, {"deep": True}]})
    db.close()

    db2 = SQLiteDB(path)
    (doc,) = db2.read("c", {"name": "n"})
    assert doc["v"] == [1, 2, {"deep": True}]
    assert db2.index_information("c") == {"name_1": True}
    with pytest.raises(DuplicateKeyError):
        db2.write("c", {"name": "n"})


def test_sqlite_unique_backfill_tolerates_existing_duplicates(tmp_path):
    """Pre-existing duplicates must not make legacy data unreadable (same
    last-wins behavior as the memory backend); NEW duplicates still raise."""
    from orion_tpu.storage.sqlitedb import SQLiteDB

    db = SQLiteDB(str(tmp_path / "db.sqlite"))
    db.write("c", {"name": "same"})
    db.write("c", {"name": "same"})
    db.ensure_index("c", ["name"], unique=True)
    assert db.count("c") == 2
    with pytest.raises(DuplicateKeyError):
        db.write("c", {"name": "same"})


def test_storage_path_header_sniffing(tmp_path):
    """A pickled DB named *.db keeps loading as pickled; new *.sqlite paths
    select the sqlite backend."""
    from orion_tpu.cli.base import _storage_type_for_path

    pkl_as_db = tmp_path / "results.db"
    create_storage({"type": "pickled", "path": str(pkl_as_db)}).create_experiment(
        {"name": "n", "version": 1}
    )
    assert _storage_type_for_path(str(pkl_as_db)) == "pickled"
    assert _storage_type_for_path(str(tmp_path / "new.sqlite")) == "sqlite"
    assert _storage_type_for_path(str(tmp_path / "new.pkl")) == "pickled"
    sq = tmp_path / "real.sqlite"
    create_storage({"type": "sqlite", "path": str(sq)}).create_experiment(
        {"name": "n", "version": 1}
    )
    assert _storage_type_for_path(str(sq)) == "sqlite"


def test_sqlite_prefilter_narrows_without_changing_semantics(tmp_path):
    """The SQL pushdown must agree with Python _matches for every query
    shape it claims to narrow — and leave the rest to _matches."""
    from orion_tpu.storage.sqlitedb import SQLiteDB

    db = SQLiteDB(str(tmp_path / "db.sqlite"))
    db.write("c", {"status": "new", "n": 1, "meta": {"user": "a"}})
    db.write("c", {"status": "reserved", "n": 2, "meta": {"user": "b"}})
    db.write("c", {"status": "completed", "n": 3, "meta": {"user": "a"}})
    # equality + $in on top-level scalars (SQL-pushable)
    assert db.count("c", {"status": "new"}) == 1
    assert db.count("c", {"status": {"$in": ["new", "reserved"]}}) == 2
    assert db.count("c", {"status": {"$in": []}}) == 0
    # dotted keys and operators stay on the Python matcher
    assert db.count("c", {"meta.user": "a"}) == 2
    assert db.count("c", {"n": {"$gte": 2}}) == 2
    # mixed pushable + non-pushable
    assert db.count("c", {"status": {"$in": ["new", "completed"]}, "meta.user": "a"}) == 2
    # booleans must NOT be pushed (json_extract yields 0/1, Python has True/False)
    db.write("c", {"status": "x", "flag": True})
    assert db.count("c", {"flag": True}) == 1


def test_sqlite_survives_nonfinite_json_and_huge_ints(tmp_path):
    """NaN/Infinity tokens in stored docs must not brick prefiltered scans,
    and out-of-range int query values must match nothing, not crash."""
    import math

    from orion_tpu.storage.sqlitedb import SQLiteDB

    db = SQLiteDB(str(tmp_path / "db.sqlite"))
    db.write("c", {"status": "completed", "objective": float("nan")})
    db.write("c", {"status": "new", "objective": 1.0})
    # Pushable status filter over a collection containing a NaN doc.
    assert db.count("c", {"status": "new"}) == 1
    docs = db.read("c", {"status": "completed"})
    assert len(docs) == 1 and math.isnan(docs[0]["objective"])
    # Int beyond SQLite's 64-bit range: Python semantics, no OverflowError.
    assert db.count("c", {"objective": 2**70}) == 0
    assert db.count("c", {"status": {"$in": [2**70, "new"]}}) == 1


def test_network_server_sqlite_backing(tmp_path):
    """--persist x.sqlite backs the server with the durable SQLite store:
    no snapshot thread, every mutation durable, restart keeps everything."""
    from orion_tpu.storage import DBServer

    path = str(tmp_path / "shared.sqlite")
    server = DBServer(port=0, persist=path)
    assert server._flusher is None  # durable by design, no snapshotting
    host, port = server.serve_background()
    storage = create_storage({"type": "network", "host": host, "port": port})
    trial = new_trial(1)
    storage.register_trial(trial)
    assert storage.reserve_trial("exp-id").id == trial.id
    server.shutdown()
    server.server_close()

    server2 = DBServer(port=0, persist=path)
    host2, port2 = server2.serve_background()
    try:
        storage2 = create_storage({"type": "network", "host": host2, "port": port2})
        fetched = storage2.fetch_trials(uid="exp-id")
        assert [t.id for t in fetched] == [trial.id]
        assert fetched[0].status == "reserved"  # mutation was durable
    finally:
        server2.shutdown()
        server2.server_close()


def test_network_server_legacy_pickle_snapshot_named_db(tmp_path):
    """A pre-existing pickle snapshot whose path ends in .db must keep
    loading as a snapshot (header sniffing), not crash SQLiteDB."""
    from orion_tpu.storage import DBServer

    path = str(tmp_path / "legacy.db")
    server = DBServer(port=0, persist=str(tmp_path / "seed.pkl"))
    server.server_close()
    # Write a legacy pickle snapshot at the .db path.
    import pickle

    from orion_tpu.storage.documents import MemoryDB

    db = MemoryDB()
    db.write("c", {"a": 1})
    with open(path, "wb") as f:
        pickle.dump(db, f)

    server2 = DBServer(port=0, persist=path)
    try:
        assert server2._snapshotting is True  # pickle mode, not sqlite
        assert server2.db.count("c") == 1
    finally:
        server2.server_close()


def test_value_map_narrowing_only_prunes():
    """Indexed-field candidate narrowing must never drop a matching doc:
    unhashable values (repr not canonical under ==) and cross-type equals
    go through the sentinel bucket / full scan."""
    db = MemoryDB()
    db.ensure_index("c", ["f"])
    db.write("c", {"f": [1.0], "tag": "listy"})
    db.write("c", {"f": "x", "tag": "str"})
    db.write("c", {"f": True, "tag": "bool"})
    # Unhashable stored value must be found via equality ([1] == [1.0]).
    assert db.read("c", {"f": [1]})[0]["tag"] == "listy"
    # Cross-type equality: True == 1 in Python/Mongo semantics.
    assert db.read("c", {"f": 1})[0]["tag"] == "bool"
    # $in mixing hashable and unhashable query values.
    assert {d["tag"] for d in db.read("c", {"f": {"$in": [[1], "x"]}})} == {
        "listy", "str",
    }


def test_value_map_buckets_do_not_grow_with_history():
    db = MemoryDB()
    db.ensure_index("c", ["status"])
    for i in range(50):
        db.write("c", {"_id": i, "status": f"s{i}"})
    db.remove("c", {})
    col = db._col("c")
    assert col._value_maps["status"] == {}


# --- batch (pipelined) protocol ops ----------------------------------------


def test_reserve_trials_batch_claims_distinct(storage):
    """reserve_trials(n) claims n DISTINCT trials (each claim individually
    atomic) on every backend — one pipelined round trip on the network
    driver, a loop elsewhere."""
    for i in range(6):
        storage.register_trial(new_trial(i))
    got = storage.reserve_trials("exp-id", 4)
    assert len(got) == 4
    assert len({t.id for t in got}) == 4
    assert all(t.status == "reserved" for t in got)
    # Over-asking returns what exists, no error.
    rest = storage.reserve_trials("exp-id", 10)
    assert len(rest) == 2
    assert storage.reserve_trials("exp-id", 3) == []


def test_register_trials_batch_reports_per_trial_duplicates(storage):
    """A duplicate in one slot must not block the rest of the batch: the
    outcome list carries the trial on success and the DuplicateKeyError for
    the taken slot."""
    storage.register_trial(new_trial(1))
    batch = [new_trial(0), new_trial(1), new_trial(2)]
    outcomes = storage.register_trials(batch)
    assert outcomes[0] is batch[0]
    assert isinstance(outcomes[1], DuplicateKeyError)
    assert outcomes[2] is batch[2]
    assert len(storage.fetch_trials(uid="exp-id")) == 3


def test_update_completed_trials_batch(storage):
    from orion_tpu.core.trial import Result

    for i in range(3):
        storage.register_trial(new_trial(i))
    got = storage.reserve_trials("exp-id", 3)
    pairs = [
        (t, [Result("objective", "objective", float(i))])
        for i, t in enumerate(got)
    ]
    outcomes = storage.update_completed_trials(pairs)
    assert all(not isinstance(o, Exception) for o in outcomes)
    done = storage.fetch_trials_by_status("exp-id", "completed")
    assert sorted(t.objective.value for t in done) == [0.0, 1.0, 2.0]


def test_network_pipeline_one_round_trip_semantics():
    """The raw pipeline op: N requests in one send, N ordered replies, per-op
    errors as instances (a DuplicateKeyError in slot 1 leaves slot 2 applied)."""
    from orion_tpu.storage import DBServer, NetworkDB
    from orion_tpu.utils.exceptions import DuplicateKeyError as Dup

    server = DBServer(port=0)
    host, port = server.serve_background()
    try:
        db = NetworkDB(host=host, port=port)
        db.ensure_index("c", ["k"], unique=True)
        results = db.pipeline(
            [
                ("write", ["c", {"k": 1}], {}),
                ("write", ["c", {"k": 1}], {}),  # duplicate
                ("write", ["c", {"k": 2}], {}),
                ("count", ["c"], {}),
            ]
        )
        assert not isinstance(results[0], Exception)
        assert isinstance(results[1], Dup)
        assert not isinstance(results[2], Exception)
        assert results[3] == 2
        assert db.pipeline([]) == []
    finally:
        server.shutdown()
        server.server_close()


def test_sqlite_register_trials_is_one_transaction(tmp_path):
    """The batched write path on SQLite: a q-batch registration (and a
    q-batch reservation beyond its probe) costs O(1) transactions — i.e.
    one COMMIT/fsync cycle — not O(q)."""
    from orion_tpu.storage.sqlitedb import SQLiteDB

    db = SQLiteDB(str(tmp_path / "one-txn.sqlite"))
    storage = DocumentStorage(db)
    before = db.txn_count
    outcomes = storage.register_trials([new_trial(i) for i in range(32)])
    assert all(not isinstance(o, Exception) for o in outcomes)
    assert db.txn_count - before == 1
    before = db.txn_count
    got = storage.reserve_trials("exp-id", 32)
    assert len(got) == 32
    # One probe claim + one batch transaction for the remaining 31.
    assert db.txn_count - before == 2


def test_sqlite_apply_batch_auto_ids_match_sequential(tmp_path):
    """Auto-assigned _ids after a mid-batch duplicate: the failed slot's
    counter draw must roll back with its savepoint exactly like the failed
    sequential write's transaction does, so both paths hand out identical
    ids to the surviving slots."""
    from orion_tpu.storage.sqlitedb import SQLiteDB

    batch_db = SQLiteDB(str(tmp_path / "ids-batch.sqlite"))
    seq_db = SQLiteDB(str(tmp_path / "ids-seq.sqlite"))
    docs = [{"u": 1}, {"u": 1}, {"u": 2}]  # slot 1 duplicates slot 0
    for db in (batch_db, seq_db):
        db.ensure_index("c", ["u"], unique=True)
    batch_out = batch_db.apply_batch(
        [("write", ["c", dict(d)], {}) for d in docs]
    )
    seq_out = []
    for d in docs:
        try:
            seq_out.append(seq_db.write("c", dict(d)))
        except DuplicateKeyError as exc:
            seq_out.append(exc)

    def norm(outcomes):
        return ["dup" if isinstance(o, Exception) else o for o in outcomes]

    assert norm(batch_out) == norm(seq_out)
    assert batch_db.read("c") == seq_db.read("c")


def test_network_register_trials_is_one_wire_request():
    """The batch wire op: a q-batch registration rides ONE request line /
    ONE response line (vs q lines pipelined, vs q round trips per-op)."""
    from orion_tpu.storage import DBServer, NetworkDB

    server = DBServer(port=0)
    host, port = server.serve_background()
    try:
        db = NetworkDB(host=host, port=port)
        storage = DocumentStorage(db)
        requests_before = db.wire_requests
        trips_before = db.round_trips
        outcomes = storage.register_trials([new_trial(i) for i in range(32)])
        assert all(not isinstance(o, Exception) for o in outcomes)
        assert db.wire_requests - requests_before == 1
        assert db.round_trips - trips_before == 1
    finally:
        server.shutdown()
        server.server_close()


def test_network_batch_reuses_socket_and_reconnects_when_dead(tmp_path):
    """The batch path rides the instance's ONE persistent socket — no
    connect-per-request — and a send-phase failure on a dead socket
    (EPIPE/EBADF after a server restart) reconnects and resends: the
    request line never reached the server, so the retry cannot
    double-apply."""
    from orion_tpu.storage import DBServer, NetworkDB

    snapshot = str(tmp_path / "batch-snap.pkl")
    server = DBServer(port=0, persist=snapshot)
    host, port = server.serve_background()
    db = NetworkDB(host=host, port=port)
    db.apply_batch([("write", ["c", {"_id": 1}], {})])
    sock = db._sock
    db.apply_batch([("write", ["c", {"_id": 2}], {})])
    db.read("c")
    db.apply_batch([("write", ["c", {"_id": 3}], {})])
    assert db._sock is sock  # one socket across batch AND per-op traffic
    # Kill the connection underneath the client (shutdown, not close: the
    # makefile reader keeps the fd alive, so close() wouldn't actually
    # sever it): the next batch must hit the send-phase error, reconnect,
    # and apply exactly once.
    import socket as _socket

    db._sock.shutdown(_socket.SHUT_RDWR)
    db.apply_batch([("write", ["c", {"_id": 4}], {})])
    assert db._sock is not sock
    assert db.count("c") == 4
    # Same guarantee across a real server restart while the client idles
    # (the probe path): the reconnect re-runs transparently.
    server.shutdown()
    server.server_close()
    server2 = DBServer(host=host, port=port, persist=snapshot)
    server2.serve_background()
    try:
        db.idle_probe = 0.0  # force the pre-batch ping probe
        outcomes = db.apply_batch([("write", ["c", {"_id": 5}], {})])
        assert not isinstance(outcomes[0], Exception)
        assert db.count("c") == 5
    finally:
        server2.shutdown()
        server2.server_close()


def test_network_batch_downgrades_to_pipeline_on_old_server(monkeypatch):
    """Talking to a pre-batch server, the rejected batch op (refused before
    dispatch — nothing applied) falls back to pipeline transparently and
    stops retrying the batch op on that instance."""
    import orion_tpu.storage.netdb as netdb_mod
    from orion_tpu.storage import DBServer, NetworkDB

    monkeypatch.setattr(
        netdb_mod, "_DB_OPS", netdb_mod._DB_OPS - {"batch"}
    )
    server = DBServer(port=0)
    host, port = server.serve_background()
    try:
        db = NetworkDB(host=host, port=port)
        outcomes = db.apply_batch(
            [("write", ["c", {"_id": i}], {}) for i in range(3)]
        )
        assert all(not isinstance(o, Exception) for o in outcomes)
        assert db._batch_unsupported
        assert db.count("c") == 3
        # Subsequent batches go straight to pipeline.
        db.apply_batch([("write", ["c", {"_id": 3}], {})])
        assert db.count("c") == 4
    finally:
        server.shutdown()
        server.server_close()


class _LoopOnlyStorage(DocumentStorage):
    """A third-party protocol implementation that never heard of the batch
    API: it overrides ONLY the singular ops (counting them), so the batch
    entry points must come from BaseStorage's loop fallbacks."""

    # Sever the DocumentStorage batch overrides — what a plugin subclassing
    # BaseStorage directly would see.
    register_trials = BaseStorage.register_trials
    reserve_trials = BaseStorage.reserve_trials
    update_completed_trials = BaseStorage.update_completed_trials

    def __init__(self, db):
        super().__init__(db)
        self.singular_calls = 0

    def register_trial(self, trial):
        self.singular_calls += 1
        return super().register_trial(trial)

    def reserve_trial(self, experiment):
        self.singular_calls += 1
        return super().reserve_trial(experiment)

    def update_completed_trial(self, trial, results):
        self.singular_calls += 1
        return super().update_completed_trial(trial, results)


def test_base_storage_batch_loop_fallbacks():
    """A custom backend that only implements the per-trial protocol gets
    register_trials / reserve_trials / update_completed_trials for free
    (BaseStorage default loops), with identical outcome semantics —
    duplicates as per-slot exceptions, short reservation on an empty
    queue."""
    from orion_tpu.core.trial import Result

    storage = _LoopOnlyStorage(MemoryDB())
    storage.register_trial(new_trial(1))
    outcomes = storage.register_trials([new_trial(0), new_trial(1), new_trial(2)])
    assert not isinstance(outcomes[0], Exception)
    assert isinstance(outcomes[1], DuplicateKeyError)
    assert not isinstance(outcomes[2], Exception)
    got = storage.reserve_trials("exp-id", 10)
    assert len(got) == 3
    pairs = [(t, [Result("objective", "objective", 1.0)]) for t in got]
    done = storage.update_completed_trials(pairs)
    assert all(not isinstance(o, Exception) for o in done)
    assert storage.count_completed_trials("exp-id") == 3
    assert storage.singular_calls >= 3 + 3 + 3  # every op went singular


def _net_worker_reserve_batched(host, port, out_queue):
    storage = create_storage(
        {"type": "network", "host": host, "port": port, "secret": "mp-secret"}
    )
    claimed = []
    while True:
        got = storage.reserve_trials("exp-id", 4)
        if not got:
            break
        claimed.extend(t.id for t in got)
    out_queue.put(claimed)


def test_network_concurrent_batched_reservation_across_processes():
    """The PIPELINED batch claims race exactly like per-op ones."""
    _run_network_reservation_race(_net_worker_reserve_batched)


def test_fetch_update_view_gates_and_orders(storage):
    """The producer's sync snapshot: count-gated completed reads (on
    cheap-count backends), completed view winning the dedup, and the same
    (submit_time, id) order fetch_trials delivers."""
    from orion_tpu.core.trial import Result

    for i in range(4):
        storage.register_trial(new_trial(i))
    trials, n_completed = storage.fetch_update_view("exp-id")
    assert [t.params["x"] for t in trials] == [
        t.params["x"] for t in storage.fetch_trials(uid="exp-id")
    ]
    assert all(t.status == "new" for t in trials)
    # Complete two; the view must re-read them exactly once per count move.
    got = storage.reserve_trials("exp-id", 2)
    for i, t in enumerate(got):
        storage.update_completed_trial(t, [Result("o", "objective", float(i))])
    cheap = getattr(storage.db, "cheap_counts", False)
    trials2, n2 = storage.fetch_update_view("exp-id", n_completed)
    statuses = sorted(t.status for t in trials2)
    assert statuses == ["completed", "completed", "new", "new"]
    if cheap:
        assert n2 == 2
        # Gate closed: completed drop out of the view, non-completed stay.
        trials3, n3 = storage.fetch_update_view("exp-id", n2)
        assert n3 == n2
        assert sorted(t.status for t in trials3) == ["new", "new"]
    else:
        assert n2 == -1  # full-fetch backends never gate
    # Order invariant on the full view: submit_time then id.
    order = [(t.submit_time, str(t.id)) for t in trials2]
    assert order == sorted(order)


def test_range_query_on_incomparable_values_never_raises():
    """A malformed range query (list/numpy field vs scalar bound) is 'no
    match' on EVERY backend — not a TypeError/ValueError that crashes an
    in-process worker while the network server translates it into a
    different error class (differential-fuzzer find)."""
    import numpy as np

    db = MemoryDB()
    db.write("c", {"_id": 1, "a": [2, 1]})
    db.write("c", {"_id": 2, "a": np.array([1, 2, 3])})
    db.write("c", {"_id": 3, "a": 5})
    assert [d["_id"] for d in db.read("c", {"a": {"$gte": 2}})] == [3]
    assert db.count("c", {"a": {"$lt": 10}}) == 1
    assert db.read("c", {"a": {"$in": 7}}) == []  # non-container $in operand


def test_numpy_field_values_match_like_their_list_form():
    """Numpy values normalize before comparison, so in-process backends
    agree with the JSON-serializing ones on EVERY operator (review find:
    $ne/$in/equality still diverged after the range-op hardening)."""
    import numpy as np

    mem = MemoryDB()
    mem.write("c", {"_id": 1, "a": np.array([1, 2, 3])})
    mem.write("c", {"_id": 2, "a": np.float64(2.0)})
    # Equality/$ne/$in judged on the list/scalar form — never a ValueError.
    assert [d["_id"] for d in mem.read("c", {"a": [1, 2, 3]})] == [1]
    assert [d["_id"] for d in mem.read("c", {"a": {"$ne": 2}})] == [1]
    assert [d["_id"] for d in mem.read("c", {"a": {"$in": [2, 9]}})] == [2]
    assert mem.count("c", {"a": 2}) == 1


def test_apply_update_cow_invariants():
    """apply_update's contract: input doc NEVER mutated; result may share
    unmodified subtrees but every path touched by the update is fresh.
    These invariants are what make the copy-on-write rewrite safe — pin
    them so a future edit cannot silently hand out mutable store state."""
    import copy as _copy

    from orion_tpu.storage.documents import apply_update

    doc = {
        "_id": 1,
        "status": "new",
        "params": [{"name": "/x", "type": "real", "value": 0.5}],
        "meta": {"a": {"deep": 1}, "b": 2},
    }
    snapshot = _copy.deepcopy(doc)
    new = apply_update(doc, {"$set": {"status": "reserved", "meta.a.deep": 9},
                             "$unset": {"meta.b": 1}})
    assert doc == snapshot  # input untouched, including the $unset path
    assert new["status"] == "reserved"
    assert new["meta"]["a"]["deep"] == 9 and "b" not in new["meta"]
    # Touched path dicts are fresh objects (mutating them cannot reach doc).
    assert new is not doc and new["meta"] is not doc["meta"]
    assert new["meta"]["a"] is not doc["meta"]["a"]
    # The $set VALUE is detached from the caller's payload.
    payload = {"results": [{"name": "o", "type": "objective", "value": 1.0}]}
    new2 = apply_update(doc, payload)
    payload["results"][0]["value"] = 999.0
    assert new2["results"][0]["value"] == 1.0


def test_store_state_immune_to_caller_mutation():
    """Mutating anything a read/CAS handed out must not change the store."""
    from orion_tpu.storage.documents import MemoryDB

    db = MemoryDB()
    db.write("c", {"_id": 1, "status": "new",
                   "params": [{"name": "/x", "value": 0.5}]})
    # Mutate a find() result, deep and shallow.
    (got,) = db.read("c", {"_id": 1})
    got["status"] = "hacked"
    got["params"][0]["value"] = -1.0
    # Mutate a read_and_write() result (post-COW doc shares subtrees with
    # the stored doc's predecessor, never with the stored doc itself).
    ret = db.read_and_write("c", {"_id": 1}, {"status": "reserved"})
    ret["params"][0]["value"] = -2.0
    (fresh,) = db.read("c", {"_id": 1})
    assert fresh["status"] == "reserved"
    assert fresh["params"][0]["value"] == 0.5


def _borrowing_storage():
    """Memory storage with two stored trials whose every mutable part is
    present: a shaped (list-valued) param, a list-valued result, parents."""
    from orion_tpu.core.trial import Result

    storage = create_storage({"type": "memory"})
    assert storage.db.shares_documents
    for i in range(2):
        storage.register_trial(Trial(
            experiment="exp-id", params={"x": float(i), "v": [float(i), 2.0]},
            results=[Result("o", "objective", 1.0), Result("g", "gradient", [0.5, 0.25])],
            parents=["p0", "p1"],
        ))
    return storage


@pytest.mark.parametrize(
    "op", ["reserve_trials", "fetch_update_view", "fetch_trials", "get_trial"]
)
def test_borrowed_trials_share_nothing_with_the_store(op):
    """On a backend that lends its stored documents, the Trials the trial ops
    build own every mutable part: mutating them leaves the store as it was."""
    storage = _borrowing_storage()
    first_id = storage.db.read("trials")[0]["_id"]
    calls = {
        "reserve_trials": lambda: storage.reserve_trials("exp-id", 2),
        "fetch_update_view": lambda: storage.fetch_update_view("exp-id")[0],
        "fetch_trials": lambda: storage.fetch_trials(uid="exp-id"),
        "get_trial": lambda: [storage.get_trial(uid=first_id)],
    }
    before = storage.db.docs_shared
    trials = calls[op]()
    assert trials and storage.db.docs_shared > before  # the borrowed path ran
    stored = sorted(storage.db.read("trials"), key=lambda d: d["_id"])
    for trial in trials:
        trial.params["x"] = -1.0
        trial.params["v"].append(9.0)
        trial.params["v"][0] = -1.0
        trial.results[1].value.append(9.0)
        trial.results[0].value = -1.0
        trial.results.append(trial.results[0])
        trial.parents.append("p9")
        trial.parents[0] = "hacked"
    assert sorted(storage.db.read("trials"), key=lambda d: d["_id"]) == stored


def test_batch_insert_shares_one_copy_that_public_reads_never_alias():
    """A batch insert whose documents share one ``parents`` list stores one
    copy of it, shared by the stored documents; a public read still hands
    out a copy that aliases neither another read nor the store."""
    db = MemoryDB()
    parents = ["a", "b"]
    docs = [{"_id": i, "params": {"x": float(i)}, "parents": parents} for i in range(3)]
    assert db.apply_batch([("write", ["c", doc], {}) for doc in docs]) == [0, 1, 2]
    db.write("c", [{"_id": 3 + i, "parents": parents} for i in range(2)])
    stored = db._collections["c"]._docs
    assert stored[0]["parents"] is stored[1]["parents"] is stored[2]["parents"]
    assert stored[3]["parents"] is stored[4]["parents"]
    assert stored[0]["parents"] is not parents and stored[0]["params"] is not docs[0]["params"]
    parents.append("caller")  # the caller's list is not the stored one
    (one,) = db.read("c", {"_id": 0})
    (two,) = db.read("c", {"_id": 1})
    assert one["parents"] == ["a", "b"] and one["parents"] is not two["parents"]
    one["parents"].append("hacked")
    assert [d["parents"] for d in db.read("c")] == [["a", "b"]] * 5
    assert (db.docs_copied, db.docs_shared) == (5 + 2 + 5, 0)


def test_network_server_refuses_borrowed_reads():
    """``shared`` is an in-process read: the network server refuses it on a
    single op and as a batch sub-op kwarg, before anything applies."""
    from orion_tpu.storage import DBServer
    from orion_tpu.storage.netdb import NetworkDB
    from orion_tpu.utils.exceptions import DatabaseError

    server = DBServer(port=0)
    host, port = server.serve_background()
    try:
        db = NetworkDB(host=host, port=port)
        db.write("c", {"_id": 1, "st": "new"})
        with pytest.raises(DatabaseError, match="in-process"):
            db._call("read_and_write", "c", {"_id": 1}, {"st": "go"}, shared=True)
        with pytest.raises(DatabaseError, match="in-process"):
            db.apply_batch([("read", ["c"], {"shared": True})])
        assert db.read("c") == [{"_id": 1, "st": "new"}]
        assert server.db.docs_shared == 0
    finally:
        server.shutdown()
        server.server_close()


def test_update_completed_trials_reports_vanished_trial(storage):
    """A completion whose trial left the store is a FailedUpdate slot on every
    backend, the borrowing memory backend included; the other slots land."""
    from orion_tpu.core.trial import Result

    for i in range(2):
        storage.register_trial(new_trial(i))
    gone, kept = storage.reserve_trials("exp-id", 2)
    storage.db.remove("trials", {"_id": gone.id})
    results = [Result("o", "objective", 1.0)]
    out = storage.update_completed_trials([(gone, results), (kept, results)])
    assert isinstance(out[0], FailedUpdate)
    assert out[1] is kept and kept.status == "completed"
    assert [t.id for t in storage.fetch_trials_by_status("exp-id", "completed")] == [kept.id]


def test_client_round_books_document_counters():
    """One q-round through ExperimentClient on memory storage: the round's
    reservations and completions borrow their documents (2q shared) and only
    the inserts copy (q trials plus the round's timing samples); both
    counters reach the telemetry registry as ``storage.memory.docs_*``."""
    from orion_tpu.client.experiment import ExperimentClient
    from orion_tpu.core.experiment import build_experiment
    from orion_tpu.telemetry import TELEMETRY

    q = 8
    storage = create_storage({"type": "memory"})
    experiment = build_experiment(
        storage, "counters", priors={"x": "uniform(0, 1)", "y": "uniform(0, 1)"},
        max_trials=4 * q, algorithms={"random": {"seed": 0}}, strategy=None, pool_size=q,
    ).instantiate(seed=0)
    client = ExperimentClient(experiment)
    db = storage.db

    def sample():
        counters = TELEMETRY.snapshot()["counters"]
        return (
            counters["storage.memory.docs_copied"], counters["storage.memory.docs_shared"],
            db.docs_copied, db.docs_shared, db.count("telemetry"),
        )

    before = sample()
    trials = client.suggest(q)
    client.observe_all(trials, [0.5] * q)
    registry_copied, registry_shared, copied, shared, timings = (
        b - a for a, b in zip(before, sample())
    )
    assert shared == 2 * q
    assert copied == q + timings
    assert (registry_copied, registry_shared) == (copied, shared)


def test_reservation_stamps_worker_identity(storage):
    """The reservation CAS must attribute the trial to this host:pid (the
    reference declares Trial.worker but never fills it — we do)."""
    import os
    import socket

    trial = Trial(experiment="e1", params={"/x": 1.0})
    storage.register_trial(trial)
    reserved = storage.reserve_trial("e1")
    assert reserved.worker == f"{socket.gethostname()}:{os.getpid()}"


def test_unset_absent_key_is_allocation_free_noop():
    """$unset of an absent (possibly nested) key must not copy dicts along
    the path (ADVICE r5): the returned doc shares the untouched subtrees."""
    from orion_tpu.storage.documents import apply_update

    doc = {"a": {"b": 1}, "c": 2}
    out = apply_update(doc, {"$unset": {"a.missing": 1, "missing.x": 1}})
    assert out["a"] is doc["a"]  # no COW copy for a no-op
    assert out == doc

    # A present key is still removed, copy-on-write (original untouched).
    out2 = apply_update(doc, {"$unset": {"a.b": 1}})
    assert out2 == {"a": {}, "c": 2}
    assert doc["a"] == {"b": 1}
