"""Differential backend testing: every backend IS the same database.

A seeded random program of document operations runs against all four
backends; after every mutation the full collection state must agree with
the in-memory oracle under canonical JSON (which already absorbs the
legitimate representation differences: tuples list-ify through sqlite,
numpy scalars de-box through the wire).  This is the contract suite's
adversarial sibling — hand-written cases pin known semantics, the random
program hunts for divergence in operator corners ($-queries over missing
fields, dotted paths, unique-index enforcement order, update-vs-insert
routing) that nobody thought to pin.
"""

import random

import pytest

from orion_tpu.storage.documents import MemoryDB, dumps_canonical
from orion_tpu.utils.exceptions import DuplicateKeyError


def _canonical_state(db, collection="c"):
    docs = db.read(collection)
    return sorted(dumps_canonical(d) for d in docs)


def _random_doc(rng, i):
    doc = {"_id": f"d{i}"}
    if rng.random() < 0.8:
        doc["a"] = rng.choice([0, 1, 2, 2.5, "x", None])
    if rng.random() < 0.6:
        doc["b"] = {"c": rng.randint(0, 3)}
    if rng.random() < 0.3:
        doc["tags"] = [rng.randint(0, 2) for _ in range(rng.randint(0, 3))]
    if rng.random() < 0.2:
        doc["u"] = rng.randint(0, 2)  # unique-indexed field (sometimes)
    return doc


def _random_query(rng):
    field = rng.choice(["a", "b.c", "missing", "tags", "u"])
    kind = rng.random()
    if kind < 0.4:
        return {field: rng.choice([0, 1, 2, "x", None])}
    if kind < 0.55:
        return {field: {"$in": [rng.randint(0, 2), "x"]}}
    if kind < 0.65:
        return {field: {"$gte": rng.randint(0, 2)}}
    if kind < 0.72:
        return {field: {rng.choice(["$gt", "$lt", "$lte"]): rng.randint(0, 2)}}
    if kind < 0.9:
        return {field: {"$ne": rng.randint(0, 2)}}
    return {}


def _apply(db, op, payload):
    """Run one op; returns (kind, normalized_result) for cross-backend
    comparison.  Exceptions are part of the contract: a DuplicateKeyError
    on one backend must be a DuplicateKeyError on every backend."""
    try:
        if op == "insert":
            db.write("c", payload)
            return ("ok", None)
        if op == "update":
            query, update = payload
            n = db.write("c", update, query=query)
            return ("n", n)
        if op == "update_many":
            # Happy-path batches only: mid-batch FAILURE state is a
            # documented backend divergence (MemoryDB.update_many), so the
            # fuzzer generates updates that cannot violate the unique index.
            return ("n", db.update_many("c", payload))
        if op == "read":
            docs = db.read("c", payload)
            return ("docs", sorted(dumps_canonical(d) for d in docs))
        if op == "project":
            query, projection = payload
            docs = db.read("c", query, projection=projection)
            return ("docs", sorted(dumps_canonical(d) for d in docs))
        if op == "dotted":
            query, dotted_update = payload
            n = db.write("c", dotted_update, query=query)
            return ("n", n)
        if op == "count":
            return ("n", db.count("c", payload))
        if op == "raw":  # read_and_write: result doc must match too
            query, update = payload
            doc = db.read_and_write("c", query, update)
            return ("doc", None if doc is None else dumps_canonical(doc))
        if op == "remove":
            db.remove("c", payload)
            return ("ok", None)
        raise AssertionError(op)
    except DuplicateKeyError:
        return ("duplicate", None)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_backends_agree_on_random_programs(seed, tmp_path):
    from orion_tpu.storage.backends import PickledDB
    from orion_tpu.storage.netdb import DBServer, NetworkDB
    from orion_tpu.storage.sqlitedb import SQLiteDB

    server = DBServer(port=0)
    host, port = server.serve_background()
    backends = {
        "memory": MemoryDB(),  # the oracle
        "sqlite": SQLiteDB(str(tmp_path / "d.sqlite")),
        "pickled": PickledDB(str(tmp_path / "d.pkl")),
        "network": NetworkDB(host=host, port=port),
    }
    try:
        rng = random.Random(seed)
        unique_on = rng.random() < 0.7
        if unique_on:
            for db in backends.values():
                db.ensure_index("c", ["u"], unique=True)
        program = []
        for i in range(70):
            r = rng.random()
            if r < 0.45:
                program.append(("insert", _random_doc(rng, i)))
            elif r < 0.56:
                program.append(
                    ("update", (_random_query(rng), {"a": rng.randint(0, 5)}))
                )
            elif r < 0.6:
                program.append(
                    ("update_many",
                     [(_random_query(rng), {"a": rng.randint(0, 5)})
                      for _ in range(rng.randint(0, 3))])
                )
            elif r < 0.66:
                program.append(("read", _random_query(rng)))
            elif r < 0.72:
                program.append(
                    ("project",
                     (_random_query(rng),
                      rng.choice([{"a": 1}, {"b.c": 1}, {"a": 1, "_id": 0}])))
                )
            elif r < 0.75:
                # Dotted-path update: creates/overwrites a nested leaf.
                program.append(
                    ("dotted",
                     (_random_query(rng), {"b.c": rng.randint(10, 12)}))
                )
            elif r < 0.78:
                # $set + $unset combo — the copy-on-write unset walk must
                # agree across backends (incl. unsetting a missing path).
                program.append(
                    ("dotted",
                     (_random_query(rng),
                      {"$set": {"a": rng.randint(0, 5)},
                       "$unset": {rng.choice(["b.c", "tags", "missing.x"]): 1}}))
                )
            elif r < 0.84:
                program.append(("count", _random_query(rng)))
            elif r < 0.9:
                # Deterministic single-doc CAS: _id-targeted, so every
                # backend picks the SAME document (a broad query's "first
                # match" choice is legitimately backend-dependent).
                program.append(
                    ("raw", ({"_id": f"d{rng.randint(0, i)}"},
                             {"st": rng.randint(0, 9)}))
                )
            else:
                program.append(("remove", {"a": rng.choice([0, 1, "x"])}))

        oracle = backends["memory"]
        for step, (op, payload) in enumerate(program):
            expected = _apply(oracle, op, payload)
            for name, db in backends.items():
                if name == "memory":
                    continue
                got = _apply(db, op, payload)
                assert got == expected, (
                    f"seed {seed} step {step} {op}: {name} returned {got!r}, "
                    f"oracle {expected!r} (payload {payload!r})"
                )
            if op in ("insert", "update", "update_many", "dotted", "raw", "remove"):
                want = _canonical_state(oracle)
                for name, db in backends.items():
                    if name == "memory":
                        continue
                    assert _canonical_state(db) == want, (
                        f"seed {seed} step {step}: {name} diverged after {op} "
                        f"{payload!r}"
                    )
    finally:
        server.shutdown()
        server.server_close()


def _make_trial(exp_id, x, submit_time=1234.5):
    from orion_tpu.core.trial import Trial

    # submit_time pre-stamped: register_trial stamps time.time() per call
    # while the batch stamps one shared now — pinning it is what makes
    # byte-identical comparison meaningful.
    return Trial(
        experiment=exp_id, params={"/x": x}, submit_time=submit_time
    )


@pytest.mark.parametrize("backend", ["memory", "sqlite"])
def test_register_trials_batch_matches_sequential(backend, tmp_path):
    """The batched write path IS the sequential path: register_trials over
    a q-batch (including a duplicate point mid-batch) must leave documents
    and unique-index state byte-identical to N sequential register_trial
    calls — the duplicate's slot fails with DuplicateKeyError on both
    sides, rolled back atomically (no stray index entries), without
    blocking the later slots."""
    from orion_tpu.storage.base import DocumentStorage
    from orion_tpu.storage.sqlitedb import SQLiteDB
    from orion_tpu.utils.exceptions import DuplicateKeyError

    def make_storage(tag):
        if backend == "sqlite":
            return DocumentStorage(SQLiteDB(str(tmp_path / f"{tag}.sqlite")))
        return DocumentStorage(MemoryDB())

    xs = [0.1, 0.2, 0.3, 0.2, 0.4]  # index 3 duplicates index 1
    batch_storage = make_storage("batch")
    seq_storage = make_storage("seq")

    batch_outcomes = batch_storage.register_trials(
        [_make_trial("e", x) for x in xs]
    )
    seq_outcomes = []
    for x in xs:
        try:
            seq_outcomes.append(seq_storage.register_trial(_make_trial("e", x)))
        except DuplicateKeyError as exc:
            seq_outcomes.append(exc)

    for i, (b, s) in enumerate(zip(batch_outcomes, seq_outcomes)):
        assert isinstance(b, Exception) == isinstance(s, Exception), (i, b, s)
        if isinstance(b, Exception):
            assert isinstance(b, DuplicateKeyError)
            assert i == 3
    assert _canonical_state(batch_storage.db, "trials") == _canonical_state(
        seq_storage.db, "trials"
    )

    # Index state: the failed slot left no stray unique entries — the SAME
    # point still collides, and a fresh point registers cleanly, on both.
    for storage in (batch_storage, seq_storage):
        [dup_outcome] = storage.register_trials([_make_trial("e", 0.2)])
        assert isinstance(dup_outcome, DuplicateKeyError)
        [ok_outcome] = storage.register_trials([_make_trial("e", 0.9)])
        assert not isinstance(ok_outcome, Exception)
    assert _canonical_state(batch_storage.db, "trials") == _canonical_state(
        seq_storage.db, "trials"
    )


def test_apply_batch_agrees_across_backends(tmp_path):
    """apply_batch (the one-transaction / one-wire-request primitive the
    batched storage path commits through) must agree with the in-memory
    oracle slot for slot — results, per-slot exceptions, and final
    collection state."""
    from orion_tpu.storage.backends import PickledDB
    from orion_tpu.storage.netdb import DBServer, NetworkDB
    from orion_tpu.storage.sqlitedb import SQLiteDB

    server = DBServer(port=0)
    host, port = server.serve_background()
    backends = {
        "memory": MemoryDB(),  # the oracle
        "sqlite": SQLiteDB(str(tmp_path / "b.sqlite")),
        "pickled": PickledDB(str(tmp_path / "b.pkl")),
        "network": NetworkDB(host=host, port=port),
    }
    ops = (
        [("write", ["c", {"_id": f"d{i}", "u": i % 4}], {}) for i in range(6)]
        + [
            ("write", ["c", {"_id": "dup", "u": 2}], {}),  # unique conflict
            ("read_and_write", ["c", {"_id": "d1"}, {"st": 7}], {}),
            ("count", ["c", {"u": {"$gte": 2}}], {}),
            ("remove", ["c", {"_id": "d5"}], {}),
            ("write", ["c", {"missing": 1}, ], {"query": {"_id": "absent"}}),
            # Empty query dict = update-ALL, never insert (the coalescing
            # fast path must route on `query is None`, not falsiness).
            ("write", ["c", {"touched": 1}], {"query": {}}),
        ]
    )
    try:
        expected = None
        for name, db in backends.items():
            db.ensure_index("c", ["u"], unique=True)
            outcomes = db.apply_batch([(op, list(a), dict(k)) for op, a, k in ops])
            normalized = [
                ("exc", type(o).__name__) if isinstance(o, Exception)
                else ("ok", dumps_canonical(o))
                for o in outcomes
            ]
            state = _canonical_state(db)
            if expected is None:
                expected = (normalized, state)
            else:
                assert (normalized, state) == expected, name
    finally:
        server.shutdown()
        server.server_close()


def _trial_protocol_run(db, monkeypatch, q=32, rounds=3):
    """The producer's trial traffic over ``db``: each round registers q trial
    documents sharing one ``parents`` list (the previous round's ids),
    reserves them (probe claim + batch), completes them with a list-valued
    result, then reads the history back through every borrowing op.  The
    clock is a counter, so two runs write identical documents.  Returns the
    ``to_dict`` of every trial an op handed back and the final collection."""
    import itertools

    from orion_tpu.core.trial import Result, TrialBatch
    from orion_tpu.storage import base
    from orion_tpu.storage.base import DocumentStorage

    clock = itertools.count(1000.0)
    monkeypatch.setattr(base.time, "time", lambda: next(clock))
    storage = DocumentStorage(db)
    rng = random.Random(7)
    seen, parents, known = [], [], -1
    for r in range(rounds):
        params = [{"/x": rng.random(), "/v": [rng.random(), float(r)]} for _ in range(q)]
        batch = TrialBatch(params).prepare("e", parents=parents, submit_time=float(r))
        outcomes = storage.register_trial_docs(batch.to_docs())
        assert not any(isinstance(o, Exception) for o in outcomes)
        reserved = storage.reserve_trials("e", q)
        assert len(reserved) == q
        seen += reserved
        pairs = [
            (t, [Result("o", "objective", float(i)), Result("g", "gradient", [float(i), 1.0])])
            for i, t in enumerate(reserved)
        ]
        seen += storage.update_completed_trials(pairs)
        view, known = storage.fetch_update_view("e", known)
        seen += view
        seen += storage.fetch_trials(uid="e")
        seen += storage.fetch_trials_by_status("e", "completed")
        seen.append(storage.get_trial(uid=reserved[0].id))
        parents = [t.id for t in reserved]
    state = sorted(db.read("trials"), key=lambda d: d["_id"])
    return [t.to_dict() for t in seen], state


def test_borrowed_trial_ops_match_copying_ops(monkeypatch):
    """The memory store lending its documents (``shares_documents``) changes
    no answer: the same q=32 produce / reserve / complete sequence on a store
    with the capability forced off returns the same trials and leaves the
    same collection."""
    lending, copying = MemoryDB(), MemoryDB()
    copying.shares_documents = False
    assert _trial_protocol_run(lending, monkeypatch) == _trial_protocol_run(copying, monkeypatch)
    assert lending.docs_shared > 0 and copying.docs_shared == 0
