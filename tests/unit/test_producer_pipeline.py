"""The N-deep speculative pipeline (ISSUE 13 tentpole a), pinned.

Two guarantees:

1. **Depth-1 is the pre-ring producer.**  The GOLDEN op sequences and
   suggestion-stream hashes below were recorded against the single-slot
   producer BEFORE the ring landed (same seeds, same scenarios).  The
   depth-1 configuration must reproduce them exactly: same DB-level
   storage op sequence (batched register, lie writes, telemetry flushes —
   what crash-consistency semantics are made of) and the same suggestion
   bit-stream.

2. **Depth is invisible to the suggestion stream.**  For speculation-safe
   algorithms the ring drains oldest-first and every dispatch consumes the
   same rng/cursor stream the synchronous path would, so ANY depth yields
   the bit-identical stream — while actually holding N rounds in flight.
"""

import hashlib
import json

import pytest

from orion_tpu.core.experiment import build_experiment
from orion_tpu.core.producer import Producer
from orion_tpu.core.trial import Result
from orion_tpu.storage import create_storage
from orion_tpu.storage.base import DocumentStorage


class RecordingDB:
    """Transparent DB wrapper recording the backend-level op sequence
    (apply_batch sub-ops included) — the observational surface the depth-1
    behavioral pin is defined over."""

    def __init__(self, inner):
        self._inner = inner
        self.ops = []

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if not callable(attr) or name.startswith("_"):
            return attr

        def wrapper(*args, **kwargs):
            if name == "apply_batch":
                self.ops.append(
                    "apply_batch:"
                    + ",".join(f"{op}/{a[0]}" for op, a, _ in args[0])
                )
            elif name in ("write", "read", "read_and_write", "count", "remove"):
                self.ops.append(f"{name}/{args[0]}")
            return attr(*args, **kwargs)

        return wrapper


def _build(db, seed=3, pipeline_depth=1):
    storage = DocumentStorage(db)
    exp = build_experiment(
        storage,
        "pin",
        priors={"x": "uniform(0, 1)", "y": "uniform(0, 1)"},
        max_trials=1000,
        algorithms="random",
        pool_size=4,
    ).instantiate(seed=seed)
    return exp, Producer(exp, pipeline_depth=pipeline_depth)


def _stream_hash(exp, sort_params=False):
    def key(t):
        if sort_params:
            return (t.submit_time, json.dumps(sorted(t.params.items())))
        return t.submit_time

    trials = sorted(exp.fetch_trials(), key=key)
    stream = [sorted(t.params.items()) for t in trials]
    return hashlib.md5(json.dumps(stream).encode()).hexdigest()


#: Recorded against the pre-ring producer (seed 3, 3 produce(4) rounds over
#: memory storage, trials left in flight): per-round = one count-gated sync
#: read, ONE batched 4-slot register, one telemetry flush pair.
#:
#: The two stream hashes were re-pinned (PR 21) for the installed JAX 0.9,
#: whose ``jax_threefry_partitionable`` default is True: the same keys draw
#: different random bits than the JAX that recorded them.  With that flag
#: set False, the old hashes (4c3ffe1e..., 3389f82c...) reproduce exactly,
#: so the producer itself did not change.
GOLDEN_ROUND_OPS = [
    "apply_batch:read/trials,count/trials",
    "apply_batch:write/trials,write/trials,write/trials,write/trials",
    "write/telemetry",
    "count/telemetry",
]
GOLDEN_STREAM = "0db948ff5df22747690ee2f91feb43c9"

#: Recorded against the pre-ring producer: round 1 completed (so the
#: MaxParallelStrategy has a lie value), round 2 left in flight, round 3's
#: ops captured — the sync read, FOUR lie registrations for the in-flight
#: batch, the batched register, the telemetry flush.
GOLDEN_LIE_ROUND_OPS = [
    "apply_batch:read/trials,count/trials",
    "write/lying_trials",
    "write/lying_trials",
    "write/lying_trials",
    "write/lying_trials",
    "apply_batch:write/trials,write/trials,write/trials,write/trials",
    "write/telemetry",
    "count/telemetry",
]
GOLDEN_LIE_STREAM = "8ec35f3fc6f7a0298a688a2d6ff4adba"


def test_depth_1_storage_op_sequence_matches_pre_ring_golden():
    db = RecordingDB(create_storage({"type": "memory"})._db)
    exp, producer = _build(db)
    db.ops.clear()
    for _ in range(3):
        producer.update()
        producer.produce(4)
    assert db.ops == GOLDEN_ROUND_OPS * 3
    assert _stream_hash(exp) == GOLDEN_STREAM


def test_depth_1_lie_round_matches_pre_ring_golden():
    db = RecordingDB(create_storage({"type": "memory"})._db)
    exp, producer = _build(db)
    storage = exp.storage
    producer.update()
    producer.produce(4)
    for t in exp.fetch_trials():
        storage.set_trial_status(t, "reserved", was="new")
        storage.update_completed_trial(
            t, [Result("obj", "objective", float(sum(t.params.values())))]
        )
    producer.update()
    producer.produce(4)  # left in flight -> lied about next round
    db.ops.clear()
    producer.update()
    producer.produce(4)
    assert db.ops == GOLDEN_LIE_ROUND_OPS
    assert _stream_hash(exp, sort_params=True) == GOLDEN_LIE_STREAM


@pytest.mark.parametrize("depth", [2, 3, 5])
def test_depth_n_stream_is_bit_identical_to_depth_1(depth):
    def run(d):
        exp, producer = _build(
            create_storage({"type": "memory"})._db, seed=9, pipeline_depth=d
        )
        for _ in range(4):
            producer.update()
            producer.produce(4)
        return _stream_hash(exp), len(producer._spec_ring)

    base_hash, base_ring = run(1)
    deep_hash, deep_ring = run(depth)
    assert deep_hash == base_hash
    assert base_ring == 1
    assert deep_ring == depth  # the ring genuinely holds N rounds in flight


def test_depth_n_register_runs_under_n_in_flight_dispatches():
    """The pipelining claim itself: when the batched register hits storage,
    the ring already holds ``pipeline_depth`` speculative rounds."""
    inner = create_storage({"type": "memory"})._db
    observed = []

    class Spy(RecordingDB):
        def __getattr__(self, name):
            attr = super().__getattr__(name)
            if name != "apply_batch":
                return attr

            def wrapper(ops):
                if any(op == "write" and a[0] == "trials" for op, a, _ in ops):
                    observed.append(len(producer._spec_ring))
                return attr(ops)

            return wrapper

    db = Spy(inner)
    exp, producer = _build(db, seed=5, pipeline_depth=3)
    for _ in range(3):
        producer.update()
        producer.produce(4)
    # Round 1 fills the ring before its commit; every commit thereafter
    # runs strictly under 3 in-flight device dispatches.
    assert observed == [3, 3, 3]


def test_pipeline_depth_resolution_order(monkeypatch):
    storage = create_storage({"type": "memory"})
    exp = build_experiment(
        storage,
        "depth-res",
        priors={"x": "uniform(0, 1)"},
        algorithms="random",
    ).instantiate(seed=0)
    assert Producer(exp).pipeline_depth == 1  # default
    monkeypatch.setenv("ORION_TPU_PIPELINE_DEPTH", "4")
    assert Producer(exp).pipeline_depth == 4  # env
    exp.pipeline_depth = 2
    assert Producer(exp).pipeline_depth == 2  # worker-level config knob
    assert Producer(exp, pipeline_depth=6).pipeline_depth == 6  # explicit arg
    assert Producer(exp, pipeline_depth=0).pipeline_depth == 1  # floor


def test_opt_in_model_based_speculation_is_capped_at_depth_1():
    """tpu_bo's `speculative_suggest=True` sets speculation_safe on the
    INSTANCE: async-BO semantics promise each in-flight round is lie-
    conditioned on the previous one, which a burst of N dispatches from
    one posterior would break (N copies of the same optimum, whole-ring
    discard on the duplicate slots).  The effective depth must stay 1
    regardless of the knob; only CLASS-level observation-independent
    algorithms ride the deep ring."""
    storage = create_storage({"type": "memory"})
    exp = build_experiment(
        storage,
        "optin-cap",
        priors={"x": "uniform(0, 1)", "y": "uniform(0, 1)"},
        max_trials=1000,
        algorithms={
            "tpu_bo": {
                "n_init": 4,
                "n_candidates": 128,
                "fit_steps": 2,
                "speculative_suggest": True,
            }
        },
        pool_size=4,
    ).instantiate(seed=0)
    producer = Producer(exp, pipeline_depth=4)
    for _ in range(3):
        producer.update()
        producer.produce(4)
    assert producer._speculative is not None  # it DOES speculate...
    assert len(producer._spec_ring) == 1  # ...but never deeper than 1


def test_instance_assigned_register_suggestion_hook_still_fires():
    """The per-slot register_suggestion gate must honor instance-level
    hooks (a plugin assigning it in __init__, a test monkeypatching it
    after the Producer was built) exactly like the pre-gate dynamic call."""
    storage = create_storage({"type": "memory"})
    exp = build_experiment(
        storage,
        "hook",
        priors={"x": "uniform(0, 1)"},
        max_trials=1000,
        algorithms="random",
        pool_size=4,
    ).instantiate(seed=0)
    producer = Producer(exp)
    seen = []
    exp.algorithm.register_suggestion = lambda params: seen.append(dict(params))
    producer.update()
    producer.produce(4)
    # One callback per registered slot on the REAL instance (4) plus the
    # speculative conditioning pass on the naive copy (4, the deepcopy
    # shares the hook) — exactly the pre-gate dynamic-call behavior.
    assert len(seen) == 8


def test_non_speculative_algorithms_never_fill_the_ring():
    storage = create_storage({"type": "memory"})
    exp = build_experiment(
        storage,
        "no-spec",
        priors={"x": "uniform(0, 1)"},
        algorithms={"tpu_bo": {"n_init": 4, "n_candidates": 64, "fit_steps": 2}},
        pool_size=4,
    ).instantiate(seed=0)
    producer = Producer(exp, pipeline_depth=4)
    producer.update()
    producer.produce(4)
    assert producer._speculative is None
    assert len(producer._spec_ring) == 0
