"""PlanPrepToken: the steady-path dispatch-prep cache (algo/tpu_bo.py).

The token may only ever be a shortcut — a run with the token disabled
(``algo._prep_token = None`` forces the full prep-key probe every round)
must produce a bit-identical suggestion stream, because both paths feed
the SAME ``_finish_plan`` tail.  And the telemetry counters it feeds (the
bench's ``dispatch_us_saved`` line reads them) must count what actually
happened: one miss to pin a bucket, hits while the bucket holds, a fresh
miss when the fast key changes (q bucket, cold→warm flip).
"""

import pytest

from orion_tpu.algo.base import create_algo
from orion_tpu.space.dsl import build_space
from orion_tpu.telemetry import TELEMETRY

CFG = {"tpu_bo": {"n_init": 4, "n_candidates": 64, "fit_steps": 2,
                   "refit_steps": 1}}
PRIORS = {"a": "uniform(0, 1)", "b": "uniform(0, 1)", "c": "uniform(0, 1)"}
SEED_POINTS = [
    {"a": 0.1, "b": 0.2, "c": 0.3},
    {"a": 0.7, "b": 0.1, "c": 0.9},
    {"a": 0.4, "b": 0.8, "c": 0.2},
    {"a": 0.9, "b": 0.5, "c": 0.6},
]


def _warm_algo(token=True):
    space = build_space(PRIORS)
    algo = create_algo(space, CFG, seed=0)
    if not token:
        algo._prep_token = None
    algo.observe(
        SEED_POINTS,
        [{"objective": p["a"] + p["b"]} for p in SEED_POINTS],
    )
    return algo


def test_token_fast_path_is_bit_identical_to_full_probe():
    fast = _warm_algo(token=True)
    slow = _warm_algo(token=False)
    assert slow._prep_token is None
    for round_ in range(4):
        q = 16 if round_ == 2 else 4  # bucket change mid-stream too
        got = fast.suggest(q)
        want = slow.suggest(q)
        assert got == want, f"streams diverged at round {round_}"
        outcomes = [{"objective": sum(p.values())} for p in got]
        fast.observe(got, outcomes)
        slow.observe(want, outcomes)
    assert fast._prep_token.pinned is not None  # the fast path was live


@pytest.fixture
def telemetry_on():
    was = TELEMETRY.enabled
    TELEMETRY.enable()
    yield
    if not was:
        TELEMETRY.disable()


def _token_counts(since=(0, 0)):
    """(misses, hits) of the prep token since ``since``."""
    return (TELEMETRY.counter_value("plan.token.misses") - since[0],
            TELEMETRY.counter_value("plan.token.hits") - since[1])


def test_dispatch_prep_stats_count_pin_hold_and_rekey(telemetry_on):
    algo = _warm_algo(token=True)
    start = _token_counts()
    algo.suggest(4)  # cold fit: miss, pins (bucket 8, warm_is_none=True)
    algo.suggest(4)  # warm now — fast key flipped: miss, re-pins
    algo.suggest(4)  # steady path
    algo.suggest(4)
    assert _token_counts(start) == (2, 2)
    algo.suggest(16)  # q bucket 8 -> 16: the token must not lie
    assert _token_counts(start) == (3, 2)
    # Every plan is timed by its ``algo.plan`` span: the counters' time
    # lives in that histogram, not in a side channel of its own.
    plan = TELEMETRY.snapshot()["histograms"]["algo.plan"]
    assert plan["count"] >= 5
