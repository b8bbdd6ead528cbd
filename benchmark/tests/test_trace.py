"""The trace reduction on one recorded round (TPU v5 lite, one chip)."""

import json
import os

import numpy as np
import pytest

from benchmark import trace
from benchmark.metrics.gram_roofline import is_gram
from benchmark.roofline import gram_work, roofline_share

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_round.json")
PEAKS = json.load(open(os.path.join(os.path.dirname(__file__), "..", "peaks.json")))


@pytest.fixture(scope="module")
def events():
    with open(DATA) as f:
        return [trace.Event(*row) for row in json.load(f)["events"]]


def test_busy_union_matches_a_timeline(events):
    tr = trace.Trace(events, 1)
    lo, hi = 0.0, max(e.start_ns + e.dur_ns for e in events)
    grid = np.zeros(int(hi // 10) + 2, bool)  # 10 ns cells
    for e in tr.ops["/device:TPU:0"]:
        grid[int(e.start_ns // 10):int((e.start_ns + e.dur_ns) // 10)] = True
    assert tr.busy_ns("/device:TPU:0", lo, hi) == pytest.approx(grid.sum() * 10, rel=1e-3)
    idle = sum(s for _, s in tr.idle_gaps(lo, hi, ("bench.suggest", "bench.observe")))
    assert idle + tr.mean_busy_ns(lo, hi) / 1e9 == pytest.approx(hi / 1e9, rel=1e-6)


def test_step_and_kernel_times(events):
    tr = trace.Trace(events, 1)
    steps = tr.module_durations_ns("jit__suggest_step")
    assert len(steps) == 1 and 1e6 < steps[0] < 2e7
    gram = tr.kernel_ns(is_gram)
    ops, nbytes = gram_work(16384, 256, 6)
    share, bound = roofline_share(ops, nbytes, gram / 1e9, PEAKS["TPU v5 lite"])
    assert bound == "memory" and 0 < share <= 100
    assert not is_gram("%multiply_bitcast_fusion")


def test_missing_pieces_raise(events):
    tr = trace.Trace(events, 1)
    with pytest.raises(trace.NotFound):
        tr.module_durations_ns("jit__no_such_step")
    with pytest.raises(trace.NotFound):
        tr.kernel_ns(lambda name: name.startswith("%no_such_kernel"))
    with pytest.raises(trace.NotFound):
        tr.collective_ns()
    with pytest.raises(trace.NotFound):
        trace.Trace(events, 2)


def test_collectives_average_over_chips(events):
    extra = [trace.Event(f"/device:TPU:{i}", trace.OPS_LINE, "%all-reduce.7", 1000.0,
                         500.0 * (i + 1)) for i in range(2)]
    tr = trace.Trace(events + extra, 2)
    assert tr.collective_ns() == pytest.approx(750.0)
