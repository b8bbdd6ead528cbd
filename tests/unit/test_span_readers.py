"""The benchmark's readers of the program's own spans
(``benchmark/metrics/``) on a synthetic profiler trace: nested spans give
the right times and self time, a renamed span fails the run
(``NotFound``), and a program that writes no spans on the clock reads as
nothing."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import load_reader  # noqa: E402
from benchmark.trace import Event, NotFound, Trace  # noqa: E402

HOST, DEVICE = "/host:CPU", "/device:TPU:0"

#: One round as (name, start, end) in ns; the second round repeats it 200 ns
#: later without the retry and its reservation.
ROUND = [
    ("bench.suggest", 0, 100),
    ("producer.update", 0, 20),
    ("storage.fetch_update_view", 2, 6),
    ("algo.observe", 8, 14),
    ("algo.encode", 8, 9),
    ("producer.round", 20, 95),
    ("producer.suggest", 21, 60),
    ("algo.suggest", 22, 58),
    ("algo.plan", 22, 26),
    ("jax.suggest_step.dispatch", 26, 28),
    ("algo.fetch", 28, 50),
    ("algo.decode", 50, 56),
    ("producer.register", 60, 80),
    ("storage.commit", 61, 75),
    ("storage.apply", 62, 65),
    ("producer.retry", 80, 94),
    ("storage.reserve_trials", 82, 86),
    ("bench.objective", 100, 110),
    ("bench.observe", 110, 130),
    ("storage.update_completed_trials", 112, 120),
]
RETRY_ONLY = {"producer.retry", "storage.reserve_trials"}

#: metric -> (per-round reading, a span whose renaming fails the run)
EXPECTED = {
    "api_prep_ms": ((4 + 2) * 1e-6, "algo.plan"),
    "api_fetch_ms": (22e-6, "algo.fetch"),
    "api_decode_ms": (6e-6, "algo.decode"),
    "api_observe_ms": (6e-6, "algo.observe"),
    # fetch 4 + commit 14 (apply nested inside) + update 8, and the retry's
    # reservation 4 in the first round: (30 + 26) / 2.
    "storage_round_ms": (28e-6, "storage."),
    # update + round cover 95 ns a round; inside them the storage, algo and
    # jax spans cover 4 + 6 + 36 + 14 (+ 4 with the retry): (31 + 35) / 2.
    "producer_self_ms": (33e-6, "producer.round"),
    "producer_retries": (0.5, "producer.round"),
}


class _Ctx:
    def __init__(self, events):
        self.trace = Trace(events, 1)
        self.lo = min(s for s, _ in self.trace.host_spans("bench.suggest"))
        self.hi = max(e for _, e in self.trace.host_spans("bench.observe"))
        self.rounds = len(self.trace.host_spans("bench.suggest"))


def _events(rename=None, program=True):
    events = [Event(DEVICE, "XLA Ops", "%fusion.1 = f32[]", 30.0, 15.0)]
    for k in range(2):
        for name, start, end in ROUND:
            if k and name in RETRY_ONLY:
                continue
            if not program and not name.startswith("bench."):
                continue
            if rename and name.startswith(rename):
                name = "renamed." + name
            events.append(Event(HOST, "python", name, float(start + 200 * k),
                                float(end - start)))
    return events


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_of_program_spans(metric):
    reader = load_reader(REPO, metric)
    value, needed = EXPECTED[metric]
    assert reader.read(_Ctx(_events())) == pytest.approx(value)
    with pytest.raises(NotFound):
        reader.read(_Ctx(_events(rename=needed)))
    # A program that writes no spans on the profiler's clock, and a run
    # without a trace, read as nothing.
    assert reader.read(_Ctx(_events(program=False))) is None
    untraced = _Ctx(_events())
    untraced.trace = None
    assert reader.read(untraced) is None
