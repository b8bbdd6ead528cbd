"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's
numbers.

``load`` flattens the trace into ``Event`` tuples; ``Trace`` answers the
questions the per-layer metrics ask: device busy time as the union of op
intervals, the executions of one jitted program found by its module name,
one kernel's device time, collective time, and the host spans the
benchmark itself wrote. What it is asked for and cannot find raises
``NotFound``; it never reads as zero.
"""

import glob
import os
from collections import defaultdict, namedtuple

Event = namedtuple("Event", "plane line name start_ns dur_ns")

#: Lines of a TPU plane: whole program executions, and the ops inside them.
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all", "send", "recv")


class NotFound(LookupError):
    """The trace holds nothing of what was asked for."""


def find_xplane(directory):
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise NotFound(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path):
    """Every event of the trace, with absolute start times in ns."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def merged(intervals):
    """Sorted, disjoint ``[start, end]`` cover of the intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_ns(intervals, lo, hi):
    """Length of the union of ``(start, end)`` intervals within ``[lo, hi]``."""
    return sum(e - s for s, e in merged((max(s, lo), min(e, hi)) for s, e in intervals)
               if e > s)


class Trace:
    def __init__(self, events, n_devices):
        self.events = events
        planes = sorted({e.plane for e in events if e.plane.startswith("/device:TPU:")},
                        key=lambda name: int(name.rsplit(":", 1)[1]))
        if len(planes) < n_devices:
            raise NotFound(f"{len(planes)} TPU planes in the trace, {n_devices} expected")
        self.devices = planes[:n_devices]
        self.ops = {p: [e for e in events if e.plane == p and e.line == OPS_LINE]
                    for p in self.devices}
        self.modules = {p: [e for e in events if e.plane == p and e.line == MODULES_LINE]
                        for p in self.devices}
        if not any(self.ops.values()):
            raise NotFound("no device ops in the trace")

    # -- host spans written by the benchmark --------------------------------
    def host_spans(self, name):
        spans = [(e.start_ns, e.start_ns + e.dur_ns) for e in self.events
                 if e.name == name and not e.plane.startswith("/device:")]
        if not spans:
            raise NotFound(f"no host span {name!r}")
        return sorted(spans)

    # -- device time ---------------------------------------------------------
    def busy_ns(self, device, lo, hi):
        return union_ns([(e.start_ns, e.start_ns + e.dur_ns) for e in self.ops[device]], lo, hi)

    def mean_busy_ns(self, lo, hi):
        return sum(self.busy_ns(p, lo, hi) for p in self.devices) / len(self.devices)

    def module_durations_ns(self, prefix, lo=None, hi=None):
        """Per execution of the jitted program whose module name starts
        with ``prefix`` (e.g. ``jit__suggest_step``): device duration,
        averaged over the devices used."""
        per_device = []
        for p in self.devices:
            durs = [e.dur_ns for e in self.modules[p] if e.name.startswith(prefix)
                    and (lo is None or lo <= e.start_ns <= hi)]
            per_device.append(durs)
        if not all(per_device):
            raise NotFound(f"no executions of module {prefix!r} on every device")
        n = min(len(d) for d in per_device)
        return [sum(d[i] for d in per_device) / len(per_device) for i in range(n)]

    def op_durations_ns(self, match, lo=None, hi=None):
        """Durations of device ops whose name ``match`` accepts, per device."""
        out = {}
        for p in self.devices:
            out[p] = [e.dur_ns for e in self.ops[p] if match(e.name)
                      and (lo is None or lo <= e.start_ns <= hi)]
        return out

    def kernel_ns(self, match, lo=None, hi=None):
        """Mean device time of one call of the kernel ``match`` accepts."""
        per = self.op_durations_ns(match, lo, hi)
        durs = [d for v in per.values() for d in v]
        if not durs:
            raise NotFound("kernel not in the trace")
        return sum(durs) / len(durs)

    def collective_ns(self, lo=None, hi=None):
        """Total collective op time, averaged over the devices used."""
        per = self.op_durations_ns(
            lambda n: any(c in n.lower() for c in COLLECTIVES), lo, hi)
        if not any(per.values()):
            raise NotFound("no collective ops in the trace")
        return sum(sum(v) for v in per.values()) / len(per)

    # -- breakdown -----------------------------------------------------------
    def top_ops(self, lo, hi, k=10):
        total = defaultdict(float)
        for p in self.devices:
            for e in self.ops[p]:
                if lo <= e.start_ns <= hi:
                    total[e.name] += e.dur_ns
        top = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9 / len(self.devices)] for name, ns in top]

    def idle_gaps(self, lo, hi, host_names, k=10):
        """Idle device time of the first device, by the benchmark span the
        host was in at each gap's midpoint."""
        busy = merged([(e.start_ns, e.start_ns + e.dur_ns) for e in self.ops[self.devices[0]]
                       if e.start_ns + e.dur_ns > lo and e.start_ns < hi])
        spans = []
        for name in host_names:
            try:
                spans += [(s, e, name) for s, e in self.host_spans(name)]
            except NotFound:
                pass
        total = defaultdict(float)
        edge = lo
        for s, e in busy + [[hi, hi]]:
            s = max(s, lo)
            if s > edge:
                mid = 0.5 * (s + edge)
                owner = next((n for a, b, n in spans if a <= mid <= b), "other")
                total[owner] += s - edge
            edge = max(edge, e)
        top = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9] for name, ns in top]
