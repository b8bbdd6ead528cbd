"""Summarise a profiler trace and cut the trace test's fixture from it.

    python benchmark/tests/record_trace.py <trace dir or .xplane.pb> <out.json>

Prints each plane and line with its event count. Writes one round: the
device ops and modules of the first TPU, and the benchmark's host spans,
from the second ``bench.suggest`` span to the third, as ``[plane, line,
name, start_ns, dur_ns]`` rows; op names are cut at `` = `` and start
times made relative to the round.
"""

import json
import os
import sys
from collections import Counter

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import trace  # noqa: E402


def main(src, out):
    path = src if src.endswith(".pb") else trace.find_xplane(src)
    events = trace.load(path)
    counts = Counter(f"{e.plane} | {e.line}" for e in events)
    for key, n in sorted(counts.items()):
        print(n, key)
    starts = sorted(e.start_ns for e in events if e.name == "bench.suggest")
    lo, hi = starts[1], starts[2]
    keep = []
    for e in events:
        if not lo <= e.start_ns < hi:
            continue
        if e.plane == "/device:TPU:0" and e.line in (trace.OPS_LINE, trace.MODULES_LINE):
            name = e.name if e.line == trace.MODULES_LINE else e.name.split(" = ")[0]
            keep.append([e.plane, e.line, name, e.start_ns - lo, e.dur_ns])
        elif e.name.startswith("bench."):
            keep.append([e.plane, e.line, e.name, e.start_ns - lo, e.dur_ns])
    with open(out, "w") as f:
        json.dump({"source": path, "events": keep}, f, separators=(",", ":"))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
