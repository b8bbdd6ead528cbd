"""What the readers of the program's own spans share.

The program writes a span on the profiler's clock for every ``with
TELEMETRY.span(name)`` while telemetry is on, as the traced run has it
(``orion_tpu/telemetry.py``). These readers take those spans from the
trace of a ``--trace 1`` run, keep the ones that start in the traced slice
``[ctx.lo, ctx.hi]`` and give time per round of the slice. A program whose
trace holds none of its spans (one that predates them) reads as nothing;
one that writes them but lacks a span a reader needs fails the run
(``NotFound``).
"""

from benchmark.trace import NotFound, merged

#: Name prefixes of the spans that mark a program writing on the clock.
PROGRAM_PREFIXES = ("algo.", "producer.")


def host_names(ctx, prefixes):
    """Names of the host spans in the trace that start with ``prefixes``."""
    return {e.name for e in ctx.trace.events
            if e.name.startswith(prefixes) and not e.plane.startswith("/device:")}


def on_clock(ctx):
    """Whether the run was traced and its program writes spans there."""
    return ctx.trace is not None and bool(host_names(ctx, PROGRAM_PREFIXES))


def spans(ctx, *names, required=True):
    """``(start, end)`` of the named host spans that start in the slice. A
    name absent from the whole trace raises ``NotFound`` if ``required``."""
    out = []
    for name in names:
        try:
            found = ctx.trace.host_spans(name)
        except NotFound:
            if required:
                raise
            continue
        out += [(s, e) for s, e in found if ctx.lo <= s <= ctx.hi]
    return out


def covered_ns(intervals):
    """Length of the union of ``intervals``."""
    return sum(e - s for s, e in merged(intervals))


def per_round_ms(ctx, intervals):
    return covered_ns(intervals) / ctx.rounds / 1e6


def overlap_ns(outer, inner):
    """Length of the union of ``outer`` that the union of ``inner`` covers."""
    inner = merged(inner)
    total = 0.0
    for lo, hi in merged(outer):
        total += sum(min(e, hi) - max(s, lo) for s, e in inner if s < hi and e > lo)
    return total
