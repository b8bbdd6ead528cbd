"""The producer's own host time per round: its ``producer.update`` and
``producer.round`` spans less the time inside an ``algo.*``, ``storage.*``
or ``jax.*`` span (naive copy, lies, trial documents, bookkeeping), from
the trace."""

from benchmark.metrics._program_spans import (
    covered_ns,
    host_names,
    on_clock,
    overlap_ns,
    spans,
)

CHILDREN = ("algo.", "storage.", "jax.")


def read(ctx):
    if not on_clock(ctx):
        return None
    producer = spans(ctx, "producer.update", "producer.round")
    inner = [(s, e) for name in host_names(ctx, CHILDREN)
             for s, e in ctx.trace.host_spans(name)]
    return (covered_ns(producer) - overlap_ns(producer, inner)) / ctx.rounds / 1e6
