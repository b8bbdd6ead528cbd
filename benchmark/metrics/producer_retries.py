"""Retries per producer round after duplicate rows: the program's
``producer.retry`` spans per ``producer.round`` span, from the trace (a
window without a retry reads 0; one without a round fails)."""

from benchmark.metrics._program_spans import on_clock, spans
from benchmark.trace import NotFound


def read(ctx):
    if not on_clock(ctx):
        return None
    rounds = spans(ctx, "producer.round")
    if not rounds:
        raise NotFound("no 'producer.round' span in the traced slice")
    return len(spans(ctx, "producer.retry", required=False)) / len(rounds)
