"""Storage time per round: the union of the program's ``storage.*`` op
spans, from the trace."""

from benchmark.metrics._program_spans import host_names, on_clock, per_round_ms, spans
from benchmark.trace import NotFound


def read(ctx):
    if not on_clock(ctx):
        return None
    names = host_names(ctx, ("storage.",))
    if not names:
        raise NotFound("no host span 'storage.*'")
    return per_round_ms(ctx, spans(ctx, *names))
