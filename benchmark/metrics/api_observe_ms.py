"""Host time per round in the algorithm's observe (encode, history append,
prewarm check, trust region): the program's ``algo.observe`` spans, from
the trace."""

from benchmark.metrics._program_spans import on_clock, per_round_ms, spans


def read(ctx):
    if not on_clock(ctx):
        return None
    return per_round_ms(ctx, spans(ctx, "algo.observe"))
