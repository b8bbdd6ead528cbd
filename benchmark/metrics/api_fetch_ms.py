"""Host time per round spent waiting for the suggest step and its row
transfer: the program's ``algo.fetch`` spans, from the trace."""

from benchmark.metrics._program_spans import on_clock, per_round_ms, spans


def read(ctx):
    if not on_clock(ctx):
        return None
    return per_round_ms(ctx, spans(ctx, "algo.fetch"))
