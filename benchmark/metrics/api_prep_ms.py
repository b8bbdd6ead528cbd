"""Host time of the fused step's plan and dispatch per round: the program's
``algo.plan`` spans (local-view gather dispatch, key split, plan build)
and ``jax.suggest_step.dispatch`` spans, from the trace."""

from benchmark.metrics._program_spans import on_clock, per_round_ms, spans


def read(ctx):
    if not on_clock(ctx):
        return None
    return per_round_ms(ctx, spans(ctx, "algo.plan", "jax.suggest_step.dispatch"))
