"""Device time of one execution of the fused suggest step, found in the
trace by its module name, mean over the traced window's executions. A
traced run without it fails (``NotFound``)."""

MODULE = "jit__suggest_step"


def read(ctx):
    if ctx.trace is None:
        return None
    durs = ctx.trace.module_durations_ns(MODULE, ctx.lo, ctx.hi)
    return sum(durs) / len(durs) / 1e6
