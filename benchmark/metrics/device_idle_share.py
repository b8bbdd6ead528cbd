"""Share of the traced window in which no op ran on the chips (mean over
the chips used); read from the profiler trace."""


def read(ctx):
    if ctx.trace is None or ctx.window_ns <= 0:
        return None
    return 1.0 - ctx.busy_ns / ctx.window_ns
