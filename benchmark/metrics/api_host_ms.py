"""Host time of the algorithm API per round: the benchmark's own spans
around suggest and observe, less the device-busy time inside them."""


def read(ctx):
    if ctx.trace is None:
        return None
    spans = ctx.trace.host_spans("bench.suggest") + ctx.trace.host_spans("bench.observe")
    host = sum(e - s for s, e in spans)
    busy = sum(ctx.trace.mean_busy_ns(s, e) for s, e in spans)
    return (host - busy) / ctx.rounds / 1e6
