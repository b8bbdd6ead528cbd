"""The program's own ``producer.round`` span (``core/producer.py``), mean
over the window's rounds."""


def read(ctx):
    durs = [s["dur"] for s in ctx.telemetry_spans if s.get("name") == "producer.round"]
    if not durs:
        return None
    return 1e3 * sum(durs) / len(durs)
