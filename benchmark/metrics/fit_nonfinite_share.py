"""Share of the GP fit's Adam steps whose loss or gradient was non-finite:
the program's counters ``gp.fit.nonfinite_steps`` over ``gp.fit.steps``,
read from the in-process telemetry registry after the traced run. The
counters run from the moment telemetry is on, so the share covers the
set-up experiment and the window alike. Such a step is zeroed and leaves
the hyper-parameters where they were: a round of them is a frozen fit that
still costs its steps. A program that never writes ``gp.fit.steps`` reads
nothing."""


def read(ctx):
    from orion_tpu.telemetry import TELEMETRY

    steps = TELEMETRY.counter_value("gp.fit.steps")
    if not steps:
        return None
    return TELEMETRY.counter_value("gp.fit.nonfinite_steps") / steps
