"""Programs compiled or loaded from the compile cache inside the measured
window (``jax.monitoring``); the warm-up should leave none."""


def read(ctx):
    return ctx.window_compiles
