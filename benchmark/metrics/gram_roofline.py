"""Roofline share of the fused gram kernel (``ops/gram.py``): its work from
the candidate-scoring shapes over its mean device time per call. A traced
run without the kernel fails (``NotFound``)."""

from benchmark.roofline import gram_work, roofline_share


def is_gram(name):
    """The kernel's own op (``%fused_gram.N = ... custom-call``), not the
    fusions that read its output."""
    return name.split(" = ", 1)[0].lstrip("%").startswith("fused_gram")


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.kernel_ns(is_gram, ctx.lo, ctx.hi) / 1e9
    algo = ctx.cell.algo
    m = int(algo["n_candidates"]) // ctx.n_devices
    n = 1 << (int(algo["tr_local_m"]) - 1).bit_length()
    ops, nbytes = gram_work(m, n, ctx.cell.dims)
    share, _ = roofline_share(ops, nbytes, seconds, ctx.peaks())
    return share
