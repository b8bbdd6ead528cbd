"""Work of the kernels from their shapes, for roofline shares.

The counts are what the algorithm needs, whatever implements it: padding
a kernel adds to its time, never to its work.
"""


def gram_work(m, n, d):
    """Matern-5/2 cross gram of ``m`` candidates against ``n`` fit rows in
    ``d`` dimensions: (ops, bytes). The cross term is 2*m*n*d multiply-adds;
    the distance expansion and the epilogue about 12 ops per entry; float32
    inputs read once and the (m, n) gram written once."""
    ops = 2.0 * m * n * d + 12.0 * m * n
    nbytes = 4.0 * (m * d + n * d + m * n)
    return ops, nbytes


def roofline_share(ops, nbytes, seconds, peaks):
    """(share in %, bound): the least time the chip could take over the time
    taken, and which of compute or memory bounds it."""
    t_compute = ops / peaks["bf16_flops_per_s"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    bound = "memory" if t_memory >= t_compute else "compute"
    return 100.0 * max(t_compute, t_memory) / seconds, bound
