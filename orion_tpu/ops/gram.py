"""Pallas fused gram kernel: pairwise kernel matrix in one VMEM pass.

The XLA path (`orion_tpu.algo.gp.kernels`) computes the candidate-scoring
cross-gram as a matmul producing an (m, n) squared-distance matrix followed
by the Matern/RBF elementwise epilogue.  At m ~ 8192 candidates that
intermediate is tens of MB: if XLA materializes it, the epilogue pays an HBM
round-trip at ~2x the matrix size in traffic.  This kernel tiles the output
over a (m/bm, n/bn) grid, runs the cross matmul per tile on the MXU, and
applies the epilogue while the tile is still in VMEM — one HBM write of the
final gram, nothing else.

Scope: forward-only scoring (acquisition / posterior over candidates).  The
MLL fit differentiates through its (n, n) kernel, and a pallas_call has no
autodiff rule — that path stays on XLA by design.
"""

import concurrent.futures
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

_BM = 256  # output tile rows (candidates)
_BN = 256  # output tile cols (observations)
_LANE = 128  # TPU lane width: last dim of VMEM tiles


def _epilogue(kind, r2, amp):
    if kind == "rbf":
        return amp * jnp.exp(-0.5 * r2)
    if kind == "matern52":
        # No double-where guard needed here: this kernel is forward-only, and
        # sqrt(r2=0) itself is finite (the guard in the XLA path protects the
        # d(sqrt)/d(r2) gradient the MLL fit takes).
        r = jnp.sqrt(r2)
        sqrt5_r = jnp.sqrt(5.0) * r
        return amp * (1.0 + sqrt5_r + (5.0 / 3.0) * r2) * jnp.exp(-sqrt5_r)
    raise ValueError(f"unknown kernel {kind!r}")


def _gram_kernel(amp_ref, a_ref, b_ref, out_ref, *, kind):
    a = a_ref[:]  # (bm, d_pad) pre-scaled by 1/lengthscale
    b = b_ref[:]  # (bn, d_pad)
    # Full-precision cross term: the aa+bb-2ab cancellation amplifies the
    # default bf16 matmul error into an indefinite gram (see kernels.py).
    cross = jax.lax.dot_general(
        a,
        b,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    aa = jnp.sum(a * a, axis=1, keepdims=True)  # (bm, 1)
    bb = jnp.sum(b * b, axis=1, keepdims=True).T  # (1, bn)
    r2 = jnp.maximum(aa + bb - 2.0 * cross, 0.0)
    out_ref[:] = _epilogue(kind, r2, amp_ref[0])


def _pad2(x, rows, cols):
    r, c = x.shape
    if r == rows and c == cols:
        return x
    return jnp.pad(x, ((0, rows - r), (0, cols - c)))


@functools.partial(jax.jit, static_argnames=("kind", "interpret"))
def fused_gram(xa, xb, inv_lengthscales, amplitude, *, kind="matern52", interpret=False):
    """Kernel matrix k(xa, xb) -> (m, n), fused matmul + epilogue.

    Matches `orion_tpu.algo.gp.kernels.kernel_matrix` numerically (forward
    values; this path defines no gradient).

    XLA's partitioner cannot split a Mosaic kernel.  Under an ambient
    multi-device mesh (`jax.sharding.use_abstract_mesh`, which the fused
    suggest step enters for its candidate scoring) the kernel therefore
    runs per device on that device's rows of ``xa`` against all of ``xb``
    (``shard_map``), rows zero-padded to a multiple of the device count.
    Each output row depends on its own input row only, so the split
    changes no value.
    """
    mesh = jax.sharding.get_abstract_mesh()
    gram = functools.partial(_gram, kind=kind, interpret=interpret)
    if mesh.empty or mesh.size == 1 or mesh.manual_axes:
        return gram(xa, xb, inv_lengthscales, amplitude)
    m = xa.shape[0]
    xa = _pad2(xa, -(-m // mesh.size) * mesh.size, xa.shape[1])
    rows = jax.sharding.PartitionSpec(mesh.axis_names, None)
    whole = jax.sharding.PartitionSpec()
    out = jax.shard_map(
        gram,
        in_specs=(rows, whole, whole, whole),
        out_specs=rows,
        check_vma=False,
    )(xa, xb, inv_lengthscales, amplitude)
    return out[:m]


def _gram(xa, xb, inv_lengthscales, amplitude, *, kind, interpret):
    from jax.experimental import pallas as pl

    m, d = xa.shape
    n = xb.shape[0]
    a = (xa * inv_lengthscales).astype(jnp.float32)
    b = (xb * inv_lengthscales).astype(jnp.float32)

    d_pad = max(_LANE, -(-d // _LANE) * _LANE)
    m_pad = -(-m // _BM) * _BM
    n_pad = -(-n // _BN) * _BN
    a = _pad2(a, m_pad, d_pad)  # zero columns add nothing to distances
    b = _pad2(b, n_pad, d_pad)
    amp = jnp.reshape(amplitude.astype(jnp.float32), (1,))

    out = pl.pallas_call(
        functools.partial(_gram_kernel, kind=kind),
        grid=(m_pad // _BM, n_pad // _BN),
        in_specs=[
            pl.BlockSpec((1,), lambda i, j: (0,)),
            pl.BlockSpec((_BM, d_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((_BN, d_pad), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((_BM, _BN), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), jnp.float32),
        interpret=interpret,
    )(amp, a, b)
    return out[:m, :n]


def _env_opt_in():
    """ORION_TPU_PALLAS as a tri-state: True / False / None (unset)."""
    forced = os.environ.get("ORION_TPU_PALLAS", "").strip()
    if not forced:  # set-but-empty means unset
        return None
    return forced.lower() not in ("0", "false", "no", "off")


@functools.lru_cache(maxsize=1)
def _probe():
    """Does the fused gram run on the default backend?  Only a TPU
    dispatches it (CPU/GPU interpret mode is for tests, not production).
    On a TPU a kernel that fails to lower or run RAISES here: quietly
    giving way to XLA would hide a broken kernel.  ORION_TPU_PALLAS=0 is
    the one explicit opt-out.

    The first caller is usually the trace of the fused step
    (`cross_kernel_matrix` asks at trace time), where a jit call would be
    staged into the outer program and could not be read back.  Trace state
    is per thread, so the probe kernel runs on a fresh one."""
    if jax.default_backend() != "tpu":
        return False

    def run():
        x = jnp.asarray(np.random.default_rng(0).uniform(size=(8, 4)), jnp.float32)
        return np.asarray(
            fused_gram(x, x, jnp.ones((4,)), jnp.asarray(1.0), kind="matern52")
        )

    with concurrent.futures.ThreadPoolExecutor(max_workers=1) as pool:
        out = pool.submit(run).result()
    if not np.isfinite(out).all():
        raise RuntimeError(f"fused_gram probe returned non-finite values: {out}")
    return True


@functools.lru_cache(maxsize=1)
def pallas_available():
    """True when the fused gram can run here; ORION_TPU_PALLAS=1/0
    overrides autodetection (tests force both branches on CPU)."""
    forced = _env_opt_in()
    if forced is not None:
        return forced
    return _probe()


@functools.lru_cache(maxsize=1)
def pallas_enabled():
    """Should the GP engine DISPATCH to the fused gram?  Auto-enabled when
    the compile/run probe passes: the dispatch-amortized micro-bench
    (`--op gram`, docs/performance.md) measures the fused kernel 1.1-1.4x
    over XLA on every production shape.  ORION_TPU_PALLAS=0 opts out;
    ORION_TPU_PALLAS=1 cannot force dispatch off a TPU (the probe answers
    False there) and a kernel failure on a TPU raises from the probe."""
    if _env_opt_in() is False:
        return False
    return _probe()
