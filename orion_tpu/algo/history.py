"""Observation history buffers: device-resident pow-2 buffers with
incremental in-place appends (:class:`DeviceHistory`) and their
amortized-growth host twin (:class:`HostHistory`).

The GP algorithms (`tpu_bo`, `asha_bo`) fit on the full observation history
every round.  Re-padding that history on host and re-uploading it with
``jnp.asarray`` per suggest costs O(n) transfer per round — O(n²) cumulative
over an experiment — for rows the device has already seen.  This module
keeps the history in preallocated power-of-2-padded device buffers owned by
the algorithm and appends each observe batch in place with one small
``dynamic_update_slice`` jit whose input buffers are donated (XLA aliases
them, so no copy of the resident history is made).  Only the new rows cross
the host→device boundary.

Invariants (what makes the incremental path bit-equal to a full re-upload):

- Buffer capacity is a power of 2 (floor 64, the GP pad floor) and only
  grows; every row at index >= ``count`` is exactly 0.0 in x and y with
  mask 0.0 — identical to the zero-padding a host re-pad produces.
- :meth:`fit_view` returns views sliced to ``_next_pow2(count)``, the exact
  shape the host re-upload path pads to, so the fused suggest jit sees the
  same shapes, same values, same jit bucket — and therefore returns
  bit-identical suggestions (the regression test in
  ``tests/unit/test_device_history.py`` pins this across a pow-2 growth
  boundary).

Naive-copy discipline (the producer deepcopies the algorithm every round to
fantasize lies): donation would invalidate a buffer the clone still
references, so ``__deepcopy__`` hands the clone the same buffers and marks
BOTH sides copy-on-write — the next append on either side runs the
non-donating twin of the update jit (the other side's view survives), after
which the appender exclusively owns its fresh buffers and donation resumes.
A bench- or client-driven algorithm that is never cloned donates on every
append.
"""

import threading
import time
import weakref
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from orion_tpu.compiler_plane import (
    COMPILE_REGISTRY,
    jit_cache_size,
    signature_fields,
)
from orion_tpu.telemetry import TELEMETRY


def _next_pow2(n, floor=64):
    out = floor
    while out < n:
        out *= 2
    return out


# --- memory accounting -------------------------------------------------------
# Live-instance registries (weak — registration must never extend a
# history's lifetime) feeding the device-memory sampler
# (orion_tpu.devmem): per-pow-2-bucket resident device bytes and the host
# mirror total.  Clones made by __deepcopy__ share buffers with their
# source and are deliberately NOT registered (they bypass __init__), so
# shared buffers are counted once.
_registry_lock = threading.Lock()
_device_histories = weakref.WeakSet()
_host_histories = weakref.WeakSet()


def history_memory_stats():
    """Resident observation-history bytes, introspected from every live
    (non-clone) history instance: ``device_buckets`` maps pow-2 capacity
    -> total device bytes at that bucket, ``device_bytes``/``host_bytes``
    the totals, ``device_count`` live DeviceHistory instances."""
    with _registry_lock:
        device = list(_device_histories)
        host = list(_host_histories)
    buckets = {}
    device_bytes = 0
    for hist in device:
        if not hist.cap or hist._x is None:
            continue
        nbytes = 0
        for buf in (hist._x, hist._y, hist._mask):
            try:
                nbytes += int(buf.nbytes)
            except Exception:  # pragma: no cover - deleted buffer mid-walk
                pass
        buckets[hist.cap] = buckets.get(hist.cap, 0) + nbytes
        device_bytes += nbytes
    host_bytes = sum(
        int(h._x.nbytes) + int(h._y.nbytes) for h in host
    )
    return {
        "device_buckets": buckets,
        "device_bytes": device_bytes,
        "host_bytes": host_bytes,
        "device_count": len(device),
    }


#: Append batches are padded to a power of 2 (floor 8) so the update jit
#: compiles once per (capacity, batch-bucket) pair instead of once per
#: distinct batch size (the producer's retry loop shrinks its request).
_BATCH_FLOOR = 8


def _donation_supported():
    # CPU ignores buffer donation and warns per compile; skip it there (the
    # tests run JAX_PLATFORMS=cpu).  Accelerator backends take the alias.
    return jax.default_backend() != "cpu"


@partial(jax.jit, static_argnames=("m", "m_pad", "dist_cols"))
def _local_subset(x, y, mask, center, m, m_pad, dist_cols):
    """Gather the ``m`` nearest real rows to ``center`` (squared euclidean
    over the leading ``dist_cols`` columns), padded to ``m_pad`` — the
    device twin of the old host ``np.argpartition`` local-GP selection.
    Ties break by lowest index (``top_k``), deterministically."""
    d2 = jnp.sum((x[:, :dist_cols] - center[None, :dist_cols]) ** 2, axis=1)
    d2 = jnp.where(mask > 0, d2, jnp.inf)
    _, idx = jax.lax.top_k(-d2, m)
    xs = jnp.take(x, idx, axis=0)
    ys = jnp.take(y, idx)
    ms = jnp.ones((m,), x.dtype)
    if m_pad > m:
        xs = jnp.pad(xs, ((0, m_pad - m), (0, 0)))
        ys = jnp.pad(ys, (0, m_pad - m))
        ms = jnp.pad(ms, (0, m_pad - m))
    return xs, ys, ms


def prewarm_local_subset(m_hist, n_cols, m, dist_cols, floor=64):
    """Compile the device local-subset gather for the ``(m_hist, n_cols)``
    history bucket by calling it on zero dummies (populates the jit
    cache).  In the local-TR regime the fused step's fit shape is pinned,
    but this gather still re-buckets with the history — without a warm it
    would pay a (small) synchronous compile at every pow-2 growth."""
    x = jnp.zeros((int(m_hist), int(n_cols)), jnp.float32)
    # No block_until_ready — the compile (and with it the jit-cache
    # insert) completes synchronously before the call returns; see
    # tpu_bo.prewarm_suggest_step.
    _local_subset(
        x,
        x[:, 0],
        x[:, 0],
        jnp.zeros((int(n_cols),), jnp.float32),
        m=int(m),
        m_pad=_next_pow2(int(m), floor=floor),
        dist_cols=int(dist_cols),
    )


def _append_impl(x, y, mask, rows, ys, mvals, n):
    x = jax.lax.dynamic_update_slice(x, rows, (n, jnp.int32(0)))
    y = jax.lax.dynamic_update_slice(y, ys, (n,))
    mask = jax.lax.dynamic_update_slice(mask, mvals, (n,))
    return x, y, mask


# Donating twin: in-place update of the resident buffers (no O(capacity)
# copy per observe).  Copying twin: used under copy-on-write and on CPU.
_append_donating = jax.jit(_append_impl, donate_argnums=(0, 1, 2))
_append_copying = jax.jit(_append_impl)


@partial(jax.jit, static_argnames=("new_cap",))
def _grow(x, y, mask, new_cap):
    pad = new_cap - x.shape[0]
    return (
        jnp.pad(x, ((0, pad), (0, 0))),
        jnp.pad(y, (0, pad)),
        jnp.pad(mask, (0, pad)),
    )


class DeviceHistory:
    """Pow-2-padded device buffers ``(x, y, mask)`` for one observation set.

    ``append`` is the only mutator; ``fit_view`` is the only reader the hot
    path needs.  ``count`` is the number of real rows; everything past it is
    zero (see module docstring for why that exact invariant matters).
    """

    def __init__(self, n_cols, floor=64):
        self.n_cols = int(n_cols)
        self.floor = int(floor)
        self.count = 0
        self.cap = 0
        self._x = None
        self._y = None
        self._mask = None
        # True while the buffers may be visible to another DeviceHistory
        # (a naive-copy clone): the next append must not donate them.
        self._cow = False
        with _registry_lock:
            _device_histories.add(self)

    @classmethod
    def from_host(cls, x, y, floor=64):
        """Bulk-build from host mirrors (state restore / resume)."""
        x = np.asarray(x, dtype=np.float32)
        hist = cls(x.shape[1] if x.ndim == 2 else 0, floor=floor)
        if x.shape[0]:
            hist.append(x, np.asarray(y, dtype=np.float32))
        return hist

    def __deepcopy__(self, memo):
        clone = DeviceHistory.__new__(DeviceHistory)
        clone.__dict__.update(self.__dict__)
        # Both sides now share the device buffers: whichever appends first
        # must copy-on-write so the other side's rows survive.
        clone._cow = True
        self._cow = True
        memo[id(self)] = clone
        return clone

    def _ensure_capacity(self, need):
        new_cap = _next_pow2(need, floor=self.floor)
        if self._x is None:
            self._x = jnp.zeros((new_cap, self.n_cols), jnp.float32)
            self._y = jnp.zeros((new_cap,), jnp.float32)
            self._mask = jnp.zeros((new_cap,), jnp.float32)
        elif new_cap > self.cap:
            self._x, self._y, self._mask = _grow(
                self._x, self._y, self._mask, new_cap=new_cap
            )
        else:
            return
        self.cap = new_cap
        self._cow = False  # fresh buffers are exclusively ours

    def append(self, rows, ys):
        """Write an observe batch at ``count``; one device dispatch.

        The batch is zero-padded to a pow-2 bucket before upload; the
        padding rows land in the region past ``count`` with mask 0.0,
        preserving the all-zeros-past-count invariant.
        """
        rows = np.asarray(rows, dtype=np.float32).reshape(-1, self.n_cols)
        ys = np.asarray(ys, dtype=np.float32).reshape(-1)
        b = rows.shape[0]
        if b == 0:
            return
        b_pad = _next_pow2(b, floor=_BATCH_FLOOR)
        mvals = np.zeros((b_pad,), dtype=np.float32)
        mvals[:b] = 1.0
        if b_pad != b:
            rows = np.concatenate(
                [rows, np.zeros((b_pad - b, self.n_cols), np.float32)]
            )
            ys = np.concatenate([ys, np.zeros((b_pad - b,), np.float32)])
        # Capacity must cover the PADDED write: dynamic_update_slice clamps
        # out-of-range starts, which would silently shift the write onto
        # valid rows.
        self._ensure_capacity(self.count + b_pad)
        donated = not self._cow and _donation_supported()
        fn = _append_donating if donated else _append_copying
        # Donation-hit accounting (orion_tpu.devmem): how often the append
        # aliased the resident buffers vs paid an O(capacity) copy (CoW
        # after a naive clone, or a CPU backend).  Constant names, one
        # enabled check — hot-path clean.
        TELEMETRY.count(
            "history.appends.donated" if donated else "history.appends.copied"
        )
        # Compiler-plane bracket: the append twins compile once per
        # (capacity bucket, batch bucket, donation mode) — cache growth
        # during the call books a plain `append`-family compile.  NOT a
        # retrace: bucket crossings legitimately compile a fresh append jit
        # (no prewarm covers it by design — the compile is milliseconds,
        # far under the fused step's), and counting it as `jax.retraces`
        # would fail the bench's retraces_after_warm == 0 gate for a stall
        # the suggest path never paid.
        tel_t0 = tel_before = None
        if TELEMETRY.enabled:
            tel_before = jit_cache_size(fn)
            tel_t0 = time.perf_counter()
        self._x, self._y, self._mask = fn(
            self._x,
            self._y,
            self._mask,
            jnp.asarray(rows),
            jnp.asarray(ys),
            jnp.asarray(mvals),
            jnp.int32(self.count),
        )
        if tel_t0 is not None:
            after = jit_cache_size(fn)
            if tel_before is not None and after is not None and after > tel_before:
                COMPILE_REGISTRY.record_compile(
                    "append",
                    signature_fields(
                        (self.cap, self.n_cols),
                        {"donated": donated, "batch": b_pad},
                    ),
                    seconds=time.perf_counter() - tel_t0,
                )
        self._cow = False
        self.count += b

    def fit_view(self):
        """``(x, y, mask, m)`` sliced to ``m = _next_pow2(count)`` — the
        exact padded shape the host re-upload path produces, regardless of
        how far capacity has grown ahead (growth is batch-bucket eager)."""
        m = _next_pow2(max(self.count, 1), floor=self.floor)
        if m == self.cap:
            return self._x, self._y, self._mask, m
        return self._x[:m], self._y[:m], self._mask[:m], m

    def local_view(self, center, m, dist_cols=None):
        """``(x, y, mask, m_pad)`` of the ``m`` rows nearest to ``center``
        (x-distance over the leading ``dist_cols`` columns; default all),
        gathered ON DEVICE from the resident buffers and padded to
        ``m_pad = _next_pow2(m)`` — the local-GP (TuRBO subset) fit set
        without the O(n·d) host distance scan, host gather, or re-upload
        the old ``np.argpartition`` path paid per suggest.  Only ``center``
        (one row) crosses the boundary.  Requires ``count >= m``."""
        m = int(m)
        x, y, mask, _ = self.fit_view()
        xs, ys, ms = _local_subset(
            x,
            y,
            mask,
            jnp.asarray(np.asarray(center, dtype=np.float32)),
            m=m,
            m_pad=_next_pow2(m, floor=self.floor),
            dist_cols=int(dist_cols) if dist_cols is not None else self.n_cols,
        )
        return xs, ys, ms, _next_pow2(m, floor=self.floor)


class HostHistory:
    """Amortized-growth host mirrors ``(x, y)`` with O(batch) appends.

    The old mirrors were rebuilt by ``np.concatenate`` per observe — an
    O(n) copy per round, O(n²) cumulative.  This keeps capacity-doubling
    numpy buffers written in place at ``count``, so a steady-state observe
    costs O(batch) host work; ``x``/``y`` are zero-copy views sliced to
    ``count`` (bit-identical to what the concatenate path held — pinned in
    ``tests/unit/test_host_history.py``).

    The incumbent is tracked incrementally: ``best_idx``/``best_y`` are
    the FIRST-occurrence argmin/min over the history (exactly what
    ``np.argmin`` returns), updated in O(batch) per append — no O(n)
    argmin scan per suggest.

    Naive-copy discipline mirrors :class:`DeviceHistory`: ``__deepcopy__``
    shares the buffers and marks both sides copy-on-write, so a lie
    clone's fantasy rows can never clobber (or be clobbered by) the real
    history — the first append on either side after a clone copies its
    rows into fresh exclusively-owned buffers (one memcpy, the same cost
    the old concatenate paid every round)."""

    def __init__(self, n_cols, floor=64):
        self.n_cols = int(n_cols)
        self.floor = max(int(floor), 1)
        self.count = 0
        self._x = np.zeros((self.floor, self.n_cols), dtype=np.float32)
        self._y = np.zeros((self.floor,), dtype=np.float32)
        self._cow = False
        self.best_idx = -1
        self.best_y = np.inf
        with _registry_lock:
            _host_histories.add(self)

    @classmethod
    def from_host(cls, x, y, floor=64):
        """Bulk-build from materialized arrays (state restore / resume)."""
        x = np.asarray(x, dtype=np.float32)
        hist = cls(x.shape[1] if x.ndim == 2 else 0, floor=floor)
        if x.shape[0]:
            hist.append(x, np.asarray(y, dtype=np.float32))
        return hist

    @property
    def x(self):
        """(count, n_cols) view — rows [:count] are never mutated in place."""
        return self._x[: self.count]

    @property
    def y(self):
        return self._y[: self.count]

    def __deepcopy__(self, memo):
        clone = HostHistory.__new__(HostHistory)
        clone.__dict__.update(self.__dict__)
        clone._cow = True
        self._cow = True
        memo[id(self)] = clone
        return clone

    def _own_with_capacity(self, need):
        """Exclusively-owned buffers covering ``need`` rows (grow and/or
        copy-on-write in one memcpy)."""
        cap = self._x.shape[0]
        new_cap = _next_pow2(need, floor=cap)  # cap is always a pow-2
        if new_cap == cap and not self._cow:
            return
        x = np.zeros((new_cap, self.n_cols), dtype=np.float32)
        y = np.zeros((new_cap,), dtype=np.float32)
        x[: self.count] = self._x[: self.count]
        y[: self.count] = self._y[: self.count]
        self._x, self._y = x, y
        self._cow = False

    def append(self, rows, ys):
        rows = np.asarray(rows, dtype=np.float32).reshape(-1, self.n_cols)
        ys = np.asarray(ys, dtype=np.float32).reshape(-1)
        b = rows.shape[0]
        if b == 0:
            return
        self._own_with_capacity(self.count + b)
        self._x[self.count : self.count + b] = rows
        self._y[self.count : self.count + b] = ys
        batch_arg = int(np.argmin(ys))
        # Strict <: ties keep the earliest index, matching np.argmin over
        # the full concatenated history.
        if float(ys[batch_arg]) < self.best_y:
            self.best_y = float(ys[batch_arg])
            self.best_idx = self.count + batch_arg
        self.count += b
