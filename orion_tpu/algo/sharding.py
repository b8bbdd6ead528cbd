"""Cached Mesh/NamedSharding helpers for the fused suggest hot path.

Every sharded dispatch in the engine — standalone `run_fused_plan`, the
producer ring, prewarm compiles, and the gateway's coalesced stacked step —
names its mesh and sharding specs from HERE, never by constructing them at
the call site.  Two reasons, both measured:

- ``Mesh(jax.devices(), ...)`` re-hashes the device list and re-derives the
  axis env on every construction; on the steady suggest path that is pure
  host tax (ROADMAP item 5's "wall ≈ device" budget).
- ``mesh`` rides the fused step's ``static_argnames``, so the *object* is
  part of the jit cache key.  Fresh per-call meshes that compare equal still
  pay ``__eq__``/``__hash__`` over the device array each lookup; a cached
  singleton makes the cache probe an identity hit.

Lint rule JIT004 (`orion_tpu/analysis/jit_rules.py`) enforces the contract:
per-call ``Mesh(...)``/``NamedSharding(...)`` construction inside a declared
hot-path function is a lint failure — the construction below happens once
per distinct topology, behind a cache.

Axis layout (docs/performance.md "Sharded suggest"):

- ``candidates`` — the throughput axis.  The fused step's candidate pool,
  EI scores, and q-batch dedup shard along it; GP fit state replicates.
- ``tenants`` — the gateway's stacked-lane axis.  Coalesced dispatches lay
  the stacked plan arrays out over it (2-D mesh, see `get_stacked_mesh`) so
  one dispatch spreads (tenant, candidate) work across chips.

This module deliberately imports only jax/numpy: `orion_tpu.parallel`
delegates here, and the algo modules import `orion_tpu.parallel`, so any
heavier import would cycle.
"""

import threading

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

CANDIDATE_AXIS = "candidates"
TENANT_AXIS = "tenants"

# Cache writes happen once per distinct topology; the lock is a leaf (no
# other lock is ever taken while holding it).
_CACHE_LOCK = threading.Lock()
_MESH_CACHE = {}
_SPEC_CACHE = {}


def get_mesh(n_devices=None, axis_name=CANDIDATE_AXIS):
    """Cached 1-D mesh over the first ``n_devices`` devices (all by default).

    The cache key includes the resolved device tuple, so a changed backend
    (tests forcing a virtual CPU mesh in a subprocess, multi-host init
    growing ``jax.devices()``) can never serve a stale mesh.
    """
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    key = (tuple(d.id for d in devices), (axis_name,), None)
    with _CACHE_LOCK:
        mesh = _MESH_CACHE.get(key)
        if mesh is None:
            mesh = Mesh(np.asarray(devices), (axis_name,))
            _MESH_CACHE[key] = mesh
    return mesh


def get_stacked_mesh(n_tenants, n_devices=None):
    """Cached 2-D ``(tenants, candidates)`` mesh for coalesced dispatch.

    The tenant axis takes the largest power-of-2 lane count that divides
    both the padded tenant width and the device count; the rest of the
    devices go to the candidate axis.  With 8 devices and a 2-lane stack
    that is a (2, 4) mesh: stacked plan arrays lay out over ``tenants``,
    and each lane's candidate pool shards over ``candidates``.
    """
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    t = _gcd_pow2(max(1, int(n_tenants)), n)
    key = (tuple(d.id for d in devices), (TENANT_AXIS, CANDIDATE_AXIS), t)
    with _CACHE_LOCK:
        mesh = _MESH_CACHE.get(key)
        if mesh is None:
            mesh = Mesh(
                np.asarray(devices).reshape(t, n // t),
                (TENANT_AXIS, CANDIDATE_AXIS),
            )
            _MESH_CACHE[key] = mesh
    return mesh


def _gcd_pow2(a, b):
    """Largest power of 2 dividing both a and b (>= 1)."""
    g = 1
    while a % 2 == 0 and b % 2 == 0 and g < b:
        a //= 2
        b //= 2
        g *= 2
    return g


def _cached_spec(mesh, spec):
    key = (mesh, spec)
    with _CACHE_LOCK:
        out = _SPEC_CACHE.get(key)
        if out is None:
            out = NamedSharding(mesh, spec)
            _SPEC_CACHE[key] = out
    return out


def candidate_spec(mesh, axis_name=CANDIDATE_AXIS):
    """(m, d) candidate matrix: shard m, replicate d.

    On a 2-D stacked mesh the spec still names only the candidate axis —
    the array replicates over ``tenants`` (each lane scores its own pool).
    """
    return _cached_spec(mesh, PartitionSpec(axis_name, None))


def replicated_spec(mesh):
    """Fully replicated (GP fit state: O(n^2) vs the O(m·F) candidate work)."""
    return _cached_spec(mesh, PartitionSpec())


def tenant_spec(mesh):
    """Stacked plan leaves: shard the leading (tenant) axis, replicate rest."""
    return _cached_spec(mesh, PartitionSpec(TENANT_AXIS))


def shard_candidates(candidates, mesh, axis_name=CANDIDATE_AXIS):
    """Place a host candidate pool sharded over the mesh (one transfer per
    shard; the full pool is never materialized on any single device)."""
    return jax.device_put(candidates, candidate_spec(mesh, axis_name))


def gather_candidates(array):
    """Bring a (possibly sharded) device array back as one host ndarray."""
    return np.asarray(jax.device_get(array))


def clear_caches():
    """Drop cached meshes/specs (tests that swap backends mid-process)."""
    with _CACHE_LOCK:
        _MESH_CACHE.clear()
        _SPEC_CACHE.clear()


# --------------------------------------------------------------------------
# Placement introspection — the observability side of sharding.  All of it
# reads array *metadata* (shard device + nbytes); nothing transfers.


def placement_fractions(*arrays):
    """device id -> fraction of the arrays' bytes resident on that device.

    Replicated arrays contribute their full size to every holding device,
    sharded arrays one shard each — so a well-sharded dispatch shows near
    1/n fractions and a silently-unsharded one shows a single device at 1.0.
    """
    per_device = {}
    for array in arrays:
        shards = getattr(array, "addressable_shards", None)
        if shards:
            for shard in shards:
                nbytes = getattr(shard.data, "nbytes", 0)
                per_device[shard.device.id] = (
                    per_device.get(shard.device.id, 0) + nbytes
                )
        else:  # pragma: no cover - non-Array leaves (host numpy)
            continue
    total = sum(per_device.values())
    if not total:
        return {}
    return {dev: nbytes / total for dev, nbytes in per_device.items()}


def mesh_utilization(mesh, *arrays):
    """(min_frac, max_frac) byte fraction across the mesh's devices.

    Devices in the mesh holding nothing count as 0.0 — exactly the "one
    device doing all the work" signal doctor rule DX006 watches for.
    """
    fractions = placement_fractions(*arrays)
    device_ids = [d.id for d in mesh.devices.flat]
    per = [fractions.get(dev, 0.0) for dev in device_ids]
    return (min(per), max(per)) if per else (0.0, 0.0)


def mesh_fingerprint(mesh):
    """Compact, stable identity of a mesh for compiler-plane signature
    fields and span args — ``"4×candidates"`` instead of the multi-line
    ``str(Mesh)`` (signatures are diffed and rendered in tables; a
    verbose mesh repr would drown the one static that actually changed).
    None-safe: an unmeshed dispatch fingerprints as ``"none"``."""
    if mesh is None:
        return "none"
    try:
        axes = ",".join(str(name) for name in mesh.axis_names)
        return f"{int(mesh.devices.size)}×{axes}"
    except Exception:  # hostile/mock mesh — degrade to the repr
        return str(mesh)


def mesh_health_fields(mesh, *arrays):
    """Host-side health-record fields describing the mesh and, when sample
    arrays are given, the measured per-device placement (`serve_width`-style:
    merged into health records next to the packed device fields)."""
    fields = {"mesh_devices": int(mesh.devices.size)}
    if arrays:
        lo, hi = mesh_utilization(mesh, *arrays)
        fields["mesh_util_min_frac"] = float(lo)
        fields["mesh_util_max_frac"] = float(hi)
    return fields


# --------------------------------------------------------------------------
# Sharded-vs-one-device comparison.  XLA promises no reduction order across
# shards, so a sharded round's fit and scores may differ from the one-device
# round in the last bits (observed on the 8-device CPU mesh: 1.5e-8 to
# 6e-8 in unit-cube rows).  The contract is the same SELECTION with rows
# equal to within a stated f32 tolerance, never bit equality.

#: Max |difference| allowed between a sharded round's unit-cube rows and
#: the one-device round's: 1e-5 is 84 f32 ulps at 1.0 — far above
#: reduction-order noise, far below the distance between two distinct
#: candidates (a different selection moves a row by ~1e-2 or more).
ROWS_ATOL = 1e-5


def compare_rows(rows_a, rows_b, atol=ROWS_ATOL):
    """Compare two suggestion batches of unit-cube rows.

    Returns ``{"same_selection", "max_abs_diff", "atol", "ok"}``:
    ``same_selection`` means row i of one batch is the nearest row of the
    other batch to row i of this one, for every i (the same candidates
    picked in the same order); ``ok`` adds ``max_abs_diff <= atol``."""
    a = np.asarray(rows_a, dtype=np.float64)
    b = np.asarray(rows_b, dtype=np.float64)
    if a.shape != b.shape:
        return {"same_selection": False, "max_abs_diff": None, "atol": atol,
                "ok": False}
    max_abs = float(np.max(np.abs(a - b))) if a.size else 0.0
    d2 = (
        np.sum(a * a, axis=1)[:, None]
        + np.sum(b * b, axis=1)[None, :]
        - 2.0 * (a @ b.T)
    )
    same = bool(np.array_equal(np.argmin(d2, axis=1), np.arange(len(a))))
    return {"same_selection": same, "max_abs_diff": max_abs, "atol": atol,
            "ok": same and max_abs <= atol}


#: Relative tolerance for a sharded round's GP state (posterior weights
#: ``alpha`` and the packed health vector) against the one-device round's:
#: f32 reduction-order noise, far below any change of fit.
STATE_RTOL = 1e-5


def compare_states(state_a, state_b, rtol=STATE_RTOL, atol=1e-6):
    """Compare two rounds' GP states (``GPState``: ``alpha`` and
    ``health``).  Returns ``{"alpha_max_abs_diff", "health_max_abs_diff",
    "rtol", "ok"}``; ``ok`` is ``np.allclose(rtol, atol)`` on both."""
    out = {"rtol": rtol, "ok": True}
    for name in ("alpha", "health"):
        a = np.asarray(getattr(state_a, name), dtype=np.float64)
        b = np.asarray(getattr(state_b, name), dtype=np.float64)
        same_shape = a.shape == b.shape
        out[f"{name}_max_abs_diff"] = (
            float(np.max(np.abs(a - b))) if same_shape and a.size else None
        )
        out["ok"] = out["ok"] and same_shape and bool(
            np.allclose(a, b, rtol=rtol, atol=atol)
        )
    return out

