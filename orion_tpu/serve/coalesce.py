"""Cross-tenant coalescing of fused suggest steps.

Tenants whose :class:`~orion_tpu.algo.tpu_bo.FusedPlan` signatures match
(same fit-buffer pow-2 bucket, same q bucket, same static args — exactly
the key ``prewarm.plan_fused_step_bucket``'s machinery buckets on) are
stacked along a leading tenant axis and dispatched as ONE device call.

**Bit-identity is the design constraint**, not a nice-to-have: a tenant
must get the same suggestion stream whether it is served alone or coalesced
with strangers, or the gateway silently changes every hosted experiment's
trajectory.  The stacked step therefore runs ``jax.lax.map`` (a scan whose
body is the SAME per-element computation graph as the standalone jitted
call, each lane independent) — ``jax.vmap`` is deliberately NOT used
because batched linalg primitives (matmul/Cholesky over a batch axis) may
lower to different reduction orders and break float equality; measured on
CPU: ``lax.map`` is bit-identical to the standalone call, ``vmap`` is not.
The differential test (``tests/unit/test_serve.py``) pins this.

The tenant axis is padded to a pow-2 bucket (lane 0 repeated) so the
stacked jit compiles once per ``(t_pad, signature)`` instead of once per
group width — and :func:`prewarm_stacked` hands the NEXT width bucket's
compile to a :class:`~orion_tpu.algo.prewarm.BucketPrewarmer` so growing
coalesce widths hit the cache, the same discipline PR 4 built for history
buckets.  Padding lanes are discarded un-read; their computation cannot
influence real lanes (scan lanes are independent).
"""

import time
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from orion_tpu.algo.history import _next_pow2
from orion_tpu.algo.prewarm import completed_prewarm_count
from orion_tpu.algo.sharding import (
    TENANT_AXIS,
    get_mesh,
    mesh_fingerprint,
    mesh_utilization,
    tenant_spec,
)
from orion_tpu.algo.tpu_bo import _suggest_step
from orion_tpu.compiler_plane import (
    COMPILE_REGISTRY,
    fields_from_plan_signature,
    jit_cache_size,
    lowered_analysis_fn,
)
from orion_tpu.telemetry import TELEMETRY

#: Static-arg names of the stacked step — exactly ``_suggest_step``'s, so a
#: FusedPlan's ``statics`` dict splats into either entry unchanged.
_STACK_STATICS = (
    "q",
    "n_candidates",
    "kernel",
    "acq",
    "fit_steps",
    "local_frac",
    "local_sigma",
    "beta",
    "trust_region",
    "tr_perturb_dims",
    "y_transform",
    "fixed_tail_cols",
    "mesh",
)


@partial(jax.jit, static_argnames=_STACK_STATICS)
def _stacked_suggest_step(stacked, **statics):
    """T same-signature fused steps as ONE compiled computation.

    ``stacked`` is the tuple of ``_suggest_step``'s traced args, each with
    a leading tenant axis.  ``lax.map`` keeps every lane's computation
    graph identical to the standalone call — the bit-identity contract."""
    return jax.lax.map(lambda args: _suggest_step(*args, **statics), stacked)


@partial(jax.jit, static_argnames=_STACK_STATICS + ("tenant_mesh",))
def _tenant_parallel_suggest_step(stacked, *, tenant_mesh, **statics):
    """The stacked step with the TENANT axis as a mesh axis: ``shard_map``
    partitions the lanes over the devices, so one coalesced dispatch runs
    T/n lanes PER CHIP concurrently instead of scanning T lanes on one.

    Each device's local computation is ``lax.map`` over its own lanes with
    the exact standalone per-lane graph, so a lane selects the same rows
    as its standalone step, within ``sharding.ROWS_ATOL`` (pinned by the
    sharded legs of ``tests/unit/test_sharded_parity.py``).
    ``statics['mesh']`` is None inside: a lane's candidate axis cannot also
    shard once its lane owns a single device, and XLA rejects nested
    sharding constraints under a manual (shard_map) subgroup.
    """

    def per_shard(shard):
        return jax.lax.map(lambda args: _suggest_step(*args, **statics), shard)

    return jax.shard_map(
        per_shard,
        mesh=tenant_mesh,
        in_specs=PartitionSpec(TENANT_AXIS),
        out_specs=PartitionSpec(TENANT_AXIS),
        check_vma=False,
    )(stacked)


#: Placement of the most recent mesh-mode coalesced dispatch (metadata-only
#: reads — no transfers): the gateway's health records and the sharded
#: bench read these to surface per-device utilization (doctor rule DX006
#: fires when one device silently ends up doing all the work).
LAST_STACK_PLACEMENT = {}


def _stacked_fields(signature, t_pad, tenant_mesh):
    """Compiler-plane signature fields of one stacked dispatch: the shared
    per-lane plan signature plus the tenant-axis statics that fork the
    stacked jit's own cache (``t_pad`` bucket, tenant-parallel mode).
    Shared by the dispatch bracket and :func:`prewarm_stacked` so a warm
    and the retrace it should have covered can never disagree."""
    fields = fields_from_plan_signature(signature)
    fields["t_pad"] = int(t_pad)
    fields["tenant_mesh"] = mesh_fingerprint(tenant_mesh)
    return fields


def stack_plans(plans, t_pad=None):
    """Stack same-signature plans' input arrays along a new leading tenant
    axis, padded to ``t_pad`` (default: the pow-2 bucket of ``len(plans)``)
    by repeating lane 0 — padding lanes compile-shape filler only."""
    t_pad = t_pad or _next_pow2(len(plans), floor=1)
    lanes = [p.arrays for p in plans]
    lanes += [plans[0].arrays] * (t_pad - len(lanes))
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *lanes)


def _tenant_mesh_for(mesh, t_pad):
    """The tenant-axis mesh a ``t_pad``-wide stack dispatches over, or None
    when lanes should NOT become a mesh axis.  Only a stack wide enough to
    give every chip at least one whole lane goes tenant-parallel; a narrow
    stack keeps the plans' own 1-D candidate mesh (each lane's candidate
    work sharded over ALL devices, lanes scanned by ``lax.map`` — the
    bit-stable per-lane module, measured: a 2-D (tenants, candidates)
    compute mesh re-partitions the per-lane graph and drifts by ulps)."""
    if mesh is None or mesh.devices.size <= 1:
        return None
    if t_pad >= mesh.devices.size:
        return get_mesh(int(mesh.devices.size), TENANT_AXIS)
    return None


def run_coalesced_plans(plans, t_pad=None):
    """Dispatch same-signature :class:`FusedPlan`s as ONE device call.

    Returns ``[(rows, state), ...]`` aligned with ``plans`` — each entry
    exactly what :func:`~orion_tpu.algo.tpu_bo.run_fused_plan` would have
    returned for that plan alone (rows sliced to the plan's ``num``, the
    lane's GPState ready for ``consume_fused_step``).

    When the plans carry a multi-device mesh, the stacked tenant axis
    becomes a SECOND mesh axis: the stacked inputs lay out over it, and
    with at least one lane per device the lanes themselves execute in
    parallel via :func:`_tenant_parallel_suggest_step` — the same rows
    selected as serving each tenant alone, within ``sharding.ROWS_ATOL``
    (XLA promises no reduction order across devices).
    """
    signature = plans[0].signature
    for plan in plans[1:]:
        if plan.signature != signature:
            raise ValueError(
                "cannot coalesce plans with differing fused-step signatures"
            )
    t_pad = t_pad or _next_pow2(len(plans), floor=1)
    stacked = stack_plans(plans, t_pad=t_pad)
    mesh = plans[0].statics.get("mesh")
    tenant_mesh = _tenant_mesh_for(mesh, t_pad)
    if tenant_mesh is not None:
        # One lane (or more) per device: lanes run concurrently, each on
        # its own chip with the single-device per-lane graph.
        stacked = jax.device_put(stacked, tenant_spec(tenant_mesh))
        lo, hi = mesh_utilization(tenant_mesh, *stacked[:4])
        LAST_STACK_PLACEMENT.update(
            devices=int(tenant_mesh.devices.size),
            t_pad=int(t_pad),
            tenant_parallel=True,
            util_min_frac=lo,
            util_max_frac=hi,
        )
        step_fn = _tenant_parallel_suggest_step
        dispatch_statics = dict(
            plans[0].statics, mesh=None, tenant_mesh=tenant_mesh
        )
    else:
        # No mesh, or a stack too narrow to give every chip a lane: the
        # scanned stacked step — with a mesh, each lane still shards its
        # candidate axis over ALL devices via the in-step constraints.
        if mesh is not None and mesh.devices.size > 1:
            LAST_STACK_PLACEMENT.update(
                devices=int(mesh.devices.size),
                t_pad=int(t_pad),
                tenant_parallel=False,
            )
            LAST_STACK_PLACEMENT.pop("util_min_frac", None)
            LAST_STACK_PLACEMENT.pop("util_max_frac", None)
        step_fn = _stacked_suggest_step
        dispatch_statics = dict(plans[0].statics)
    # Retrace bracket — the stacked twin of run_fused_plan's: jit cache
    # growth during the call with no prewarm completing in the window is a
    # synchronous compile THIS dispatch paid, attributed by the compiler
    # plane against the nearest prior stacked signature.
    tel_t0 = tel_before = None
    if TELEMETRY.enabled:
        tel_before = jit_cache_size(step_fn)
        tel_prewarms_before = completed_prewarm_count()
        tel_t0 = time.perf_counter()
    rows, states = step_fn(stacked, **dispatch_statics)
    if tel_t0 is not None:
        after = jit_cache_size(step_fn)
        retraced = (
            tel_before is not None
            and after is not None
            and after > tel_before
            # A prewarm completing mid-window explains the growth —
            # classify as a cached dispatch (same conservative call as
            # run_fused_plan: a coinciding genuine retrace goes uncounted
            # rather than a cache hit being booked as a stall).
            and completed_prewarm_count() == tel_prewarms_before
        )
        TELEMETRY.record_span(
            "jax.stacked.compile" if retraced else "jax.stacked.dispatch",
            start=tel_t0,
            args={"t_pad": int(t_pad), "lanes": len(plans)},
        )
        if retraced:
            TELEMETRY.count("jax.retraces")
            COMPILE_REGISTRY.record_retrace(
                "stacked",
                _stacked_fields(signature, t_pad, tenant_mesh),
                seconds=time.perf_counter() - tel_t0,
                analysis_fn=lowered_analysis_fn(
                    step_fn, stacked, dispatch_statics
                ),
            )
    out = []
    for lane, plan in enumerate(plans):
        lane_state = jax.tree.map(lambda leaf, lane=lane: leaf[lane], states)
        out.append((rows[lane][: plan.num], lane_state))
    return out


def prewarm_stacked(sample_plan, t_pad):
    """Zero-dummy compile closure for the stacked step at tenant-axis
    bucket ``t_pad`` and ``sample_plan``'s signature — hand it to a
    :class:`~orion_tpu.algo.prewarm.BucketPrewarmer` keyed by
    ``("stacked", t_pad) + sample_plan.signature`` so a growing coalesce
    width crosses its pow-2 bucket on a jit-cache hit, never a synchronous
    stall in the middle of a dispatch cycle.  Mirrors the dispatch-mode
    choice in :func:`run_coalesced_plans` so it warms the entry the real
    dispatch will hit."""
    dummies = jax.tree.map(
        lambda leaf: jnp.zeros((t_pad,) + leaf.shape, leaf.dtype),
        sample_plan.arrays,
    )
    statics = dict(sample_plan.statics)
    tenant_mesh = _tenant_mesh_for(statics.get("mesh"), t_pad)
    signature = sample_plan.signature

    def compile_fn():
        t0 = time.perf_counter()
        if tenant_mesh is None:
            _stacked_suggest_step(dummies, **statics)
        else:
            placed = jax.device_put(dummies, tenant_spec(tenant_mesh))
            _tenant_parallel_suggest_step(
                placed, tenant_mesh=tenant_mesh, **dict(statics, mesh=None)
            )
        if TELEMETRY.enabled:
            # Book the warmed signature: a later retrace at EXACTLY these
            # fields is a prewarm bug (doctor rule DX052), not a missing
            # prewarm — the fields must match the dispatch bracket's, which
            # is why both go through _stacked_fields.
            COMPILE_REGISTRY.record_prewarm(
                "stacked",
                _stacked_fields(signature, t_pad, tenant_mesh),
                seconds=time.perf_counter() - t0,
            )

    return compile_fn
