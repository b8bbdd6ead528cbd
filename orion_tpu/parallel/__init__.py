"""Device-mesh parallelism for the on-device optimizer core.

The reference's only parallelism is N worker processes around a shared DB
(SURVEY.md §2.9); that layer survives over DCN.  *This* layer is the
TPU-native one the reference could not have: inside a single suggest step,
candidate evaluation is sharded across a `jax.sharding.Mesh` so acquisition
over tens of thousands of candidates rides ICI collectives under one jit.

With sharded inputs, XLA's SPMD partitioner inserts the collectives
(the top-k/argmax reductions become all-gathers over the candidate axis);
no hand-written pmap plumbing needed.
"""

import jax

from orion_tpu.algo.sharding import (
    CANDIDATE_AXIS,
    TENANT_AXIS,
    candidate_spec,
    get_mesh,
    get_stacked_mesh,
    replicated_spec,
    shard_candidates,
)

__all__ = [
    "CANDIDATE_AXIS",
    "TENANT_AXIS",
    "device_mesh",
    "candidate_sharding",
    "replicated",
    "shard_candidates",
    "get_stacked_mesh",
    "init_distributed",
]


def device_mesh(n_devices=None, axis_name=CANDIDATE_AXIS):
    """1-D mesh over available devices (candidate/data parallel).

    Cached: repeated calls with the same topology return the SAME mesh
    object (`orion_tpu.algo.sharding.get_mesh`), so the fused step's
    static-arg cache probe is an identity hit and per-call construction
    never lands on the hot path (lint rule JIT004).
    """
    return get_mesh(n_devices, axis_name)


def candidate_sharding(mesh, axis_name=CANDIDATE_AXIS):
    """Shard an (m, d) candidate matrix along m; d replicated (cached)."""
    return candidate_spec(mesh, axis_name)


def replicated(mesh):
    return replicated_spec(mesh)


def init_distributed(coordinator=None, num_processes=None, process_id=None,
                     local_device_ids=None):
    """Join a multi-host cohort so one *worker* spans several hosts.

    Two distinct scaling axes exist (docs/multi_node.md):

    - Independent workers coordinate through shared storage over DCN — they
      must NOT call this; each keeps its own single-host jax.
    - ONE logical worker running on a multi-host slice calls this in every
      process of the cohort (same arguments everywhere, standard
      `jax.distributed` contract).  Afterwards `jax.devices()` spans all
      hosts, `device_mesh()` builds the global mesh, and the fused suggest
      step's candidate axis shards across the whole slice — XLA routes the
      top-k/argmin collectives over ICI within a host and DCN between
      hosts.  Every process must then execute the same suggest calls
      (SPMD); the producer/storage side stays per-cohort, not per-process.

    Arguments default to jax's env-based autodetection (JAX_COORDINATOR_*,
    cloud TPU metadata); pass them explicitly elsewhere.  Idempotent.
    """
    if jax.distributed.is_initialized():
        return
    kwargs = {}
    if coordinator is not None:
        kwargs["coordinator_address"] = coordinator
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if local_device_ids is not None:
        kwargs["local_device_ids"] = local_device_ids
    jax.distributed.initialize(**kwargs)
