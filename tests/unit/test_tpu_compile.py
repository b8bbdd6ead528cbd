"""The main path's kernels, compiled for a described TPU v5e.

Nothing runs: the TPU compiler installed here compiles for a chip that is
described, not attached, and refuses what the chip's compiler would refuse
(a tile not aligned to the tiling, too much VMEM, a program that does not
fit).  Interpret-mode tests cannot see those.  The topology is described
inside a module fixture only: describing it loads libtpu, which one process
at a time may hold, so it must never happen while a module is imported.
Keep every such compile in this one file (one xdist worker holds the lock).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep these out of it."""
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize(
    "m,n,d",
    [(16384, 256, 6), (16384, 256, 20), (8192, 512, 51)],
    ids=["hartmann6", "rosenbrock20", "ackley50"],
)
def test_fused_gram_compiles_for_v5e(one_chip, m, n, d):
    from orion_tpu.ops.gram import fused_gram

    compiled = fused_gram.lower(
        _spec((m, d), one_chip),
        _spec((n, d), one_chip),
        _spec((d,), one_chip),
        _spec((), one_chip),
        kind="matern52",
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_hartmann6_suggest_step_compiles_with_fused_gram(one_chip, monkeypatch):
    """The hartmann6-q1024 fused step: it fits a v5e chip and dispatches
    the Pallas gram.  Dispatch is steered on here (the probe answers False
    off a TPU); tracing caches are cleared on both sides so neither a CPU
    trace nor this one leaks into the other."""
    from orion_tpu.algo.tpu_bo import _suggest_step
    from orion_tpu.benchmarks.suggest_bench import SHAPES, _make_args, _step_kwargs
    from orion_tpu.ops import gram

    monkeypatch.setattr(gram, "_probe", lambda: True)
    monkeypatch.delenv("ORION_TPU_PALLAS", raising=False)
    gram.pallas_enabled.cache_clear()
    jax.clear_caches()
    try:
        cfg = SHAPES["hartmann6-q1024"]
        args = _make_args(cfg, np.random.default_rng(0))
        specs = jax.tree.map(
            lambda a: _spec(np.shape(a), one_chip, jnp.asarray(a).dtype), args
        )
        compiled = _suggest_step.lower(*specs, **_step_kwargs(cfg)).compile()
    finally:
        gram.pallas_enabled.cache_clear()
        jax.clear_caches()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # The benchmark finds the step by its module name and the gram by its
    # op name; the stage scopes ride the ops' metadata, not their names.
    assert text.startswith("HloModule jit__suggest_step")
    assert "%fused_gram" in text and 'op_name="jit(_suggest_step)/posterior/' in text
    memory = compiled.memory_analysis()
    # Small next to the chip's 16 GB: a regression that materializes the
    # (pool, n) gram in f32 several times over shows up here first.
    assert memory.temp_size_in_bytes < 256 * 2**20


def test_sharded_suggest_step_compiles_for_v5e_2x2(topo, monkeypatch):
    """use_mesh=True over the four chips of a v5e:2x2 host, Pallas gram on:
    XLA's partitioner cannot split a Mosaic kernel, so the step must run
    it per device (`ops.gram.fused_gram` under the ambient mesh) — this
    compile refused the automatically partitioned kernel before that."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from orion_tpu.algo.base import create_algo
    from orion_tpu.algo.sharding import CANDIDATE_AXIS
    from orion_tpu.algo.tpu_bo import _suggest_step
    from orion_tpu.ops import gram
    from orion_tpu.space.dsl import build_space

    # The plan's shapes and statics come from a CPU mesh of the same size.
    space = build_space({f"x{i}": "uniform(0, 1)" for i in range(6)})
    algo = create_algo(space, {"tpu_bo": {
        "n_init": 16, "n_candidates": 16384, "fit_steps": 40,
        "use_mesh": True, "n_devices": 4, "prewarm": False}}, seed=0)
    X = np.random.default_rng(0).uniform(size=(130, 6))
    algo.observe([{f"x{i}": float(r[i]) for i in range(6)} for r in X],
                 [{"objective": float(v)} for v in X.sum(axis=1)])
    plan = algo.fused_step_plan(1024)
    mesh = Mesh(np.asarray(topo.devices), (CANDIDATE_AXIS,))
    replicated = NamedSharding(mesh, PartitionSpec())
    specs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated),
        plan.arrays,
    )
    monkeypatch.setattr(gram, "_probe", lambda: True)
    monkeypatch.delenv("ORION_TPU_PALLAS", raising=False)
    gram.pallas_enabled.cache_clear()
    jax.clear_caches()
    try:
        compiled = _suggest_step.lower(*specs, **dict(plan.statics, mesh=mesh)).compile()
    finally:
        gram.pallas_enabled.cache_clear()
        jax.clear_caches()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text or "all-reduce" in text  # really partitioned
