"""Sharded-suggest parity pins (ISSUE 16): a mesh-built algorithm must
reproduce the unsharded one.

Three layers of the contract:

- 1-device mesh == no mesh, bit for bit, for all four GP/KDE-backed
  algorithms — the cheapest differential: every with_sharding_constraint
  inserted by the mesh path must be a no-op when the mesh holds one device;
- 8-device mesh vs no mesh for the fused tpu_bo round (the full SPMD
  build: split GP fit, replicated polish splice, sharded EI/dedup pool):
  the same rows selected, equal to within ``sharding.ROWS_ATOL`` — XLA
  promises no reduction order across shards, so the last bits may move.
  The promoted multichip gate asserts the same at q=1024;
- the sharding helpers themselves: mesh/spec caching (JIT004's reason to
  exist — hot paths must reuse ONE mesh object) and per-device placement
  accounting.

These run under the suite's 8-device virtual CPU mesh (tests/conftest.py).
"""

import jax
import numpy as np
import pytest

from orion_tpu.algo.base import create_algo
from orion_tpu.algo.sharding import STATE_RTOL, compare_rows, compare_states
from orion_tpu.space.dsl import build_space

def _uniform_space(d=4):
    return build_space({f"x{i}": "uniform(0, 1)" for i in range(d)})


def _fidelity_space(d=4):
    return build_space(
        {**{f"x{i}": "uniform(0, 1)" for i in range(d)},
         "budget": "fidelity(1, 16, 4)"}
    )


def _observed_pair(name, space, cfg, n_devices, n_obs=20, seed=3, fidelity=False):
    """(mesh_algo, plain_algo) with identical seed + observations."""
    rng = np.random.default_rng(seed)
    params = space.sample(0, n=n_obs)
    if fidelity:
        for p in params:
            p["budget"] = 1
    objs = [{"objective": float(v)} for v in rng.normal(size=len(params))]
    out = []
    for use_mesh in (True, False):
        algo = create_algo(
            space,
            {name: dict(cfg, use_mesh=use_mesh,
                        **({"n_devices": n_devices} if use_mesh else {}))},
            seed=seed,
        )
        algo.observe(params, objs)
        out.append(algo)
    return out


GP_CFG = {"n_init": 8, "n_candidates": 512, "fit_steps": 8}
FOUR_ALGOS = [
    ("tpu_bo", GP_CFG, False),
    ("turbo", GP_CFG, False),
    ("asha_bo", dict(GP_CFG, trust_region=True), True),
    ("bohb", {"n_candidates": 512, "min_points": 8}, True),
]


@pytest.mark.parametrize(
    "name,cfg,fidelity", FOUR_ALGOS, ids=[a[0] for a in FOUR_ALGOS]
)
def test_one_device_mesh_bit_identical(name, cfg, fidelity):
    space = _fidelity_space() if fidelity else _uniform_space()
    mesh_algo, plain_algo = _observed_pair(
        name, space, cfg, n_devices=1, fidelity=fidelity
    )
    assert mesh_algo.suggest(8) == plain_algo.suggest(8)
    health_m, health_p = mesh_algo.health_record(), plain_algo.health_record()
    assert health_m.get("mesh_devices") == 1
    for k in health_m:
        if k not in health_p:
            continue
        vm, vp = health_m[k], health_p[k]
        if isinstance(vm, (dict, list, tuple)):
            equal = vm == vp  # ragged payloads (tier/bracket occupancy)
        else:
            equal = np.array_equal(np.asarray(vm), np.asarray(vp))
        assert equal, f"{name} health field {k!r} drifts under the 1-device mesh"


def test_eight_device_mesh_matches_rows_state_health():
    space = _uniform_space()
    mesh_algo, plain_algo = _observed_pair("tpu_bo", space, GP_CFG, n_devices=8)
    rows_m = np.asarray(mesh_algo._suggest_cube(8))
    rows_p = np.asarray(plain_algo._suggest_cube(8))
    parity = compare_rows(rows_m, rows_p)
    assert parity["ok"], parity
    # GP state: the mesh build fits on a single device at plan time (split
    # fit) — its posterior must match the in-plan fit to f32 noise.
    state_m, state_p = mesh_algo._gp_state, plain_algo._gp_state
    state = compare_states(state_m, state_p)
    assert state["ok"], state
    np.testing.assert_allclose(
        np.asarray(state_m.hypers.log_lengthscales),
        np.asarray(state_p.hypers.log_lengthscales),
        rtol=STATE_RTOL,
    )
    health = mesh_algo.health_record()
    assert health["mesh_devices"] == 8
    # Fresh sharded dispatch just ran: utilization fields must be present
    # and every device fraction bounded by the replicated-vs-sharded split.
    assert 0.0 <= health["mesh_util_min_frac"] <= health["mesh_util_max_frac"] <= 1.0


def test_split_fit_stays_on_one_device_in_warm_rounds(monkeypatch):
    """Mesh mode fits the GP hypers in a separate single-device jit.  The
    warm state a sharded step returns is replicated over the mesh; the fit
    must still see it on one device, or it compiles again as a
    multi-device program (18.9 s on the first warm round on a v5e 2x2)."""
    from orion_tpu.algo import tpu_bo

    seen = []
    real = tpu_bo._fit_gp_host

    def spy(x, y, mask, warm, **kw):
        seen.append({
            leaf.sharding.num_devices
            for leaf in jax.tree.leaves((x, warm))
            if isinstance(leaf, jax.Array)
        })
        return real(x, y, mask, warm, **kw)

    monkeypatch.setattr(tpu_bo, "_fit_gp_host", spy)
    space = _uniform_space()
    algo = create_algo(
        space, {"tpu_bo": dict(GP_CFG, use_mesh=True, n_devices=8)}, seed=3
    )
    rng = np.random.default_rng(3)
    params = space.sample(0, n=20)
    algo.observe(params, [{"objective": float(v)} for v in rng.normal(size=20)])
    for _ in range(2):
        rows = np.asarray(algo._suggest_cube(8))
        algo.observe(
            [{f"x{i}": float(r[i]) for i in range(4)} for r in rows],
            [{"objective": float(v)} for v in rng.normal(size=len(rows))],
        )
    assert len(seen) == 2  # cold round, then the warm one
    assert seen == [{1}, {1}]


def test_mesh_and_spec_caches_return_same_objects():
    from orion_tpu.algo.sharding import (
        candidate_spec,
        get_mesh,
        replicated_spec,
    )

    mesh_a = get_mesh(8)
    mesh_b = get_mesh(8)
    assert mesh_a is mesh_b  # one Mesh per (n, axis) — the JIT004 contract
    assert candidate_spec(mesh_a) is candidate_spec(mesh_b)
    assert replicated_spec(mesh_a) is replicated_spec(mesh_b)
    assert get_mesh(1) is not mesh_a


def test_placement_fractions_cover_every_device():
    from orion_tpu.algo.sharding import (
        get_mesh,
        placement_fractions,
        shard_candidates,
    )

    mesh = get_mesh(8)
    pool = shard_candidates(np.zeros((64, 4), dtype=np.float32), mesh)
    fractions = placement_fractions(pool)
    assert len(fractions) == 8
    assert all(f > 0 for f in fractions.values())
    assert abs(sum(fractions.values()) - 1.0) < 1e-6


def test_coalesced_mesh_dispatch_matches_standalone():
    """Gateway coalescing over mesh-built plans (tenant-parallel shard_map
    when the stack is wide enough) must reproduce standalone dispatch."""
    from orion_tpu.algo.tpu_bo import run_fused_plan
    from orion_tpu.serve.coalesce import LAST_STACK_PLACEMENT, run_coalesced_plans

    space = _uniform_space()
    rng = np.random.default_rng(5)
    plans, want = [], []
    algos = []
    for lane in range(8):
        algo = create_algo(
            space,
            {"tpu_bo": dict(GP_CFG, use_mesh=True, n_devices=8)},
            seed=lane,
        )
        params = space.sample(lane, n=16)
        objs = [{"objective": float(v)} for v in rng.normal(size=len(params))]
        algo.observe(params, objs)
        algos.append(algo)
        plans.append(algo.fused_step_plan(4))
    for plan in plans:
        rows, _state = run_fused_plan(plan)
        want.append(np.asarray(rows))
    got = run_coalesced_plans(plans)
    assert LAST_STACK_PLACEMENT.get("tenant_parallel") is True
    for lane in range(8):
        rows, _state = got[lane]
        parity = compare_rows(rows, want[lane])
        assert parity["ok"], (lane, parity)


def test_compare_states_flags_a_drifted_fit():
    """Rows alone can agree while the fit drifts: the state comparison
    holds alpha and the health vector to STATE_RTOL."""
    from types import SimpleNamespace

    alpha = np.linspace(-3.0, 3.0, 16, dtype=np.float32)
    health = np.linspace(0.1, 2.0, 8, dtype=np.float32)
    base = SimpleNamespace(alpha=alpha, health=health)
    noise = SimpleNamespace(alpha=alpha * (1 + 2e-6), health=health)
    drift = SimpleNamespace(alpha=alpha * (1 + 1e-3), health=health)
    assert compare_states(base, noise)["ok"]
    out = compare_states(base, drift)
    assert not out["ok"] and out["alpha_max_abs_diff"] > 1e-3
