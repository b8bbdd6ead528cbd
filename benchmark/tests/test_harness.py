"""The harness end to end at a small size on the CPU, past its look for a
chip: a cell added by files alone, the control and the planted faults each
turning ``correct`` false."""

import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmark import harness
from orion_tpu.algo.tpu_bo import TPUBO

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LIMITS_OF = "rosenbrock20-q256.rounds"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with one more cell, traffic mix and metric, added as
    files and entries only."""
    root = tmp_path_factory.mktemp("checkout")
    bench = root / "benchmark"
    shutil.copytree(os.path.join(REPO, "benchmark"), bench,
                    ignore=shutil.ignore_patterns(".out", ".jax_cache", "tests"))
    cfg = json.load(open(bench / "configs" / "rosenbrock20-q256.json"))
    cfg.update(name="tiny", q=32, trials_per_experiment=128)
    cfg["algorithm"]["tpu_bo"].update(n_init=32, n_candidates=1024, fit_steps=10,
                                      tr_local_m=64, prewarm=False)
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    traffic = json.load(open(bench / "traffic" / "rounds.json"))
    traffic.update(keep_fraction=0.5)
    (bench / "traffic" / "tiny_rounds.json").write_text(json.dumps(traffic))
    workloads = bench / "workloads"
    shutil.copy(workloads / f"{LIMITS_OF}.json", workloads / "tiny.rounds.json")
    shutil.copy(workloads / "rosenbrock20-q256.optimize.json", workloads / "tiny.optimize.json")
    (bench / "metrics" / "rounds_seen.py").write_text(
        "def read(ctx):\n    return ctx.window_rounds\n")
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    spec["configs"].append({"name": "tiny", "source": "test", "reduced": [], "why": "test",
                            "file": "benchmark/configs/tiny.json"})
    spec["workloads"].append({"name": "tiny.rounds", "config": "tiny", "traffic": "tiny_rounds",
                              "chips": 1, "why": "test"})
    spec["workloads"].append({"name": "tiny.optimize", "config": "tiny", "traffic": "optimize",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "rounds_seen", "unit": "rounds", "better": "higher",
                              "source": "host_clock", "layer": "test",
                              "moves": "suggestions_per_s", "workloads": ["tiny.rounds"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    # The program's compilation cache, kept inside the test's checkout.
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    yield str(root)
    jax.config.update("jax_compilation_cache_dir", before)


def _run(root, tmp_path, seed=1750112387, seconds=2.0):
    run = harness.Run(harness.Cell("tiny.rounds", root=root), seed, seconds, False,
                      require_tpu=False)
    run.setup()
    run.window()
    run.release()
    return run


def _passes(cell, numbers):
    return all(numbers.get(k, np.nan) <= v for k, v in cell.limits.items())


@pytest.fixture
def fresh_jit():
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_added_cell_runs_correct_and_the_control_does_not(root, tmp_path, fresh_jit):
    run = _run(root, tmp_path)
    assert run.window_compiles == 0
    assert _passes(run.cell, run.check())
    assert not _passes(run.cell, run.check(control=True))
    e2e = run.end_to_end()
    # A metric listing its cells (suggest_p95_ms) leaves the new cell out.
    assert set(e2e) == {"suggestions_per_s", "suggest_p50_ms", "setup_s"}
    assert all(v["value"] > 0 for v in e2e.values())
    metrics, _ = run.layer_metrics()
    assert metrics["rounds_seen"]["value"] == len(run.latencies)
    assert "step_device_ms" not in metrics  # nothing traced, nothing read


def _fails(root, tmp_path):
    run = _run(root, tmp_path)
    return not _passes(run.cell, run.check())


def test_duplicate_retry_compiles_in_setup(root, fresh_jit):
    """Every round of the window repeats stored trials, so the producer
    retries in every q bucket; set-up warmed each retry and the window
    compiles nothing."""
    run = harness.Run(harness.Cell("tiny.optimize", root=root), 7, 3.0, False,
                      require_tpu=False)
    run.setup()
    with run.loop.warm_up():
        run.window()
    assert run.window_compiles == 0
    run.release()
    assert run.storage_checks == {"unstored_suggestions": 0, "lost_observations": 0}


def test_warm_up_waits_out_slow_compiles(root, monkeypatch):
    """On a cold cache a warm-up round's chain of retries compiles a step per
    q bucket, longer than the producer's idle limit; set-up waits it out.
    Here every producer's limit is all but zero."""
    from orion_tpu.client.experiment import ExperimentClient

    monkeypatch.setattr(ExperimentClient.__init__, "__defaults__", (1e-9,))
    run = harness.Run(harness.Cell("tiny.optimize", root=root), 7, 1.0, False,
                      require_tpu=False)
    run.setup()
    trials = run.loop.client.experiment.fetch_trials()
    assert len(trials) == run.cell.config["trials_per_experiment"]


def test_state_left_unchanged_fails(root, tmp_path, fresh_jit, monkeypatch):
    from orion_tpu.algo.history import DeviceHistory

    append = DeviceHistory.append

    def stale_append(self, rows, ys):
        if self.count == 0:
            return append(self, rows, ys)
        self.count += np.asarray(ys).reshape(-1).shape[0]

    monkeypatch.setattr(DeviceHistory, "append", stale_append)
    assert _fails(root, tmp_path)


def test_half_the_batch_left_out_fails(root, tmp_path, fresh_jit, monkeypatch):
    observe = TPUBO.observe_arrays

    def half(self, cube, objectives, **kw):
        n = max(1, len(objectives) // 2)
        return observe(self, np.asarray(cube)[:n], np.asarray(objectives)[:n])

    monkeypatch.setattr(TPUBO, "observe_arrays", half)
    assert _fails(root, tmp_path)


def test_exchange_between_chips_left_out_fails(root, tmp_path, fresh_jit, monkeypatch):
    from orion_tpu.algo.gp import acquisition

    rff = acquisition.rff_thompson

    def one_shard(key, state, candidates, q, **kw):
        # What one chip of four picks from its own quarter of the pool.
        return rff(key, state, candidates[: candidates.shape[0] // 4], q, **kw)

    monkeypatch.setattr(acquisition, "rff_thompson", one_shard)
    assert _fails(root, tmp_path)


def test_frozen_fit_fails(root, tmp_path, fresh_jit, monkeypatch):
    from benchmark import control

    monkeypatch.setattr(TPUBO, "_step_kw", TPUBO._step_kw)  # undone after the test
    control.freeze_fit()
    run = _run(root, tmp_path)
    numbers = run.check()
    assert numbers["fit_opt_gap"] > run.cell.limits["fit_opt_gap"]
    assert not _passes(run.cell, numbers)


def test_unknown_traffic_key_is_refused(root, tmp_path):
    """A mix may not ask for what the harness does not do (here 16 clients)."""
    other = tmp_path / "checkout"
    shutil.copytree(root, other, ignore=shutil.ignore_patterns(".jax_cache"))
    mixes = other / "benchmark" / "traffic"
    mix = json.load(open(mixes / "tiny_rounds.json"))
    (mixes / "tiny_rounds.json").write_text(json.dumps(dict(mix, clients=16)))
    with pytest.raises(harness.BenchError, match="clients"):
        harness.Cell("tiny.rounds", root=str(other))


def test_altered_answer_fails(root, tmp_path, fresh_jit, monkeypatch):
    suggest = TPUBO._suggest_cube

    def altered(self, num):
        rows = np.array(suggest(self, num))
        if self._gp_state is not None:
            rows[1] = np.random.default_rng(0).random(rows.shape[1])
        return rows

    monkeypatch.setattr(TPUBO, "_suggest_cube", altered)
    assert _fails(root, tmp_path)


def test_no_tpu_means_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", LIMITS_OF, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout == ""
