"""info / status / list / insert / db command behavior through the real CLI.

Parity model: reference tests/functional/commands/.
"""

import os

import pytest

from orion_tpu.cli import main as cli_main
from orion_tpu.storage import create_storage

HERE = os.path.dirname(os.path.abspath(__file__))
BLACK_BOX = os.path.join(HERE, "black_box.py")


@pytest.fixture(scope="module")
def _populated_template(tmp_path_factory):
    """Run the 4-trial hunt ONCE per module; ~10s of subprocess trials that
    eight tests each paid before this was a template."""
    root = tmp_path_factory.mktemp("populated-template")
    cli_main(["hunt", "-n", "cmd-exp", "--storage-path", str(root / "db.pkl"),
              "--max-trials", "4", "--worker-trials", "4",
              BLACK_BOX, "-x~uniform(-50, 50)"])
    return root / "db.pkl"


@pytest.fixture
def populated(_populated_template, tmp_path):
    """Per-test COPY of the template DB: mutating tests (insert, resume,
    branching hunts) keep full isolation at file-copy cost."""
    import shutil

    shutil.copy(_populated_template, tmp_path / "db.pkl")
    return tmp_path, ["--storage-path", str(tmp_path / "db.pkl")]


def test_info(populated, capsys):
    tmp_path, db = populated
    rc = cli_main(["info", "-n", "cmd-exp", *db])
    assert rc == 0
    out = capsys.readouterr().out
    assert "cmd-exp" in out
    assert "/x: uniform(-50, 50)" in out
    assert "trials completed: 4" in out
    assert "best evaluation:" in out


def test_status(populated, capsys):
    tmp_path, db = populated
    rc = cli_main(["status", *db])
    assert rc == 0
    out = capsys.readouterr().out
    # Default aggregates a name's versions under the bare name (reference
    # shows per-version sections only with --expand-versions).
    assert "cmd-exp" in out and "cmd-exp-v1" not in out
    assert "completed" in out and "4" in out


def test_status_expand_versions(populated, capsys):
    tmp_path, db = populated
    rc = cli_main(["status", "--expand-versions", *db])
    assert rc == 0
    out = capsys.readouterr().out
    assert "cmd-exp-v1" in out


def test_status_all_lists_trials(populated, capsys):
    tmp_path, db = populated
    rc = cli_main(["status", "-n", "cmd-exp", "--all", *db])
    assert rc == 0
    out = capsys.readouterr().out
    assert out.count("completed") == 4


def test_list_shows_evc_tree(populated, capsys):
    tmp_path, db = populated
    # Branch it to get a tree.
    cli_main(["hunt", "-n", "cmd-exp", *db, "--max-trials", "2", "--worker-trials", "0",
              BLACK_BOX, "-x~uniform(-10, 10)"])
    rc = cli_main(["list", *db])
    assert rc == 0
    out = capsys.readouterr().out
    assert "cmd-exp-v1" in out
    assert "└── cmd-exp-v2" in out


def test_insert_and_defaults(populated, capsys):
    tmp_path, db = populated
    rc = cli_main(["insert", "-n", "cmd-exp", *db, "x=3.5"])
    assert rc == 0
    storage = create_storage({"type": "pickled", "path": str(tmp_path / "db.pkl")})
    exp = storage.fetch_experiments({"name": "cmd-exp"})[0]
    new = [t for t in storage.fetch_trials(uid=exp["_id"]) if t.status == "new"]
    assert len(new) == 1
    assert new[0].params == {"/x": 3.5}


def test_insert_rejects_out_of_bounds(populated):
    tmp_path, db = populated
    with pytest.raises(ValueError):
        cli_main(["insert", "-n", "cmd-exp", *db, "x=999"])


def test_db_test_checks(populated, capsys):
    tmp_path, db = populated
    rc = cli_main(["db", "test", *db])
    assert rc == 0
    out = capsys.readouterr().out
    assert "check presence... ok" in out
    assert "check creation... ok" in out
    assert "check operations... ok" in out


def test_db_upgrade_backfills(populated, capsys):
    tmp_path, db = populated
    storage = create_storage({"type": "pickled", "path": str(tmp_path / "db.pkl")})
    # Simulate an old-schema experiment document.
    storage.db.write("experiments", {"_id": "old1", "name": "legacy"})
    rc = cli_main(["db", "upgrade", *db])
    assert rc == 0
    doc = storage.fetch_experiments({"name": "legacy"})[0]
    assert doc["version"] == 1
    assert doc["priors"] == {}
    assert doc["refers"] == {}


def test_db_setup_writes_user_config(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv("XDG_CONFIG_HOME", str(tmp_path / "cfg"))
    rc = cli_main(["db", "setup", "--path", str(tmp_path / "mydb.pkl")])
    assert rc == 0
    import yaml

    path = tmp_path / "cfg" / "orion_tpu" / "config.yaml"
    data = yaml.safe_load(path.read_text())
    assert data["storage"]["type"] == "pickled"
    assert data["storage"]["path"] == str(tmp_path / "mydb.pkl")


def test_sectioned_config_files_are_not_silently_ignored(tmp_path, capsys):
    """`experiment:`-wrapped keys and the reference's `producer:`/`database:`
    sections must configure the run — a config whose algorithms sat under
    `experiment:` previously ran RANDOM search without a word."""
    conf = tmp_path / "exp.yaml"
    conf.write_text(
        "experiment:\n"
        "  algorithms:\n"
        "    grid_search:\n"
        "      n_values: 3\n"
        "producer:\n"
        "  strategy: StubParallelStrategy\n"
        f"database:\n  type: pickleddb\n  path: {tmp_path / 'ref.pkl'}\n"
    )
    rc = cli_main(["hunt", "-n", "sect", "-c", str(conf), "--max-trials", "3",
                   "--working-dir", str(tmp_path / "w"),
                   BLACK_BOX, "-x~uniform(-5, 5)"])
    assert rc == 0
    capsys.readouterr()
    # The database: section routed storage to the reference-style pickleddb
    # path, and the experiment: section selected grid_search.
    storage = create_storage({"type": "pickled", "path": str(tmp_path / "ref.pkl")})
    [exp] = storage.fetch_experiments({"name": "sect"})
    assert "grid_search" in exp["algorithms"]
    rc = cli_main(["info", "-n", "sect", "-c", str(conf)])
    assert rc == 0
    assert "grid_search" in capsys.readouterr().out


def test_sectioned_user_level_config(tmp_path, monkeypatch):
    """The ~/.config user file layer normalizes sections too — that is
    exactly where reference users keep their `database:` section."""
    cfg_dir = tmp_path / "xdg" / "orion_tpu"
    cfg_dir.mkdir(parents=True)
    (cfg_dir / "config.yaml").write_text(
        f"database:\n  type: pickleddb\n  path: {tmp_path / 'user.pkl'}\n"
    )
    monkeypatch.setenv("XDG_CONFIG_HOME", str(tmp_path / "xdg"))
    from orion_tpu.config import resolve_config

    config = resolve_config()
    assert config["storage"]["type"] == "pickleddb"
    assert config["storage"]["path"] == str(tmp_path / "user.pkl")


def test_sectioned_config_top_level_wins():
    """experiment:-hoisted keys lose WHOLE to explicit top-level ones
    (shallow replace): never a merged two-algorithm dict create_algo
    would reject."""
    from orion_tpu.config import normalize_sections

    cfg = normalize_sections(
        {"experiment": {"algorithms": {"tpe": {}}}, "algorithms": {"random": {}}}
    )
    assert cfg["algorithms"] == {"random": {}}


def test_hunt_n_workers_shares_the_budget(tmp_path, capsys):
    """--n-workers N spawns N-1 identical child hunts against the shared
    storage; the cohort completes the global budget exactly once."""
    db = ["--storage-path", str(tmp_path / "db.pkl")]
    rc = cli_main(["hunt", "-n", "nw", *db, "--max-trials", "8",
                   "--n-workers", "2", "--working-dir", str(tmp_path / "w"),
                   BLACK_BOX, "-x~uniform(-5, 5)"])
    assert rc == 0
    assert "trials completed:" in capsys.readouterr().out
    storage = create_storage({"type": "pickled", "path": str(tmp_path / "db.pkl")})
    [exp] = storage.fetch_experiments({"name": "nw"})
    trials = storage.fetch_trials(uid=exp["_id"])
    completed = sum(1 for t in trials if t.status == "completed")
    # Async workers check is_done before consuming, so a final in-flight
    # trial per extra worker may land past the budget — same soft-budget
    # semantics as N manually-launched hunts (and the reference).
    assert 8 <= completed <= 9


def test_hunt_n_workers_children_never_load_the_device(tmp_path, monkeypatch):
    """One process owns the chip: with tpu_bo, the spawned worker runs with
    JAX_PLATFORMS=cpu and suggests through the parent's in-process gateway
    (its algorithm is a gateway tenant), never through a device of its own."""
    import subprocess

    import yaml

    from orion_tpu.serve.gateway import GatewayServer

    child_envs, gateway_stats = [], []
    real_popen, real_shutdown = subprocess.Popen, GatewayServer.shutdown

    def recording_popen(cmd, *a, env=None, **kw):
        child_envs.append(dict(env or {}))
        return real_popen(cmd, *a, env=env, **kw)

    def recording_shutdown(self):
        gateway_stats.append(self.stats_snapshot())
        real_shutdown(self)

    monkeypatch.setattr(subprocess, "Popen", recording_popen)
    monkeypatch.setattr(GatewayServer, "shutdown", recording_shutdown)
    monkeypatch.delenv("ORION_SERVE_ADDRESS", raising=False)
    config = tmp_path / "conf.yaml"
    config.write_text(yaml.safe_dump({"algorithms": {"tpu_bo": {
        "n_init": 2, "n_candidates": 64, "fit_steps": 2}}}))
    # Each worker's first trial waits (at most 120 s) until the other
    # worker runs one too: the parent cannot finish the budget before the
    # slower-starting child suggests, and with one suggestion per producer
    # round the child finds no pending trial to take without suggesting.
    marks = tmp_path / "marks"
    marks.mkdir()
    rendezvous = tmp_path / "rendezvous.py"
    rendezvous.write_text(
        "import os, runpy, time\n"
        f"marks = {str(marks)!r}\n"
        "who = 'child' if os.environ.get('ORION_TPU_SPAWNED_WORKER') else 'parent'\n"
        "open(os.path.join(marks, who), 'w').close()\n"
        "deadline = time.time() + 120\n"
        "while len(os.listdir(marks)) < 2 and time.time() < deadline:\n"
        "    time.sleep(0.05)\n"
        f"runpy.run_path({BLACK_BOX!r}, run_name='__main__')\n"
    )
    rc = cli_main(["hunt", "-n", "nwt", "-c", str(config),
                   "--storage-path", str(tmp_path / "db.pkl"),
                   "--max-trials", "6", "--n-workers", "2", "--pool-size", "1",
                   "--working-dir", str(tmp_path / "w"),
                   str(rendezvous), "-x~uniform(-5, 5)"])
    assert rc == 0
    workers = [e for e in child_envs if e.get("ORION_TPU_SPAWNED_WORKER")]
    assert len(workers) == 1
    assert workers[0]["JAX_PLATFORMS"] == "cpu"
    assert workers[0]["ORION_SERVE_ADDRESS"].startswith("127.0.0.1:")
    assert workers[0]["ORION_SERVE_SECRET"]
    assert sorted(os.listdir(marks)) == ["child", "parent"]
    [stats] = gateway_stats
    assert stats["suggests"] >= 1  # the child's algorithm was a tenant


def test_hunt_n_workers_refuses_memory_storage(capsys):
    rc = cli_main(["hunt", "-n", "nwm", "--debug", "--max-trials", "2",
                   "--n-workers", "2", BLACK_BOX, "-x~uniform(-5, 5)"])
    assert rc == 1
    assert "in-memory storage is per-process" in capsys.readouterr().err


def test_setup_and_test_db_top_level_aliases(tmp_path, monkeypatch, capsys):
    """`setup` and `test-db` mirror `db setup` / `db test` (reference
    `cli/setup.py`, `cli/test_db.py` historical spellings)."""
    monkeypatch.setenv("XDG_CONFIG_HOME", str(tmp_path / "cfg"))
    assert cli_main(["setup", "--storage-type", "sqlite",
                     "--path", str(tmp_path / "a.sqlite")]) == 0
    import yaml

    data = yaml.safe_load(
        (tmp_path / "cfg" / "orion_tpu" / "config.yaml").read_text()
    )
    assert data["storage"]["type"] == "sqlite"
    assert cli_main(["test-db"]) == 0
    out = capsys.readouterr().out
    assert "check presence... ok" in out
    assert "check operations... ok" in out


def test_branching_diff_lines_colorize_on_tty(monkeypatch):
    import io

    from orion_tpu.utils.diff import colorize_diff_line

    class Tty(io.StringIO):
        def isatty(self):
            return True

    monkeypatch.delenv("NO_COLOR", raising=False)  # ambient CI shells set it
    assert colorize_diff_line("+ x~uniform(0,1)", stream=Tty()).startswith("\x1b[0;32m")
    assert colorize_diff_line("- y~uniform(0,1)", stream=Tty()).startswith("\x1b[0;31m")
    assert colorize_diff_line("~ z: a -> b", stream=Tty()).startswith("\x1b[0;33m")
    # Non-TTY (scripted sessions, tests) and NO_COLOR stay plain.
    assert colorize_diff_line("+ x", stream=io.StringIO()) == "+ x"
    monkeypatch.setenv("NO_COLOR", "1")
    assert colorize_diff_line("+ x", stream=Tty()) == "+ x"


def test_resume_preserves_stored_budgets(populated, capsys):
    """Regression: resolver defaults must not override stored per-experiment
    settings on resume (max_trials inf clobbered a stored value)."""
    tmp_path, db = populated
    rc = cli_main(["info", "-n", "cmd-exp", *db])
    assert rc == 0
    out = capsys.readouterr().out
    assert "max_trials: 4" in out


def test_info_unknown_experiment_no_ghost(tmp_path, capsys):
    """Regression: read-only commands must not persist ghost experiments;
    the unknown name surfaces as a one-line error, not a traceback."""
    db = ["--storage-path", str(tmp_path / "db.pkl")]
    assert cli_main(["info", "-n", "typo", *db]) == 1
    assert "no experiment matching" in capsys.readouterr().err
    assert cli_main(["insert", "-n", "typo", *db, "x=1"]) == 1
    storage = create_storage({"type": "pickled", "path": str(tmp_path / "db.pkl")})
    assert storage.fetch_experiments({}) == []


def test_info_wrong_version_no_ghost(populated, capsys):
    tmp_path, db = populated
    assert cli_main(["info", "-n", "cmd-exp", "--exp-version", "99", *db]) == 1
    assert "no experiment matching" in capsys.readouterr().err
    storage = create_storage({"type": "pickled", "path": str(tmp_path / "db.pkl")})
    assert len(storage.fetch_experiments({"name": "cmd-exp"})) == 1


def test_status_collapse_aggregates_tree(populated, capsys):
    tmp_path, db = populated
    cli_main(["hunt", "-n", "cmd-exp", *db, "--max-trials", "6", "--worker-trials", "2",
              BLACK_BOX, "-x~uniform(-10, 10)"])
    capsys.readouterr()  # drop the hunt's own stats output
    rc = cli_main(["status", "--collapse", *db])
    assert rc == 0
    out = capsys.readouterr().out
    assert out.count("cmd-exp") == 1  # one collapsed tree, not per-version
    assert "6" in out  # 4 (v1) + 2 (v2) completed


def test_env_var_coercion(tmp_path, monkeypatch):
    monkeypatch.setenv("ORION_MAX_TRIALS", "7")
    from orion_tpu.config import resolve_config

    config = resolve_config()
    assert config["max_trials"] == 7.0
    assert isinstance(config["max_trials"], float)


def test_config_file_heartbeat_governs_lost_trial_sweep(tmp_path):
    """A config-file `heartbeat:` must change the sweep threshold — the knob
    was previously defined in DEFAULTS but never plumbed (round-1 verdict)."""
    import argparse
    import time as _time

    from orion_tpu.cli.base import build_from_args
    from orion_tpu.core.trial import Trial

    conf = tmp_path / "orion.yaml"
    conf.write_text("heartbeat: 7.5\nmax_idle_time: 3.0\n")
    args = argparse.Namespace(
        name="hb-exp",
        exp_version=None,
        config=str(conf),
        debug=False,
        storage_path=str(tmp_path / "db.pkl"),
        manual_resolution=False,
        user_args=[BLACK_BOX, "-x~uniform(-5, 5)"],
    )
    experiment, _parser = build_from_args(args)
    assert experiment.heartbeat == 7.5

    # A reserved trial whose heartbeat is older than 7.5s is swept...
    trial = Trial(experiment=experiment.id, params={"/x": 1.0}, status="new")
    experiment.storage.register_trial(trial)
    reserved = experiment.storage.reserve_trial(experiment.id)
    experiment.storage._db.write(
        "trials", {"heartbeat": _time.time() - 8.0}, query={"_id": reserved.id}
    )
    experiment.fix_lost_trials()
    statuses = {t.id: t.status for t in experiment.fetch_trials()}
    assert statuses[reserved.id] == "interrupted"

    # ...but with the default 120s threshold it would have survived.
    conf2 = tmp_path / "orion2.yaml"
    conf2.write_text("heartbeat: 120.0\n")
    args.config = str(conf2)
    args.name = "hb-exp"
    experiment2, _ = build_from_args(args)
    assert experiment2.heartbeat == 120.0
    trial2 = Trial(experiment=experiment2.id, params={"/x": 2.0}, status="new")
    experiment2.storage.register_trial(trial2)
    reserved2 = experiment2.storage.reserve_trial(experiment2.id)
    experiment2.storage._db.write(
        "trials", {"heartbeat": _time.time() - 8.0}, query={"_id": reserved2.id}
    )
    experiment2.fix_lost_trials()
    statuses = {t.id: t.status for t in experiment2.fetch_trials()}
    assert statuses[reserved2.id] == "reserved"


def test_heartbeat_cli_flag_overrides_config_file(tmp_path):
    import argparse

    from orion_tpu.cli.base import build_from_args

    conf = tmp_path / "orion.yaml"
    conf.write_text("heartbeat: 99.0\nmax_idle_time: 44.0\n")
    args = argparse.Namespace(
        name="hb-cli",
        exp_version=None,
        config=str(conf),
        debug=False,
        storage_path=str(tmp_path / "db.pkl"),
        manual_resolution=False,
        user_args=[BLACK_BOX, "-x~uniform(-5, 5)"],
        heartbeat=33.0,
    )
    experiment, _ = build_from_args(args)
    assert experiment.heartbeat == 33.0  # flag beats config file
    assert experiment.max_idle_time == 44.0  # config file beats default


def test_user_namespacing(tmp_path, capsys):
    """-u/--user scopes lookups: the same name under another user is
    invisible (reference `cli/base.py:94`)."""
    db = ["--storage-path", str(tmp_path / "db.pkl")]
    cli_main(["hunt", "-n", "ns", "-u", "alice", *db, "--max-trials", "2",
              "--worker-trials", "2", BLACK_BOX, "-x~uniform(-5, 5)"])
    capsys.readouterr()

    storage = create_storage({"type": "pickled", "path": str(tmp_path / "db.pkl")})
    [exp] = storage.fetch_experiments({"name": "ns"})
    assert exp["metadata"]["user"] == "alice"

    # bob filters it out of status...
    rc = cli_main(["status", "-u", "bob", *db])
    assert rc == 0
    assert "No experiment found" in capsys.readouterr().out
    # ...and cannot info it (clean one-line error, exit 1).
    assert cli_main(["info", "-n", "ns", "-u", "bob", *db]) == 1
    assert "no experiment matching" in capsys.readouterr().err
    # alice sees it.
    rc = cli_main(["info", "-n", "ns", "-u", "alice", *db])
    assert rc == 0
    assert "ns" in capsys.readouterr().out


def test_experiment_view_blocks_writes(populated):
    import argparse

    import pytest as _pytest

    from orion_tpu.cli.base import build_from_args

    tmp_path, db = populated
    args = argparse.Namespace(
        name="cmd-exp", exp_version=None, config=None, debug=False,
        storage_path=db[1], manual_resolution=False, user=None, user_args=[],
    )
    view, _ = build_from_args(
        args, need_user_args=False, allow_create=False, view=True
    )
    assert view.name == "cmd-exp"
    assert len(view.fetch_trials()) == 4
    assert view.stats()["trials_completed"] == 4
    with _pytest.raises(AttributeError):
        view.register_trial(None)
    with _pytest.raises(AttributeError):
        view.max_trials = 3


def test_info_shows_latency_percentiles(populated, capsys):
    """Producer telemetry surfaces as suggest/observe percentiles in info
    (SURVEY §5 timing hooks; round-1 verdict #9)."""
    tmp_path, db = populated
    rc = cli_main(["info", "-n", "cmd-exp", *db])
    assert rc == 0
    out = capsys.readouterr().out
    assert "Performance" in out
    assert "suggest:" in out and "p50" in out and "p99" in out
    assert "observe:" in out


def test_two_users_can_own_same_experiment_name(tmp_path):
    """Per-user namespacing must actually hold two users' same-named
    experiments (identity + unique index include metadata.user)."""
    db = ["--storage-path", str(tmp_path / "db.pkl")]
    for user, lo, hi in (("alice", -5, 5), ("bob", -9, 9)):
        rc = cli_main(["hunt", "-n", "shared", "-u", user, *db,
                       "--max-trials", "2", "--worker-trials", "2",
                       BLACK_BOX, f"-x~uniform({lo}, {hi})"])
        assert rc == 0
    storage = create_storage({"type": "pickled", "path": str(tmp_path / "db.pkl")})
    exps = storage.fetch_experiments({"name": "shared"})
    assert len(exps) == 2
    assert {e["metadata"]["user"] for e in exps} == {"alice", "bob"}
    assert len({e["_id"] for e in exps}) == 2


def test_db_copy_between_backends(tmp_path):
    """`db copy` migrates an experiment between backends and is idempotent."""
    from orion_tpu.cli import main

    src = str(tmp_path / "src.pkl")
    dst = str(tmp_path / "dst.sqlite")
    assert main([
        "hunt", "-n", "copy-exp", "--storage-path", src, "--max-trials", "3",
        "--working-dir", str(tmp_path / "w"), BLACK_BOX, "-x~uniform(0,1)",
    ]) == 0
    assert main(["db", "copy", "--src", src, "--dst", dst]) == 0
    # The copied experiment is fully usable from the new backend.
    assert main(["status", "--storage-path", dst]) == 0
    from orion_tpu.storage import create_storage

    out = create_storage({"type": "sqlite", "path": dst})
    exps = out.fetch_experiments({"name": "copy-exp"})
    assert len(exps) == 1
    assert len(out.fetch_trials(uid=exps[0]["_id"])) == 3
    # Idempotent re-copy: nothing duplicated.
    assert main(["db", "copy", "--src", src, "--dst", dst]) == 0
    assert len(out.fetch_trials(uid=exps[0]["_id"])) == 3


def test_db_copy_refuses_conflicting_ids(tmp_path):
    """Same _id, different content -> loud failure, nothing cross-wired."""
    from orion_tpu.cli import main
    from orion_tpu.storage import create_storage

    src = str(tmp_path / "a.pkl")
    dst = str(tmp_path / "b.pkl")
    s = create_storage({"type": "pickled", "path": src})
    s.db.write("experiments", {"_id": 1, "name": "left", "version": 1})
    # Src trials that would cross-wire onto dst's unrelated experiment 1.
    s.db.write("trials", {"_id": "t1", "experiment": 1, "status": "new"})
    create_storage({"type": "pickled", "path": dst}).db.write(
        "experiments", {"_id": 1, "name": "right", "version": 1}
    )
    assert main(["db", "copy", "--src", src, "--dst", dst]) == 1
    out = create_storage({"type": "pickled", "path": dst})
    assert [e["name"] for e in out.db.read("experiments")] == ["right"]
    assert out.db.read("trials") == []  # conflict aborts the WHOLE copy


def test_db_copy_refuses_unique_index_collision(tmp_path):
    """Distinct _ids but same (name, version, user) — the 'same experiment
    created independently on both sides' case — must abort during PLANNING,
    not traceback mid-write with earlier docs already committed."""
    from orion_tpu.cli import main
    from orion_tpu.storage import create_storage

    src = str(tmp_path / "a.pkl")
    dst = str(tmp_path / "b.pkl")
    config = {"name": "exp", "version": 1, "metadata": {"user": "alice"}}
    s = create_storage({"type": "pickled", "path": src})
    s.db.write("experiments", {"_id": "src-id", **config})
    s.db.write("trials", {"_id": "t1", "experiment": "src-id", "status": "new"})
    create_storage({"type": "pickled", "path": dst}).db.write(
        "experiments", {"_id": "dst-id", **config}
    )
    assert main(["db", "copy", "--src", src, "--dst", dst]) == 1
    out = create_storage({"type": "pickled", "path": dst})
    assert [e["_id"] for e in out.db.read("experiments")] == ["dst-id"]
    assert out.db.read("trials") == []  # nothing was copied


def test_db_copy_idempotent_across_representations(tmp_path):
    """Re-copying must merge even when backend representations differ:
    numpy values in the pickled source (dict.__eq__ would raise) and
    tuples that come back as lists through the sqlite destination."""
    import numpy as np

    from orion_tpu.cli import main
    from orion_tpu.storage import create_storage

    src = str(tmp_path / "a.pkl")
    dst = str(tmp_path / "b.sqlite")
    s = create_storage({"type": "pickled", "path": src})
    s.db.write(
        "experiments",
        {"_id": "e1", "name": "exp", "version": 1,
         "metadata": {"user": "u", "arr": np.arange(3), "tup": (1, 2),
                      "nan": float("nan")}},  # NaN != NaN must not re-conflict
    )
    assert main(["db", "copy", "--src", src, "--dst", dst]) == 0
    # Second run: dst already holds the JSON-normalized form.
    assert main(["db", "copy", "--src", src, "--dst", dst]) == 0
    out = create_storage({"type": "sqlite", "path": dst})
    assert len(out.db.read("experiments")) == 1


def test_db_copy_refuses_duplicates_within_source(tmp_path):
    """Two src experiments sharing (name, version, user) under different _ids
    (legacy databases tolerate this; index backfill is last-wins) must abort
    during planning, not DuplicateKeyError mid-write."""
    from orion_tpu.cli import main
    from orion_tpu.storage import create_storage

    from orion_tpu.storage.backends import PickledDB

    src = str(tmp_path / "a.pkl")
    dst = str(tmp_path / "b.pkl")
    config = {"name": "exp", "version": 1, "metadata": {"user": "alice"}}
    # Bypass index enforcement the way a legacy DB would: raw backend writes
    # before any storage protocol has ensured the unique index.
    raw = PickledDB(src)
    raw.write("experiments", {"_id": 1, **config})
    raw.write("experiments", {"_id": 2, **config})
    assert main(["db", "copy", "--src", src, "--dst", dst]) == 1
    out = create_storage({"type": "pickled", "path": dst})
    assert out.db.read("experiments") == []  # nothing was copied


def test_sqlite_routing_treats_empty_file_as_new(tmp_path):
    """A zero-byte *.sqlite file (crash between connect and first schema
    commit, or a pre-touched path) must stay on the sqlite backend."""
    from orion_tpu.storage.sqlitedb import sqlite_path_selected

    path = tmp_path / "db.sqlite"
    path.touch()
    assert sqlite_path_selected(str(path))
    other = tmp_path / "db.pkl"
    other.touch()
    assert not sqlite_path_selected(str(other))


# --- db dump / db load ------------------------------------------------------


def _seed_storage(path):
    st = create_storage({"type": "sqlite", "path": str(path)})
    from orion_tpu.core.trial import Result, Trial

    st.create_experiment({"name": "dmp", "version": 1, "metadata": {"user": "u"}})
    exp = st.fetch_experiments({"name": "dmp"})[0]
    for i in range(3):
        st.register_trial(
            Trial(experiment=exp["_id"], params={"/x": float(i)})
        )
    t = st.reserve_trial(exp["_id"])
    st.update_completed_trial(t, [Result("o", "objective", 0.25)])
    return st, exp


def test_db_dump_load_roundtrip(tmp_path, capsys):
    """dump -> load into a fresh backend reproduces every document; a second
    load is an idempotent no-op."""
    src_path = tmp_path / "src.sqlite"
    _seed_storage(src_path)
    dump = tmp_path / "dump.jsonl"
    assert cli_main(["db", "dump", "--src", str(src_path), "--out", str(dump)]) == 0
    assert cli_main(
        ["db", "load", "--src", str(dump), "--dst", str(tmp_path / "dst.sqlite")]
    ) == 0
    dst = create_storage({"type": "sqlite", "path": str(tmp_path / "dst.sqlite")})
    exp = dst.fetch_experiments({"name": "dmp"})[0]
    trials = dst.fetch_trials(uid=exp["_id"])
    assert len(trials) == 3
    assert sum(1 for t in trials if t.status == "completed") == 1
    # Idempotent merge.
    assert cli_main(
        ["db", "load", "--src", str(dump), "--dst", str(tmp_path / "dst.sqlite")]
    ) == 0
    assert len(dst.fetch_trials(uid=exp["_id"])) == 3
    out = capsys.readouterr().out
    assert "already present" in out


def test_db_load_mongoexport_array(tmp_path):
    """A mongoexport --jsonArray file (Mongo extended JSON: $oid/$date
    wrappers) loads with --collection, normalized to this framework's plain
    documents — the reference-Oríon migration path docs/design.md names."""
    import json

    exps = [
        {
            "_id": {"$oid": "64b1f0c2e4b0a1a2b3c4d5e6"},
            "name": "legacy",
            "version": 1,
            "metadata": {
                "user": "u",
                "datetime": {"$date": "2023-07-14T12:00:00Z"},
            },
        }
    ]
    path = tmp_path / "experiments.json"
    path.write_text(json.dumps(exps))
    dst = tmp_path / "dst.sqlite"
    assert cli_main(
        ["db", "load", "--src", str(path), "--dst", str(dst),
         "--collection", "experiments"]
    ) == 0
    st = create_storage({"type": "sqlite", "path": str(dst)})
    exp = st.fetch_experiments({"name": "legacy"})[0]
    assert exp["_id"] == "64b1f0c2e4b0a1a2b3c4d5e6"
    assert isinstance(exp["metadata"]["datetime"], float)  # epoch seconds


def test_db_load_conflict_aborts_before_writing(tmp_path, capsys):
    """Same _id with different content aborts the WHOLE load."""
    import json

    src_path = tmp_path / "src.sqlite"
    _, exp = _seed_storage(src_path)
    dump = tmp_path / "dump.jsonl"
    assert cli_main(["db", "dump", "--src", str(src_path), "--out", str(dump)]) == 0
    dst_path = tmp_path / "dst.sqlite"
    dst = create_storage({"type": "sqlite", "path": str(dst_path)})
    dst.create_experiment(
        {"_id": exp["_id"], "name": "OTHER", "version": 9, "metadata": {"user": "x"}}
    )
    rc = cli_main(["db", "load", "--src", str(dump), "--dst", str(dst_path)])
    assert rc == 1
    assert "NOTHING was loaded" in capsys.readouterr().err
    # The conflicting load wrote no trials.
    assert dst.fetch_trials(uid=exp["_id"]) == []


def test_db_load_raw_lines_require_collection(tmp_path, capsys):
    path = tmp_path / "raw.jsonl"
    path.write_text('{"name": "n", "version": 1}\n')
    rc = cli_main(["db", "load", "--src", str(path),
                   "--dst", str(tmp_path / "d.sqlite")])
    assert rc == 1
    assert "collection" in capsys.readouterr().err


def test_db_dump_refuses_missing_source(tmp_path, capsys):
    """A typo'd --src must not create an empty DB and truncate the backup."""
    out = tmp_path / "backup.jsonl"
    out.write_text("precious\n")
    rc = cli_main(["db", "dump", "--src", str(tmp_path / "typo.sqlite"),
                   "--out", str(out)])
    assert rc == 1
    assert "does not exist" in capsys.readouterr().err
    assert out.read_text() == "precious\n"  # prior backup untouched
    assert not (tmp_path / "typo.sqlite").exists()


def test_db_load_unique_index_collision_detected_in_plan(tmp_path, capsys):
    """Distinct _ids sharing an experiment's name/version/user must abort in
    the PLAN phase with the actionable message, not die mid-write."""
    src_path = tmp_path / "src.sqlite"
    _seed_storage(src_path)
    dump = tmp_path / "dump.jsonl"
    assert cli_main(["db", "dump", "--src", str(src_path), "--out", str(dump)]) == 0
    dst_path = tmp_path / "dst.sqlite"
    dst = create_storage({"type": "sqlite", "path": str(dst_path)})
    dst.create_experiment(
        {"_id": "OTHER-ID", "name": "dmp", "version": 1, "metadata": {"user": "u"}}
    )
    rc = cli_main(["db", "load", "--src", str(dump), "--dst", str(dst_path)])
    assert rc == 1
    err = capsys.readouterr().err
    assert "NOTHING was loaded" in err and "version" in err
    assert dst.fetch_trials(uid="OTHER-ID") == []


def test_db_load_concatenated_dumps_merge(tmp_path):
    """cat day1.jsonl day2.jsonl: repeated identical documents merge as
    'already present', they are not conflicts."""
    src_path = tmp_path / "src.sqlite"
    _seed_storage(src_path)
    dump = tmp_path / "dump.jsonl"
    assert cli_main(["db", "dump", "--src", str(src_path), "--out", str(dump)]) == 0
    doubled = tmp_path / "doubled.jsonl"
    doubled.write_text(dump.read_text() + dump.read_text())
    dst_path = tmp_path / "dst.sqlite"
    assert cli_main(["db", "load", "--src", str(doubled), "--dst", str(dst_path)]) == 0
    dst = create_storage({"type": "sqlite", "path": str(dst_path)})
    exp = dst.fetch_experiments({"name": "dmp"})[0]
    assert len(dst.fetch_trials(uid=exp["_id"])) == 3


def test_db_load_idless_raw_docs_dedup_by_content(tmp_path):
    """Raw JSONL documents without _id must not duplicate on re-load."""
    raw = tmp_path / "raw.jsonl"
    raw.write_text('{"experiment": "e1", "params": {"/x": 1.0}, "status": "new"}\n')
    dst_path = tmp_path / "dst.sqlite"
    for _ in range(2):
        assert cli_main(["db", "load", "--src", str(raw), "--dst", str(dst_path),
                         "--collection", "trials"]) == 0
    dst = create_storage({"type": "sqlite", "path": str(dst_path)})
    assert len(dst.fetch_trials(uid="e1")) == 1


def test_db_dump_load_preserves_wrapper_shaped_values(tmp_path):
    """Our own dump format is lossless: a legitimate document value shaped
    like a Mongo wrapper must NOT be rewritten on load."""
    src_path = tmp_path / "src.sqlite"
    st = create_storage({"type": "sqlite", "path": str(src_path)})
    st.create_experiment(
        {"name": "wrap", "version": 1,
         "metadata": {"user": "u", "odd": {"$date": 123}}}
    )
    dump = tmp_path / "dump.jsonl"
    assert cli_main(["db", "dump", "--src", str(src_path), "--out", str(dump)]) == 0
    dst_path = tmp_path / "dst.sqlite"
    assert cli_main(["db", "load", "--src", str(dump), "--dst", str(dst_path)]) == 0
    dst = create_storage({"type": "sqlite", "path": str(dst_path)})
    exp = dst.fetch_experiments({"name": "wrap"})[0]
    assert exp["metadata"]["odd"] == {"$date": 123}


def test_db_copy_refuses_missing_source(tmp_path, capsys):
    rc = cli_main(["db", "copy", "--src", str(tmp_path / "typo.pkl"),
                   "--dst", str(tmp_path / "d.sqlite")])
    assert rc == 1
    assert "does not exist" in capsys.readouterr().err
    assert not (tmp_path / "typo.pkl").exists()


def test_audit_clean_experiment(populated, capsys):
    tmp_path, db = populated
    rc = cli_main(["audit", "-n", "cmd-exp", *db])
    out = capsys.readouterr().out
    assert rc == 0
    assert "audit: OK" in out
    assert "4 completed" in out


def test_audit_reports_violations_and_exits_nonzero(populated, capsys):
    tmp_path, db = populated
    # Corrupt the store the way a dead worker would leave it: a reserved
    # trial whose heartbeat went stale far past the sweep threshold.
    storage = create_storage({"type": "pickled", "path": str(tmp_path / "db.pkl")})
    exp = storage.fetch_experiments({"name": "cmd-exp"})[0]
    from orion_tpu.core.trial import Trial

    storage.register_trial(
        Trial(
            experiment=exp["_id"], status="reserved", params={"/x": 3.25},
            start_time=1.0, heartbeat=1.0,
        )
    )
    rc = cli_main(["audit", "-n", "cmd-exp", *db])
    out = capsys.readouterr().out
    assert rc == 1
    assert "orphaned-reservation" in out


def test_audit_all_experiments(populated, capsys):
    tmp_path, db = populated
    rc = cli_main(["audit", "--all", *db])
    out = capsys.readouterr().out
    assert rc == 0
    assert "audit: OK" in out
