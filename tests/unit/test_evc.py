"""EVC tests: conflict detection, adapters, branching, tree trial fetch.

Parity model: reference tests/unittests/core/evc/ (test_conflicts.py,
test_adapters.py, test_experiment_tree.py, test_tree.py) and
branching functional tests.
"""

import pytest

from orion_tpu.core.experiment import build_experiment
from orion_tpu.core.trial import Result, Trial
from orion_tpu.evc.adapters import (
    CodeChange,
    CompositeAdapter,
    DimensionAddition,
    DimensionDeletion,
    DimensionPriorChange,
    DimensionRenaming,
    build_adapter,
)
from orion_tpu.evc.conflicts import (
    AlgorithmConflict,
    ChangedDimensionConflict,
    ExperimentNameConflict,
    MissingDimensionConflict,
    NewDimensionConflict,
    detect_conflicts,
)
from orion_tpu.evc.tree import DepthFirstTraversal, PreOrderTraversal, TreeNode
from orion_tpu.storage import create_storage


def make_trials(params_list):
    return [Trial(experiment="p", params=p) for p in params_list]


# --- tree -------------------------------------------------------------------


def test_tree_structure_and_traversals():
    root = TreeNode("a")
    b = TreeNode("b", parent=root)
    c = TreeNode("c", parent=root)
    d = TreeNode("d", parent=b)
    assert root.children == [b, c]
    assert d.root is root
    assert [n.item for n in PreOrderTraversal(root)] == ["a", "b", "d", "c"]
    assert [n.item for n in DepthFirstTraversal(root)] == ["d", "b", "c", "a"]
    assert root.flattened == ["a", "b", "d", "c"]
    assert {n.item for n in root.leafs} == {"d", "c"}
    c.set_parent(b)
    assert root.children == [b]
    assert c.parent is b


# --- adapters ---------------------------------------------------------------


def test_dimension_addition_roundtrip():
    adapter = DimensionAddition("/y", default_value=3)
    fwd = adapter.forward(make_trials([{"/x": 1.0}]))
    assert fwd[0].params == {"/x": 1.0, "/y": 3}
    back = adapter.backward(fwd)
    assert back[0].params == {"/x": 1.0}
    # Child trials off the default are NOT portable to the parent.
    assert adapter.backward(make_trials([{"/x": 1.0, "/y": 9}])) == []


def test_dimension_deletion_is_inverse():
    adapter = DimensionDeletion("/y", default_value=3)
    fwd = adapter.forward(make_trials([{"/x": 1.0, "/y": 3}, {"/x": 2.0, "/y": 5}]))
    assert len(fwd) == 1 and fwd[0].params == {"/x": 1.0}
    back = adapter.backward(make_trials([{"/x": 1.0}]))
    assert back[0].params == {"/x": 1.0, "/y": 3}


def test_prior_change_filters_support():
    adapter = DimensionPriorChange("/x", "uniform(0, 10)", "uniform(0, 5)")
    fwd = adapter.forward(make_trials([{"/x": 3.0}, {"/x": 8.0}]))
    assert [t.params["/x"] for t in fwd] == [3.0]
    back = adapter.backward(make_trials([{"/x": 4.0}]))
    assert len(back) == 1


def test_renaming_roundtrip():
    adapter = DimensionRenaming("/x", "/z")
    fwd = adapter.forward(make_trials([{"/x": 1.0}]))
    assert fwd[0].params == {"/z": 1.0}
    back = adapter.backward(fwd)
    assert back[0].params == {"/x": 1.0}


def test_change_type_break_drops():
    assert CodeChange("break").forward(make_trials([{"/x": 1}])) == []
    assert len(CodeChange("noeffect").forward(make_trials([{"/x": 1}]))) == 1
    with pytest.raises(ValueError):
        CodeChange("wat")


def test_composite_serialization_roundtrip():
    comp = CompositeAdapter(
        DimensionRenaming("/a", "/b"), DimensionAddition("/c", default_value=1)
    )
    rebuilt = build_adapter(comp.to_dict())
    fwd = rebuilt.forward(make_trials([{"/a": 2.0}]))
    assert fwd[0].params == {"/b": 2.0, "/c": 1}
    assert rebuilt.backward(fwd)[0].params == {"/a": 2.0}


# --- conflict detection ------------------------------------------------------


def old_config(**over):
    base = {
        "name": "exp",
        "version": 1,
        "priors": {"/x": "uniform(0, 10)"},
        "algorithms": "random",
        "metadata": {},
    }
    base.update(over)
    return base


def test_detect_no_conflicts_on_same_config():
    conflicts = detect_conflicts(old_config(), {"priors": {"/x": "uniform(0, 10)"}})
    assert conflicts.conflicts == []


def test_detect_whitespace_insensitive():
    conflicts = detect_conflicts(old_config(), {"priors": {"/x": "uniform(0,10)"}})
    assert conflicts.conflicts == []


def test_detect_new_changed_missing():
    conflicts = detect_conflicts(
        old_config(priors={"/x": "uniform(0, 10)", "/y": "uniform(0, 1)"}),
        {"priors": {"/x": "uniform(0, 5)", "/z": "+normal(0, 1)"}},
    )
    types = {type(c) for c in conflicts.conflicts}
    assert types == {
        NewDimensionConflict,
        ChangedDimensionConflict,
        MissingDimensionConflict,
        ExperimentNameConflict,
    }


def test_rename_marker_detection():
    conflicts = detect_conflicts(
        old_config(), {"priors": {"/x": ">/y", "/y": "uniform(0, 10)"}}
    )
    missing = conflicts.get([MissingDimensionConflict])
    assert len(missing) == 1 and missing[0].rename_to == "/y"
    # No NewDimensionConflict for /y: it is the rename target.
    assert conflicts.get([NewDimensionConflict]) == []


def test_algorithm_conflict():
    conflicts = detect_conflicts(
        old_config(), {"priors": {"/x": "uniform(0, 10)"}, "algorithms": "tpe"}
    )
    assert len(conflicts.get([AlgorithmConflict])) == 1


def test_auto_resolution_produces_adapters_and_bump():
    conflicts = detect_conflicts(
        old_config(),
        {"priors": {"/x": "uniform(0, 10)", "/y": "+uniform(0, 1, default_value=0.5)"}},
    )
    conflicts.try_resolve_all()
    assert conflicts.are_resolved
    adapters = conflicts.get_adapters()
    assert len(adapters) == 1
    assert isinstance(adapters[0], DimensionAddition)
    assert adapters[0].default_value == 0.5
    name = conflicts.get([ExperimentNameConflict])[0]
    assert name.resolution.info == {"name": "exp", "version": 2}


# --- end-to-end branching ----------------------------------------------------


@pytest.fixture
def storage():
    return create_storage({"type": "memory"})


def run_trials(exp, values):
    from orion_tpu.core.producer import Producer

    producer = Producer(exp)
    for v in values:
        producer.update()
        producer.produce(1)
        trial = exp.reserve_trial()
        exp.update_completed_trial(trial, [Result("o", "objective", v)])


def test_build_experiment_branches_on_prior_change(storage):
    e1 = build_experiment(
        storage, "b", priors={"/x": "uniform(0, 10)"}, algorithms="random"
    ).instantiate()
    run_trials(e1, [1.0, 2.0])

    e2 = build_experiment(
        storage, "b", priors={"/x": "uniform(0, 5)"}, algorithms="random"
    )
    assert e2.version == 2
    assert e2.refers["parent_id"] == e1.id
    assert e2.refers["root_id"] == e1.id
    assert e2.priors == {"/x": "uniform(0, 5)"}

    # Tree fetch: parent trials inside the narrowed prior flow forward.
    in_range = [
        t for t in storage.fetch_trials(uid=e1.id) if t.params["/x"] <= 5
    ]
    tree_trials = e2.fetch_trials(with_evc_tree=True)
    assert len(tree_trials) == len(in_range)


def test_branch_adds_dimension_with_default(storage):
    e1 = build_experiment(storage, "c", priors={"/x": "uniform(0, 10)"}).instantiate()
    run_trials(e1, [1.0])
    e2 = build_experiment(
        storage,
        "c",
        priors={"/x": "uniform(0, 10)", "/y": "+uniform(0, 1, default_value=0.3)"},
    )
    assert e2.version == 2
    tree_trials = e2.fetch_trials(with_evc_tree=True)
    assert len(tree_trials) == 1
    assert tree_trials[0].params["/y"] == 0.3
    # Child's own space has both dims, markers stripped.
    assert set(e2.space.keys()) == {"/x", "/y"}


def test_branch_rename_dimension(storage):
    e1 = build_experiment(storage, "d", priors={"/x": "uniform(0, 10)"}).instantiate()
    run_trials(e1, [4.0])
    e2 = build_experiment(
        storage, "d", priors={"/x": ">/z", "/z": "uniform(0, 10)"}
    )
    assert e2.version == 2
    tree_trials = e2.fetch_trials(with_evc_tree=True)
    assert len(tree_trials) == 1
    assert "/z" in tree_trials[0].params and "/x" not in tree_trials[0].params


def test_branch_children_backward(storage):
    """Parent sees child trials adapted backward."""
    e1 = build_experiment(storage, "e", priors={"/x": "uniform(0, 10)"}).instantiate()
    run_trials(e1, [1.0])
    e2 = build_experiment(storage, "e", priors={"/x": "uniform(0, 5)"}).instantiate()
    run_trials(e2, [2.0])
    # Reload v1 explicitly.
    e1b = build_experiment(storage, "e", version=1)
    tree_trials = e1b.fetch_trials(with_evc_tree=True)
    assert len(tree_trials) == 2  # own + child's (inside old support)


def test_concurrent_branching_bumps_version(storage):
    e1 = build_experiment(storage, "f", priors={"/x": "uniform(0, 10)"})
    a = build_experiment(storage, "f", priors={"/x": "uniform(0, 6)"})
    b = build_experiment(storage, "f", priors={"/x": "uniform(0, 7)"})
    assert {a.version, b.version} == {2, 3}


# --- regression tests from review findings ----------------------------------


def test_rename_only_branch_keeps_dimension(storage):
    e1 = build_experiment(storage, "ro", priors={"/x": "uniform(0, 10)"}).instantiate()
    run_trials(e1, [2.0])
    e2 = build_experiment(storage, "ro", priors={"/x": ">/z"})
    assert e2.version == 2
    assert e2.priors == {"/z": "uniform(0, 10)"}  # old prior under new name
    assert e2.space is not None
    tree = e2.fetch_trials(with_evc_tree=True)
    assert tree and "/z" in tree[0].params


def test_algorithm_change_branches(storage):
    e1 = build_experiment(storage, "ac", priors={"/x": "uniform(0, 1)"})
    assert e1.algo_config == "random"
    # Resume WITHOUT algorithms: no branch.
    e2 = build_experiment(storage, "ac", priors={"/x": "uniform(0, 1)"})
    assert e2.version == 1
    # Resume with an explicit different algorithm: branch.
    e3 = build_experiment(
        storage, "ac", priors={"/x": "uniform(0, 1)"},
        algorithms={"tpe": {"n_init": 4}},
    )
    assert e3.version == 2
    assert e3.algo_config == {"tpe": {"n_init": 4}}


def test_branched_child_warm_starts_from_parent(storage):
    """Producer must feed adapted ancestor trials to the child's algorithm."""
    from orion_tpu.core.producer import Producer
    from tests.unit.test_worker import DumbAlgo  # registered scriptable fake

    e1 = build_experiment(
        storage, "ws", priors={"/x": "uniform(0, 10)"}, algorithms="random"
    ).instantiate()
    run_trials(e1, [1.0, 2.0, 3.0])
    e2 = build_experiment(
        storage, "ws", priors={"/x": "uniform(0, 5)"}, algorithms={"dumbalgo": {}}
    ).instantiate()
    assert e2.version == 2
    producer = Producer(e2)
    producer.update()
    # Parent trials within the narrowed prior flow in as observations.
    parent_xs = [
        t.params["/x"] for t in storage.fetch_trials(uid=e1.id) if t.params["/x"] <= 5
    ]
    assert len(e2.algorithm.observed_params) == len(parent_xs)


def test_new_dimension_without_default_refuses_branch(storage):
    e1 = build_experiment(storage, "nd", priors={"/x": "uniform(0, 10)"}).instantiate()
    run_trials(e1, [1.0])
    with pytest.raises(ValueError, match="default_value"):
        build_experiment(
            storage, "nd",
            priors={"/x": "uniform(0, 10)", "/y": "+uniform(0, 1)"},
        )
    # Nothing persisted for the failed branch.
    assert len(storage.fetch_experiments({"name": "nd"})) == 1


def test_tree_fetcher_incremental_reads_and_adaptation(tmp_path):
    """Producer rounds must not re-fetch/re-adapt the whole family each time:
    unchanged rounds do one signature read per family node and ZERO bulk
    reads / adapter calls (round-1 verdict #7)."""
    from orion_tpu.core.experiment import build_experiment
    from orion_tpu.core.trial import Result, Trial
    from orion_tpu.evc.adapters import DimensionAddition
    from orion_tpu.evc.experiment import TreeTrialsFetcher
    from orion_tpu.storage import create_storage

    storage = create_storage({"type": "memory"})
    parent = build_experiment(
        storage, "tree", priors={"/x": "uniform(0, 1)"}, version=1
    )
    for i in range(5):
        t = Trial(experiment=parent.id, params={"/x": i / 10},
                  results=[Result("o", "objective", float(i))], status="completed")
        storage.register_trial(t)
    child_cfg = {
        "name": "tree", "version": 2, "priors": {"/x": "uniform(0, 1)", "/y": "uniform(0, 1)"},
        "refers": {"root_id": parent.id, "parent_id": parent.id,
                   "adapter": {"of_type": "compositeadapter", "adapters": [
                       {"of_type": "dimensionaddition", "name": "/y", "default_value": 0.5}]}},
        "_id": "child-id",
    }
    storage.create_experiment(child_cfg)
    from orion_tpu.core.experiment import Experiment
    child = Experiment(storage, storage.fetch_experiments({"version": 2})[0])

    fetcher = TreeTrialsFetcher(child)

    reads = {"n": 0}
    adaptations = {"n": 0}
    orig_read = storage.db.read
    orig_forward = DimensionAddition.forward

    def counting_read(collection, query=None, projection=None, **kwargs):
        if collection == "trials" and projection is None:
            reads["n"] += 1
        return orig_read(collection, query=query, projection=projection, **kwargs)

    def counting_forward(self, trials):
        adaptations["n"] += len(trials)
        return orig_forward(self, trials)

    storage.db.read = counting_read
    DimensionAddition.forward = counting_forward
    try:
        first = fetcher.fetch()
        assert len(first) == 5
        assert all("/y" in t.params for t in first)
        first_adaptations = adaptations["n"]
        assert first_adaptations == 5

        # 10 unchanged rounds: no bulk reads beyond the own-collection fetch,
        # no re-adaptation at all.
        reads_before = reads["n"]
        for _ in range(10):
            out = fetcher.fetch()
            assert len(out) == 5
        assert adaptations["n"] == first_adaptations
        # own-experiment fetch is 1 unprojected read per round; family bulk
        # reads would add more.
        assert reads["n"] - reads_before == 10

        # A new parent trial is picked up AND only IT is adapted.
        t = Trial(experiment=parent.id, params={"/x": 0.9},
                  results=[Result("o", "objective", 9.0)], status="completed")
        storage.register_trial(t)
        out = fetcher.fetch()
        assert len(out) == 6
        assert adaptations["n"] == first_adaptations + 1

        # A status change re-adapts exactly that one trial.
        storage.db.write("trials", {"status": "broken"},
                         query={"_id": t.id})
        out = fetcher.fetch()
        assert adaptations["n"] == first_adaptations + 2
    finally:
        storage.db.read = orig_read
        DimensionAddition.forward = orig_forward


def test_tree_fetcher_picks_up_midrun_branches(tmp_path):
    """A branch created AFTER the fetcher was built must become visible
    (another user branching the tree while a worker hunts)."""
    from orion_tpu.core.experiment import Experiment, build_experiment
    from orion_tpu.core.trial import Result, Trial
    from orion_tpu.evc.experiment import TreeTrialsFetcher
    from orion_tpu.storage import create_storage

    storage = create_storage({"type": "memory"})
    parent = build_experiment(storage, "mid", priors={"/x": "uniform(0, 1)"})
    fetcher = TreeTrialsFetcher(parent)
    assert fetcher.fetch() == []

    child_cfg = {
        "name": "mid", "version": 2, "priors": {"/x": "uniform(0, 1)"},
        "refers": {"root_id": parent.id, "parent_id": parent.id,
                   "adapter": {"of_type": "compositeadapter", "adapters": []}},
        "_id": "mid-child",
    }
    storage.create_experiment(child_cfg)
    t = Trial(experiment="mid-child", params={"/x": 0.4},
              results=[Result("o", "objective", 1.0)], status="completed")
    storage.register_trial(t)

    out = fetcher.fetch()
    assert [x.params["/x"] for x in out] == [0.4]


def test_branching_prompt_scripted_session(capsys):
    """The interactive prompt (reference branching_prompt.py) resolved via a
    scripted session: status shows pending conflicts, add/name resolve them,
    commit exits with everything resolved."""
    from orion_tpu.evc.branching_prompt import BranchingPrompt
    from orion_tpu.evc.builder import ExperimentBranchBuilder

    conflicts = detect_conflicts(
        old_config(),
        {"priors": {"/x": "uniform(0, 10)", "/y": "uniform(0, 5)"}},
    )
    builder = ExperimentBranchBuilder(conflicts, manual_resolution=True)
    prompt = BranchingPrompt(builder)
    prompt.cmdqueue = [
        "status",
        "add /y 2.5",
        "name exp2",
        "status",
        "commit",
    ]
    prompt.cmdloop(intro="")
    out = capsys.readouterr().out
    assert "PENDING" in out  # first status: unresolved
    assert conflicts.are_resolved
    resolved_names = {type(c).__name__ for c in conflicts.conflicts}
    assert "NewDimensionConflict" in resolved_names


def test_branching_prompt_bad_input_keeps_session(capsys):
    """A resolution error must be reported, not crash the session."""
    from orion_tpu.evc.branching_prompt import BranchingPrompt
    from orion_tpu.evc.builder import ExperimentBranchBuilder

    conflicts = detect_conflicts(
        old_config(), {"priors": {"/x": "uniform(0, 10)", "/y": "uniform(0, 5)"}}
    )
    builder = ExperimentBranchBuilder(conflicts, manual_resolution=True)
    prompt = BranchingPrompt(builder)
    # "add /y" with no default hits the ValueError path (the new dimension
    # has no default to backfill parent trials with); the session must
    # report it and stay alive for the corrected commands.
    prompt.cmdqueue = ["add /y", "add /y 1.0", "name exp2", "commit"]
    prompt.cmdloop(intro="")
    out = capsys.readouterr().out
    assert "cannot resolve" in out
    assert conflicts.are_resolved


def test_branching_prompt_per_command_completion():
    """Tab completion offers only what each command can act on (reference
    ships complete_* per command): `add` sees new dims, `remove`/`rename`
    see missing dims, the change classifiers see the three change types."""
    from orion_tpu.evc.branching_prompt import BranchingPrompt
    from orion_tpu.evc.builder import ExperimentBranchBuilder

    conflicts = detect_conflicts(
        {**old_config(), "priors": {"/x": "uniform(0, 10)", "/old": "uniform(0, 1)"}},
        {"priors": {"/x": "uniform(0, 10)", "/y": "uniform(0, 5)"}},
    )
    builder = ExperimentBranchBuilder(conflicts, manual_resolution=True)
    prompt = BranchingPrompt(builder)
    assert prompt.complete_add("/", "add /", 4, 5) == ["/y"]
    assert prompt.complete_add("/z", "add /z", 4, 6) == []
    assert prompt.complete_remove("/", "remove /", 7, 8) == ["/old"]
    # rename completes old (missing) name first, then the new name.
    assert prompt.complete_rename("/", "rename /", 7, 8) == ["/old"]
    assert prompt.complete_rename("/", "rename /old /", 12, 13) == ["/y"]
    assert prompt.complete_code("un", "code un", 5, 7) == ["unsure"]
    assert prompt.complete_commandline("", "commandline ", 12, 12) == [
        "noeffect", "unsure", "break"
    ]
    # Resolved conflicts drop out of the candidates.
    prompt.do_add("/y 2.5")
    assert prompt.complete_add("/", "add /", 4, 5) == []


def test_readonly_view_fetches_evc_tree(storage):
    """Regression: the EVC tree fetch must ride WHITELISTED read-only
    storage ops (read_trial_docs), not storage.db — a dashboard holding an
    ExperimentView over a branched experiment used to get AttributeError
    from the read-only proxy on exactly the call with_evc_tree exists for."""
    from orion_tpu.core.experiment import ExperimentView

    e1 = build_experiment(
        storage, "ro", priors={"/x": "uniform(0, 10)"}, algorithms="random"
    ).instantiate()
    run_trials(e1, [1.0, 2.0])
    e2 = build_experiment(
        storage, "ro", priors={"/x": "uniform(0, 5)"}, algorithms="random"
    )
    assert e2.version == 2

    view = ExperimentView(e2)
    tree_trials = view.fetch_trials(with_evc_tree=True)
    in_range = [
        t for t in storage.fetch_trials(uid=e1.id) if t.params["/x"] <= 5
    ]
    assert len(tree_trials) == len(in_range)
    # The view stays read-only: raw db access is still refused.
    with pytest.raises(AttributeError):
        view.storage.db
