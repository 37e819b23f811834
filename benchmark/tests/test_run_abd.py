"""A whole run of the ABD register on the CPU at 2 clients and 2 servers
(tests/configs/abd-2-2.json), driven through the harness's functions as
test_run.py drives paxos-2: sound, it reads correct, every discovery path
checked step by step by bfs_ref_abd.cpp; with a successor dropped, half
the batch left out or a path altered underneath, it does not. And the
reference itself against the host checker, before its golden is pinned."""

import pytest

from test_run import _correct, _half_batch, _patch_expand, _run


def _drop_first_action(states, succs, valid):
    """Each state loses the successor of its first envelope."""
    return succs, valid.at[:, 0].set(False)


def test_sound_run_is_correct():
    r = _run("abd-2-2", "exhaustive")
    assert _correct(r) and r["attempted"] >= 1 and r["failed"] == 0
    assert all(c["value"] == 0 for c in r["compared"].values())


@pytest.mark.parametrize("fault", [_drop_first_action, _half_batch],
                         ids=["dropped_successor", "half_batch"])
def test_broken_step_is_not_correct(monkeypatch, fault):
    from stateright_tpu.tensor.abd import TensorAbd

    _patch_expand(monkeypatch, TensorAbd, fault)
    assert not _correct(_run("abd-2-2", "exhaustive"))


def test_altered_path_is_not_correct(monkeypatch):
    """A discovery path whose last state is replaced by its first: the
    reference refuses the step into it."""
    from stateright_tpu.checker.tpu import TpuChecker
    from stateright_tpu.core.path import Path

    orig = TpuChecker.discoveries

    def discoveries(self):
        return {k: Path(p.into_pairs()[:-1] + [(p.states()[0], None)])
                for k, p in orig(self).items()}

    monkeypatch.setattr(TpuChecker, "discoveries", discoveries)
    r = _run("abd-2-2", "exhaustive")
    assert r["compared"]["path_invalid_steps"]["value"] > 0
    assert not _correct(r)


def test_reference_agrees_with_the_host_checker():
    """bfs_ref_abd against the host checker (`examples/abd.py`) before its
    numbers are pinned: the whole 2×2 space, and the 2×3 space's level
    sums through depths 9, 12 and 15 against the host's depth-bounded
    checks (9 rerun here; 12 and 15 as the host gave them, 8 s and about
    100 s)."""
    import json
    import os

    from harness import reference, spec
    from stateright_tpu.examples.abd import AbdModelCfg

    def ref_of(path):
        with open(os.path.join(spec.BENCH_DIR, path)) as f:
            return reference.bfs(json.load(f)["reference"])

    small = ref_of("tests/configs/abd-2-2.json")
    host = AbdModelCfg(2, 2).into_model().checker().spawn_bfs().join()
    assert (small.generated, small.unique, small.depth) == (
        host.state_count(), host.unique_state_count(), host.max_depth()) == (
        875, 544, 25)
    assert small.min_depth == {"value chosen": 12}

    full = ref_of("configs/abd-2.json")
    host9 = (AbdModelCfg(2, 3).into_model().checker().target_max_depth(9)
             .spawn_bfs().join())
    # A host check cut at depth d expands depths 1..d-1 and holds 1..d.
    assert full.through(8) == (host9.state_count(), host9.unique_state_count())
    assert [full.through(d - 1) for d in (9, 12, 15)] == [
        (2_435, 1_246), (22_739, 9_302), (132_052, 50_983)]
