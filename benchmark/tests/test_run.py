"""A whole run on the CPU at a tiny size (paxos-2 and 2pc-4, kept under
tests/configs/), driven through the harness's functions — run.py itself
refuses the CPU — with the timed path sound and then broken underneath:
each fault must turn `correct` false."""

import json
import os
import subprocess
import sys
import time

import pytest

from harness import runner, spec

HERE = os.path.dirname(os.path.abspath(__file__))


def _cell(config: str, mix: str) -> spec.Cell:
    with open(os.path.join(HERE, "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(spec.BENCH_DIR, "traffic", mix + ".json")) as f:
        traffic = json.load(f)
    e2e = [spec.Metric("setup_s", "s", "lower", "host_clock"),
           spec.Metric("states_per_s", "states/s", "higher", "host_clock"),
           spec.Metric("verdict_s", "s", "lower", "host_clock")]
    return spec.Cell(f"{config}.{mix}", 1, cfg, traffic, e2e, [])


def _run(config="paxos-2", mix="exhaustive"):
    return runner.run_cell(_cell(config, mix), 2_147_483_700, 1.0, False,
                           time.monotonic())


def _correct(r):
    assert r["correct"] == all(c["value"] <= c["limit"]
                               for c in r["compared"].values())
    return r["correct"]


@pytest.mark.parametrize("config,mix", [("paxos-2", "exhaustive"),
                                        ("paxos-2", "first-discovery"),
                                        ("2pc-4", "exhaustive")])
def test_sound_run_is_correct_and_its_line_has_the_keys(config, mix):
    r = _run(config, mix)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "compared"
    assert _correct(r) and r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "states_per_s", "verdict_s"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(r["device"])
    json.dumps(r)


def _patch_expand(monkeypatch, cls, fault):
    orig = cls.expand

    def expand(self, states):
        succs, valid = orig(self, states)
        return fault(states, succs, valid)

    monkeypatch.setattr(cls, "expand", expand)


def _unchanged(states, succs, valid):
    import jax.numpy as jnp

    return jnp.broadcast_to(states[:, None, :], succs.shape), valid


def _half_batch(states, succs, valid):
    import jax.numpy as jnp

    keep = jnp.arange(states.shape[0]) < states.shape[0] // 2
    return succs, valid & keep[:, None]


@pytest.mark.parametrize("mix", ["exhaustive", "first-discovery"])
@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["step_leaves_state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(monkeypatch, mix, fault):
    from stateright_tpu.tensor.paxos import TensorPaxos

    _patch_expand(monkeypatch, TensorPaxos, fault)
    assert not _correct(_run("paxos-2", mix))


@pytest.mark.parametrize("mix", ["exhaustive", "first-discovery"])
def test_altered_answer_is_not_correct(monkeypatch, mix):
    from stateright_tpu.checker.tpu import TpuChecker

    orig = TpuChecker.discoveries

    def discoveries(self):
        from stateright_tpu.core.path import Path

        out = orig(self)
        # The path's last state replaced by its first: a wrong answer.
        return {k: Path(p.into_pairs()[:-1] + [(p.states()[0], None)])
                for k, p in out.items()}

    monkeypatch.setattr(TpuChecker, "discoveries", discoveries)
    assert not _correct(_run("paxos-2", mix))


def test_altered_count_is_not_correct(monkeypatch):
    from stateright_tpu.checker.tpu import TpuChecker

    orig = TpuChecker.unique_state_count
    monkeypatch.setattr(TpuChecker, "unique_state_count",
                        lambda self: orig(self) + 1)
    assert not _correct(_run("2pc-4", "exhaustive"))


def test_run_py_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", "paxos3.exhaustive", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "no TPU found" in p.stderr


def test_run_py_fails_without_the_program(tmp_path):
    import shutil

    root = tmp_path / "only_benchmark"
    shutil.copytree(spec.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns(".build", "__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "paxos3.exhaustive", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
