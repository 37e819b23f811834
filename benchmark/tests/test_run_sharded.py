"""A whole run of a four-chip cell on the CPU at a tiny size (2pc-4 on four
virtual devices, tests/configs/2pc-4-sharded4.json), driven through the
harness's functions as test_run.py drives the one-chip cells: sound, it
reads correct; with one of test_run.py's planted faults underneath, it does
not. Each run is a process of its own, started with four host devices
(this directory's conftest sets no device count)."""

import json
import os
import subprocess
import sys

import pytest

from harness import spec

HERE = os.path.dirname(os.path.abspath(__file__))

RUN = """
import json, sys, time, types
sys.path[:0] = {paths!r}
from harness import runner
import test_run
from stateright_tpu.tensor.models import TensorTwoPhaseSys
if {fault!r}:
    test_run._patch_expand(types.SimpleNamespace(setattr=setattr),
                           TensorTwoPhaseSys, getattr(test_run, {fault!r}))
cell = test_run._cell("2pc-4-sharded4", "exhaustive")
cell.chips = 4
r = runner.run_cell(cell, 2_147_483_700, 1.0, False, time.monotonic())
print(json.dumps(r))
"""


def _run(fault: str = "") -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = RUN.format(paths=[spec.BENCH_DIR, HERE, spec.ROOT], fault=fault)
    p = subprocess.run([sys.executable, "-c", code], cwd=spec.ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _correct(r):
    assert r["correct"] == all(c["value"] <= c["limit"]
                               for c in r["compared"].values())
    return r["correct"]


@pytest.mark.parametrize("fault", ["", "_half_batch"],
                         ids=["sound", "half_batch"])
def test_four_chip_run_is_correct_only_when_sound(fault):
    r = _run(fault)
    assert r["device"]["count"] == 4
    if fault:
        assert not _correct(r)
    else:
        assert _correct(r) and r["attempted"] >= 1 and r["failed"] == 0
        assert r["compared"]["generated_gap"]["value"] == 0
