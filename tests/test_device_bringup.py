"""Bring-up on the chip: the device check's phases (chip_smoke.py) at small
size on the CPU, and the rules that keep every path off a silent CPU
fallback — one process per chip, a compile cache placed from outside,
Pallas interpret mode only on the CPU, peaks only for listed device kinds,
and native builds keyed by host."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

import jax

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from bench import GOLDEN  # noqa: E402

PAXOS2 = GOLDEN[("paxos", 2)]


def _cpu_env():
    return {**os.environ, "JAX_PLATFORMS": "cpu"}


# -- chip_smoke.py phases, rehearsed at paxos-2 size ---------------------------


def test_chip_smoke_refuses_the_cpu():
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=_cpu_env(),
        cwd=REPO,
    )
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok": true' not in proc.stdout


def test_device_phase_requires_a_tpu():
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU found"):
        chip_smoke.require_tpu(1)


def test_main_path_phase_at_golden_parity():
    from stateright_tpu.tensor.paxos import TensorPaxos

    with chip_smoke.CompileClock() as clock:
        rec, _ = chip_smoke.check_main_path(
            TensorPaxos(client_count=2), 2048, 16, PAXOS2, clock
        )
    assert (rec["generated"], rec["unique"]) == PAXOS2
    assert 0 < rec["compile_s"] < rec["wall_s"]


def test_main_path_phase_rejects_wrong_counts():
    from stateright_tpu.tensor.paxos import TensorPaxos

    with chip_smoke.CompileClock() as clock:
        with pytest.raises(chip_smoke.SmokeFailure, match="golden"):
            chip_smoke.check_main_path(
                TensorPaxos(client_count=2), 2048, 16, (1, 1), clock
            )


def test_pallas_phase_refuses_the_interpreter():
    # Golden parity through the Pallas insert, but on the CPU the engine
    # traced the interpreter: the phase must not pass it as the kernel.
    from stateright_tpu.tensor.paxos import TensorPaxos

    with chip_smoke.CompileClock() as clock:
        rec, checker = chip_smoke.check_main_path(
            TensorPaxos(client_count=2), 2048, 16, PAXOS2, clock,
            insert_variant="pallas",
        )
    assert (rec["generated"], rec["unique"]) == PAXOS2
    with pytest.raises(chip_smoke.SmokeFailure, match="interpreter"):
        chip_smoke.assert_compiled_kernel(checker)


def test_sharded_phase_on_four_virtual_devices():
    from stateright_tpu.tensor.paxos import TensorPaxos

    with chip_smoke.CompileClock() as clock:
        rec = chip_smoke.check_sharded(
            TensorPaxos(client_count=2), 4, 512, 14, PAXOS2, clock
        )
    assert len(rec["per_chip_unique"]) == 4
    assert sum(rec["per_chip_unique"]) == PAXOS2[1]
    assert min(rec["per_chip_unique"]) > 0


# -- one process per chip ------------------------------------------------------


def test_importing_bench_leaves_jax_out():
    code = (
        "import json, sys; import bench, stateright_tpu; "
        "print(json.dumps('jax' in sys.modules))"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=REPO, check=True,
    ).stdout
    assert json.loads(out.strip().splitlines()[-1]) is False


class _Proc:
    def __init__(self, alive):
        self.alive = alive

    def poll(self):
        return None if self.alive else 0


@pytest.mark.parametrize(
    "env,procs,refused",
    [
        ({"JAX_PLATFORMS": "cpu"}, [_Proc(True)], False),
        ({"JAX_PLATFORMS": "tpu"}, [], False),
        ({"JAX_PLATFORMS": "tpu"}, [_Proc(False)], False),
        ({"JAX_PLATFORMS": "tpu"}, [_Proc(True)], True),
        ({}, [_Proc(True)], True),
    ],
)
def test_chip_replica_needs_a_free_chip(env, procs, refused):
    from stateright_tpu.service.remote import check_chip_free

    if refused:
        with pytest.raises(RuntimeError, match="chip"):
            check_chip_free(env, procs)
    else:
        check_chip_free(env, procs)


def test_chip_replica_refused_while_parent_holds_the_chip(monkeypatch):
    from stateright_tpu.service import remote

    monkeypatch.setattr(remote, "_parent_holds_chip", lambda: True)
    with pytest.raises(RuntimeError, match="holds the TPU"):
        remote.check_chip_free({"JAX_PLATFORMS": "tpu"})
    remote.check_chip_free({"JAX_PLATFORMS": "cpu"})


# -- compile cache -------------------------------------------------------------


def test_compile_cache_leaves_an_outside_placement_alone(
    monkeypatch, tmp_path
):
    from stateright_tpu.compile_cache import use_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_a_fixed_path(monkeypatch):
    from stateright_tpu.compile_cache import use_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    set_to = {}
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: set_to.__setitem__(k, v)
    )
    path = use_compile_cache()
    assert path == str(REPO / ".jax_cache_cpu")
    assert set_to == {"jax_compilation_cache_dir": path}


# -- Pallas interpret mode -----------------------------------------------------


@pytest.mark.parametrize(
    "backend,interpret", [("cpu", True), ("tpu", False), ("gpu", None)]
)
def test_pallas_interprets_only_on_the_cpu(monkeypatch, backend, interpret):
    from stateright_tpu.tensor.pallas_hashtable import default_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="no kernel for backend"):
            default_interpret()
    else:
        assert default_interpret() is interpret


# -- device peaks --------------------------------------------------------------


def test_calibration_turns_off_for_an_unlisted_device(monkeypatch, caplog):
    from stateright_tpu.obs import calib
    from stateright_tpu.tensor import costmodel

    monkeypatch.delenv(calib.ENV_DEVICE, raising=False)
    monkeypatch.delenv(calib.ENV_ENABLE, raising=False)
    assert calib.calib_enabled()
    assert calib.default_device_kind() == costmodel.CPU1.name
    monkeypatch.setattr(costmodel, "BY_DEVICE_KIND", {})
    with pytest.raises(ValueError, match="no cost-model peaks"):
        calib.default_device_kind()
    with caplog.at_level("WARNING"):
        assert not calib.calib_enabled()
    assert "calibration off" in caplog.text


# -- native builds -------------------------------------------------------------


def test_native_artifacts_are_keyed_by_host(monkeypatch):
    from stateright_tpu import _native

    here = _native.artifact_path("baseline_bfs", exe=True)
    assert here == _native.artifact_path("baseline_bfs", exe=True)
    assert here != _native.artifact_path("baseline_bfs", exe=False)
    monkeypatch.setattr(_native, "_host_id", lambda: b"another host")
    assert _native.artifact_path("baseline_bfs", exe=True) != here
