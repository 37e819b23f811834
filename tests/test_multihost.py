"""Two-process `jax.distributed` validation: ShardedSearch
over a mesh spanning two OS processes (4 virtual CPU devices each, gloo
collectives) must complete with the single-process goldens, identically on
every rank. Proves the `make_mesh` multi-host claim (parallel/sharded.py)
with a real cross-process transport rather than a docstring."""

import json
import pathlib
import socket
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
SCRIPT = REPO / "scripts" / "multihost_sharded.py"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_ranks(extra_args=()):
    """Launch the SPMD script as two OS processes; return each rank's parsed
    MULTIHOST_RESULT. Kills both processes on any hang (a rendezvous failure
    or collective deadlock must not leak gloo processes + the coordinator
    port into the rest of the pytest session)."""
    port = _free_port()
    procs = [
        subprocess.Popen(
            [
                sys.executable,
                str(SCRIPT),
                "--num-processes",
                "2",
                "--process-id",
                str(i),
                "--coordinator",
                f"127.0.0.1:{port}",
                *extra_args,
            ],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            cwd=REPO,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=900)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    results = []
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"rank failed:\n{out[-3000:]}"
        lines = [
            ln for ln in out.splitlines() if ln.startswith("MULTIHOST_RESULT ")
        ]
        assert len(lines) == 1, out[-3000:]
        results.append(json.loads(lines[0].split(" ", 1)[1]))
    return results


@pytest.mark.slow
def test_two_process_sharded_search_golden():
    results = _run_ranks()
    for r in results:
        assert r["global_devices"] == 8
        assert r["local_devices"] == 4  # each process really owns only half
        assert (r["generated"], r["unique"]) == (8258, 1568)
        assert r["complete"]
        assert r["discoveries"] == ["abort agreement", "commit agreement"]
        assert sum(r["per_chip_unique"]) == 1568

    # Every rank observed the SAME global result (counts, witnesses, balance).
    a, b = results
    for key in ("generated", "unique", "max_depth", "per_chip_unique"):
        assert a[key] == b[key]


@pytest.mark.slow
def test_two_process_checkpoint_writes_once_and_resumes(tmp_path):
    """Cross-process checkpoint: every rank calls checkpoint() (collective
    gather) and, after the in-script barrier, every rank sees the single
    written file (rank 0 is the writer — engine contract, checkpoint()
    docstring); exactly ONE file appears; the suspended multi-process run
    resumes to golden; and the file restores + completes in a plain
    single-process engine."""
    ckpt = str(tmp_path / "mh_ckpt.npz")
    results = _run_ranks(("--checkpoint", ckpt))
    for r in results:
        # Post-barrier, the shared-filesystem existence check is
        # deterministic on both ranks.
        assert r["checkpoint_file_exists"] is True
        # The suspended-then-resumed multi-process run still lands on golden.
        assert (r["generated"], r["unique"]) == (8258, 1568)
        assert r["complete"]

    # Exactly one checkpoint file was produced (no per-rank duplicates).
    files = list(tmp_path.iterdir())
    assert [f.name for f in files] == ["mh_ckpt.npz"]

    # It restores in a plain single-process engine and completes to golden.
    from stateright_tpu.parallel import ShardedSearch, make_mesh
    from stateright_tpu.tensor.models import TensorTwoPhaseSys

    ss = ShardedSearch.load_checkpoint(
        TensorTwoPhaseSys(4), ckpt, mesh=make_mesh(8)
    )
    r = ss.run()
    assert (r.state_count, r.unique_state_count) == (8258, 1568)
    assert r.complete
