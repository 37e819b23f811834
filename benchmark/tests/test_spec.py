"""The harness finds configurations, mixes and per-layer metrics by the
names in BENCHMARK.json: adding one is adding files and entries."""

import json
import os
import shutil

import pytest

from harness import spec


def _copy_root(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns(".build", "__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    return root


def test_every_cell_of_the_benchmark_loads():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert any(m.name == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert all(callable(m.read) for m in cell.per_layer)


def test_a_new_config_mix_and_metric_are_found_from_files_alone(tmp_path):
    root = _copy_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "benchmark/configs/paxos-3.json").read_text())
    cfg["model"]["kwargs"] = {"client_count": 2}
    (root / "benchmark/configs/paxos-2.json").write_text(json.dumps(cfg))
    (root / "benchmark/traffic/shallow.json").write_text(
        json.dumps({"answer": "first", "finish_when": "ANY"}))
    (root / "benchmark/metrics/checks_n.py").write_text(
        "def read(r):\n    return len(r.checks)\n")
    bench["configs"].append({"name": "paxos-2", "source": "x",
                             "file": "benchmark/configs/paxos-2.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "paxos2.shallow", "config": "paxos-2",
                               "traffic": "shallow", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "checks_n", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "x", "moves": "verdict_s",
                               "workloads": ["paxos2.shallow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("paxos2.shallow", root=str(root))
    assert cell.config["model"]["kwargs"] == {"client_count": 2}
    assert cell.traffic["finish_when"] == "ANY"
    assert [m.name for m in cell.per_layer] == ["checks_n"]
    assert cell.per_layer[0].read(type("R", (), {"checks": [1, 2]})) == 2
    # A metric without "workloads" applies to every cell.
    assert {m.name for m in cell.end_to_end} == {"setup_s"}


def test_unknown_names_are_refused(tmp_path):
    with pytest.raises(spec.SpecError):
        spec.load_cell("no.such.cell")
    root = _copy_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"][0]["traffic"] = "missing"
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(spec.SpecError):
        spec.load_cell(bench["workloads"][0]["name"], root=str(root))
