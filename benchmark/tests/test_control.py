"""The control (tests/control.py) at each cell's own size: narrower
fingerprints lose states by collision, which each cell's numbers catch on
every seed — 32 bits in the exhaustive cells; in the first-discovery cell,
whose check visits 10,470 states, 21 bits (PERF.md)."""

import json
import os

import pytest

from control import control_numbers
from harness import spec

with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [11, 2_147_483_659, 4_000_000_007])
def test_control(workload, seed):
    cell = spec.load_cell(workload)
    bits, numbers = control_numbers(cell, seed)
    assert numbers["reference_golden_gap"][0] == 0
    if cell.traffic["answer"] == "exhaustive":
        assert bits == 32
        assert numbers["unique_gap"][0] > 0
        assert numbers["generated_gap"][0] > 0
    assert not all(v <= lim for v, lim in numbers.values())


def test_first_prefix_is_the_programs_exit_counts():
    """paxos-3 stops after expanding level 9, where 'value chosen' is first
    discovered: 18,109 generated and 10,470 unique in every chip run."""
    from harness import compare, reference

    cell = spec.load_cell("paxos3.first-discovery")
    ref = reference.bfs(cell.config["reference"])
    assert compare.first_prefix(ref, 8192) == (18109, 10470)
    assert compare.first_prefix(ref, 2000) is None  # level 9 spans batches
