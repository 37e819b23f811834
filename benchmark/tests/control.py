"""The control of `correct`: the reference put in the program's place at
a precision below the program's — dedup by fingerprints narrower than its
64 bits, salted by the seed — read through the same comparison as a run.
The width is 32 bits, the nearest below, or narrower where a check visits
too few states for 32 bits to collide (`control_bits`): it has to come
out not correct (PERF.md gives the readings).

    python3 benchmark/tests/control.py --workload 2pc8.exhaustive --seeds 1 2 3
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(1, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from harness import compare, reference, spec  # noqa: E402
from harness.window import Check  # noqa: E402

CONTROL_FP_BITS = 32


def control_bits(unique: int) -> int:
    """32, or the widest width at which `unique` states expect 16 or more
    collisions (unique^2 / 2^(bits+1) >= 16)."""
    return min(CONTROL_FP_BITS, (unique * unique // 32).bit_length() - 1)


def control_numbers(cell, seed: int) -> tuple:
    """(fingerprint bits, {name: (value, limit)}) of one check answered by
    the control."""
    ref_cfg = cell.config["reference"]
    ref = reference.bfs(ref_cfg)
    exhaustive = cell.traffic["answer"] == "exhaustive"
    prefix = None if exhaustive else compare.first_prefix(
        ref, cell.config["spawn"]["batch_size"])
    bits = control_bits(prefix[1] if prefix else ref.unique)
    low = reference.bfs(ref_cfg, fp_bits=bits, salt=seed)
    check = Check(0.0, 1.0, generated=low.generated, unique=low.unique,
                  depth=low.depth, discovered=low.discovered)
    if not exhaustive and low.min_depth:
        first = min(low.min_depth.values())
        check.discovered = {k for k, d in low.min_depth.items() if d == first}
        check.generated, check.unique = low.through(first)
    numbers, _ = compare.compare(cell.config, cell.traffic, [check], ref, [])
    return bits, numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    for seed in args.seeds:
        bits, numbers = control_numbers(cell, seed)
        correct = all(v <= lim for v, lim in numbers.values())
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "fp_bits": bits, "correct": correct,
                          "compared": {k: v for k, (v, _) in numbers.items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
