#!/usr/bin/env python3
"""The benchmark: one run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs on the chips of the machine it is started on and on nothing else:
without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result. The last line of stdout is the result's JSON; the last
lines of stderr are the numbers that decided `correct`, each beside its
limit. See PERF.md for what each metric means."""

import time

_T0 = time.monotonic()  # set-up (setup_s) is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(1, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import runner, spec  # noqa: E402


class NoChip(Exception):
    pass


def require_tpu(n_chips: int) -> None:
    """The cell's chips must be TPUs that JAX sees (no CPU fallback)."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no backend: {e}") from None
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found: JAX sees {len(devices)} "
                     f"{devices[0].platform} device(s)")
    if len(devices) < n_chips:
        raise NoChip(f"the cell needs {n_chips} TPUs; JAX sees {len(devices)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    try:
        require_tpu(cell.chips)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    result = runner.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                             _T0)
    for name, c in result["compared"].items():
        runner.log(f"compared {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
