"""span_breakdown.py with the models' own named scopes added to the step's:
`deliver`, `server` and `emit` inside `expand` (TensorPaxos, TensorAbd) and
`linearizable` inside `properties` (the register mask both share). Each op
counts toward the innermost scope on its path, so the device ms per step
per scope still add up to `step_ms`. A measurement for PERF.md on the chip.

    python3 scripts/span_breakdown_scopes.py --workload abd2.exhaustive --out <dir>
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests"))

import span_breakdown  # noqa: E402

span_breakdown.SCOPES = span_breakdown.SCOPES + (
    "deliver", "server", "emit", "linearizable")

if __name__ == "__main__":
    sys.exit(span_breakdown.main())
