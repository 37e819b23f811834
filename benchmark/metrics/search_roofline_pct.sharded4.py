"""Share of the bytes roofline that the four-chip search program reaches,
in percent: the least bytes any implementation must move for the window's
checks (harness/roofline.py least_bytes, a bound for a sharded search as
for any other), over four chips' peak bandwidth, over the search program's
device busy time per chip (the reading averages it over the chips). A
bytes bound; the exchange between the chips is not in it.

This reader's cell checks through `spawn_tpu(chips=4)`. The harness takes
a check that raises for the check's answer, so a program whose `spawn_tpu`
has no `chips` option would print a result line for the cell, every check
an error, instead of failing; loading the cell's readers refuses such a
program at once (see PERF.md, section 7)."""

import inspect
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness.roofline import least_bytes  # noqa: E402

#: The chips whose memory the search's bytes spread over.
CHIPS = 4


def _require_chips_option() -> None:
    from stateright_tpu.checker.tpu import TpuChecker

    if "chips" not in inspect.signature(TpuChecker).parameters:
        raise RuntimeError("this program's spawn_tpu takes no `chips`: it "
                           "cannot run a four-chip cell")


_require_chips_option()


def read(r):
    if not r.checks or r.program_busy_s <= 0:
        return None
    nbytes = sum(least_bytes(c["generated"], c["unique"], r.lanes)
                 for c in r.checks)
    return 100.0 * nbytes / (CHIPS * r.peak_bytes_per_s) / r.program_busy_s
