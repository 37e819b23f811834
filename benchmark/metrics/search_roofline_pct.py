"""Share of the bytes roofline that the search program reaches, in
percent: the least bytes any implementation must move for the window's
checks (harness/roofline.py least_bytes), over the chip's peak bandwidth,
over the device busy time of the search program. A bytes bound."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness.roofline import least_bytes  # noqa: E402


def read(r):
    if not r.checks or r.program_busy_s <= 0:
        return None
    nbytes = sum(least_bytes(c["generated"], c["unique"], r.lanes)
                 for c in r.checks)
    return 100.0 * nbytes / r.peak_bytes_per_s / r.program_busy_s
