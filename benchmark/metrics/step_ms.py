"""Milliseconds of device time per search step: the device busy time
inside the search program's executions over the steps the window's checks
took (the program's telemetry counter)."""


def read(r):
    steps = sum(c["steps"] for c in r.checks)
    if not steps or r.program_busy_s <= 0:
        return None
    return 1e3 * r.program_busy_s / steps
