"""Host seconds per check in the first-discovery mix: each check's wall time
(the benchmark's span around spawn, join and discoveries) less the device
busy time inside it, averaged over the window's checks."""


def read(r):
    if not r.checks:
        return None
    return sum(c["wall_s"] - c["busy_s"] for c in r.checks) / len(r.checks)
