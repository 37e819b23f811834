"""Share of the traced window in which no operation ran on the device, in
percent: 1 - busy / window, busy being the union of the device's op
intervals (averaged over the chips)."""


def read(r):
    if r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
