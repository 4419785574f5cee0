"""Device: share of the traced window in which no operation ran on
the device (1 - busy / window, busy = union of op intervals), in %."""


def read(rec):
    t = rec["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
