"""Milliseconds of host-to-device placement per step in the window (the
feeder's ``transfer_s`` over its steps)."""


def read(run):
    c = run["counters"]
    if c["feed_steps"] <= 0:
        return None
    return 1e3 * c["feed_transfer_s"] / c["feed_steps"]
