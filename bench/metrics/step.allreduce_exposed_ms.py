"""Milliseconds per step in which a collective ran on the first device with
no other operation beside it, over the traced window (``bench/trace.py``).
Read only where the cell spans more than one chip."""


def read(run):
    t = run["trace"]
    if not t or run["chips"] < 2 or run["steps"] <= 0:
        return None
    return 1e3 * t["collective_exposed_s"] / run["steps"]
