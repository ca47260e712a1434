"""Share, in percent, of the traced window in which the first device was
idle while the feeder's transfer thread was inside ``feed.fetch``: idle
time put down to the service (``bench/program.py``)."""


def read(run):
    idle = (run.get("program") or {}).get("idle_fetch_s")
    t = run["trace"]
    if idle is None or not t or t["window_s"] <= 0:
        return None
    return 100.0 * idle / t["window_s"]
