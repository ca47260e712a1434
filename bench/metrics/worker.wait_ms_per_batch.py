"""Milliseconds per batch the workers served in the window spent in their
long-poll for a first element: the program's ``worker.wait`` spans over
the workers' ``batches_served`` (``bench/program.py``)."""


def read(run):
    wait = (run.get("program") or {}).get("worker_wait_s")
    served = run["counters"].get("worker_batches_served", 0)
    if wait is None or served <= 0:
        return None
    return 1e3 * wait / served
