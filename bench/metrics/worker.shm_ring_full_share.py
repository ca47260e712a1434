"""Share, in percent, of the batches the workers served in the window that
asked for the shared-memory ring and went inline because its slots were
all leased (``worker_shm_inline_total{reason=ring_full}`` over
``worker_batches_served``, ``bench/program.py``); nothing to read where
the workers do not count their inline answers."""


def read(run):
    c = run["counters"]
    served = c.get("worker_batches_served") or 0
    if c.get("worker_shm_inline_total") is None or served <= 0:
        return None
    return 100.0 * (c.get("worker_shm_inline_total{reason=ring_full}") or 0.0) / served
