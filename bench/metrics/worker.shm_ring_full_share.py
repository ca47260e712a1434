"""Share, in percent, of the batches the workers served in the window that
asked for the shared-memory ring and went inline because its slots were
all leased (``worker_shm_inline_total{reason=ring_full}`` over
``batches_served``, ``bench/program.py``)."""


def read(run):
    c = run["counters"]
    full, served = c.get("worker_shm_ring_full"), c.get("worker_batches_served", 0)
    if full is None or served <= 0:
        return None
    return 100.0 * full / served
