"""Share, in percent, of the batches the client received in the window that
came through the shared-memory ring rather than inline over tcp."""


def read(run):
    c = run["counters"]
    if c["client_batches"] <= 0:
        return None
    return 100.0 * c["client_shm_batches"] / c["client_batches"]
