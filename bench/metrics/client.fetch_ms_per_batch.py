"""Milliseconds the service client spent fetching each batch it received in
the window (its ``fetch_time`` over its ``batches``)."""


def read(run):
    c = run["counters"]
    if c["client_batches"] <= 0:
        return None
    return 1e3 * c["client_fetch_s"] / c["client_batches"]
