"""90th percentile, by nearest rank, of the milliseconds the training loop
waited in ``DeviceFeeder.next()`` at each step of the window (the
benchmark's own timer)."""

from bench import stats


def read(run):
    if not run["wait_s"]:
        return None
    return 1e3 * stats.nearest_rank(run["wait_s"], 0.9)
