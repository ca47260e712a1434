"""90th percentile, by nearest rank, of the milliseconds of the program's
own ``feed.next`` spans in the traced window: the training loop's wait in
``DeviceFeeder.next()``, timed by the program (``bench/program.py``)."""

from bench import stats


def read(run):
    spans = (run.get("program") or {}).get("feed_next_s")
    if not spans:
        return None
    return 1e3 * stats.nearest_rank(spans, 0.9)
