"""Ephemeral data sharing (paper §3.5): sliding-window cache semantics and
end-to-end multi-job sharing on one deployment."""
import pytest

pytest.importorskip("hypothesis", reason="optional [test] dependency")
import threading

import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import SlidingWindowCache
from repro.data import Dataset


def counter_producer(n=10**9):
    return iter(range(n))


class TestSlidingWindowCache:
    def test_single_job_sees_sequence(self):
        c = SlidingWindowCache(counter_producer(), capacity=4)
        c.attach("j1")
        got = [c.read("j1")[0] for _ in range(10)]
        assert got == list(range(10))

    def test_two_equal_speed_jobs_share_compute(self):
        produced = []

        def prod():
            i = 0
            while True:
                produced.append(i)
                yield i
                i += 1

        c = SlidingWindowCache(prod(), capacity=8)
        c.attach("a")
        c.attach("b")
        for i in range(20):
            va, _ = c.read("a")
            vb, _ = c.read("b")
            assert va == vb == i
        # each batch computed ONCE despite two consumers (the k×C -> C saving)
        assert len(produced) == 20

    def test_slow_job_skips_evicted_batches(self):
        c = SlidingWindowCache(counter_producer(), capacity=4)
        c.attach("fast")
        c.attach("slow")
        for _ in range(10):
            c.read("fast")
        v, _ = c.read("slow")
        # slow job's pointer was clamped to the window tail: it skips evicted
        # batches instead of stalling the fast job (relaxed visitation, §3.5)
        assert v >= 10 - 4
        lo, hi = c.window_range()
        assert hi - lo <= 4

    def test_late_attach_reads_from_window(self):
        c = SlidingWindowCache(counter_producer(), capacity=4)
        c.attach("a")
        for _ in range(6):
            c.read("a")
        c.attach("late")
        v, _ = c.read("late")
        assert v >= 2  # only the live window is visible

    def test_detach_releases_job(self):
        c = SlidingWindowCache(counter_producer(), capacity=4)
        c.attach("a")
        c.attach("b")
        c.read("a")
        c.detach("b")
        assert c.num_jobs == 1

    @given(
        capacity=st.integers(min_value=1, max_value=16),
        reads=st.lists(st.sampled_from(["a", "b", "c"]), min_size=1, max_size=120),
    )
    @settings(max_examples=50, deadline=None)
    def test_property_monotone_no_duplicates_per_job(self, capacity, reads):
        """Each job's stream is strictly increasing (no duplicates, possible
        gaps == at-most-once within the shared window)."""
        c = SlidingWindowCache(counter_producer(), capacity=capacity)
        for j in ("a", "b", "c"):
            c.attach(j)
        seen = {"a": [], "b": [], "c": []}
        for j in reads:
            v, end = c.read(j)
            if not end:
                seen[j].append(v)
        assert any(seen.values())
        for j, vals in seen.items():
            assert vals == sorted(set(vals)), f"job {j} saw duplicates/regression"

    def test_thread_safety_under_concurrent_reads(self):
        c = SlidingWindowCache(counter_producer(), capacity=8)
        jobs = [f"j{i}" for i in range(4)]
        for j in jobs:
            c.attach(j)
        results = {j: [] for j in jobs}

        def run(j):
            for _ in range(200):
                v, end = c.read(j)
                if not end:
                    results[j].append(v)

        ts = [threading.Thread(target=run, args=(j,)) for j in jobs]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        for j in jobs:
            assert results[j] == sorted(set(results[j]))


class TestSharedServiceE2E:
    def test_two_jobs_share_one_deployment(self, service_factory):
        svc = service_factory(num_workers=2, cache_capacity=64)
        pipe = Dataset.range(40).map(lambda x: x * 2).batch(4)

        def consume(results, idx):
            # two jobs (one shared job_name would make the two consumers
            # split one job's data, and the faster one could take it all)
            dds = pipe.distribute(
                service=svc, processing_mode="off", sharing=True,
                job_name=f"hparam_sweep_{idx}",
            )
            results[idx] = [np.asarray(b).tolist() for b in dds]

        results = {}
        ts = [
            threading.Thread(target=consume, args=(results, i)) for i in range(2)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert set(results) == {0, 1}
        # both jobs read every batch from the shared caches: the window (64)
        # holds all of each worker's 10 batches, so neither job misses one
        for i in (0, 1):
            vals = [v for b in results[i] for v in np.ravel(b).tolist()]
            assert vals, f"job {i} starved"
            assert set(vals) == {2 * x for x in range(40)}

    def test_sharing_worker_stats_report_cache(self, service_factory):
        svc = service_factory(num_workers=1, cache_capacity=16)
        dds = Dataset.range(20).batch(2).distribute(
            service=svc, processing_mode="off", sharing=True, job_name="s"
        )
        _ = [b for b in dds]
        w = svc.orchestrator.live_workers[0]
        stats = w.rpc_stats()
        assert any("cache" in k for k in stats), stats
