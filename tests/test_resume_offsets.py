"""resume_offsets=True — the paper's sketched exactly-once upgrade (§3.4):
the dispatcher logs shard distribution, workers checkpoint per-shard
offsets, and a failed worker's shard is RE-QUEUED at the last offset
instead of being dropped.

Practical guarantee: NO LOSS; duplicates bounded by the checkpoint window
(elements yielded after the last offset checkpoint are re-produced by the
replacement worker) — at-least-once within the window, exactly-once at
window granularity."""
import time

import numpy as np

from repro.core import ShardingPolicy, VisitationGuarantee, guarantee_for
from repro.core.sharding import ShardManager
from repro.core.worker import _DynamicRunner
from repro.data import Dataset


class TestShardManagerResume:
    def test_failed_shard_requeued_at_offset(self):
        g = Dataset.range(100).graph
        mgr = ShardManager(
            g, policy=ShardingPolicy.DYNAMIC, num_workers_hint=4,
            overpartition=1, resume_offsets=True,
        )
        sid, shard, off = mgr.next_shard("A")
        assert off == 0
        mgr.checkpoint_offset(sid, "A", 17)
        lost = mgr.worker_failed("A")
        assert lost == [sid]
        # the shard comes back, starting at the checkpointed offset
        seen = []
        while True:
            nxt = mgr.next_shard("B")
            if nxt is None:
                break
            s2, sh2, o2 = nxt
            if s2 == sid:
                assert o2 == 17
            seen.append(s2)
            mgr.complete_shard(s2, "B")
        assert sid in seen
        assert mgr.done()

    def test_no_loss_with_resume(self):
        """Drain with a mid-stream failure: every element delivered at
        least once; duplicates only from the post-checkpoint window."""
        g = Dataset.range(120).graph
        mgr = ShardManager(
            g, policy=ShardingPolicy.DYNAMIC, num_workers_hint=4,
            overpartition=1, resume_offsets=True,
        )
        out = []
        # worker A takes a shard, emits 10 elements, checkpoints at 8, dies
        sid, shard, off = mgr.next_shard("A")
        vals = [int(np.asarray(e)) for e in Dataset(g.bind_shard(shard))]
        out.extend(vals[:10])
        mgr.checkpoint_offset(sid, "A", 8)
        mgr.worker_failed("A")
        # worker B drains everything (including the re-queued shard)
        while True:
            nxt = mgr.next_shard("B")
            if nxt is None:
                break
            s2, sh2, o2 = nxt
            vals = [int(np.asarray(e)) for e in Dataset(g.bind_shard(sh2))]
            out.extend(vals[o2:])
            mgr.complete_shard(s2, "B")
        assert set(out) == set(range(120)), "resume_offsets must not lose data"
        dupes = len(out) - len(set(out))
        assert dupes == 2  # elements 8..9: emitted by A after its checkpoint

    def test_guarantee_mapping(self):
        assert (
            guarantee_for(ShardingPolicy.DYNAMIC, True, True)
            == VisitationGuarantee.EXACTLY_ONCE
        )


class TestServiceResumeE2E:
    def test_kill_worker_no_loss(self, service_factory):
        svc = service_factory(num_workers=3, heartbeat_timeout=0.5,
                              gc_interval=0.1)
        ds = Dataset.range(300).batch(1).distribute(
            service=svc, processing_mode="dynamic", resume_offsets=True
        )
        it = iter(ds)
        got = []
        for i, b in enumerate(it):
            got.extend(np.asarray(b).ravel().tolist())
            if i == 10:
                svc.orchestrator.kill_worker(0)
        assert set(got) == set(range(300)), (
            f"lost {sorted(set(range(300)) - set(got))[:10]}..."
        )
        # duplicates bounded by one checkpoint window per lost shard
        dupes = len(got) - len(set(got))
        assert dupes <= _DynamicRunner.CHECKPOINT_EVERY * 3

    def test_shard_completes_only_once_its_tail_is_delivered(self, service_factory):
        """A shard whose last elements still sit in the worker's buffer is
        not complete yet: a worker lost at that moment leaves the shard to
        be re-queued at its offset, not dropped with its tail."""
        svc = service_factory(
            num_workers=1, heartbeat_timeout=0.5, gc_interval=0.1,
            worker_buffer_size=64,
        )
        ds = Dataset.range(32).batch(1).distribute(
            service=svc, processing_mode="dynamic", resume_offsets=True,
            buffer_size=1, fetch_window=1, max_batch=1,
        )
        it = iter(ds)
        got = [int(np.asarray(next(it)).ravel()[0])]
        time.sleep(0.5)  # the worker produces every shard into its buffer
        job = next(iter(svc.orchestrator.stats()["jobs"].values()))
        assert job["shards"]["total"] > 1
        assert job["shards"]["completed"] == 0
        svc.orchestrator.kill_worker(0)
        svc.orchestrator.add_worker()
        got += [int(np.asarray(b).ravel()[0]) for b in it]
        assert set(got) == set(range(32))
