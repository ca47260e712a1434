"""Accelerator-feed subsystem (repro.feed): double-buffered device
prefetch, per-host sharded consumption, stall metrics, and the
autoscaler's client-latency signal."""
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import Autoscaler, AutoscalerConfig  # noqa: E402
from repro.data import Dataset  # noqa: E402
from repro.feed import DeviceFeeder, FeedMetrics, StallWindow  # noqa: E402


def _ids_pipeline(n, batch=4):
    """Batches whose contents identify their source elements."""
    return (
        Dataset.range(n)
        .map(lambda i: {"x": np.full((8,), int(i), np.int64)})
        .batch(batch, drop_remainder=True)
    )


class TestDeviceFeeder:
    def test_delivers_every_batch_as_device_arrays(self, service_factory):
        svc = service_factory(num_workers=2)
        dds = _ids_pipeline(32).distribute(service=svc, processing_mode="dynamic")
        seen = []
        with DeviceFeeder(dds) as feeder:
            for b in feeder:
                assert isinstance(b["x"], jax.Array)
                seen.extend(np.asarray(b["x"])[:, 0].tolist())
        # DYNAMIC: exactly-once without failures, modulo per-shard
        # drop_remainder tails
        assert len(seen) == len(set(seen))
        assert set(seen) <= set(range(32))
        assert len(seen) >= 16

    def test_ring_views_are_borrowed_only_where_device_put_copies(
        self, service_factory
    ):
        """The CPU backend's device_put aliases an aligned host buffer, so
        a device array made from a borrowed ring view would change when the
        worker reuses the slot: on the CPU the feeder's client copies each
        batch out of the ring instead of lending views."""
        raw = np.zeros(2 * 4096, np.uint8)
        start = -raw.ctypes.data % 4096  # page-aligned, like a ring slot
        host = raw[start : start + 4096].view(np.int32)
        placed = jax.device_put(host)
        host[:] = 1
        assert np.asarray(placed).any(), "the CPU backend copied this time"
        svc = service_factory(num_workers=1, transport="tcp")
        dds = _ids_pipeline(16).distribute(service=svc, processing_mode="dynamic")
        with DeviceFeeder(dds) as feeder:
            seen = [int(v) for b in feeder for v in np.asarray(b["x"])[:, 0]]
        assert len(seen) == len(set(seen)) and set(seen) <= set(range(16))
        assert dds.last_client.metrics.shm_batches > 0
        assert dds.last_client._zero_copy is False

    def test_double_buffer_hides_slow_producer(self, service_factory):
        """With a sleep-map producer and a sleeping 'accelerator', the
        feeder overlaps production/transfer with compute: wall time must
        beat the no-overlap serial bound by a wide margin."""
        produce_s, compute_s, steps = 0.03, 0.03, 8
        svc = service_factory(num_workers=2)

        def slow(i):
            time.sleep(produce_s)
            return {"x": np.full((4,), int(i), np.float32)}

        dds = (
            Dataset.range(256)
            .map(slow)
            .batch(1)
            .distribute(service=svc, processing_mode="dynamic")
        )
        with DeviceFeeder(dds, depth=2) as feeder:
            feeder.next()  # ramp: job rollout + first production
            t0 = time.perf_counter()
            for _ in range(steps):
                feeder.next()
                time.sleep(compute_s)  # the 'train step'
            wall = time.perf_counter() - t0
        serial = steps * (produce_s + compute_s)
        assert wall < 0.75 * serial, (
            f"no overlap: {wall:.3f}s vs serial bound {serial:.3f}s"
        )
        assert feeder.metrics.steps >= steps
        assert feeder.metrics.compute_s > 0

    def test_clean_shutdown_mid_epoch(self, service_factory):
        svc = service_factory(num_workers=2)
        dds = _ids_pipeline(10_000).distribute(
            service=svc, processing_mode="dynamic"
        )
        feeder = DeviceFeeder(dds, depth=2)
        for _ in range(3):
            feeder.next()
        feeder.close()
        assert not feeder._thread.is_alive()
        feeder.close()  # idempotent
        with pytest.raises(StopIteration):
            feeder.next()
        # the service survives the mid-epoch disconnect
        assert svc.orchestrator.stats()["num_workers"] == 2

    def test_static_mode_registers_per_host_consumers(self, service_factory):
        """Two 'hosts' (threads) of a static-mode feed consume disjoint
        coordinated slots of every round."""
        svc = service_factory(num_workers=2)
        dds = _ids_pipeline(64, batch=2).distribute(
            service=svc, processing_mode="dynamic", job_name="hosts"
        )
        out = [None, None]

        def host(h):
            f = DeviceFeeder(dds, num_hosts=2, host_index=h)
            got = []
            for b in f:
                got.append(tuple(np.asarray(b["x"])[:, 0].tolist()))
                if len(got) >= 4:
                    break
            f.close()
            out[h] = got

        ts = [threading.Thread(target=host, args=(h,)) for h in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert out[0] and out[1], out
        assert len(out[0]) == len(out[1]) == 4
        # coordinated consumer indexing: slot h of round r goes to host h,
        # so the two hosts never see the same batch
        assert not (set(out[0]) & set(out[1])), out

    def test_raw_dataset_requires_service(self):
        with pytest.raises(TypeError):
            DeviceFeeder(_ids_pipeline(8))

    def test_feed_stall_reaches_dispatcher_stats(self, service_factory):
        """The feeder's stall windows flow: report_feed_stall -> client
        heartbeat -> dispatcher job aggregate -> stats()."""
        svc = service_factory(num_workers=1)

        def slow(i):
            time.sleep(0.02)
            return np.full((4,), int(i), np.float32)

        dds = (
            Dataset.range(4000)
            .map(slow)
            .batch(4)
            .distribute(service=svc, processing_mode="dynamic")
        )
        feeder = DeviceFeeder(dds, report_interval_s=0.1)
        try:
            cs = None
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                feeder.next()
                stats = svc.orchestrator.stats()
                vals = [
                    j.get("client_stall")
                    for j in stats["jobs"].values()
                    if j.get("client_stall")
                ]
                if vals:
                    cs = vals[0]
                    break
            assert cs is not None, "no client_stall aggregate ever appeared"
            assert cs["clients"] >= 1
            # a producer sleeping 80ms/batch against a ~0ms consumer must
            # read as heavily stalled, and as fetch-dominated
            assert cs["stall_frac"] > 0.5
            assert cs["fetch_s_per_step"] > cs["transfer_s_per_step"]
        finally:
            feeder.close()


class TestShardedPlacement:
    def test_per_host_shards_disjoint_on_multidevice_mesh(self, tmp_path):
        """On a forced 4-device CPU mesh, feeder batches arrive sharded
        over the data axis: addressable shards are disjoint row ranges
        that reassemble to the host batch.  Needs its own process —
        XLA_FLAGS must be set before jax initializes."""
        script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro.core import start_service
from repro.data import Dataset
from repro.dist import ShardingPlan
from repro.feed import DeviceFeeder

mesh = jax.make_mesh((2, 2), ("data", "model"))
plan = ShardingPlan(data_axes=("data",), model_axis="model")
svc = start_service(num_workers=2)
try:
    ds = (Dataset.range(32)
          .map(lambda i: {"x": np.full((6,), int(i), np.int32)})
          .batch(4, drop_remainder=True)
          .distribute(service=svc, processing_mode="dynamic"))
    with DeviceFeeder(ds, mesh=mesh, plan=plan) as feeder:
        checked = 0
        for b in feeder:
            arr = b["x"]
            assert isinstance(arr.sharding, jax.sharding.NamedSharding)
            assert arr.sharding.spec == jax.sharding.PartitionSpec("data")
            host = np.asarray(arr)
            rows = []
            for s in arr.addressable_shards:
                lo = s.index[0].start or 0
                hi = s.index[0].stop or arr.shape[0]
                np.testing.assert_array_equal(np.asarray(s.data), host[lo:hi])
                rows.append((lo, hi))
            # the data-axis shards partition the batch dim: 2 distinct
            # half-open ranges (each replicated over the model axis),
            # disjoint and covering [0, B)
            uniq = sorted(set(rows))
            assert uniq == [(0, 2), (2, 4)], uniq
            checked += 1
        assert checked >= 4
finally:
    svc.orchestrator.stop()
print("SHARDING-OK")
"""
        p = tmp_path / "shard_check.py"
        p.write_text(script)
        res = subprocess.run(
            [sys.executable, str(p)],
            capture_output=True,
            text=True,
            timeout=240,
            env={**__import__("os").environ, "PYTHONPATH": "src"},
            cwd=__import__("os").path.join(
                __import__("os").path.dirname(__file__), ".."
            ),
        )
        assert res.returncode == 0, res.stderr
        assert "SHARDING-OK" in res.stdout


class TestFeedMetrics:
    def test_breakdown_and_stall_fraction(self):
        m = FeedMetrics()
        m.add_fetch(0.2)
        m.add_transfer(0.1, 1024)
        m.add_step(idle=0.3, compute=None, depth_frac=0.5)
        m.add_step(idle=0.1, compute=0.1, depth_frac=0.5)
        assert m.steps == 2 and m.batches_fetched == 1
        assert m.idle_s == pytest.approx(0.4)
        assert m.stall_fraction == pytest.approx(0.4 / 0.5)
        bd = m.breakdown()
        assert bd["fetch"] == pytest.approx(0.5)
        assert sum(bd.values()) == pytest.approx(1.0)
        assert m.summary()["bytes_to_device"] == 1024

    def test_stall_window_reports_deltas_only(self):
        m = FeedMetrics()
        w = StallWindow(m)
        assert w.report() is None  # no steps yet
        m.add_step(idle=0.5, compute=0.5, depth_frac=0.0)
        r = w.report()
        assert r["stall_frac"] == pytest.approx(0.5)
        assert r["steps"] == 1
        assert w.report() is None  # nothing new since
        m.add_step(idle=0.0, compute=1.0, depth_frac=1.0)
        r = w.report()
        assert r["stall_frac"] == pytest.approx(0.0)


class TestAutoscalerClientLatencySignal:
    """The feed-stall aggregate replaces buffer occupancy as the primary
    scaling signal when present."""

    class _Orch:
        def __init__(self, occupancy, stall):
            self._occ = occupancy
            self._stall = stall
            self.workers = ["w0", "w1"]

        def stats(self):
            job = {"finished": False}
            if self._stall is not None:
                job["client_stall"] = {"clients": 1.0, "stall_frac": self._stall}
            return {
                "workers": {w: {"buffer_occupancy": self._occ} for w in self.workers},
                "jobs": {"job-1": job},
            }

        def add_worker(self):
            self.workers.append(f"w{len(self.workers)}")

        def remove_worker(self, worker):
            self.workers.remove(worker)

        @property
        def live_workers(self):
            return list(self.workers)

    def _scaler(self, orch):
        return Autoscaler(
            orch, AutoscalerConfig(cooldown_s=0.0, min_workers=1, max_workers=8)
        )

    def test_stalled_clients_scale_out_despite_full_buffers(self):
        # buffer occupancy alone would say "over-provisioned, scale IN" —
        # the consumers disagree, and they win
        orch = self._Orch(occupancy=1.0, stall=0.4)
        assert self._scaler(orch).step() == 1
        assert len(orch.workers) == 3

    def test_fed_clients_and_full_buffers_scale_in(self):
        orch = self._Orch(occupancy=1.0, stall=0.0)
        assert self._scaler(orch).step() == -1
        assert len(orch.workers) == 1

    def test_fed_clients_with_empty_buffers_hold(self):
        # consumers happy but buffers draining: neither signal says act
        orch = self._Orch(occupancy=0.1, stall=0.0)
        assert self._scaler(orch).step() == 0

    def test_occupancy_fallback_without_reports(self):
        orch = self._Orch(occupancy=0.1, stall=None)
        assert self._scaler(orch).step() == 1  # starved buffers => out

    def test_malformed_worker_entry_tolerated(self):
        orch = self._Orch(occupancy=0.1, stall=None)
        orig = orch.stats

        def stats():
            s = orig()
            s["workers"]["w0"] = {}  # mid-registration: no occupancy key
            return s

        orch.stats = stats
        assert self._scaler(orch).step() == 1  # .get default, no crash

    def test_decision_records_signal(self):
        orch = self._Orch(occupancy=1.0, stall=0.4)
        s = self._scaler(orch)
        s.step()
        assert s.decisions[-1]["signal"] == "client_stall"
        assert s.decisions[-1]["client_stall"] == pytest.approx(0.4)
