"""The service's spans on the profiler's clock, the shm ring's inline
fallback counter, and pool children's op stats sent on a timer.

A served run under ``jax.profiler`` on the CPU backend must show the
worker's long-poll, the transport, the client and the feeder as host
events inside the traced window; an unsampled span records nothing; a
process that never imported JAX pays a ``nullcontext``.
"""
import glob
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.data import Dataset
from repro.data.executors import (
    INITIAL_CREDITS,
    RING_MIN_BYTES,
    STATS_INTERVAL_S,
    ProcessPoolExecutor,
)
from repro.data.iterators import ExecContext
from repro.obs.registry import MetricsRegistry
from repro.obs.tracing import Tracer, annotate

SRC = Path(__file__).resolve().parents[1] / "src"


def _host_events(trace_dir):
    """(name, start_ns, end_ns, metadata) of every host event of the newest
    profile under ``trace_dir``."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                  key=os.path.getmtime)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.mark.parametrize("width", [256, RING_MIN_BYTES // 4], ids=["small", "large"])
def test_served_run_puts_the_service_spans_in_the_profiler_trace(service_factory, tmp_path,
                                                                  width):
    """Elements of at least RING_MIN_BYTES leave the pool child through its
    ring, so ``executor.copy_out`` joins ``executor.recv``."""
    import jax

    from repro.feed import DeviceFeeder

    svc = service_factory(num_workers=1, transport="tcp", worker_processes=1)
    dds = Dataset.range(400).map(lambda i: np.full((width,), i, np.float32)).batch(4).distribute(
        service=svc, processing_mode="dynamic")
    with DeviceFeeder(dds, depth=2) as feeder:
        for _ in range(3):  # warm: tasks, ring and pool child are up
            feeder.next(timeout=60)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                for _ in range(40):  # past what the client holds prefetched
                    jax.block_until_ready(feeder.next(timeout=60))
        finally:
            jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    (lo, hi), = [(s, e) for n, s, e, _ in events if n == "bench.window"]
    inside = {n for n, s, e, _ in events if lo <= s and e <= hi}
    for name in ("feed.fetch", "feed.device_put", "feed.next", "client.fetch",
                 "client.decode", "transport.recv", "transport.decode", "worker.wait",
                 "worker.encode", "executor.recv"):
        assert name in inside, (name, sorted(inside))
    assert ("executor.copy_out" in inside) == (width >= RING_MIN_BYTES // 4)
    methods = {m.get("method") for n, s, e, m in events if n == "transport.recv"}
    assert "get_elements" in methods


def test_unsampled_span_annotates_and_records_nothing(tmp_path):
    import jax

    tr = Tracer(sample_rate=0.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("probe.unsampled", None, k="v") as ctx:
            assert ctx is None
    finally:
        jax.profiler.stop_trace()
    assert len(tr) == 0
    assert [m for n, _, _, m in _host_events(str(tmp_path)) if n == "probe.unsampled"] == [{"k": "v"}]


def test_sampled_span_records_and_reraises():
    tr = Tracer(sample_rate=1.0)
    root = tr.start_trace()
    with pytest.raises(KeyError):
        with tr.span("probe.sampled", root, k="v") as ctx:
            assert ctx.trace_id == root.trace_id
            raise KeyError("x")
    (span,) = tr.drain()
    assert span["name"] == "probe.sampled" and span["parent_id"] == root.span_id
    assert span["span_id"] == ctx.span_id and span["attrs"] == {"k": "v"}


def test_a_process_without_jax_pays_a_nullcontext():
    code = (
        "import sys, contextlib\n"
        "from repro.obs.tracing import Tracer, annotate\n"
        "with Tracer().span('x', None) as c:\n"
        "    assert c is None\n"
        "assert isinstance(annotate('x', method='m'), contextlib.nullcontext)\n"
        "assert 'jax' not in sys.modules\n"
    )
    env = {**os.environ, "PYTHONPATH": str(SRC)}
    res = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True,
                         timeout=60)
    assert res.returncode == 0, res.stderr


def test_annotate_is_the_profiler_annotation_only_inside_a_session(tmp_path):
    import contextlib

    import jax

    assert isinstance(annotate("x", method="m"), contextlib.nullcontext)
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert isinstance(annotate("x", method="m"), jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    assert isinstance(annotate("x"), contextlib.nullcontext)


# ---------------------------------------------------------------------------
# worker_shm_inline_total{reason}
# ---------------------------------------------------------------------------
@pytest.fixture
def worker():
    from repro.core.worker import Worker

    w = Worker("inproc://no-dispatcher", transport="tcp")
    yield w
    w._release_shm_channels()


def _inline(w):
    return w.registry.snapshot()["worker_shm_inline_total"].get("series", {})


def _small():
    return [np.arange(16, dtype=np.int64)]


def test_shm_fallback_counts_an_unknown_channel(worker):
    assert worker._shm_serve({}, "shmch-unknown", _small(), None) is False
    assert _inline(worker) == {"reason=no_channel": 1.0}


def test_shm_fallback_counts_a_full_ring(worker):
    ch = worker.rpc_shm_attach(slots=1)["channel"]
    out = {}
    assert worker._shm_serve(out, ch, _small(), None) is True  # leases the one slot
    assert worker._shm_serve({}, ch, _small(), None) is False
    assert _inline(worker) == {"reason=ring_full": 1.0}
    worker._shm_channels[ch].release(out["shm_slot"])
    assert worker._shm_serve({}, ch, _small(), None) is True
    assert _inline(worker) == {"reason=ring_full": 1.0}


@pytest.mark.parametrize("compression", [None, "zlib"])
def test_shm_fallback_counts_a_frame_larger_than_the_slot(worker, compression):
    ch = worker.rpc_shm_attach(slots=2, slot_bytes=4096)["channel"]
    big = [np.random.default_rng(0).random(4096)]  # 32 KB, incompressible
    out = {}
    assert worker._shm_serve(out, ch, big, compression) is False
    assert "shm_codec" not in out
    assert _inline(worker) == {"reason=too_large": 1.0}
    assert worker._shm_channels[ch].free_slots() == 2  # the slot went back


def test_shm_fallback_counts_an_encode_error(worker, monkeypatch):
    from repro.core import worker as worker_mod

    def broken(elems, view):
        raise RuntimeError("encode failed")

    monkeypatch.setattr(worker_mod, "encode_elements_into", broken)
    ch = worker.rpc_shm_attach(slots=1)["channel"]
    assert worker._shm_serve({}, ch, _small(), None) is False
    assert _inline(worker) == {"reason=error": 1.0}


def test_served_lm_style_fetches_count_ring_misses_beside_batches_served(service_factory):
    svc = service_factory(num_workers=1, transport="tcp")
    dds = Dataset.range(64).map(lambda i: np.full((4,), i, np.int64)).distribute(
        service=svc, processing_mode="dynamic", max_batch=8)
    got = sorted(int(v) for e in dds.session() for v in np.ravel(e))
    assert got == sorted(v for i in range(64) for v in [i] * 4)
    values = svc.orchestrator.workers[0].registry.values()
    assert values["worker_batches_served"] == 64
    inline = _inline(svc.orchestrator.workers[0])
    assert set(inline) <= {"reason=ring_full"}  # the ring's 8 slots may fill


# ---------------------------------------------------------------------------
# Pool children's op stats on a timer
# ---------------------------------------------------------------------------
def test_a_credit_starved_child_still_sends_its_op_stats():
    """After its credited elements a child blocks on credit; its op stats
    reach the parent's context within two stats intervals all the same,
    though the parent pulls nothing more."""
    pool = ProcessPoolExecutor(1, MetricsRegistry())
    ctx = ExecContext()
    graph = Dataset.range(10_000).map(lambda i: np.full((4,), i, np.int64)).graph
    it = pool.iterate(graph, ctx, affinity="starved")
    try:
        next(it)
        (req,) = pool._pending.values()
        deadline = time.monotonic() + 60
        while req.inq.qsize() < INITIAL_CREDITS - 1:  # every credited element sent
            assert time.monotonic() < deadline, "the child did not use its credit"
            time.sleep(0.01)
        blocked = time.monotonic()

        def produced():
            return max((st.elements for st in list(ctx.stats.values())), default=0)

        while produced() < INITIAL_CREDITS:
            assert time.monotonic() - blocked < 2 * STATS_INTERVAL_S, produced()
            time.sleep(0.005)
    finally:
        it.close()
        pool.stop()
