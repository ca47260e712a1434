"""shm:// data plane: frame/codec round-trips, co-location negotiation,
worker-churn degrade, and process-pool pipeline execution.

Covers the zero-copy transport stack bottom-up: the buffer-direct ``R``
frame format (property-style, over every buffer container type and
codec), the client's shm negotiation and fallback rules, the mid-job
shm→tcp degrade when a co-located worker dies, and the process-pool
executor's delivery/fallback semantics (including snapshot
byte-identity vs the in-thread engine).
"""
import os

import numpy as np
import pytest

from repro.core import available_codecs
from repro.core.codecs import compress, decompress
from repro.core.transport import Stub, TransportError
from repro.data import Dataset
from repro.core.shm_ring import SEGMENT_PREFIX, new_segment_name, unlink_segment
from repro.data.elements import (
    FrameTooLarge,
    copy_element,
    decode_elements,
    encode_elements,
    encode_elements_into,
)
from repro.data.executors import RING_MIN_BYTES, InThreadExecutor, ProcessPoolExecutor
from repro.data.iterators import ExecContext
from repro.obs.registry import MetricsRegistry


# ---------------------------------------------------------------------------
# Property-style frame/codec round-trip
# ---------------------------------------------------------------------------
def _random_element(rng: np.random.Generator, depth: int = 0):
    """One random element drawn from everything the R format must carry."""
    kinds = ["ndarray", "int", "float", "bool", "none", "str", "bytes"]
    if depth < 2:
        kinds += ["dict", "list", "tuple"]
    kind = kinds[int(rng.integers(len(kinds)))]
    if kind == "ndarray":
        dt = rng.choice(["f4", "f8", "i4", "i8", "u1", "b1"])
        shape = tuple(int(d) for d in rng.integers(0, 5, size=int(rng.integers(0, 3))))
        return np.asarray(rng.random(shape) * 100).astype(dt)
    if kind == "int":
        return int(rng.integers(-(2**62), 2**62))
    if kind == "float":
        return float(rng.standard_normal())
    if kind == "bool":
        return bool(rng.integers(2))
    if kind == "none":
        return None
    if kind == "str":
        return "υnicode-" + str(int(rng.integers(1e9)))
    if kind == "bytes":
        return bytes(rng.integers(0, 256, size=int(rng.integers(0, 64))).astype(np.uint8))
    if kind == "dict":
        return {
            f"k{i}": _random_element(rng, depth + 1)
            for i in range(int(rng.integers(0, 4)))
        }
    if kind == "list":
        return [_random_element(rng, depth + 1) for _ in range(int(rng.integers(0, 4)))]
    return tuple(_random_element(rng, depth + 1) for _ in range(int(rng.integers(0, 3))))


def _assert_equal(a, b):
    assert type(a) is type(b) or (
        isinstance(a, (int, np.integer)) and isinstance(b, (int, np.integer))
    ), f"{type(a)} != {type(b)}"
    if isinstance(a, np.ndarray):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)
    elif isinstance(a, dict):
        assert sorted(a) == sorted(b)
        for k in a:
            _assert_equal(a[k], b[k])
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            _assert_equal(x, y)
    else:
        assert a == b


_CONTAINERS = {
    "bytes": bytes,
    "bytearray": bytearray,
    "memoryview": lambda b: memoryview(bytearray(b)),
}


class TestFrameRoundTrip:
    @pytest.mark.parametrize("container", sorted(_CONTAINERS))
    @pytest.mark.parametrize("codec", ["none", "zlib", "lz4"])
    def test_property_roundtrip(self, container, codec):
        """Random nested elements survive slot-encode → codec → any
        bytes-like container → decode, byte- and type-exactly."""
        if codec != "none" and codec not in available_codecs():
            pytest.skip(f"{codec} not installed")
        rng = np.random.default_rng(hash((container, codec)) % 2**32)
        for trial in range(20):
            elems = [_random_element(rng) for _ in range(int(rng.integers(0, 6)))]
            slot = memoryview(bytearray(1 << 20))
            n = encode_elements_into(elems, slot)
            frame = bytes(slot[:n])
            if codec != "none":
                frame = decompress(compress(frame, codec))
            out = decode_elements(_CONTAINERS[container](frame))
            assert len(out) == len(elems)
            for e, o in zip(elems, out):
                _assert_equal(e, o)

    def test_into_matches_inline_layout(self):
        """Both encoders produce frames the one decoder reads: same
        elements out, whatever mix of R and msgpack tags inside."""
        elems = [np.arange(6, dtype=np.float32), {"a": 1, "b": "x"}, None]
        slot = memoryview(bytearray(4096))
        n = encode_elements_into(elems, slot)
        for frame in (bytes(slot[:n]), encode_elements(elems)):
            out = decode_elements(frame)
            for e, o in zip(elems, out):
                _assert_equal(e, o)

    def test_zero_copy_decode_borrows_buffer(self):
        arr = np.arange(32, dtype=np.int64)
        slot = memoryview(bytearray(4096))
        n = encode_elements_into([arr], slot)
        [out] = decode_elements(slot[:n])
        assert not out.flags.owndata and not out.flags.writeable
        assert np.shares_memory(out, np.frombuffer(slot, dtype=np.uint8))
        # copy_element detaches it from the (soon-to-be-reused) slot
        cp = copy_element(out)
        assert cp.flags.owndata
        np.testing.assert_array_equal(cp, arr)

    def test_frame_too_large_is_typed(self):
        big = np.zeros(1024, dtype=np.float64)
        with pytest.raises(FrameTooLarge):
            encode_elements_into([big], memoryview(bytearray(64)))
        # FrameTooLarge is a ValueError: callers catching broadly still work
        assert issubclass(FrameTooLarge, ValueError)


# ---------------------------------------------------------------------------
# shm negotiation e2e
# ---------------------------------------------------------------------------
def _values(sess):
    return sorted(int(v) for e in sess for v in np.ravel(e))


def _graph_ds(n=64, width=4):
    return Dataset.range(n).map(lambda i: np.full((width,), i, dtype=np.int64))


_EXPECT64 = sorted(v for i in range(64) for v in [i] * 4)


class TestShmNegotiation:
    @pytest.mark.parametrize("zero_copy", [False, True])
    def test_colocated_tcp_worker_negotiates_shm(self, service_factory, zero_copy):
        svc = service_factory(num_workers=1, transport="tcp")
        dds = _graph_ds().distribute(
            service=svc, processing_mode="dynamic", compression=None, max_batch=8
        )
        sess = dds.session(zero_copy=zero_copy)
        assert _values(sess) == _EXPECT64
        assert sess.metrics.shm_tasks > 0, "co-located tcp worker must offer shm"
        assert sess.metrics.shm_batches > 0

    @pytest.mark.parametrize(
        "shm,zero_copy", [(True, True), (True, False), (False, True)]
    )
    def test_borrowed_marks_ring_views(self, service_factory, shm, zero_copy):
        """``borrowed`` is True exactly for elements that are views into a
        ring slot: those the DeviceFeeder must finish copying before it
        asks for the next element."""
        svc = service_factory(num_workers=1, transport="tcp")
        dds = _graph_ds().distribute(
            service=svc, processing_mode="dynamic", compression=None, max_batch=8
        )
        sess = dds.session(shm=shm, zero_copy=zero_copy)
        flags = [sess.borrowed for _ in sess]
        assert len(flags) == 64
        assert any(flags) == (shm and zero_copy)

    def test_shm_false_stays_inline(self, service_factory):
        svc = service_factory(num_workers=1, transport="tcp")
        dds = _graph_ds().distribute(
            service=svc, processing_mode="dynamic", compression=None, max_batch=8
        )
        sess = dds.session(shm=False)
        assert _values(sess) == _EXPECT64
        assert sess.metrics.shm_tasks == 0
        assert sess.metrics.shm_batches == 0

    def test_host_mismatch_stays_inline(self, service_factory):
        """A worker advertising another host is never shm-attached, even
        though it is (physically) reachable in this process."""
        svc = service_factory(num_workers=0, transport="tcp")
        svc.orchestrator.add_worker(host_key="other-host.example")
        dds = _graph_ds().distribute(
            service=svc, processing_mode="dynamic", compression=None, max_batch=8
        )
        sess = dds.session()
        assert _values(sess) == _EXPECT64
        assert sess.metrics.shm_tasks == 0
        assert sess.metrics.shm_batches == 0

    def test_inproc_transport_never_negotiates(self, service_factory):
        """inproc responses are already zero-copy; a ring would only add
        bookkeeping."""
        svc = service_factory(num_workers=1, transport="inproc")
        dds = _graph_ds().distribute(
            service=svc, processing_mode="dynamic", compression=None, max_batch=8
        )
        sess = dds.session()
        assert _values(sess) == _EXPECT64
        assert sess.metrics.shm_tasks == 0


# ---------------------------------------------------------------------------
# Churn: shm degrades to tcp mid-job, no loss
# ---------------------------------------------------------------------------
class TestChurnDegrade:
    def test_kill_colocated_worker_degrades_to_tcp_no_loss(self, service_factory):
        """Kill the only shm-serving worker mid-stream: the job finishes on
        the 'remote' worker over inline tcp, and resume_offsets keeps the
        no-loss guarantee (dupes bounded by the checkpoint window)."""
        from repro.core.worker import _DynamicRunner

        svc = service_factory(
            num_workers=1, transport="tcp",
            heartbeat_timeout=0.5, gc_interval=0.1,
        )
        svc.orchestrator.add_worker(host_key="other-host.example")
        n = 300
        dds = Dataset.range(n).batch(1).distribute(
            service=svc, processing_mode="dynamic", resume_offsets=True,
            compression=None, max_batch=4,
        )
        sess = dds.session()
        got = []
        killed = False
        for i, b in enumerate(sess):
            got.extend(np.asarray(b).ravel().tolist())
            # kill only once the ring demonstrably served data (under a
            # loaded box the co-located task may start late)
            if not killed and i >= 20 and sess.metrics.shm_batches > 0:
                svc.orchestrator.kill_worker(0)  # the co-located one
                killed = True
        assert killed, "shm path never engaged before the stream drained"
        assert set(got) == set(range(n)), (
            f"lost {sorted(set(range(n)) - set(got))[:10]}..."
        )
        dupes = len(got) - len(set(got))
        # overpartition=4 → at most 4 shards in flight on the dead worker
        assert dupes <= _DynamicRunner.CHECKPOINT_EVERY * 4
        # shm genuinely served batches before the kill; the survivor is
        # host-mismatched, so everything after it is inline tcp
        assert sess.metrics.shm_tasks > 0
        assert sess.metrics.shm_batches > 0


# ---------------------------------------------------------------------------
# Process-pool pipeline execution
# ---------------------------------------------------------------------------
# int64 values in an element just over the pool's ring threshold
_LARGE = RING_MIN_BYTES // 8 + 8


def _pool_iter(pool, graph, affinity="a"):
    return pool.iterate(graph, ExecContext(), affinity=affinity)


def _pool_counts(registry):
    """Elements through the ring, and through the pipe by reason."""
    snap = registry.snapshot()
    pipe = snap["executor_pipe_elements_total"].get("series", {})
    return (
        snap["executor_ring_elements_total"]["value"],
        {k.split("=", 1)[1]: v for k, v in pipe.items() if v},
    )


def _typed_elem(i, width):
    """An element with leaves a frame codec could flatten: numpy scalars in
    a tuple, a Fortran-order array, an int dict key, a bytearray."""
    return {
        "x": np.full((width,), i, dtype=np.int64),
        "k": (np.int64(i), np.float32(i)),
        "f": np.asfortranarray(np.full((4, 3), i, dtype=np.float64)),
        7: bytearray(b"b"),
    }


def _types(e):
    """Type, dtype, shape and memory order of every leaf, with its value."""
    if isinstance(e, dict):
        return {k: _types(v) for k, v in e.items()}
    if isinstance(e, (tuple, list)):
        return (type(e), [_types(v) for v in e])
    if isinstance(e, np.ndarray):
        order = "F" if e.flags.f_contiguous and not e.flags.c_contiguous else "C"
        return (type(e), e.dtype, e.shape, order, e.tobytes())
    return (type(e), e)


def _segments():
    return {n for n in os.listdir("/dev/shm") if n.startswith(SEGMENT_PREFIX)}


class TestProcessPoolExecutor:
    @pytest.mark.parametrize("width", [4, _LARGE], ids=["small", "large"])
    def test_dynamic_exact_counts_with_pool(self, service_factory, width):
        """Multi-pump workers must not double-produce shards: exactly one
        delivery per element with no churn (the holding-reconciliation
        contract between pumps and the dispatcher), the same multiset and
        the same leaf types as the in-thread engine.  Large elements
        (>= RING_MIN_BYTES) cross from the children through their shm
        rings, small ones through the pipe."""
        n = 96 if width == 4 else 32
        got = {}
        for procs in (0, 2):
            svc = service_factory(
                num_workers=1, transport="tcp", worker_processes=procs
            )
            dds = Dataset.range(n).map(lambda i: _typed_elem(i, width)).distribute(
                service=svc, processing_mode="dynamic", compression=None, max_batch=8
            )
            elems = sorted(dds.session(), key=lambda e: int(e["x"][0]))
            got[procs] = [_types(e) for e in elems]
            if procs:
                counts = _pool_counts(svc.orchestrator.workers[0].registry)
        assert got[2] == got[0]
        # exact: no pump-duplicated shards
        assert [t["x"] for t in got[0]] == [
            _types(np.full((width,), i, dtype=np.int64)) for i in range(n)
        ]
        ring, pipe = counts
        if width == _LARGE:
            # a request that starts while its lane-mate holds every slot
            # sends through the pipe until that lane-mate frees one
            assert set(pipe) <= {"ring_busy"}
            busy = pipe.get("ring_busy", 0)
            assert ring + busy == n and ring > busy, counts
        else:
            assert counts == (0, {"small": n})

    @pytest.mark.parametrize("width", [4, _LARGE], ids=["small", "large"])
    def test_an_element_keeps_its_types_through_either_route(self, width):
        """What the pool yields is what the in-thread engine yields, leaf by
        leaf, whether the element came through the pipe or the ring."""
        graph = Dataset.range(6).map(lambda i: _typed_elem(i, width)).graph
        registry = MetricsRegistry()
        pool = ProcessPoolExecutor(1, registry)
        try:
            pooled = [(seq, _types(e)) for seq, e in _pool_iter(pool, graph)]
        finally:
            pool.stop()
        inthread = InThreadExecutor().iterate(graph, ExecContext(), affinity="a")
        assert pooled == [(seq, _types(e)) for seq, e in inthread]
        assert _pool_counts(registry)[0] == (6 if width == _LARGE else 0)

    def test_an_element_larger_than_its_slot_takes_the_pipe_intact(self):
        """The ring's slots are sized from the first large element; a later
        one too large for a slot arrives through the pipe, counted
        ``too_large``, beside the ring's and the pipe's other elements."""
        sizes = [_LARGE, 4 * _LARGE, _LARGE, 4]
        graph = Dataset.range(len(sizes)).map(
            lambda i: np.full((sizes[i],), i, dtype=np.int64)
        ).graph
        registry = MetricsRegistry()
        pool = ProcessPoolExecutor(1, registry)
        try:
            out = list(_pool_iter(pool, graph))
        finally:
            pool.stop()
        assert [seq for seq, _ in out] == [1, 2, 3, 4]
        for i, (_, e) in enumerate(out):
            np.testing.assert_array_equal(e, np.full((sizes[i],), i, dtype=np.int64))
        assert _pool_counts(registry) == (2, {"small": 1, "too_large": 1})

    def test_a_request_stalled_on_a_full_ring_does_not_stop_its_lane_mate(self):
        """Two requests on one child's ring: the first holds every slot and
        its consumer never pulls again; the second still runs to its end,
        its large elements through the pipe, counted ``ring_busy``."""
        import threading
        import time

        from repro.data.executors import RING_SLOTS

        graph = _graph_ds(16, _LARGE).graph
        registry = MetricsRegistry()
        pool = ProcessPoolExecutor(1, registry)
        stalled = _pool_iter(pool, graph, affinity="a")
        try:
            next(stalled)
            (req,) = pool._pending.values()
            deadline = time.monotonic() + 30
            while req.inq.qsize() < RING_SLOTS:  # every slot leased to "a"
                assert time.monotonic() < deadline, req.inq.qsize()
                time.sleep(0.01)
            out = []
            mate = threading.Thread(
                target=lambda: out.extend(_pool_iter(pool, graph, affinity="b")),
                daemon=True,
            )
            mate.start()
            mate.join(timeout=60)
            assert not mate.is_alive(), f"lane-mate stuck after {len(out)} elements"
            assert [seq for seq, _ in out] == list(range(1, 17))
            for seq, e in out:
                np.testing.assert_array_equal(e, np.full((_LARGE,), seq - 1, np.int64))
            assert _pool_counts(registry) == (1, {"ring_busy": 16})
        finally:
            stalled.close()
            pool.stop()

    def test_a_lane_without_a_ring_sends_large_elements_through_the_pipe(
        self, monkeypatch
    ):
        """Where the ring's pages cannot be reserved (a full ``/dev/shm``),
        the child removes the segment and every large element takes the
        pipe, counted ``no_ring``."""
        from repro.data import executors

        def full(name):
            raise OSError(28, "No space left on device")

        monkeypatch.setattr(executors, "_reserve", full)  # children fork after
        registry = MetricsRegistry()
        pool = ProcessPoolExecutor(1, registry)
        try:
            out = list(_pool_iter(pool, _graph_ds(4, _LARGE).graph))
            ring_name = pool._lanes[0].ring_name
            assert ring_name not in _segments()
        finally:
            pool.stop()
        assert [seq for seq, _ in out] == [1, 2, 3, 4]
        assert _pool_counts(registry) == (0, {"no_ring": 4})

    def test_a_yielded_element_does_not_alias_the_ring(self):
        """An element is copied out of its slot before it is yielded:
        overwriting every slot of the ring leaves it as it was."""
        graph = _graph_ds(8, _LARGE).graph
        pool = ProcessPoolExecutor(1, MetricsRegistry())
        it = _pool_iter(pool, graph)
        try:
            seq, elem = next(it)
            ring = pool._lanes[0].ring()
            for s in range(ring.slots):
                view = np.frombuffer(ring.slot_view(s), dtype=np.uint8)
                assert not np.shares_memory(elem, view)
                view[:] = 0xFF
                del view
            assert elem.flags.writeable
            np.testing.assert_array_equal(elem, np.full((_LARGE,), seq - 1, np.int64))
        finally:
            it.close()
            pool.stop()

    def test_a_closed_stream_gives_its_ring_slots_back(self):
        """Closing a stream whose queue holds descriptors frees every slot,
        and so does a descriptor that reaches the router after its stream
        has ended."""
        import time

        from repro.data.executors import RING_SLOTS

        pool = ProcessPoolExecutor(1, MetricsRegistry())
        it = _pool_iter(pool, _graph_ds(64, _LARGE).graph)
        try:
            next(it)
            ring = pool._lanes[0].ring()
            (req,) = pool._pending.values()
            deadline = time.monotonic() + 30
            while req.inq.qsize() < RING_SLOTS:
                assert time.monotonic() < deadline, req.inq.qsize()
                time.sleep(0.01)
            it.close()
            while ring.free_slots() < ring.slots:
                assert time.monotonic() < deadline, ring.free_slots()
                time.sleep(0.01)
            slot = ring.try_acquire()
            ring.commit(slot, 8)
            pool._lanes[0].out.put(("elem_ring", "r-ended", 1, slot, 8))
            while ring.free_slots() < ring.slots:
                assert time.monotonic() < deadline, ring.free_slots()
                time.sleep(0.01)
        finally:
            it.close()
            pool.stop()

    def test_a_cancel_reaches_a_child_waiting_for_a_ring_slot(self):
        """A child whose ring is full waits for a slot, sending its op stats
        on their timer, and ends promptly once its request is cancelled."""
        import pickle
        import queue
        import threading

        from repro.data.executors import (
            INITIAL_CREDITS,
            STATS_INTERVAL_S,
            _ChildRequest,
            _ChildRing,
            _run_request,
        )

        lane = _ChildRing(new_segment_name())
        try:
            ring, _ = lane.for_element(_LARGE * 8)
            for _ in range(ring.slots):  # the request's consumer holds every slot
                lane.lease("r0")
            req = _ChildRequest("r0", INITIAL_CREDITS)
            out: "queue.Queue" = queue.Queue()
            blob = pickle.dumps(_graph_ds(8, _LARGE).graph)
            t = threading.Thread(
                target=_run_request, args=(req, blob, 0, 0, 1, out, lane), daemon=True
            )
            t.start()
            waiting = [out.get(timeout=30) for _ in range(2)]
            assert [m[0] for m in waiting] == ["stats", "stats"]
            req.stop.set()
            t.join(timeout=STATS_INTERVAL_S)
            assert not t.is_alive()
            rest = []
            while not out.empty():
                rest.append(out.get_nowait()[0])
            assert "elem_ring" not in rest and "elem" not in rest
            assert rest[-1] == "end"
        finally:
            unlink_segment(lane.name)

    def test_a_lane_keeps_a_free_slot_for_each_lane_mate_holding_none(self):
        """A request that holds a slot takes another only while one stays
        free for every live lane-mate holding none; a request holding none
        that finds every slot leased to lane-mates is told to take the pipe
        (-1), one holding a slot to wait for its own consumer (None)."""
        from repro.data.executors import RING_SLOTS, _ChildRing

        lane = _ChildRing(new_segment_name())
        try:
            ring, _ = lane.for_element(_LARGE * 8)
            lane.enter("a")
            lane.enter("b")
            got = [lane.lease("a") for _ in range(RING_SLOTS)]
            assert None not in got[:-1] and got[-1] is None  # the last is b's
            assert lane.lease("b") is not None
            assert lane.lease("b") is None  # b waits for its own consumer
            lane.enter("c")
            assert lane.lease("c") == -1  # every slot leased to lane-mates
            ring.release(got[0])  # a's consumer copies one out
            assert lane.lease("a") is None  # kept for c
            assert lane.lease("c") == got[0]
            lane.leave("c")
            lane.leave("b")
            ring.release(got[1])
            assert lane.lease("a") == got[1]  # no lane-mate holds none
        finally:
            unlink_segment(lane.name)

    def test_a_child_killed_with_leased_slots_raises_and_leaves_no_segment(self):
        """A child that dies mid-stream while the parent holds descriptors
        of its ring: the stream ends in ExecutorError and the parent has
        removed the child's segment."""
        import signal
        import time

        from repro.data.executors import RING_SLOTS, ExecutorError

        pool = ProcessPoolExecutor(1, MetricsRegistry())
        it = _pool_iter(pool, _graph_ds(64, _LARGE).graph)
        try:
            next(it)
            lane = pool._lanes[0]
            (req,) = pool._pending.values()
            deadline = time.monotonic() + 30
            while req.inq.qsize() < RING_SLOTS:  # every slot leased to the parent
                assert time.monotonic() < deadline, req.inq.qsize()
                time.sleep(0.01)
            assert lane.ring_name in _segments()
            os.kill(lane.proc.pid, signal.SIGKILL)
            with pytest.raises(ExecutorError):
                for _ in it:
                    pass
            assert lane.ring_name not in _segments()
        finally:
            it.close()
            pool.stop()

    def test_child_failure_before_first_element_falls_back_in_thread(
        self, service_factory
    ):
        """A pipeline that dies in the pool child before producing anything
        (state the fork predates) reruns on the in-thread engine instead of
        failing the job."""
        parent = os.getpid()

        def parent_only(i):
            if os.getpid() != parent:
                raise RuntimeError("needs parent-process state")
            return np.full((2,), i, dtype=np.int64)

        svc = service_factory(num_workers=1, worker_processes=2)
        dds = Dataset.range(32).map(parent_only).distribute(
            service=svc, processing_mode="dynamic"
        )
        got = sorted(int(v) for e in dds.session() for v in np.ravel(e))
        assert got == sorted(v for i in range(32) for v in [i] * 2)
        # the degradation is counted, not only logged
        registry = svc.orchestrator.workers[0].registry
        assert registry.values()["executor_inthread_fallbacks_total"] > 0

    @pytest.mark.parametrize("shape", [(), (_LARGE,)], ids=["small", "large"])
    def test_snapshot_byte_identity_across_engines(
        self, service_factory, tmp_path, shape
    ):
        """worker_processes=0 and =2 materialize byte-identical chunk files
        — per-stream seeding and resume offsets are engine-invariant, and
        so is the route (pipe or ring) an element takes out of the child."""
        from repro.core import materialize

        def chunks(root):
            out = {}
            for dirpath, _, files in os.walk(root):
                for f in files:
                    p = os.path.join(dirpath, f)
                    rel = os.path.relpath(p, root)
                    if "chunk" in f:
                        out[rel] = open(p, "rb").read()
            return out

        pipe = Dataset.range(80 if shape == () else 16).map(
            lambda x: np.full(shape, x * 3 + 1, dtype=np.int64)
        ).batch(2)
        roots = {}
        for procs in (0, 2):
            svc = service_factory(num_workers=1, worker_processes=procs)
            root = str(tmp_path / f"snap_p{procs}")
            st = materialize(svc, pipe, root, chunk_bytes=256, timeout=60)
            assert st["finished"]
            roots[procs] = chunks(root)
            if procs:
                ring = _pool_counts(svc.orchestrator.workers[0].registry)[0]
                assert (ring > 0) == (shape != ())
        assert roots[0], "no chunk files written"
        assert sorted(roots[0]) == sorted(roots[2])
        for rel in roots[0]:
            assert roots[0][rel] == roots[2][rel], f"chunk differs: {rel}"


# ---------------------------------------------------------------------------
# Transport framing and error contract
# ---------------------------------------------------------------------------
class TestTransportErrorContract:
    def test_a_frame_larger_than_the_socket_buffers_round_trips(self):
        """A tcp frame goes out as gathered writes and comes in to one
        buffer, over as many partial sends and receives as the kernel
        makes of it."""
        import socket
        import threading

        from repro.core.transport import _recv_msg, _send_msg

        payload = {"x": np.arange(4 << 20, dtype=np.int32), "s": "tail"}
        a, b = socket.socketpair()
        try:
            t = threading.Thread(target=_send_msg, args=(a, payload), daemon=True)
            t.start()
            got = _recv_msg(b)
            t.join(timeout=30)
            assert not t.is_alive()
        finally:
            a.close()
            b.close()
        np.testing.assert_array_equal(got["x"], payload["x"])
        assert got["s"] == "tail"

    def test_tcp_connection_refused_is_typed(self):
        with pytest.raises(TransportError):
            Stub("tcp://127.0.0.1:1").call("ping")

    def test_inproc_unbound_endpoint_is_typed(self):
        with pytest.raises(TransportError):
            Stub("inproc://no-such-endpoint").call("ping")

    def test_unknown_scheme_is_typed(self):
        with pytest.raises(TransportError):
            Stub("carrier-pigeon://x").call("ping")
