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
from repro.data.elements import (
    FrameTooLarge,
    copy_element,
    decode_elements,
    encode_elements,
    encode_elements_into,
)


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


def _graph_ds(n=64):
    return Dataset.range(n).map(lambda i: np.full((4,), i, dtype=np.int64))


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
class TestProcessPoolExecutor:
    def test_dynamic_exact_counts_with_pool(self, service_factory):
        """Multi-pump workers must not double-produce shards: exactly one
        delivery per element with no churn (the holding-reconciliation
        contract between pumps and the dispatcher)."""
        svc = service_factory(num_workers=1, transport="tcp", worker_processes=2)
        dds = _graph_ds(96).distribute(
            service=svc, processing_mode="dynamic", compression=None, max_batch=8
        )
        got = [int(v) for e in dds.session() for v in np.ravel(e)]
        assert sorted(got) == sorted(v for i in range(96) for v in [i] * 4)
        assert len(got) == 96 * 4  # exact: no pump-duplicated shards

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

    def test_snapshot_byte_identity_across_engines(self, service_factory, tmp_path):
        """worker_processes=0 and =2 materialize byte-identical chunk files
        — per-stream seeding and resume offsets are engine-invariant."""
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

        pipe = Dataset.range(80).map(
            lambda x: np.asarray(x, dtype=np.int64) * 3 + 1
        ).batch(2)
        roots = {}
        for procs in (0, 2):
            svc = service_factory(num_workers=1, worker_processes=procs)
            root = str(tmp_path / f"snap_p{procs}")
            st = materialize(svc, pipe, root, chunk_bytes=256, timeout=60)
            assert st["finished"]
            roots[procs] = chunks(root)
        assert roots[0], "no chunk files written"
        assert sorted(roots[0]) == sorted(roots[2])
        for rel in roots[0]:
            assert roots[0][rel] == roots[2][rel], f"chunk differs: {rel}"


# ---------------------------------------------------------------------------
# Transport error contract
# ---------------------------------------------------------------------------
class TestTransportErrorContract:
    def test_tcp_connection_refused_is_typed(self):
        with pytest.raises(TransportError):
            Stub("tcp://127.0.0.1:1").call("ping")

    def test_inproc_unbound_endpoint_is_typed(self):
        with pytest.raises(TransportError):
            Stub("inproc://no-such-endpoint").call("ping")

    def test_unknown_scheme_is_typed(self):
        with pytest.raises(TransportError):
            Stub("carrier-pigeon://x").call("ping")
