"""tf.data-service worker (paper §3.1): stateless data-plane node.

A worker executes *tasks* (one per job) shipped by the dispatcher as
serialized pipeline graphs.  Four runner flavors:

* buffered   — OFF/STATIC policies: background producer into a bounded queue.
* dynamic    — DYNAMIC policy: pulls disjoint shards from the dispatcher
               first-come-first-served, optionally checkpointing element
               offsets for exactly-once-style recovery.
* shared     — ephemeral data sharing (§3.5): jobs attach pointers to a
               worker-global SlidingWindowCache keyed by pipeline fingerprint.
* coordinated— coordinated reads (§3.6): serves round-indexed, same-bucket
               batches; all consumers of round r read from this worker.

Statelessness: a restarted worker re-registers and receives its tasks anew;
it never persists local state (paper §3.4).
"""
from __future__ import annotations

import logging
import socket
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional, Set, Tuple, Type

from ..data.elements import (
    Element,
    FrameTooLarge,
    element_nbytes,
    encode_element,
    encode_elements,
    encode_elements_into,
)
from ..data.executors import make_executor
from ..data.graph import Graph
from ..data.iterators import ExecContext, build_iterator
from ..obs.profiling import attribute_stalls, merge_profiles, profile_ops
from ..obs.registry import MetricsRegistry
from ..obs.tracing import TraceContext, Tracer
from ..snapshot.format import ChunkRecord
from ..snapshot.writer import StreamReassigned, StreamWriter
from .cache import SlidingWindowCache
from .shm_ring import (
    DEFAULT_SLOT_BYTES,
    DEFAULT_SLOTS,
    ShmRing,
    ShmRingError,
)
from .protocol import (
    DATA_PLANE_VERSION,
    DEFAULT_MAX_BATCH,
    FetchStatus,
    ShardingPolicy,
    new_id,
)
from .transport import INPROC, Backoff, Stub, TCPServer, TransportError, compress


logger = logging.getLogger(__name__)


class WorkerMetrics:
    """Cumulative worker counters, hammered concurrently by every runner
    producer thread and every data-plane handler thread.

    Now a facade over :class:`repro.obs.registry.MetricsRegistry` — each
    counter is a registry family named ``worker_<field>`` so the same
    numbers the heartbeat reports are scraped by ``metrics_dump`` / the
    fleet dashboard with no second bookkeeping path.  The exactness
    contract is unchanged: every mutation is serialized per-series (a bare
    ``+=`` loses updates under thread switches, and ``busy_time`` feeds the
    autoscaler's ``cpu_busy`` signal, so lost updates read as idle
    capacity); ``snapshot()`` stays lock-free for readers.
    """

    _COUNTERS = ("batches_produced", "batches_served", "bytes_served", "rpc_count", "busy_time")
    _GAUGES = ("pending_responses",)

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self.registry = registry if registry is not None else MetricsRegistry()
        self._series: Dict[str, Any] = {}
        for name in self._COUNTERS:
            self._series[name] = self.registry.counter(
                f"worker_{name}", "cumulative worker data-plane counter"
            )
        for name in self._GAUGES:
            self._series[name] = self.registry.gauge(
                f"worker_{name}", "current worker data-plane level"
            )

    def add(self, **deltas: float) -> None:
        for name, delta in deltas.items():
            self._series[name].add(delta)

    def snapshot(self) -> Dict[str, Any]:
        """Point-in-time copy for heartbeats/stats (never blocks writers)."""
        return {name: s.value for name, s in self._series.items()}


class _TaskRunner:
    status: str = "running"  # running | done

    def __init__(self) -> None:
        self._stopped = threading.Event()
        # every pipeline this runner executed keeps its ExecContext here so
        # per-op timings survive shard restarts and roll up in op_profile()
        self._ctxs: List[ExecContext] = []

    def _new_ctx(self) -> ExecContext:
        # fresh context per build_iterator call: sharing one would replay
        # the `cache` op's store across shards; stats are merged at rollup
        ctx = ExecContext()
        self._ctxs.append(ctx)
        return ctx

    def op_profile(self) -> List[Dict[str, Any]]:
        """Per-op wall/CPU/element rollup across every pipeline context this
        runner has executed (feeds metrics_dump + stall attribution)."""
        return merge_profiles(profile_ops(c.stats) for c in list(self._ctxs))

    def get(self, job_id: str, round_index: int, consumer_index: int):
        raise NotImplementedError

    def get_many(self, job_id: str, max_batch: int, timeout: float = 0.0):
        """Drain up to ``max_batch`` ready elements (batched data plane).

        Returns ``(status, elements)``: OK with a non-empty list when
        anything was ready, otherwise the blocking status (PENDING /
        END_OF_TASK) with an empty list.  ``timeout`` is a long-poll bound:
        implementations MAY wait up to that long for the first element
        (the base implementation is non-blocking).
        """
        out: List[Element] = []
        status = FetchStatus.PENDING
        for _ in range(max_batch):
            status, elem = self.get(job_id, -1, -1)
            if status != FetchStatus.OK:
                break
            out.append(elem)
        if out:
            return FetchStatus.OK, out
        return status, out

    def buffer_occupancy(self) -> float:
        return 0.0

    def extra_stats(self) -> Dict[str, Any]:
        return {}

    def stop(self) -> None:
        self._stopped.set()


class _BufferedRunner(_TaskRunner):
    """OFF / STATIC: produce into a bounded deque from a background thread."""

    def __init__(self, worker: "Worker", spec: Dict[str, Any], buffer_size: int):
        super().__init__()
        self._worker = worker
        self._spec = spec
        # the job's root trace context rides in the task spec (journaled
        # dispatcher-side, so it survives failover); pipeline spans parent
        # to it and sample at the minting client's rate
        self._trace = TraceContext.from_wire(spec.get("trace"))
        self._buffer: deque = deque()
        self._buffer_size = buffer_size
        self._cond = threading.Condition()
        self._done = False
        self._thread = threading.Thread(target=self._produce, daemon=True)
        self._thread.start()

    def _iterate(self) -> Iterator[Element]:
        graph = Graph.from_bytes(self._spec["graph_bytes"])
        policy = ShardingPolicy(self._spec["policy"])
        executor = self._worker._executor
        tid = self._spec.get("task_id", "")
        if policy == ShardingPolicy.STATIC:
            for k, shard in enumerate(self._spec.get("static_shards") or []):
                g = graph.bind_shard(shard).bind_seed(self._spec["worker_seed"])
                # per-shard affinity: every element of static shard k comes
                # from the same executor lane, preserving in-thread ordering
                for _seq, elem in executor.iterate(
                    g, self._new_ctx(), affinity=f"{tid}/{k}"
                ):
                    yield elem
        else:  # OFF: whole dataset, worker-specific order
            g = graph.bind_seed(self._spec["worker_seed"])
            for _seq, elem in executor.iterate(
                g, self._new_ctx(), affinity=tid or "off"
            ):
                yield elem

    def _produce(self) -> None:
        try:
            self._pump(self._iterate())
        except Exception as e:  # pipeline failure: surface, then finish
            self._worker._note_error(
                f"task {self._spec.get('task_id')} pipeline", e
            )
        finally:
            with self._cond:
                self._done = True
                self._cond.notify_all()

    def _pump(self, elements: Iterator[Element]) -> None:
        """Drive one element stream into the shared bounded buffer."""
        tracer = self._worker.tracer
        last = time.perf_counter()
        for elem in elements:
            t0 = time.perf_counter()
            with self._cond:
                while len(self._buffer) >= self._buffer_size:
                    if self._worker._stopping.is_set() or self._stopped.is_set():
                        return
                    self._cond.wait(timeout=0.1)
                self._buffer.append(elem)
                self._cond.notify_all()
            self._worker.metrics.add(
                batches_produced=1, busy_time=time.perf_counter() - t0
            )
            if self._trace is not None and tracer.should_sample(self._trace.sample):
                # pipeline-execution span: production time of this
                # element (iterator pull), excluding the buffer wait
                dur = t0 - last
                tracer.record(
                    "worker.pipeline",
                    self._trace.child(),
                    time.time() - dur,
                    dur,
                    parent_id=self._trace.span_id,
                    task_id=self._spec.get("task_id"),
                )
            last = time.perf_counter()
            if self._stopped.is_set():
                return

    def get(self, job_id: str, round_index: int, consumer_index: int):
        with self._cond:
            if self._buffer:
                elem = self._buffer.popleft()
                self._cond.notify_all()
                return FetchStatus.OK, elem
            if self._done:
                self.status = "done"
                return FetchStatus.END_OF_TASK, None
            return FetchStatus.PENDING, None

    def get_many(self, job_id: str, max_batch: int, timeout: float = 0.0):
        # Single lock acquisition for the whole drain (vs. max_batch round
        # trips through get()); the producer refills concurrently.  The
        # long-poll wait releases the lock, so production proceeds while we
        # wait for the first element.
        deadline = time.perf_counter() + max(0.0, timeout)
        with self._cond:
            while not self._buffer and not self._done:
                remaining = deadline - time.perf_counter()
                if remaining <= 0 or self._stopped.is_set():
                    return FetchStatus.PENDING, []
                self._cond.wait(remaining)
            if not self._buffer:  # done and drained
                self.status = "done"
                return FetchStatus.END_OF_TASK, []
            out = []
            while self._buffer and len(out) < max_batch:
                out.append(self._buffer.popleft())
            self._cond.notify_all()
            return FetchStatus.OK, out

    def buffer_occupancy(self) -> float:
        with self._cond:
            return len(self._buffer) / max(1, self._buffer_size)

    def stop(self) -> None:
        self._stopped.set()
        with self._cond:
            self._cond.notify_all()


class _DynamicRunner(_BufferedRunner):
    """DYNAMIC: pull disjoint shards from the dispatcher FCFS (paper §3.3).

    Elements travel through the buffer annotated with (shard, offset) so the
    runner knows exactly how far each shard has been DELIVERED to clients —
    not just produced into the buffer.  Offset checkpoints report the
    delivered watermark (always ≤ delivered, so re-queuing at it never
    skips an undelivered element), and a pruned runner files one final
    truth-report through the redelivery queue so the dispatcher's deferred
    task-retirement reclaim resumes the shard at the exact delivered
    position: 0 duplicates, 0 lost, even when the checkpoints sent during a
    dispatcher outage were dropped.  A shard is reported complete only once
    its last element has been delivered, never while its tail still sits
    in the buffer.
    """

    CHECKPOINT_EVERY = 64

    def __init__(self, worker: "Worker", spec: Dict[str, Any], buffer_size: int):
        # watermarks must exist before the base ctor starts the producer
        self._delivered: Dict[int, int] = {}  # shard_id -> delivered offset
        # shards currently mid-production, one per pump thread (the pool
        # executor runs several shard streams concurrently)
        self._active_shards: Set[int] = set()
        # serializes get_shard hand-out + _active_shards registration across
        # pump threads: a concurrent get_shard whose `holding` snapshot
        # misses a shard another pump just accepted would trick the
        # dispatcher's reconciliation into re-queuing it (duplicates)
        self._shard_lock = threading.Lock()
        # fully produced shards whose last element is still in the buffer:
        # shard_id -> that element's offset.  A shard completes only once
        # its last element is delivered, so a worker lost with the tail in
        # its buffer leaves the shard to be re-queued, not dropped.
        self._shard_end: Dict[int, int] = {}
        self._end_lock = threading.Lock()
        super().__init__(worker, spec, buffer_size)

    def _produce(self) -> None:
        # With a process-pool engine the GIL no longer serializes pipeline
        # work, so run one shard pump per executor lane: each pump pulls its
        # own shards FCFS and pushes into the shared bounded buffer.  Width 1
        # (in-thread engine) keeps the paper's single sequential stream.
        width = max(1, int(getattr(self._worker._executor, "width", 1)))
        if width <= 1:
            super()._produce()
            return
        pumps = [
            threading.Thread(
                target=self._pump_guarded, daemon=True, name=f"dyn-pump-{i}"
            )
            for i in range(width)
        ]
        for t in pumps:
            t.start()
        for t in pumps:
            t.join()
        with self._cond:
            self._done = True
            self._cond.notify_all()

    def _pump_guarded(self) -> None:
        try:
            self._pump(self._iterate())
        except Exception as e:
            self._worker._note_error(
                f"task {self._spec.get('task_id')} pipeline", e
            )

    def _iterate(self) -> Iterator[Element]:
        graph = Graph.from_bytes(self._spec["graph_bytes"])
        job_id = self._spec["job_id"]
        wid = self._worker.worker_id
        backoff = Backoff(base=0.05, cap=1.0)
        while not self._worker._stopping.is_set() and not self._stopped.is_set():
            # the lock spans RPC -> _active_shards registration: the holding
            # snapshot must be consistent with what the dispatcher journals,
            # or a concurrent pump's snapshot re-queues this grant
            with self._shard_lock:
                try:
                    # The lock is per-job, per-worker, and holding it across
                    # the (timeout-bounded) RPC is the whole point: sibling
                    # pumps must not snapshot `holding` mid-grant.
                    # analysis: allow(D001, L003)
                    resp = self._worker._dispatcher.call(
                        "get_shard",
                        job_id=job_id,
                        worker_id=wid,
                        # shard ids we hold: mid-production on any pump, plus
                        # journaled-but-unacked completions — lets a freshly
                        # promoted dispatcher re-queue ONLY assignments whose
                        # response died with the old primary (never received)
                        holding=self._held_shards(job_id),
                    )
                except TransportError:
                    resp = None
                else:
                    if not resp.get("done") and not resp.get("wait"):
                        sid = resp["shard_id"]
                        self._delivered.setdefault(sid, resp.get("offset", 0))
                        self._active_shards.add(sid)
            if resp is None:
                # dispatcher down: no NEW shards can be handed out, but we keep
                # serving what we have (paper §3.4) — retry with jittered
                # backoff so a worker fleet doesn't stampede the standby.
                self._stopped.wait(backoff.next_delay())
                continue
            backoff.reset()
            if resp.get("done"):
                return
            if resp.get("wait"):  # queue empty but a shard may be re-queued
                time.sleep(0.05)
                continue
            sid, shard, offset = resp["shard_id"], resp["shard"], resp.get("offset", 0)
            g = graph.bind_shard(shard).bind_seed(self._spec["worker_seed"] + sid)
            produced = 0
            # shard affinity `{job}/{sid}` pins this shard's whole element
            # stream to one executor lane: per-stream seed + resume offset
            # behave exactly as in-thread.  The executor skips the resumed
            # prefix at the source and yields the absolute offset (i+1).
            for abs_off, elem in self._worker._executor.iterate(
                g,
                self._new_ctx(),
                affinity=f"{job_id}/{sid}",
                offset=offset,
            ):
                produced += 1
                yield (elem, sid, abs_off)  # get()/get_many() strip the tag
                if (
                    self._spec.get("resume_offsets")
                    and produced % self.CHECKPOINT_EVERY == 0
                ):
                    # checkpoint the DELIVERED watermark, not the produced
                    # position: elements still in the buffer would be lost
                    # to a re-queue that skips past them
                    self._try_call(
                        "checkpoint_offset",
                        job_id=job_id,
                        shard_id=sid,
                        worker_id=wid,
                        offset=self._delivered[sid],
                    )
            with self._end_lock:
                delivered = self._delivered[sid] >= offset + produced
                if not delivered:  # the pop of the last element completes it
                    self._shard_end[sid] = offset + produced
            if delivered:
                self._complete(sid)

    def _complete(self, sid: int) -> None:
        # complete BEFORE dropping from _active_shards: between the two,
        # another pump's get_shard must still report this shard as held
        # (a lost completion ack re-enters via _pending_control instead)
        self._try_call(
            "complete_shard",
            job_id=self._spec["job_id"],
            shard_id=sid,
            worker_id=self._worker.worker_id,
        )
        self._active_shards.discard(sid)

    def _unwrap(self, entry: Any) -> Element:
        elem, sid, off = entry
        with self._end_lock:
            self._delivered[sid] = off  # pops follow production order: monotonic
            last = self._shard_end.get(sid) == off
            if last:
                del self._shard_end[sid]
        if last:
            self._complete(sid)
        return elem

    def get(self, job_id: str, round_index: int, consumer_index: int):
        status, entry = super().get(job_id, round_index, consumer_index)
        if entry is None:
            return status, None
        return status, self._unwrap(entry)

    def get_many(self, job_id: str, max_batch: int, timeout: float = 0.0):
        status, entries = super().get_many(job_id, max_batch, timeout)
        return status, [self._unwrap(e) for e in entries]

    def stop(self) -> None:
        super().stop()
        if self._spec.get("resume_offsets"):
            # Pruned mid-shard (task retirement): file one final offset
            # truth-report per in-flight shard through the redelivery
            # queue.  It drains on the next heartbeat — before the
            # dispatcher's second-heartbeat reclaim — so the re-queue
            # resumes at exactly the delivered position even though
            # checkpoints sent while the dispatcher was down were dropped.
            for sid in sorted(self._active_shards):
                self._worker._pending_control.append(
                    (
                        "checkpoint_offset",
                        {
                            "job_id": self._spec["job_id"],
                            "shard_id": sid,
                            "worker_id": self._worker.worker_id,
                            "offset": self._delivered.get(sid, 0),
                        },
                    )
                )

    def _held_shards(self, job_id: str) -> List[int]:
        """Shard ids the dispatcher may see as assigned to us that must NOT
        be re-queued: shards mid-production on any pump thread
        (``_active_shards`` — with a process-pool executor several run
        concurrently) plus shards finished but not yet acknowledged (queued
        ``complete_shard`` redeliveries)."""
        held = set(self._active_shards)
        held.update(
            kw["shard_id"]
            for (m, kw) in list(self._worker._pending_control)
            if m == "complete_shard" and kw.get("job_id") == job_id
        )
        return sorted(held)

    def _try_call(self, method: str, **kw: Any) -> None:
        try:
            self._worker._dispatcher.call(method, **kw)
        except TransportError:
            # dispatcher down: completions are liveness-critical (an
            # uncompleted shard blocks job finish) — queue for redelivery
            # from the heartbeat loop once the dispatcher is back.
            if method == "complete_shard":
                self._worker._pending_control.append((method, kw))


class _SharedRunner(_TaskRunner):
    """Ephemeral data sharing (§3.5): read via the worker-global cache."""

    def __init__(self, worker: "Worker", spec: Dict[str, Any]):
        super().__init__()
        self._worker = worker
        self._cache = worker._get_or_create_cache(spec)
        self._cache.attach(spec["job_id"])
        # profile the shared producer pipeline (one ctx per cache, owned by
        # the worker; all attached jobs see the same rollup)
        ctx = worker._cache_ctxs.get(spec["cache_key"] or spec["dataset_id"])
        if ctx is not None:
            self._ctxs.append(ctx)

    def get(self, job_id: str, round_index: int, consumer_index: int):
        t0 = time.perf_counter()
        batch, eos = self._cache.read(job_id)
        self._worker.metrics.add(busy_time=time.perf_counter() - t0)
        if eos:
            # Single monotonic str store (running -> done) read by the
            # heartbeat thread; atomic under the GIL, so no lock needed.
            self.status = "done"  # analysis: allow(L001)
            return FetchStatus.END_OF_TASK, None
        return FetchStatus.OK, batch

    def buffer_occupancy(self) -> float:
        lo, hi = self._cache.window_range()
        return min(1.0, (hi - lo) / max(1, self._cache._capacity))


class _CoordinatedRunner(_TaskRunner):
    """Coordinated reads (§3.6): round-indexed same-bucket batch service.

    The element stream arrives pre-grouped (bucket_by_sequence_length →
    group_by_window(m) → flat_map upstream), so m consecutive elements form
    one round's same-bucket window.  All m consumers of round r read their
    ``consumer_index``-th element of that window from this worker.  Windows
    materialize lazily in round order; finished rounds are GC'd once every
    consumer has read its slot.
    """

    MAX_BUFFERED_ROUNDS = 8

    def __init__(self, worker: "Worker", spec: Dict[str, Any]):
        super().__init__()
        self._worker = worker
        self._m = max(1, int(spec["num_consumers"]))
        graph = Graph.from_bytes(spec["graph_bytes"]).bind_seed(spec["worker_seed"])
        self._it = build_iterator(graph, self._new_ctx())
        self._lock = threading.Lock()
        self._rounds: Dict[int, List[Element]] = {}  # round -> window
        self._consumed: Dict[int, set] = {}
        self._served_rounds: set = set()  # fully-consumed (GC'd) rounds
        self._exhausted = False
        self.evictions = 0

    def _materialize(self, round_index: int) -> bool:
        """Produce ONE window and bind it to ``round_index``.

        Global round numbers are striped across workers (round r is served by
        worker r mod n), so this worker only materializes windows for the
        rounds actually directed at it — window identity per round is what
        matters, not global ordering.

        Skew control: a fast consumer may request rounds far ahead of a slow
        one.  Evicting the slow consumer's pending window would strand it in
        a PENDING retry loop forever, so instead the fast consumer WAITS —
        we refuse to materialize more than MAX_BUFFERED_ROUNDS windows and
        return PENDING, bounding consumer skew (the paper's "predetermined
        round-robin client-side buffer slots" imply the same backpressure).
        """
        if len(self._rounds) >= self.MAX_BUFFERED_ROUNDS:
            self.evictions += 1  # counted as backpressure events
            return False
        window: List[Element] = []
        t0 = time.perf_counter()
        for _ in range(self._m):
            try:
                window.append(next(self._it))
            except StopIteration:
                self._exhausted = True
                break
        self._worker.metrics.add(busy_time=time.perf_counter() - t0)
        if len(window) < self._m:
            return False
        self._rounds[round_index] = window
        self._consumed[round_index] = set()
        self._worker.metrics.add(batches_produced=self._m)
        return True

    def extra_stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "coordinated_rounds_served": len(self._served_rounds),
                "coordinated_evictions": self.evictions,
                "coordinated_rounds_buffered": len(self._rounds),
            }

    def get(self, job_id: str, round_index: int, consumer_index: int):
        with self._lock:
            if round_index not in self._rounds:
                if round_index in self._served_rounds:
                    # consumer retry after GC (shouldn't happen with one read
                    # per consumer per round) — treat as pending
                    return FetchStatus.PENDING, None
                if self._exhausted or not self._materialize(round_index):
                    if self._exhausted:
                        self.status = "done"
                        return FetchStatus.END_OF_TASK, None
                    return FetchStatus.PENDING, None
            elem = self._rounds[round_index][consumer_index % self._m]
            self._consumed[round_index].add(consumer_index % self._m)
            if len(self._consumed[round_index]) == self._m:
                del self._rounds[round_index]
                del self._consumed[round_index]
                self._served_rounds.add(round_index)
            return FetchStatus.OK, elem

    def get_many(self, job_id: str, max_batch: int, timeout: float = 0.0):
        raise ValueError(
            "coordinated tasks are round-indexed; use get_element with a "
            "round_index (batched fetch would break same-bucket rounds)"
        )

    def buffer_occupancy(self) -> float:
        with self._lock:
            return len(self._rounds) / self.MAX_BUFFERED_ROUNDS


class _SnapshotStreamRunner:
    """Materializes ONE snapshot stream on this worker (repro.snapshot).

    Runs the stream's pipeline shards through the normal execution engine
    and appends the output into a ``StreamWriter`` (size-bounded chunks,
    atomic commit, manifest update, dispatcher ack).  ``resume_offset``
    skips the element prefix a previous owner already committed — streams
    are seeded per STREAM (not per worker), so a replacement re-produces
    the identical element sequence and commit races converge bytewise.
    """

    def __init__(self, worker: "Worker", spec: Dict[str, Any]):
        self._worker = worker
        self._spec = spec
        self.status = "running"  # running | done | stopped | failed
        self.error: Optional[str] = None
        self._stopped = threading.Event()
        self._ctxs: List[ExecContext] = []
        self.writer = StreamWriter(
            spec["path"],
            spec["stream_id"],
            codec=spec.get("codec"),
            chunk_bytes=spec["chunk_bytes"],
            committed=[ChunkRecord(*c) for c in spec.get("committed", [])],
            on_commit=self._report_commit,
        )
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stopped.set()

    def op_profile(self) -> List[Dict[str, Any]]:
        return merge_profiles(profile_ops(c.stats) for c in list(self._ctxs))

    def _should_stop(self) -> bool:
        return self._worker._stopping.is_set() or self._stopped.is_set()

    def _report_commit(self, rec: ChunkRecord) -> bool:
        sp = self._spec
        kw = dict(
            snapshot_id=sp["snapshot_id"],
            stream_id=sp["stream_id"],
            worker_id=self._worker.worker_id,
            seq=rec.seq,
            count=rec.count,
            nbytes=rec.nbytes,
        )
        if self._worker._pending_control:
            # earlier acks are still queued (dispatcher was down): keep this
            # one BEHIND them so the dispatcher sees seqs in order
            self._worker._pending_control.append(("snapshot_commit_chunk", kw))
            return True
        try:
            resp = self._worker._dispatcher.call("snapshot_commit_chunk", **kw)
        except TransportError:
            # dispatcher down: the chunk is already durable on shared
            # storage; queue the ack for redelivery (heartbeat loop drains
            # in order once the dispatcher is back) and keep writing —
            # the restored dispatcher validates seqs consecutively.
            self._worker._pending_control.append(("snapshot_commit_chunk", kw))
            return True
        if resp.get("ok"):
            return True
        if resp.get("retry"):
            # seq gap dispatcher-side: queued acks haven't drained yet
            self._worker._pending_control.append(("snapshot_commit_chunk", kw))
            return True
        return False  # reassigned: a replacement owns this stream now

    def _run(self) -> None:
        sp = self._spec
        graph = Graph.from_bytes(sp["graph_bytes"])
        skip = int(sp.get("resume_offset", 0))
        produced = 0
        try:
            for shard in sp["shards"]:
                g = graph.bind_shard(shard).bind_seed(sp["seed"])
                ctx = ExecContext()
                self._ctxs.append(ctx)  # retained for op profiling
                # stream affinity: the whole stream (all its shards) runs on
                # one executor lane — per-STREAM seeding stays intact, so a
                # pooled worker re-produces the byte-identical sequence an
                # in-thread one would.  The committed-prefix skip stays
                # parent-side: `produced` must count EVERY element.
                for _seq, elem in self._worker._executor.iterate(
                    g,
                    ctx,
                    affinity=f"snap/{sp['snapshot_id']}/{sp['stream_id']}",
                ):
                    if self._should_stop():
                        self.writer.abort()
                        self.status = "stopped"
                        return
                    produced += 1
                    if produced <= skip:
                        continue  # committed by a previous owner
                    t0 = time.perf_counter()
                    self.writer.append(elem)
                    self._worker.metrics.add(busy_time=time.perf_counter() - t0)
            self.writer.finish()
            self.status = "done"
            self._report_done()
        except StreamReassigned:
            self.status = "stopped"  # a replacement owns the stream now
        except Exception as e:  # surface in worker stats, don't kill the worker
            # Log-first-instance (the autoscaler's pattern): a stream that
            # fails every retry would otherwise die in silence — the status
            # travels in heartbeats, but nobody greps heartbeats.
            self._worker._note_error(
                f"snapshot stream {self._spec['stream_id']}", e
            )
            self.status = "failed"
            self.error = repr(e)

    def _report_done(self) -> None:
        kw = dict(
            snapshot_id=self._spec["snapshot_id"],
            stream_id=self._spec["stream_id"],
            worker_id=self._worker.worker_id,
        )
        if self._worker._pending_control:
            # keep the done-report ordered behind any queued chunk acks
            self._worker._pending_control.append(("snapshot_stream_done", kw))
            return
        try:
            self._worker._dispatcher.call("snapshot_stream_done", **kw)
        except TransportError:
            self._worker._pending_control.append(("snapshot_stream_done", kw))


class Worker:
    def __init__(
        self,
        dispatcher_address: str,
        worker_id: Optional[str] = None,
        transport: str = "inproc",
        buffer_size: int = 8,
        heartbeat_interval: float = 0.5,
        cache_capacity: int = 16,
        tags: Optional[Dict[str, Any]] = None,
        worker_processes: int = 0,
        host_key: Optional[str] = None,
    ):
        self.worker_id = worker_id or new_id("worker")
        self.registry = MetricsRegistry()
        self.metrics = WorkerMetrics(self.registry)
        self.tracer = Tracer(process=f"worker:{self.worker_id}")
        # fetches that asked for the shm ring and went inline, by reason
        self._shm_inline = self.registry.counter(
            "worker_shm_inline_total",
            "shm-channel fetches answered inline: ring_full, too_large, "
            "no_channel or error",
        )
        # worker_processes=0 keeps the paper's in-thread engine; N>=1 runs
        # pipelines in a pool of N forked children (data.executors)
        self._executor = make_executor(worker_processes, self.registry)
        # host identity for client-side shm:// co-location detection;
        # advertised in register_worker tags and the ping response
        self._host_key = host_key or socket.gethostname()
        # shm data-plane channels negotiated by co-located clients:
        # channel_id -> owned ShmRing (created by rpc_shm_attach)
        self._shm_channels: Dict[str, ShmRing] = {}
        self._cache_ctxs: Dict[str, ExecContext] = {}
        # rolling per-op rollup of pruned (finished) tasks, so the stall
        # report still names the bottleneck after a job completes; merged
        # by (op index, name) so it stays a handful of rows, not a history
        self._retired_profiles: List[Dict[str, Any]] = []
        self._dispatcher = Stub(dispatcher_address)
        self._transport = transport
        self._buffer_size = buffer_size
        self._hb_interval = heartbeat_interval
        self._cache_capacity = cache_capacity
        # host rides in tags (NOT journaled beyond worker_id/address — the
        # dispatcher keeps tags in memory only) so list_workers/negotiation
        # can see where each worker runs; explicit user tags win on clash
        self._tags = {"host": self._host_key, **(tags or {})}
        self._tasks: Dict[str, _TaskRunner] = {}
        self._task_specs: Dict[str, Dict[str, Any]] = {}
        self._caches: Dict[str, SlidingWindowCache] = {}
        # (snapshot_id, stream_id) -> runner materializing that stream
        self._snapshot_writers: Dict[Any, _SnapshotStreamRunner] = {}
        self._pending_control: deque = deque()  # control calls to redeliver
        # log-first-instance bookkeeping for background-thread exceptions
        self._logged_errors: Set[Tuple[str, Type[BaseException]]] = set()
        self._lock = threading.RLock()
        self._stopping = threading.Event()
        self._failed = threading.Event()  # simulated crash (tests/benchmarks)
        self._hb_thread: Optional[threading.Thread] = None
        self._tcp: Optional[TCPServer] = None
        self.address = ""

    # ------------------------------------------------------------------
    # Lifecycle
    # ------------------------------------------------------------------
    def start(self) -> "Worker":
        if self._transport == "tcp":
            self._tcp = TCPServer(self).start()
            self.address = self._tcp.address
        elif self._transport == "grpc":
            from .transport import GrpcServer

            self._tcp = GrpcServer(self).start()  # same stop()/address API
            self.address = self._tcp.address
        else:
            self.address = INPROC.bind(self.worker_id, self)
        resp = self._dispatcher.call(
            "register_worker",
            worker_id=self.worker_id,
            address=self.address,
            tags=self._tags,
        )
        for spec in resp.get("tasks", []):
            self._add_task(spec)
        for spec in resp.get("snapshot_streams", []):
            self._add_snapshot_stream(spec)
        self._hb_thread = threading.Thread(target=self._heartbeat_loop, daemon=True)
        self._hb_thread.start()
        return self

    def stop(self) -> None:
        self._stopping.set()
        with self._lock:
            for r in self._tasks.values():
                r.stop()
            for sr in self._snapshot_writers.values():
                sr.stop()
        if self._tcp is not None:
            self._tcp.stop()
        elif self.address:
            INPROC.unbind(self.worker_id)
        self._executor.stop()
        self._release_shm_channels()

    def fail(self) -> None:
        """Simulate a crash: stop serving and heartbeating WITHOUT dispatcher
        notification — failure must be detected via heartbeat timeout."""
        self._failed.set()
        self._stopping.set()
        if self._tcp is not None:
            self._tcp.stop()
        elif self.address:
            INPROC.unbind(self.worker_id)
        # a real crash takes the executor children and /dev/shm segments
        # with it (process death / OS reclaim); emulate that here so the
        # simulated crash leaks neither
        self._executor.stop()
        self._release_shm_channels()

    def _release_shm_channels(self) -> None:
        """Close + unlink every owned shm ring (attached clients keep their
        mappings alive until they release; the NAME disappears now)."""
        with self._lock:
            rings = list(self._shm_channels.values())
            self._shm_channels.clear()
        for ring in rings:
            ring.close()
            ring.unlink()

    # ------------------------------------------------------------------
    # Task management
    # ------------------------------------------------------------------
    def _add_task(self, spec: Dict[str, Any]) -> None:
        with self._lock:
            tid = spec["task_id"]
            if tid in self._tasks:
                return
            if spec.get("shared"):
                runner: _TaskRunner = _SharedRunner(self, spec)
            elif spec.get("round_robin"):
                runner = _CoordinatedRunner(self, spec)
            elif spec["policy"] == ShardingPolicy.DYNAMIC.value:
                runner = _DynamicRunner(self, spec, self._buffer_size)
            else:
                runner = _BufferedRunner(self, spec, self._buffer_size)
            self._tasks[tid] = runner
            self._task_specs[tid] = spec

    def _add_snapshot_stream(self, spec: Dict[str, Any]) -> None:
        key = (spec["snapshot_id"], spec["stream_id"])
        with self._lock:
            existing = self._snapshot_writers.get(key)
            if existing is not None and existing.status in ("running", "done"):
                return  # re-delivery (e.g. after a dispatcher restart)
            self._snapshot_writers[key] = _SnapshotStreamRunner(self, spec)

    def _get_or_create_cache(self, spec: Dict[str, Any]) -> SlidingWindowCache:
        key = spec["cache_key"] or spec["dataset_id"]
        with self._lock:
            if key not in self._caches:
                graph = Graph.from_bytes(spec["graph_bytes"]).bind_seed(
                    spec["worker_seed"]
                )
                ctx = ExecContext()
                self._cache_ctxs[key] = ctx  # retained for op profiling
                producer = build_iterator(graph, ctx)
                self._caches[key] = SlidingWindowCache(
                    producer, capacity=self._cache_capacity
                )
            return self._caches[key]

    def _heartbeat_loop(self) -> None:
        backoff = Backoff(
            base=self._hb_interval, cap=max(1.0, 4 * self._hb_interval)
        )
        delay = self._hb_interval
        while not self._stopping.wait(delay):
            try:
                self._heartbeat_once()
            except TransportError:
                # dispatcher down: keep serving current tasks (§3.4) and
                # retry with jittered backoff — a whole fleet reconnecting
                # to a freshly promoted standby must not thundering-herd it
                delay = backoff.next_delay()
                continue
            backoff.reset()
            delay = self._hb_interval

    def _heartbeat_once(self) -> None:
        """One heartbeat round-trip; raises TransportError when the
        dispatcher is unreachable (the loop above backs off and retries)."""
        while self._pending_control:
            method, kw = self._pending_control[0]
            resp = self._dispatcher.call(method, **kw)  # raises if still down
            self._pending_control.popleft()
            if resp and resp.get("reassigned") and "snapshot_id" in kw:
                # a queued snapshot ack answered "reassigned": a
                # replacement owns the stream — stop our writer
                # (the direct-call path learns this in _report_commit;
                # the queued path must honor it too)
                with self._lock:
                    r = self._snapshot_writers.get(
                        (kw["snapshot_id"], kw["stream_id"])
                    )
                if r is not None:
                    r.stop()
        with self._lock:
            occ = [r.buffer_occupancy() for r in self._tasks.values()]
            completed = [
                tid for tid, r in self._tasks.items() if r.status == "done"
            ]
            # sharing-efficiency counters ride along with every
            # heartbeat so the dispatcher (and the autocache policy)
            # can observe per-fingerprint cache behavior (§3.5)
            cache_stats = {
                k: dict(vars(c.stats), num_jobs=c.num_jobs)
                for k, c in self._caches.items()
            }
            # streams whose writer died on an exception: hand them
            # back so the dispatcher can reassign (possibly to us —
            # a fresh runner retries from the committed offset)
            failed_streams = [
                list(key)
                for key, r in self._snapshot_writers.items()
                if r.status == "failed"
            ]
        resp = self._dispatcher.call(
            "worker_heartbeat",
            worker_id=self.worker_id,
            buffer_occupancy=sum(occ) / len(occ) if occ else 0.0,
            cpu_busy=self.metrics.snapshot()["busy_time"],
            completed_tasks=completed,
            cache_stats=cache_stats,
            failed_streams=failed_streams,
        )
        if failed_streams:
            # the dispatcher has released them; drop the dead
            # runners so a re-assignment starts a fresh one
            with self._lock:
                for key in failed_streams:
                    r = self._snapshot_writers.get(tuple(key))
                    if r is not None and r.status == "failed":
                        del self._snapshot_writers[tuple(key)]
        if resp.get("reregister"):
            resp = self._dispatcher.call(
                "register_worker",
                worker_id=self.worker_id,
                address=self.address,
                tags=self._tags,
            )
            for spec in resp.get("tasks", []):
                self._add_task(spec)
            for spec in resp.get("snapshot_streams", []):
                self._add_snapshot_stream(spec)
            return
        for spec in resp.get("new_tasks", []):
            self._add_task(spec)
        for spec in resp.get("snapshot_streams", []):
            self._add_snapshot_stream(spec)
        valid = resp.get("valid_tasks")
        if valid is not None:
            self._prune_tasks(set(valid))

    def drain_stats(self) -> Dict[str, float]:
        """What scale-in victim selection needs to know (see
        ``LocalOrchestrator.pick_removable``): removing this worker while
        it holds an unfinished snapshot stream forces a stream
        reassignment + re-production, and removing it while it buffers
        unconsumed coordinated rounds stalls every consumer of those
        rounds — both strictly worse than draining an idle worker."""
        with self._lock:
            streams = sum(
                1 for r in self._snapshot_writers.values() if r.status == "running"
            )
            rounds = sum(
                int(r.extra_stats().get("coordinated_rounds_buffered", 0))
                for r in self._tasks.values()
            )
            occ = [r.buffer_occupancy() for r in self._tasks.values()]
        return {
            "active_snapshot_streams": streams,
            "pending_coordinated_rounds": rounds,
            "buffer_occupancy": sum(occ) / len(occ) if occ else 0.0,
        }

    def _note_error(self, context: str, exc: BaseException) -> None:
        """Log the FIRST instance of each (context, exception type) from a
        background thread; repeats are suppressed (the retry loops would
        otherwise flood the log at their poll interval).  Every instance is
        counted in the registry so metrics_dump shows chronic failures the
        log-once policy hides."""
        self.registry.counter(
            "worker_errors_total",
            "swallowed background errors in the worker, by context",
        ).labels(context=context, kind=type(exc).__name__).inc()
        key = (context, type(exc))
        with self._lock:
            if key in self._logged_errors:
                return
            self._logged_errors.add(key)
        logger.warning(
            "worker %s: %s failed with %r (suppressing repeats)",
            self.worker_id, context, exc,
        )

    def _prune_tasks(self, valid: set) -> None:
        """Drop orphaned tasks (finished/garbage-collected jobs), folding
        their op profiles into the retired rollup first."""
        with self._lock:
            pruned = []
            for tid in list(self._tasks):
                if tid not in valid:
                    pruned.append(self._tasks[tid].op_profile())
                    self._tasks[tid].stop()
                    del self._tasks[tid]
                    self._task_specs.pop(tid, None)
            if pruned:
                self._retired_profiles = merge_profiles(
                    [self._retired_profiles, *pruned]
                )

    # ------------------------------------------------------------------
    # RPC entry point (data plane)
    # ------------------------------------------------------------------
    def handle(self, method: str, payload: Dict[str, Any]) -> Dict[str, Any]:
        # Same getattr dispatch as Dispatcher.handle: one rpc_* method per
        # wire method, so the RPC-conformance pass sees one uniform surface.
        if self._failed.is_set():
            raise TransportError(f"worker {self.worker_id} is down")
        fn = getattr(self, f"rpc_{method}", None)
        if fn is None:
            raise ValueError(f"worker: unknown method {method}")
        return fn(**payload)

    def rpc_ping(self) -> Dict[str, Any]:
        """Liveness + data-plane version probe (used at worker bring-up and
        by clients negotiating the shm:// data plane: ``host`` is compared
        against the client's own host key, ``shm`` says whether this worker
        can serve ring descriptors at all)."""
        return {
            "worker_id": self.worker_id,
            "data_plane_version": DATA_PLANE_VERSION,
            "host": self._host_key,
            "shm": not self._transport.startswith("inproc"),
        }

    # maximum rings one worker will own at a time: each co-located client
    # session holds one per fetched task, so this bounds /dev/shm usage
    # under a pathological client that attaches without detaching
    MAX_SHM_CHANNELS = 64

    def rpc_shm_attach(
        self,
        slots: int = DEFAULT_SLOTS,
        slot_bytes: int = DEFAULT_SLOT_BYTES,
    ) -> Dict[str, Any]:
        """Create one shm ring for a co-located client (data plane v2+shm).

        Returns ``{ok, channel, segment, slots, slot_bytes}``; the client
        attaches to ``segment`` and passes ``channel`` on every
        ``get_elements`` call that should answer with a ring descriptor.
        Refusals (``ok=False``) mean "use the inline data plane": worker at
        channel capacity, oversize geometry, or shm unavailable.
        """
        if self._stopping.is_set():
            return {"ok": False, "error": "worker stopping"}
        try:
            with self._lock:
                if len(self._shm_channels) >= self.MAX_SHM_CHANNELS:
                    return {"ok": False, "error": "shm channel limit reached"}
            ring = ShmRing.create(slots=int(slots), slot_bytes=int(slot_bytes))
        except (ShmRingError, OSError, ValueError) as e:
            return {"ok": False, "error": repr(e)}
        channel = new_id("shmch")
        with self._lock:
            self._shm_channels[channel] = ring
        return {
            "ok": True,
            "channel": channel,
            "segment": ring.name,
            "slots": ring.slots,
            "slot_bytes": ring.slot_bytes,
        }

    def rpc_shm_detach(self, channel: str) -> Dict[str, Any]:
        """Tear down a ring created by ``shm_attach`` (client session end).

        Idempotent; unknown channels are fine (the worker may have released
        them already at stop()).  Segments of channels never detached are
        reclaimed when the worker stops — the client side only loses the
        fast path, never data.
        """
        with self._lock:
            ring = self._shm_channels.pop(channel, None)
        if ring is not None:
            ring.close()
            ring.unlink()
        return {"ok": True}

    def _shm_serve(
        self,
        out: Dict[str, Any],
        channel: str,
        elems: List[Element],
        compression: Optional[str],
    ) -> bool:
        """Try to answer a fetch via the shm ring; False means go inline.

        Zero-copy path (no codec): the batch frame is encoded straight into
        the leased slot (no intermediate ``bytes``).  Compressed path: the
        frame is built and compressed in memory, then copied into the slot —
        still one socket payload saved, but the client must copy out to
        decompress, so ``shm_codec`` rides in the descriptor.
        """
        with self._lock:
            ring = self._shm_channels.get(channel)
        if ring is None:
            self._shm_inline.labels(reason="no_channel").inc()
            return False
        slot = ring.try_acquire()
        if slot is None:  # ring full: consumer behind (or leases lost)
            self._shm_inline.labels(reason="ring_full").inc()
            return False
        try:
            view = ring.slot_view(slot)
            if compression:
                try:
                    frame = compress(encode_elements(elems), compression)
                except ValueError:
                    frame = compress(encode_elements(elems), None)
                if len(frame) > ring.slot_bytes:
                    raise FrameTooLarge(len(frame))
                view[: len(frame)] = frame
                length = len(frame)
                out["shm_codec"] = True
            else:
                length = encode_elements_into(elems, view)
        except FrameTooLarge:
            ring.cancel(slot)
            out.pop("shm_codec", None)
            self._shm_inline.labels(reason="too_large").inc()
            return False
        except Exception as e:  # never poison the fetch path: go inline
            ring.cancel(slot)
            out.pop("shm_codec", None)
            self._note_error("shm serve", e)
            self._shm_inline.labels(reason="error").inc()
            return False
        out["shm_slot"] = slot
        out["shm_len"] = length
        out["shm_seq"] = ring.commit(slot, length)
        return True

    def rpc_get_elements(
        self,
        task_id: str,
        job_id: str = "",
        max_batch: int = DEFAULT_MAX_BATCH,
        timeout: float = 0.0,
        shm_channel: str = "",
        trace: Optional[Dict[str, Any]] = None,
    ) -> Dict[str, Any]:
        """Batched fetch (data plane v2): drain up to ``max_batch`` elements.

        ``timeout`` long-polls: the call may wait up to that many seconds
        for the FIRST element before answering PENDING, sparing the client a
        retry/backoff round trip.  With a negotiated codec the whole batch
        is one compressed frame (compressed once, worker-side).

        ``shm_channel`` (from ``shm_attach``) asks for a ring descriptor:
        when a slot is free and the frame fits, the batch is encoded
        directly into shared memory and the response carries
        ``shm_slot``/``shm_len``/``shm_seq`` (plus ``shm_codec`` when the
        frame is compressed) instead of inline bytes.  Ring full, frame too
        large, or unknown channel all degrade to the inline payload — the
        caller never has to retry.

        ``trace`` is present only on SAMPLED fetches (client-minted span
        context): the unsampled hot path pays exactly one None check.
        """
        self.metrics.add(rpc_count=1)
        ctx = TraceContext.from_wire(trace) if trace else None
        sctx = ctx.child() if ctx is not None else None  # our serve span
        wall = time.time() if sctx is not None else 0.0
        t0 = time.perf_counter()
        with self._lock:
            runner = self._tasks.get(task_id)
            spec = self._task_specs.get(task_id)
        if runner is None:
            return {"status": FetchStatus.PENDING.value, "count": 0}
        # the long-poll: until the first element is there or the poll ends
        with self.tracer.span("worker.wait", sctx):
            status, elems = runner.get_many(
                job_id, max(1, int(max_batch)), timeout=min(1.0, float(timeout))
            )
        out: Dict[str, Any] = {"status": status.value, "count": len(elems)}
        nbytes = 0
        if elems:
            nbytes = sum(element_nbytes(e) for e in elems)
            self.metrics.add(batches_served=len(elems), bytes_served=nbytes)
            out["nbytes"] = nbytes
            compression = spec.get("compression") if spec else None
            in_ring = False
            if shm_channel:
                with self.tracer.span("worker.encode", sctx, nbytes=nbytes, path="shm"):
                    in_ring = self._shm_serve(out, shm_channel, elems, compression)
            if in_ring:
                pass  # descriptor is in `out`; nothing travels inline
            elif compression:
                with self.tracer.span(
                    "worker.encode", sctx, nbytes=nbytes, codec=compression
                ):
                    encoded = encode_elements(elems)
                    try:
                        frame = compress(encoded, compression)
                    except ValueError:
                        # the negotiated codec is not in THIS worker's
                        # registry (heterogeneous pool): ship uncompressed
                        # rather than fail every fetch — frames are
                        # tag-prefixed, so the client decodes either way.
                        frame = compress(encoded, None)
                out["batch_compressed"] = frame
            else:
                out["elements"] = elems
        if sctx is not None:
            self.tracer.record(
                "worker.serve",
                sctx,
                wall,
                time.perf_counter() - t0,
                parent_id=ctx.span_id,
                task_id=task_id,
                count=len(elems),
                nbytes=nbytes,
                status=status.value,
            )
        return out

    def rpc_get_element(
        self,
        task_id: str,
        job_id: str = "",
        round_index: int = -1,
        consumer_index: int = -1,
        trace: Optional[Dict[str, Any]] = None,
    ) -> Dict[str, Any]:
        self.metrics.add(rpc_count=1)
        ctx = TraceContext.from_wire(trace) if trace else None
        sctx = ctx.child() if ctx is not None else None
        wall = time.time() if sctx is not None else 0.0
        t0 = time.perf_counter()
        with self._lock:
            runner = self._tasks.get(task_id)
            spec = self._task_specs.get(task_id)
        if runner is None:
            return {"status": FetchStatus.PENDING.value}
        status, elem = runner.get(job_id, round_index, consumer_index)
        out: Dict[str, Any] = {"status": status.value}
        if elem is not None:
            nbytes = element_nbytes(elem)
            self.metrics.add(batches_served=1, bytes_served=nbytes)
            if spec and spec.get("compression"):
                out["element_compressed"] = compress(
                    encode_element(elem), spec["compression"]
                )
            else:
                out["element"] = elem
            out["nbytes"] = nbytes
        if sctx is not None:
            self.tracer.record(
                "worker.serve",
                sctx,
                wall,
                time.perf_counter() - t0,
                parent_id=ctx.span_id,
                task_id=task_id,
                round_index=round_index,
                status=status.value,
            )
        return out

    def rpc_stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "worker_id": self.worker_id,
                "metrics": self.metrics.snapshot(),
                "tasks": {
                    tid: {
                        "status": r.status,
                        "occupancy": r.buffer_occupancy(),
                        "kind": type(r).__name__,
                        **r.extra_stats(),
                    }
                    for tid, r in self._tasks.items()
                },
                "caches": {
                    k: vars(c.stats).copy() for k, c in self._caches.items()
                },
                "snapshot_streams": {
                    f"{sid}/{stream_id}": {
                        "status": r.status,
                        "elements": r.writer.stats.elements,
                        "chunks": r.writer.stats.chunks,
                        "bytes": r.writer.stats.bytes_written,
                        "error": r.error,
                    }
                    for (sid, stream_id), r in self._snapshot_writers.items()
                },
            }

    def rpc_metrics_dump(self) -> Dict[str, Any]:
        """Observability scrape: registry snapshot + per-op pipeline
        profiles + the worker-level stall-attribution report (the op whose
        standalone capacity bounds throughput).  Read-mostly and lock-light:
        safe to poll at dashboard rates while the data plane is hot."""
        with self._lock:
            runners = dict(self._tasks)
            specs = dict(self._task_specs)
            stream_runners = list(self._snapshot_writers.values())
            retired = list(self._retired_profiles)
        tasks: Dict[str, Any] = {}
        profiles: List[List[Dict[str, Any]]] = []
        for tid, r in runners.items():
            prof = r.op_profile()
            profiles.append(prof)
            tasks[tid] = {
                "job_id": (specs.get(tid) or {}).get("job_id"),
                "status": r.status,
                "occupancy": r.buffer_occupancy(),
                "profile": prof,
            }
        for sr in stream_runners:
            profiles.append(sr.op_profile())
        profiles.append(retired)
        return {
            "worker_id": self.worker_id,
            "registry": self.registry.snapshot(),
            "stall_report": attribute_stalls(merge_profiles(profiles)),
            "tasks": tasks,
            "trace": {"buffered": len(self.tracer), "dropped": self.tracer.dropped},
        }

    def rpc_trace_dump(self, max_spans: int = 0) -> Dict[str, Any]:
        """Drain this worker's span ring buffer (consumed by
        ``repro.obs.export``; draining keeps repeat exports disjoint)."""
        return {
            "process": self.tracer.process,
            "spans": self.tracer.drain(max_spans),
        }
