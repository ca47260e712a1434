"""tf.data-service client (paper §3.1): fetches preprocessed batches.

Two read modes:

* **parallel fetch** (default): a *window* of ``fetch_window`` fetcher
  threads per worker task, each with its own connection, keeps that many
  ``get_elements`` requests outstanding against the worker — transfer
  overlaps with worker-side production and client-side decode, and each RPC
  drains up to ``max_batch`` elements, amortizing per-RPC overhead.  Order
  across (and now within) workers is unspecified — the paper's
  relaxed-visitation stance makes this fine.  Workers that predate the
  batched protocol are detected via the unknown-method error and served by
  the single-element ``get_element`` fallback.
* **coordinated reads** (``num_consumers > 0``): strict round-robin — for
  training step r every consumer fetches its ``consumer_index`` slot of round
  r from worker ``sorted_workers[r % n]``, guaranteeing same-bucket batches
  across all clients in the step (§3.6).  Round identity is per-element, so
  this path always uses single-element fetch.

Compression is negotiated per job: the client requests a codec by name (or
``"auto"``); the dispatcher resolves it against the deployment's codec
registry (``core.codecs``) and the agreed name is applied worker-side.
Frames are tag-prefixed, so decode never needs out-of-band codec state.

The client records stall time (time blocked waiting for data): the paper's
"input-bound" diagnosis is ``stall_time / wall_time``.
"""
from __future__ import annotations

import queue
import socket
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional

from ..data.elements import (
    Element,
    copy_element,
    decode_element,
    decode_elements,
)
from ..data.graph import Graph
from ..obs.registry import MetricsRegistry
from ..obs.tracing import TraceContext, Tracer
from .protocol import (
    DEFAULT_FETCH_WINDOW,
    DEFAULT_MAX_BATCH,
    DEFAULT_POLL_TIMEOUT,
    FetchStatus,
    new_id,
)
from .codecs import available_codecs
from .shm_ring import ShmRing
from .transport import Backoff, Stub, TransportError, decompress


class ClientMetrics:
    """Session counters, now backed by a :class:`MetricsRegistry`.

    The old dataclass was mutated with bare ``+=`` from every fetcher
    thread in the window — read-modify-writes that lose updates under
    thread switches.  Mutation now goes through :meth:`add` (per-series
    locked, exact); reads stay attribute-style (``metrics.batches``) via
    ``__getattr__`` so callers and tests are unchanged, and the same
    series surface in the registry scraped by ``metrics_dump`` dashboards.
    """

    _FIELDS = (
        "batches",
        "bytes_received",
        "stall_time",
        "fetch_time",
        "rpcs",
        "retries",
        "fallback_tasks",  # tasks demoted to the single-element v1 path
        "shm_tasks",  # tasks that negotiated a shm:// ring data plane
        "shm_batches",  # OK responses served via a ring descriptor
    )

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self.registry = registry if registry is not None else MetricsRegistry()
        self._series = {
            name: self.registry.counter(f"client_{name}", "client session counter")
            for name in self._FIELDS
        }

    def add(self, **deltas: float) -> None:
        for name, delta in deltas.items():
            self._series[name].add(delta)

    def __getattr__(self, name: str):
        series = self.__dict__.get("_series") or {}
        if name in series:
            return series[name].value
        raise AttributeError(name)

    def snapshot(self) -> Dict[str, float]:
        return {name: s.value for name, s in self._series.items()}


@dataclass
class _FetchError:
    """Queued in place of an element to surface a fatal decode error."""

    task_id: str
    error: Exception


@dataclass
class _ShmRelease:
    """Queued AFTER a zero-copy batch: the consumer loop releases the ring
    slot once it has advanced past every element borrowed from it."""

    ring: ShmRing
    slot: int


@dataclass
class _TaskHandle:
    task_id: str
    job_id: str
    worker_id: str
    worker_address: str
    stub: Stub
    done: bool = False
    failed: bool = False
    batched: bool = True  # flips False when the worker lacks get_elements
    poisoned: bool = False  # undecodable responses: never resurrect
    # shm:// data-plane negotiation state (per task handle; the fetch
    # window's threads share the ring — slot leases are per-descriptor)
    shm_state: str = "unknown"  # unknown | active | off
    shm_channel: str = ""
    shm_ring: Optional[ShmRing] = None
    shm_lock: threading.Lock = field(default_factory=threading.Lock)


class DataServiceClient:
    """One iteration session over a service-backed dataset.

    Data-plane knobs (parallel-fetch mode):

    * ``buffer_size``  — capacity of the client-side element queue the
      training loop consumes from.
    * ``fetch_window`` — outstanding ``get_elements`` requests kept in
      flight per worker task; each slot is a thread with its own
      connection, so transfer pipelines with decode and production.
    * ``max_batch``    — maximum elements a worker may return per RPC.
    * ``compression``  — requested codec name (``None``/``"none"``,
      ``"zlib"``, ``"lz4"``, or ``"auto"``); the dispatcher negotiates the
      codec actually applied (``negotiated_compression`` after iteration
      starts) against what the deployment has available.

    Tasks on workers that predate the batched protocol automatically fall
    back to one-element-per-RPC ``get_element`` (``metrics.fallback_tasks``
    counts them); coordinated reads always use the single-element path
    because rounds are element-indexed.
    """

    _END = object()

    def __init__(
        self,
        dispatcher_address: str,
        graph: Graph,
        processing_mode: str = "off",
        job_name: Optional[str] = None,
        num_consumers: int = 0,
        consumer_index: int = 0,
        sharing: bool = False,
        compression: Optional[str] = None,
        target_workers: str = "any",
        max_workers: int = 0,
        weight: float = 1.0,
        resume_offsets: bool = False,
        autocache: bool = False,
        buffer_size: int = 8,
        fetch_window: int = DEFAULT_FETCH_WINDOW,
        max_batch: int = DEFAULT_MAX_BATCH,
        prefer_batched: bool = True,
        heartbeat_interval: float = 0.3,
        optimize: bool = True,
        trace_sample: float = 0.0,
        shm: bool = True,
        zero_copy: bool = False,
        host_key: Optional[str] = None,
    ):
        self.client_id = new_id("client")
        self.metrics = ClientMetrics()
        # trace_sample > 0 mints a session-level root trace at registration
        # (journaled dispatcher-side with the job) and samples that fraction
        # of element-batch fetches into cross-process spans
        self.tracer = Tracer(
            process=f"client:{self.client_id}", sample_rate=trace_sample
        )
        self.trace_root: Optional[TraceContext] = None
        self._dispatcher = Stub(dispatcher_address)
        # the RAW graph is registered; the dispatcher optimizes it once so
        # identical pipelines from different jobs share a dataset_id (§3.5)
        self._graph = graph
        self._mode = processing_mode
        self._job_name = job_name
        self._m = num_consumers
        self._consumer_index = consumer_index
        self._sharing = sharing
        self._compression = compression
        self._target_workers = target_workers
        self._max_workers = max_workers
        self._weight = weight
        self._resume_offsets = resume_offsets
        self._autocache = autocache
        self._buffer_size = buffer_size
        self._fetch_window = max(1, fetch_window)
        self._max_batch = max(1, max_batch)
        # False forces the v1 one-element-per-RPC path from the start:
        # benchmark baseline and mixed-version deployment drills.
        self._prefer_batched = prefer_batched
        self._hb_interval = heartbeat_interval
        # shm:// negotiation: enabled by default; rings are only attached to
        # workers whose ping() host matches ours AND whose control channel is
        # a real socket (inproc workers are already zero-copy).
        self._shm_enabled = shm
        # zero_copy=True hands out decoded views that BORROW the ring slot
        # ("valid until the next element") instead of copying out — the
        # DeviceFeeder path, where every element is device_put immediately.
        self._zero_copy = zero_copy
        # ids of decoded elements that borrow a ring slot, and whether the
        # element last yielded was one of them (its lease goes back to the
        # worker when the consumer asks for the next element)
        self._lent_ids: set = set()
        self.borrowed = False
        self._host_key = host_key or socket.gethostname()
        self.negotiated_compression: Optional[str] = None
        # the dispatcher's autocache verdict for this job, once registered:
        # "compute" | "write_through" | "read" | None (autocache off)
        self.autocache_decision: Optional[str] = None

        # latest feed-side stall window (set by repro.feed.DeviceFeeder via
        # report_feed_stall); forwarded on every dispatcher heartbeat as the
        # autoscaler's client-latency signal
        self._feed_stats: Optional[Dict[str, float]] = None

        self._tasks: Dict[str, _TaskHandle] = {}
        self._tasks_lock = threading.Lock()
        self._active_fetchers = 0  # window threads still running (all tasks)
        self._queue: "queue.Queue[Any]" = queue.Queue(maxsize=max(2, buffer_size))
        self._job_finished = threading.Event()
        self._closed = threading.Event()
        self._fetchers: Dict[str, List[threading.Thread]] = {}
        self._job_id = ""

    # ------------------------------------------------------------------
    # Session setup
    # ------------------------------------------------------------------
    def _register(self) -> None:
        resp = self._dispatcher.call(
            "get_or_register_dataset", graph_bytes=self._graph.to_bytes()
        )
        self.trace_root = self.tracer.start_trace()
        kw: Dict[str, Any] = dict(
            dataset_id=resp["dataset_id"],
            job_name=self._job_name,
            policy=self._mode,
            num_consumers=self._m,
            sharing=self._sharing,
            compression=self._compression,
            max_workers=self._max_workers,
            weight=self._weight,
            resume_offsets=self._resume_offsets,
            client_id=self.client_id,
            client_codecs=available_codecs(),  # negotiation: what WE decode
            autocache=self._autocache,
        )
        if self.trace_root is not None:
            # the job-level root context: journaled with job_created, so a
            # promoted standby keeps stamping spans with the same trace_id
            kw["trace"] = self.trace_root.to_wire()
            # zero-duration root marker, recorded BEFORE anything downstream
            # can parent to it, so every span's parent chain resolves even
            # if the dispatcher crashes mid-registration
            self.tracer.record(
                "client.session",
                self.trace_root,
                time.time(),
                0.0,
                client_id=self.client_id,
            )
        view = self._dispatcher.call("get_or_create_job", **kw)
        self._job_id = view["job_id"]
        self.negotiated_compression = view.get("compression")
        self.autocache_decision = view.get("autocache")
        self._sync_tasks(view)

    def _sync_tasks(self, view: Dict[str, Any]) -> None:
        with self._tasks_lock:
            seen = set()
            for t in view["tasks"]:
                seen.add(t["task_id"])
                h = self._tasks.get(t["task_id"])
                if h is None:
                    h = self._tasks[t["task_id"]] = _TaskHandle(
                        task_id=t["task_id"],
                        job_id=t["job_id"],
                        worker_id=t["worker_id"],
                        worker_address=t["worker_address"],
                        stub=Stub(t["worker_address"]),
                        batched=self._prefer_batched,
                    )
                    if self._m == 0 and not self._closed.is_set():
                        self._spawn_fetcher(h)
                elif h.failed and not h.done and not h.poisoned:
                    # the dispatcher re-listed a task we gave up on (e.g. the
                    # transient window right after a dispatcher restart when
                    # workers had not yet re-registered): resurrect it.
                    # Poisoned tasks (undecodable responses from a healthy
                    # worker) stay dead — resurrecting would drain-and-drop
                    # the worker's elements in an endless loop.
                    h.failed = False
                    if self._m == 0 and not self._closed.is_set():
                        self._spawn_fetcher(h)
            # tasks whose worker died are dropped by the dispatcher view
            for tid, h in self._tasks.items():
                if tid not in seen and not h.done:
                    h.failed = True
            if view.get("finished"):
                self._job_finished.set()

    def report_feed_stall(self, stats: Dict[str, float]) -> None:
        """Feed-side stall hook (``repro.feed``): record the consumer's
        latest stall window; the heartbeat loop forwards it so the
        dispatcher (and through it the autoscaler) sees what the
        *accelerator* observes, not just worker buffer occupancy."""
        self._feed_stats = dict(stats)

    def _heartbeat_loop(self) -> None:
        backoff = Backoff(
            base=self._hb_interval, cap=max(1.0, 4 * self._hb_interval)
        )
        delay = self._hb_interval
        while not self._closed.wait(delay):
            try:
                kw: Dict[str, Any] = dict(
                    job_id=self._job_id, client_id=self.client_id
                )
                # report-once: each stall window is forwarded on ONE
                # heartbeat, so a consumer that stops stepping stops
                # reporting and the dispatcher's TTL ages the job's
                # aggregate out — re-sending the last window forever would
                # pin a stale "starving" signal on the autoscaler
                stall_stats, self._feed_stats = self._feed_stats, None
                if stall_stats is not None:
                    kw["stall_stats"] = stall_stats
                hbctx = (
                    self.trace_root.child()
                    if self.trace_root is not None
                    else None
                )
                if hbctx is not None:
                    kw["trace"] = hbctx.to_wire()
                wall, t0 = time.time(), time.perf_counter()
                try:
                    view = self._dispatcher.call("client_heartbeat", **kw)
                finally:
                    # record even when the call dies mid-flight: the
                    # dispatcher may have recorded its child span before
                    # crashing, and that child's parent must exist
                    if hbctx is not None:
                        self.tracer.record(
                            "client.heartbeat",
                            hbctx,
                            wall,
                            time.perf_counter() - t0,
                            parent_id=self.trace_root.span_id,
                            job_id=self._job_id,
                        )
                self._sync_tasks(view)
            except TransportError:
                # dispatcher down: keep consuming from workers (§3.4);
                # jittered backoff avoids stampeding a promoted standby
                delay = backoff.next_delay()
                continue
            backoff.reset()
            delay = self._hb_interval
            if self._job_finished.is_set():
                return

    # ------------------------------------------------------------------
    # Parallel-fetch mode (pipelined, batched)
    # ------------------------------------------------------------------
    def _spawn_fetcher(self, handle: _TaskHandle) -> None:
        """Start ``fetch_window`` fetcher threads for one task.

        Each thread owns a private ``Stub`` (its own connection over
        ``tcp://``/``grpc://``), so the window's requests genuinely overlap
        on the wire instead of serializing on one socket.
        """
        threads = []
        for _ in range(self._fetch_window):
            stub = Stub(handle.worker_address)
            th = threading.Thread(
                target=self._fetch_run, args=(handle, stub), daemon=True
            )
            threads.append(th)
            self._active_fetchers += 1  # caller holds _tasks_lock
            th.start()
        self._fetchers[handle.task_id] = threads

    def _fetch_run(self, handle: _TaskHandle, stub: Stub) -> None:
        """Thread body: fetch loop + completion accounting.

        The END sentinel may only be enqueued once NO fetcher thread is
        still running: with ``fetch_window > 1`` a sibling thread can reach
        END_OF_TASK while this thread still holds decoded elements it has
        not enqueued yet — finishing on task state alone would drop them.
        """
        try:
            self._fetch_loop(handle, stub)
        finally:
            with self._tasks_lock:
                self._active_fetchers -= 1
            self._maybe_finish()

    def _negotiate_shm(self, handle: _TaskHandle, stub: Stub) -> None:
        """Decide the task's data plane ONCE per handle (first fetcher wins).

        shm:// is used only when (a) this session enables it, (b) the
        worker's control channel is a real socket (inproc is already
        zero-copy), and (c) the worker's advertised host matches ours.
        Anything going wrong — old worker without the RPC, attach refusal,
        segment unreachable — leaves the handle on the inline data plane;
        negotiation never fails a fetch.
        """
        with handle.shm_lock:
            if handle.shm_state != "unknown":
                return
            handle.shm_state = "off"
            if not self._shm_enabled or self._m > 0:
                return
            if handle.worker_address.startswith("inproc://"):
                return
            try:
                pong = stub.call("ping")
                if not pong.get("shm") or pong.get("host") != self._host_key:
                    return
                resp = stub.call("shm_attach")
                if not resp.get("ok"):
                    return
                ring = ShmRing.attach(resp["segment"])
            except Exception:
                return  # any failure: stay on the inline plane
            handle.shm_ring = ring
            handle.shm_channel = resp["channel"]
            handle.shm_state = "active"
            self.metrics.add(shm_tasks=1)

    def _fetch_loop(self, handle: _TaskHandle, stub: Stub) -> None:
        """One slot of the task's prefetch window.

        Prefers the batched ``get_elements`` RPC; demotes the whole task to
        the single-element v1 path when the worker reports an unknown
        method.  A transport failure marks the task failed — the dispatcher
        notices the dead worker and re-lists tasks via heartbeat (worker
        churn also tears the shm ring down with the handle: the replacement
        task renegotiates from scratch, so shm:// degrades to tcp://
        mid-job without consumer-visible effect).
        """
        self._negotiate_shm(handle, stub)
        backoff = 0.005
        while not self._closed.is_set() and not handle.done and not handle.failed:
            # per-element-batch sampling decision: unsampled fetches carry
            # no trace key at all, keeping the hot-path payload unchanged
            root = (
                self.trace_root
                if self.trace_root is not None and self.tracer.should_sample()
                else None
            )
            try:
                t0 = time.perf_counter()
                # the span is recorded even on failure: the worker may have
                # recorded children before the response was lost
                with self.tracer.span(
                    "client.fetch", root, task_id=handle.task_id
                ) as ctx:
                    kw: Dict[str, Any] = dict(
                        task_id=handle.task_id, job_id=self._job_id
                    )
                    if ctx is not None:
                        kw["trace"] = ctx.to_wire()
                    if handle.batched:
                        if handle.shm_state == "active":
                            kw["shm_channel"] = handle.shm_channel
                        resp = stub.call(
                            "get_elements",
                            max_batch=self._max_batch,
                            timeout=DEFAULT_POLL_TIMEOUT,  # worker long-polls
                            **kw,
                        )
                    else:
                        resp = stub.call("get_element", **kw)
                self.metrics.add(
                    fetch_time=time.perf_counter() - t0, rpcs=1
                )
            except (TransportError, ValueError) as e:
                # ValueError surfaces directly over inproc://; TransportError
                # wraps the remote repr over tcp:// and grpc://.
                if handle.batched and "unknown method get_elements" in str(e):
                    with self._tasks_lock:  # dedup across window threads
                        if handle.batched:
                            handle.batched = False
                            self.metrics.add(fallback_tasks=1)
                    continue
                handle.failed = True  # worker died; dispatcher will notice
                break
            status = resp["status"]
            if status == FetchStatus.OK.value:
                backoff = 0.005
                try:
                    with self.tracer.span(
                        "client.decode", ctx, task_id=handle.task_id
                    ):
                        elems = self._decode_batch(resp, handle)
                except Exception as e:
                    # corrupt/undecodable frame (e.g. codec tag this process
                    # cannot handle): poison the task — permanently failed,
                    # never resurrected — and surface the error to the
                    # consumer instead of dying silently.
                    handle.poisoned = True
                    handle.failed = True
                    self._enqueue(_FetchError(handle.task_id, e))
                    break
                for elem in elems:
                    self._enqueue(elem)
            elif status == FetchStatus.PENDING.value:
                self.metrics.add(retries=1)
                time.sleep(backoff)
                # batched calls already long-polled worker-side, so PENDING
                # means "genuinely dry" — keep the client-side pause short.
                backoff = min(backoff * 2, 0.02 if handle.batched else 0.1)
            else:  # END_OF_TASK
                handle.done = True

    def _decode(self, resp: Dict[str, Any]) -> Element:
        """Decode a single-element (v1) response."""
        if "element_compressed" in resp:
            elem = decode_element(decompress(resp["element_compressed"]))
        else:
            elem = resp["element"]
        self.metrics.add(bytes_received=resp.get("nbytes", 0))
        return elem

    def _decode_batch(
        self, resp: Dict[str, Any], handle: Optional[_TaskHandle] = None
    ) -> List[Any]:
        """Decode a batched (v2) OR single-element (v1) OK response."""
        if (
            "shm_slot" in resp
            and handle is not None
            and handle.shm_ring is not None
        ):
            return self._decode_shm(resp, handle)
        if "batch_compressed" in resp:
            elems = decode_elements(decompress(resp["batch_compressed"]))
        elif "elements" in resp:
            elems = resp["elements"]
        else:
            return [self._decode(resp)]
        self.metrics.add(bytes_received=resp.get("nbytes", 0))
        return elems

    def _decode_shm(
        self, resp: Dict[str, Any], handle: _TaskHandle
    ) -> List[Any]:
        """Resolve a ring descriptor into elements.

        Default: decode views out of the slot, deep-copy every element, and
        release the lease immediately — callers can hold elements as long as
        they like.  ``zero_copy=True``: the decoded arrays BORROW the slot
        (read-only, no copy) and a ``_ShmRelease`` marker queued after the
        batch frees the lease once the consumer has moved past it.
        Compressed frames always copy (decompression materializes anyway).
        """
        ring = handle.shm_ring
        slot = resp["shm_slot"]
        view = ring.payload(slot, resp["shm_len"], resp.get("shm_seq"))
        self.metrics.add(bytes_received=resp.get("nbytes", 0), shm_batches=1)
        if resp.get("shm_codec"):
            data = bytes(view)
            ring.release(slot)
            return decode_elements(decompress(data))
        if self._zero_copy:
            elems: List[Any] = list(decode_elements(view))
            self._lent_ids.update(id(e) for e in elems)
            elems.append(_ShmRelease(ring, slot))
            return elems
        try:
            return [copy_element(e) for e in decode_elements(view)]
        finally:
            ring.release(slot)

    def _enqueue(self, elem: Element) -> None:
        try:
            self._queue.put_nowait(elem)
            return
        except queue.Full:
            pass
        with self.tracer.span("client.enqueue", None):  # blocked: queue full
            while not self._closed.is_set():
                try:
                    self._queue.put(elem, timeout=0.1)
                    return
                except queue.Full:
                    continue

    def _maybe_finish(self) -> None:
        with self._tasks_lock:
            all_done = (
                self._tasks
                and self._active_fetchers == 0
                and all(h.done or h.failed for h in self._tasks.values())
            )
        if all_done and self._job_finished.is_set():
            try:
                self._queue.put_nowait(self._END)
            except queue.Full:
                # consumer will re-check completion on queue timeout
                pass

    # ------------------------------------------------------------------
    # Iteration
    # ------------------------------------------------------------------
    def __iter__(self) -> Iterator[Element]:
        self._register()
        hb = threading.Thread(target=self._heartbeat_loop, daemon=True)
        hb.start()
        try:
            if self._m > 0:
                yield from self._iter_coordinated()
            else:
                yield from self._iter_parallel()
        finally:
            self.close()

    def _iter_parallel(self) -> Iterator[Element]:
        while True:
            t0 = time.perf_counter()
            try:
                item = self._queue.get(timeout=0.2)
            except queue.Empty:
                self.metrics.add(stall_time=time.perf_counter() - t0)
                with self._tasks_lock:
                    # fetcher threads may still hold decoded elements after
                    # their task flips done — wait for them to exit too
                    done = (
                        self._tasks
                        and self._active_fetchers == 0
                        and all(h.done or h.failed for h in self._tasks.values())
                    )
                if done and self._job_finished.is_set() and self._queue.empty():
                    return
                continue
            self.metrics.add(stall_time=time.perf_counter() - t0)
            if item is self._END:
                return
            if isinstance(item, _ShmRelease):
                # consumer has advanced past every element of the zero-copy
                # batch that borrowed this slot: lease goes back to the worker
                item.ring.release(item.slot)
                continue
            if isinstance(item, _FetchError):
                raise RuntimeError(
                    f"task {item.task_id}: undecodable response "
                    f"({item.error!r}) — client/worker codec registries "
                    f"likely disagree"
                ) from item.error
            self.metrics.add(batches=1)
            self.borrowed = id(item) in self._lent_ids
            self._lent_ids.discard(id(item))
            yield item

    def _iter_coordinated(self) -> Iterator[Element]:
        """Round-robin over workers; all consumers see same-bucket rounds."""
        round_index = 0
        backoff = 0.005
        while not self._closed.is_set():
            with self._tasks_lock:
                live = sorted(
                    (h for h in self._tasks.values() if not h.failed and not h.done),
                    key=lambda h: h.worker_id,
                )
            if not live:
                if self._job_finished.is_set():
                    return
                time.sleep(0.02)
                continue
            handle = live[round_index % len(live)]
            root = (
                self.trace_root
                if self.trace_root is not None and self.tracer.should_sample()
                else None
            )
            kw: Dict[str, Any] = dict(
                task_id=handle.task_id,
                job_id=self._job_id,
                round_index=round_index,
                consumer_index=self._consumer_index,
            )
            t0 = time.perf_counter()
            with self.tracer.span(
                "client.fetch", root, task_id=handle.task_id, round_index=round_index
            ) as ctx:
                if ctx is not None:
                    kw["trace"] = ctx.to_wire()
                try:
                    resp = handle.stub.call("get_element", **kw)
                    self.metrics.add(rpcs=1)
                except TransportError:
                    handle.failed = True
                    continue
                finally:
                    self.metrics.add(stall_time=time.perf_counter() - t0)
            status = resp["status"]
            if status == FetchStatus.OK.value:
                self.metrics.add(batches=1)
                backoff = 0.005
                yield self._decode(resp)
                round_index += 1
            elif status == FetchStatus.PENDING.value:
                self.metrics.add(retries=1)
                time.sleep(backoff)
                backoff = min(backoff * 2, 0.05)
            else:  # END_OF_TASK: coordinated jobs end at first exhausted worker
                return

    def close(self) -> None:
        first = not self._closed.is_set()
        self._closed.set()
        if not first:
            return
        with self._tasks_lock:
            handles = list(self._tasks.values())
        for h in handles:
            with h.shm_lock:
                ring, channel = h.shm_ring, h.shm_channel
                h.shm_ring, h.shm_channel, h.shm_state = None, "", "off"
            if ring is None:
                continue
            try:
                # best-effort: the worker unlinks the segment; if it is
                # already gone it reclaims the ring at stop() instead
                h.stub.call("shm_detach", channel=channel)
            except Exception:
                pass
            # NOTE: no ring.close() here — fetcher threads may be mid-decode
            # on a borrowed view; dropping the reference lets GC unmap once
            # the last view dies (the worker owns the segment NAME).


class DistributedDataset:
    """Iterable returned by ``Dataset.distribute(...)`` (paper Fig. 4)."""

    def __init__(
        self,
        graph: Graph,
        service: Any,
        processing_mode: str = "off",
        job_name: Optional[str] = None,
        num_consumers: int = 0,
        consumer_index: int = 0,
        sharing: bool = False,
        compression: Optional[str] = None,
        target_workers: str = "any",
        max_workers: int = 0,
        weight: float = 1.0,
        resume_offsets: bool = False,
        autocache: bool = False,
        buffer_size: int = 8,
        fetch_window: int = DEFAULT_FETCH_WINDOW,
        max_batch: int = DEFAULT_MAX_BATCH,
        prefer_batched: bool = True,
        trace_sample: float = 0.0,
        shm: bool = True,
        zero_copy: bool = False,
        host_key: Optional[str] = None,
    ):
        self._graph = graph
        address = getattr(service, "dispatcher_address", service)
        if not isinstance(address, str):
            raise TypeError("service must be a ServiceHandle or dispatcher address")
        self._address = address
        self._kw = dict(
            processing_mode=processing_mode,
            job_name=job_name,
            num_consumers=num_consumers,
            consumer_index=consumer_index,
            sharing=sharing,
            compression=compression,
            target_workers=target_workers,
            max_workers=max_workers,
            weight=weight,
            resume_offsets=resume_offsets,
            autocache=autocache,
            buffer_size=buffer_size,
            fetch_window=fetch_window,
            max_batch=max_batch,
            prefer_batched=prefer_batched,
            trace_sample=trace_sample,
            shm=shm,
            zero_copy=zero_copy,
            host_key=host_key,
        )
        self.last_client: Optional[DataServiceClient] = None

    def session(self, **overrides: Any) -> DataServiceClient:
        """Open one iteration session; ``overrides`` patch the distribute-
        time client kwargs (e.g. ``repro.feed.DeviceFeeder`` sets
        ``num_consumers``/``consumer_index`` for per-host registration)."""
        kw = {**self._kw, **overrides}
        self.last_client = DataServiceClient(self._address, self._graph, **kw)
        return self.last_client

    def __iter__(self) -> Iterator[Element]:
        return iter(self.session())


def materialize(
    service: Any,
    dataset: Any,
    path: str,
    num_streams: int = 0,
    compression: Optional[str] = None,
    chunk_bytes: int = 0,
    wait: bool = True,
    timeout: float = 300.0,
    poll_interval: float = 0.05,
) -> Dict[str, Any]:
    """Materialize a pipeline into a snapshot through the service.

    Registers the dataset with the dispatcher and starts (or joins — the
    call is idempotent per path) a distributed snapshot write: the
    dispatcher partitions the source into streams, workers execute the
    pipeline and append committed chunks under ``path``.  With ``wait``
    the call polls until the snapshot is finalized (riding through
    dispatcher downtime like any client, §3.4) and returns the final
    status; otherwise it returns the initial status view immediately.

    Consume the result with ``Dataset.from_snapshot(path)`` — including
    mid-write via ``tail=True``.
    """
    address = getattr(service, "dispatcher_address", service)
    if not isinstance(address, str):
        raise TypeError("service must be a ServiceHandle or dispatcher address")
    graph: Graph = dataset.graph if hasattr(dataset, "graph") else dataset
    stub = Stub(address)
    resp = stub.call(
        "start_snapshot",
        path=path,
        graph_bytes=graph.to_bytes(),
        num_streams=num_streams,
        compression=compression,
        client_codecs=available_codecs(),
        chunk_bytes=chunk_bytes,
    )
    if not wait or resp.get("finished"):
        return resp
    deadline = time.monotonic() + timeout
    while True:
        try:
            st = stub.call("snapshot_status", snapshot_id=resp["snapshot_id"])
        except TransportError:
            st = {}  # dispatcher down: keep polling (it restarts in place)
        if st.get("finished"):
            return st
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"snapshot {resp['snapshot_id']} at {path} not finished "
                f"after {timeout:.0f}s: {st}"
            )
        time.sleep(poll_interval)
