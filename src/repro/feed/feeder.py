"""DeviceFeeder: the bridge between the data service and the jax mesh.

The service half of this repo ends at a host iterator (``DataServiceClient``
yields numpy batches); the model half starts at device-resident sharded
``jax.Array``s.  The seed training loops crossed that gap synchronously —
``next(it)`` then ``jnp.asarray`` on the step's critical path — which is
precisely the data-stall pattern software pipelining exists to hide
(tf.data's ``prefetch``-to-device, Murray et al. §3; Gong et al. measure
the host→device hop as a dominant end-to-end cost).  The feeder closes it:

1. **Per-host consumer registration.**  Each host of a multi-host jax
   deployment registers as a distinct consumer of ONE service job.  In
   ``static`` mode the feeder reuses the coordinated-reads consumer
   indexing (``num_consumers = num_hosts``, ``consumer_index = host``,
   ``core/protocol.py`` §3.6): every round, host h receives slot h of the
   round's window, so hosts consume disjoint, aligned per-host shards of
   the global batch without any cross-host coordination of their own.  In
   ``dynamic`` mode each host is an independent client of a DYNAMIC job —
   disjoint FCFS shards, no round alignment (fine for pure data
   parallelism over an OFF/DYNAMIC pipeline).

2. **Background fetch + transfer with a double-buffered device queue.**
   A transfer thread pulls host batches and immediately places them with
   ``jax.device_put`` onto the batch ``NamedSharding``s derived from
   ``repro.dist.sharding_rules`` (each host uploads only its addressable
   shards; multi-process meshes assemble global ``jax.Array``s via
   ``make_array_from_process_local_data`` — never a host gather).  Placed
   batches wait in a depth-``depth`` queue (default 2: classic double
   buffering), so fetch and host→device copy of batch N+1 overlap the
   train step on batch N.

3. **Feed-side stall metrics.**  ``FeedMetrics`` splits wall time into
   accelerator-idle / fetch / transfer / compute; a rolling window of the
   same numbers is pushed through the client's dispatcher heartbeat
   (``DataServiceClient.report_feed_stall``), where it becomes the
   autoscaler's Cachew-style client-latency scaling signal.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Iterator, Optional

import jax

from .metrics import FeedMetrics, StallWindow
from .sharded import host_layout, infer_batch_shardings, leaf_nbytes, put_batch, resolve_shardings


class _FeedError:
    """Queued in place of a batch to surface a transfer-thread failure."""

    def __init__(self, error: BaseException):
        self.error = error


class DeviceFeeder:
    """Double-buffered device prefetch over a service-backed dataset.

    Parameters
    ----------
    dataset:
        A ``DistributedDataset`` (from ``Dataset.distribute(...)``), or a
        plain ``repro.data.Dataset`` together with ``service=``.
    service:
        Service handle / dispatcher address; only needed when ``dataset``
        is a raw ``Dataset``.
    mesh, plan:
        When given, per-leaf batch ``NamedSharding``s are derived once from
        the first batch via ``dist.sharding_rules.batch_sharding`` — the
        identical rule the train step's ``in_shardings`` use.
    shardings:
        Explicit override: a single ``Sharding`` for every leaf or a
        pytree matching the batch.  Wins over ``mesh``/``plan``.
    depth:
        Device-queue capacity (2 = double buffering).
    sharding_mode:
        ``"static"`` — per-host static sharding via coordinated-reads
        consumer indexing (forces ``processing_mode="off"``: round-robin
        windows are materialized whole on each worker).
        ``"dynamic"`` — each host is an independent client (DYNAMIC/OFF
        pipelines).  ``"auto"`` (default) — static iff ``num_hosts > 1``.
    host_index, num_hosts:
        Override the jax process layout (defaults: ``jax.process_index()``
        / ``jax.process_count()``).  Tests use these to emulate multiple
        hosts inside one process.
    report_interval_s:
        How often the rolling stall window is pushed to the service client
        for the autoscaler (0 disables reporting).
    """

    _END = object()

    def __init__(
        self,
        dataset: Any,
        *,
        service: Any = None,
        mesh: Any = None,
        plan: Any = None,
        shardings: Any = None,
        depth: int = 2,
        sharding_mode: str = "auto",
        host_index: Optional[int] = None,
        num_hosts: Optional[int] = None,
        report_interval_s: float = 1.0,
        **client_kw: Any,
    ):
        if sharding_mode not in ("auto", "static", "dynamic"):
            raise ValueError(f"unknown sharding_mode {sharding_mode!r}")
        if hasattr(dataset, "session"):  # DistributedDataset
            if client_kw:
                raise TypeError(
                    "client kwargs belong on Dataset.distribute(...) when "
                    "passing an already-distributed dataset"
                )
            self._dds = dataset
        else:  # raw Dataset: distribute it here
            if service is None:
                raise TypeError("service= is required for a raw Dataset")
            client_kw.setdefault("processing_mode", "dynamic")
            self._dds = dataset.distribute(service=service, **client_kw)

        default_index, default_count = host_layout()
        self._host_index = default_index if host_index is None else int(host_index)
        self._num_hosts = default_count if num_hosts is None else int(num_hosts)
        if sharding_mode == "auto":
            sharding_mode = "static" if self._num_hosts > 1 else "dynamic"
        self.sharding_mode = sharding_mode

        self._mesh, self._plan = mesh, plan
        self._explicit_shardings = shardings
        self._shardings: Any = None
        self._shardings_ready = False

        self.metrics = FeedMetrics()
        self._window = StallWindow(self.metrics)
        self._report_interval = report_interval_s
        self._last_report = time.perf_counter()

        self._queue: "queue.Queue[Any]" = queue.Queue(maxsize=max(1, depth))
        self._depth = max(1, depth)
        self._closed = threading.Event()
        self._last_return: Optional[float] = None
        self._client = self._make_session()
        self._thread = threading.Thread(
            target=self._run, name="device-feeder", daemon=True
        )
        self._thread.start()

    # ------------------------------------------------------------------
    # Session / registration
    # ------------------------------------------------------------------
    def _make_session(self) -> Any:
        """Register this host's consumer session per the sharding mode.

        On an accelerator the feeder opts into ``zero_copy=True``: with a
        co-located worker the shm ring's borrowed views feed
        ``jax.device_put`` directly — host batch bytes are copied exactly
        once, shm slot → device.  The lease contract (views valid until
        the next ``next(it)``) holds because ``_run`` waits until a
        borrowed batch's transfer has completed before fetching the next
        one: ``device_put`` may return while the copy still reads the host
        buffer.  Owned batches are not waited for, so their copy overlaps
        the next fetch.  The CPU backend's ``device_put`` does not copy an aligned
        host buffer at all — the device array aliases it, and would change
        when the worker reuses the slot — so on the CPU the client copies
        each batch out of the ring instead.  Remote workers are unaffected
        (tcp path decodes owned arrays).
        """
        overrides: dict = {"zero_copy": jax.default_backend() != "cpu"}
        if self.sharding_mode == "static":
            # Coordinated-reads consumer indexing (§3.6): round r, slot
            # host_index — per-host static sharding of every round's window.
            overrides.update(
                processing_mode="off",
                num_consumers=self._num_hosts,
                consumer_index=self._host_index,
            )
        return self._dds.session(**overrides)

    # ------------------------------------------------------------------
    # Transfer thread
    # ------------------------------------------------------------------
    def _run(self) -> None:
        # The service client owns the job's trace context; the feeder's
        # spans (fetch / device_put) parent onto the same root so one
        # Perfetto track shows client->dispatcher->worker->feeder.
        tracer = getattr(self._client, "tracer", None)
        root = getattr(self._client, "trace_root", None)
        try:
            it = iter(self._client)
            while not self._closed.is_set():
                t0 = time.perf_counter()
                try:
                    batch = next(it)
                except StopIteration:
                    break
                dt = time.perf_counter() - t0
                self.metrics.add_fetch(dt)
                sampled = (
                    tracer is not None
                    and root is not None
                    and tracer.should_sample()
                )
                if sampled:
                    tracer.record(
                        "feed.fetch", root.child(), time.time() - dt, dt,
                        parent_id=root.span_id,
                    )
                t0 = time.perf_counter()
                placed = self._to_device(batch)
                if getattr(self._client, "borrowed", False):
                    # the next fetch hands the batch's ring slot back
                    jax.block_until_ready(placed)
                dt = time.perf_counter() - t0
                nbytes = leaf_nbytes(batch)
                self.metrics.add_transfer(dt, nbytes)
                if sampled:
                    tracer.record(
                        "feed.device_put", root.child(), time.time() - dt, dt,
                        parent_id=root.span_id, nbytes=nbytes,
                    )
                if not self._put(placed):
                    return  # closed while the queue was full
                self._maybe_report()
        except Exception as e:  # surface to the consumer, don't die silently
            self._put(_FeedError(e))
        finally:
            self._put(self._END)
            self._report()

    def _to_device(self, batch: Any) -> Any:
        if not self._shardings_ready:
            if self._explicit_shardings is not None:
                self._shardings = resolve_shardings(batch, self._explicit_shardings)
            elif self._mesh is not None and self._plan is not None:
                self._shardings = infer_batch_shardings(batch, self._mesh, self._plan)
            self._shardings_ready = True
        return put_batch(batch, self._shardings)

    def _put(self, item: Any) -> bool:
        while not self._closed.is_set():
            try:
                self._queue.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    # ------------------------------------------------------------------
    # Stall reporting (autoscaler client-latency signal)
    # ------------------------------------------------------------------
    def _maybe_report(self) -> None:
        if self._report_interval <= 0:
            return
        now = time.perf_counter()
        if now - self._last_report >= self._report_interval:
            self._last_report = now
            self._report()

    def _report(self) -> None:
        stats = self._window.report()
        if stats is None:
            return
        report = getattr(self._client, "report_feed_stall", None)
        if report is not None:
            report(stats)

    # ------------------------------------------------------------------
    # Consumption
    # ------------------------------------------------------------------
    def next(self, timeout: Optional[float] = None) -> Any:
        """Block until the next device-resident batch is ready.

        The blocked time IS the accelerator-idle metric: with the double
        buffer keeping up it is ~0; when it grows, the feed (service fetch
        or host→device transfer) is the bottleneck, and the reported stall
        window tells the autoscaler which.
        """
        t0 = time.perf_counter()
        compute = None if self._last_return is None else t0 - self._last_return
        deadline = None if timeout is None else t0 + timeout
        while True:
            if self._closed.is_set():
                raise StopIteration("feeder closed")
            try:
                item = self._queue.get(timeout=0.1)
                break
            except queue.Empty:
                if deadline is not None and time.perf_counter() > deadline:
                    raise TimeoutError(
                        f"no batch after {timeout:.1f}s (service stalled?)"
                    )
        now = time.perf_counter()
        if item is self._END:
            self._queue.put(self._END)  # idempotent end for later calls
            raise StopIteration
        if isinstance(item, _FeedError):
            raise RuntimeError("device feed failed") from item.error
        self.metrics.add_step(
            idle=now - t0,
            compute=compute,
            depth_frac=self._queue.qsize() / self._depth,
        )
        self._last_return = time.perf_counter()
        return item

    def __iter__(self) -> Iterator[Any]:
        while True:
            try:
                yield self.next()
            except StopIteration:
                return

    def __next__(self) -> Any:
        return self.next()

    # ------------------------------------------------------------------
    # Lifecycle
    # ------------------------------------------------------------------
    def close(self) -> None:
        """Stop the transfer thread and the service session.  Idempotent;
        safe mid-epoch — in-flight batches are dropped, the service job
        keeps running for other consumers."""
        if self._closed.is_set():
            return
        self._closed.set()
        self._client.close()
        self._thread.join(timeout=5.0)
        # unblock any consumer stuck in next()
        try:
            self._queue.put_nowait(self._END)
        except queue.Full:
            pass

    def __enter__(self) -> "DeviceFeeder":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()
