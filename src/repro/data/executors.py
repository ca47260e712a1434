"""Pipeline executors: where a worker actually runs its dataset graphs.

The paper's workers are single processes that execute every assigned task's
pipeline on internal threads (§3.1).  That is this module's
:class:`InThreadExecutor`, and it remains the default.  For CPU-heavy
user-defined transforms, Python's GIL makes one worker process a hard
ceiling no matter how many ``_ParallelMap`` threads the autotuner adds —
so :class:`ProcessPoolExecutor` runs pipelines in a small pool of forked
child processes instead, with the parent worker keeping ownership of the
control plane (RPCs, checkpoints, snapshots, heartbeats).

Invariants both engines honour:

* **Request affinity** — ``iterate(..., affinity=key)`` pins a given key to
  one child for the executor's lifetime (``crc32(key) % processes``), so a
  shard's elements always come from the same child: per-stream seeding,
  resume offsets and snapshot byte-identity are preserved exactly as in
  the in-thread engine.
* **Deterministic sequence numbers** — ``iterate`` yields
  ``(absolute_seq, element)`` with ``absolute_seq`` starting at
  ``offset + 1``; skipping for resume happens at the source (child side
  for the pool — skipped elements never cross the IPC boundary).
* **Observability flows back** — children ship cumulative per-op stats
  snapshots every ``STATS_INTERVAL_S``, also while they wait for credit,
  and the parent's router thread folds each into the request's own
  ``ExecContext`` as it arrives, so stall attribution, ``metrics_dump`` and
  ``trace_dump`` see pooled pipelines exactly like in-thread ones.
  Parent-side knob writes (e.g. an autotuner adjusting parallelism) are
  forwarded to the owning child.

Failure contract: a child that dies or errors *before yielding anything*
triggers a transparent in-thread retry (covers graphs that capture
process-local state a fork can't see, e.g. ``__local__/`` registry tokens
created after the child forked).  Every such retry, and every pipeline the
pool could not hand to a child at all, counts on the owning registry's
``executor_inthread_fallbacks_total`` counter, so a pool that silently
degraded to the in-thread engine is visible.  A child lost *mid-stream*
raises ``ExecutorError`` — the worker's task machinery already treats a
runner error as a task failure and the dispatcher reassigns.

The pool uses the ``fork`` start method deliberately: forked children
inherit ``data.registry._LOCAL_FNS``, so lambda/closure transforms that
were registered before the child started resolve without being picklable.
Children fork lazily, on the first request routed to them, so in a trainer
they fork after the accelerator backend is up.  They never touch JAX: a
pipeline is numpy and Python only, and the device stays with the parent.
"""
from __future__ import annotations

import itertools
import logging
import pickle
import queue
import threading
import time
import zlib
from typing import Any, Dict, Iterator, List, Optional, Tuple

from ..obs.registry import MetricsRegistry
from ..obs.tracing import annotate
from .iterators import ExecContext, Knob, build_iterator

logger = logging.getLogger(__name__)

# Flow control: a child may have this many elements in flight before it
# blocks; the parent replenishes in batches so steady state costs one
# control message per REPLENISH_EVERY elements, not one per element.
INITIAL_CREDITS = 64
REPLENISH_EVERY = 32
STATS_INTERVAL_S = 0.2


class ExecutorError(RuntimeError):
    """A pooled pipeline failed after it had already produced elements."""


class PipelineExecutor:
    """Engine interface: turn a bound graph into a numbered element stream."""

    #: how many pipelines can genuinely make progress at once
    width: int = 1

    def iterate(
        self,
        graph: Any,
        ctx: ExecContext,
        *,
        affinity: str,
        offset: int = 0,
    ) -> Iterator[Tuple[int, Any]]:
        """Yield ``(absolute_seq, element)`` with seq starting at offset+1.

        ``ctx`` is the request's parent-side ExecContext: its ``stats``
        receive the pipeline's per-op profile and its ``stop_event``
        aborts the stream.  ``affinity`` pins the request to one execution
        lane (same key → same child process) for determinism.
        """
        raise NotImplementedError

    def stop(self) -> None:
        """Release engine resources; in-flight iterators abort."""


class InThreadExecutor(PipelineExecutor):
    """The paper's engine: run the pipeline on the calling worker's threads."""

    width = 1

    def iterate(self, graph, ctx, *, affinity, offset=0):
        for i, elem in enumerate(build_iterator(graph, ctx)):
            if i < offset:
                continue
            yield i + 1, elem

    def stop(self) -> None:
        pass


# ---------------------------------------------------------------------------
# Child process side
# ---------------------------------------------------------------------------
class _ChildRequest:
    __slots__ = ("rid", "stop", "credits", "ctx")

    def __init__(self, rid: str, initial_credits: int):
        self.rid = rid
        self.stop = threading.Event()
        self.credits = threading.Semaphore(initial_credits)
        self.ctx: Optional[ExecContext] = None


def _stats_snapshot(ctx: ExecContext) -> Dict[int, Dict[str, Any]]:
    out: Dict[int, Dict[str, Any]] = {}
    for idx, st in list(ctx.stats.items()):
        out[idx] = {
            "name": st.name,
            "elements": st.elements,
            "busy_time": st.busy_time,
            "cpu_time": st.cpu_time,
            "buffer_occupancy": st.buffer_occupancy,
            "parallelism": st.parallelism.get() if st.parallelism else None,
            "buffer_size": st.buffer_size.get() if st.buffer_size else None,
        }
    return out


def _run_request(req: _ChildRequest, graph_blob, seed, offset, default_par, out_q):
    ctx = ExecContext(
        seed=seed, stop_event=req.stop, default_parallelism=default_par
    )
    req.ctx = ctx
    sent = 0
    last_stats = time.monotonic()

    def stats_due() -> None:
        nonlocal last_stats
        now = time.monotonic()
        if now - last_stats >= STATS_INTERVAL_S:
            out_q.put(("stats", req.rid, _stats_snapshot(ctx)))
            last_stats = now

    try:
        graph = pickle.loads(graph_blob)
        for i, elem in enumerate(build_iterator(graph, ctx)):
            if req.stop.is_set():
                break
            if i < offset:
                continue
            # block on flow-control credit, staying responsive to cancel;
            # the op stats still go out on their timer, so a child the
            # consumer holds back is not silent
            while not req.credits.acquire(timeout=0.1):
                if req.stop.is_set():
                    break
                stats_due()
            if req.stop.is_set():
                break
            out_q.put(("elem", req.rid, i + 1, elem))
            sent += 1
            stats_due()
    except Exception as e:  # ship the failure; the parent decides policy
        try:
            out_q.put(("stats", req.rid, _stats_snapshot(ctx)))
            out_q.put(("err", req.rid, repr(e), sent))
        except Exception:
            pass
        return
    try:
        out_q.put(("stats", req.rid, _stats_snapshot(ctx)))
        out_q.put(("end", req.rid))
    except Exception:
        pass


def _child_main(ctrl_q, out_q) -> None:
    """Entry point of one executor child: a tiny request multiplexer.

    Runs each ``start`` request on its own thread so one child serves
    several affinity keys concurrently; ``credit``/``knob``/``cancel``
    messages are applied to the matching live request.
    """
    active: Dict[str, _ChildRequest] = {}
    lock = threading.Lock()
    while True:
        msg = ctrl_q.get()
        kind = msg[0]
        if kind == "shutdown":
            with lock:
                reqs = list(active.values())
            for req in reqs:
                req.stop.set()
                req.credits.release()
            return
        if kind == "start":
            _, rid, graph_blob, seed, offset, default_par = msg
            req = _ChildRequest(rid, INITIAL_CREDITS)
            with lock:
                active[rid] = req

            def _run(req=req, blob=graph_blob, seed=seed, offset=offset, dp=default_par):
                try:
                    _run_request(req, blob, seed, offset, dp, out_q)
                finally:
                    with lock:
                        active.pop(req.rid, None)

            threading.Thread(
                target=_run, daemon=True, name=f"exec-req-{rid}"
            ).start()
        elif kind == "credit":
            _, rid, n = msg
            with lock:
                req = active.get(rid)
            if req is not None:
                for _ in range(n):
                    req.credits.release()
        elif kind == "cancel":
            _, rid = msg
            with lock:
                req = active.get(rid)
            if req is not None:
                req.stop.set()
                req.credits.release()  # wake a credit-blocked producer
        elif kind == "knob":
            _, rid, idx, knob_kind, value = msg
            with lock:
                req = active.get(rid)
            st = req.ctx.stats.get(idx) if req is not None and req.ctx else None
            knob = getattr(st, knob_kind, None) if st is not None else None
            if isinstance(knob, Knob):
                knob.value = max(knob.minimum, min(knob.maximum, int(value)))


# ---------------------------------------------------------------------------
# Parent side
# ---------------------------------------------------------------------------
class _PoolRequest:
    """Parent-side state of one pipeline running in a child."""

    __slots__ = ("child", "inq", "ctx", "ctrl", "last_knob")

    def __init__(self, child: int, ctx: ExecContext, ctrl: Any):
        self.child = child
        self.inq: "queue.Queue[Any]" = queue.Queue()  # elements and ends
        self.ctx = ctx
        self.ctrl = ctrl
        self.last_knob: Dict[Tuple[int, str], int] = {}


class ProcessPoolExecutor(PipelineExecutor):
    """Run pipelines in ``processes`` forked children with request affinity."""

    def __init__(self, processes: int, registry: MetricsRegistry):
        import multiprocessing

        self.width = max(1, int(processes))
        self._fallbacks = registry.counter(
            "executor_inthread_fallbacks_total",
            "pipelines the process pool ran in the worker's own thread",
        )
        self._mp = multiprocessing.get_context("fork")
        self._children: List[Optional[Any]] = [None] * self.width
        self._ctrl: List[Optional[Any]] = [None] * self.width
        self._out: List[Optional[Any]] = [None] * self.width
        self._lock = threading.Lock()
        # rid -> its request; plain dict reads from the router threads are
        # GIL-safe
        self._pending: Dict[str, _PoolRequest] = {}
        self._rid_counter = itertools.count()
        self._stopping = threading.Event()
        self._inthread = InThreadExecutor()

    # -- child lifecycle ---------------------------------------------------
    def _ensure_child(self, i: int) -> Tuple[Any, Any]:
        """Start (or restart after death) child ``i``; returns (ctrl, proc)."""
        with self._lock:
            proc = self._children[i]
            if proc is not None and proc.is_alive():
                return self._ctrl[i], proc
            if self._stopping.is_set():
                raise ExecutorError("executor is stopped")
            ctrl = self._mp.Queue()
            out = self._mp.Queue()
            proc = self._mp.Process(
                target=_child_main,
                args=(ctrl, out),
                daemon=True,
                name=f"repro-exec-{i}",
            )
            proc.start()
            self._children[i], self._ctrl[i], self._out[i] = proc, ctrl, out
            threading.Thread(
                target=self._route,
                args=(i, proc, out),
                daemon=True,
                name=f"exec-route-{i}",
            ).start()
            return ctrl, proc

    def _route(self, i: int, proc, out_q) -> None:
        """Demultiplex one child's output queue: op stats are applied here,
        as they arrive, even while the request's consumer is not pulling;
        everything else goes to the request's queue."""
        while not self._stopping.is_set():
            # wait outside the span: executor.recv is the pipe read and the
            # unpickling of one message (mp.Queue has no public blocking poll)
            if not out_q._reader.poll(0.2):
                if proc.is_alive():
                    continue
                # child died: poison every request routed to it, then exit
                with self._lock:
                    victims = [r.inq for r in self._pending.values() if r.child == i]
                for q in victims:
                    q.put(("died",))
                return
            try:
                with annotate("executor.recv"):
                    msg = out_q.get(timeout=0.2)
            except queue.Empty:
                continue
            req = self._pending.get(msg[1])
            if req is None:
                continue
            if msg[0] == "stats":
                self._apply_stats(req, msg[1], msg[2])
            else:
                req.inq.put(msg)

    # -- stats / knob plumbing ----------------------------------------------
    def _apply_stats(self, req: _PoolRequest, rid: str, snap) -> None:
        ctx, last = req.ctx, req.last_knob
        for idx, s in snap.items():
            st = ctx.stat(idx, s["name"])
            st.elements = s["elements"]
            st.busy_time = s["busy_time"]
            st.cpu_time = s["cpu_time"]
            st.buffer_occupancy = s["buffer_occupancy"]
            for kind in ("parallelism", "buffer_size"):
                child_val = s.get(kind)
                if child_val is None:
                    continue
                knob = getattr(st, kind)
                if knob is None:
                    setattr(st, kind, Knob(value=int(child_val)))
                    last[(idx, kind)] = int(child_val)
                    continue
                prev = last.get((idx, kind))
                if (
                    prev is not None
                    and knob.get() != prev
                    and knob.get() != child_val
                ):
                    # the parent side moved the knob (autotuner): forward to
                    # the owning child instead of clobbering the new value
                    try:
                        req.ctrl.put(("knob", rid, idx, kind, knob.get()))
                    except Exception:
                        pass
                    last[(idx, kind)] = knob.get()
                else:
                    knob.value = int(child_val)
                    last[(idx, kind)] = int(child_val)

    def _fallback(self, graph, ctx, affinity, offset):
        """Run the request in the calling thread, and count that it did."""
        self._fallbacks.inc()
        return self._inthread.iterate(
            graph, ctx, affinity=affinity, offset=offset
        )

    # -- the engine ----------------------------------------------------------
    def iterate(self, graph, ctx, *, affinity, offset=0):
        child_idx = zlib.crc32(str(affinity).encode("utf-8")) % self.width
        rid = f"r{next(self._rid_counter)}"
        # Pickle BEFORE (possibly) forking the child: FnRef.__getstate__
        # stashes non-picklable transforms (lambdas/closures) into the
        # process-local registry at pickle time, and a child forked AFTER
        # the stash inherits it — so lazily started children can still
        # resolve locally-defined functions.
        try:
            graph_blob = pickle.dumps(graph, protocol=pickle.HIGHEST_PROTOCOL)
        except Exception as e:
            logger.warning(
                "graph not picklable for executor pool (%r); running in-thread",
                e,
            )
            yield from self._fallback(graph, ctx, affinity, offset)
            return
        try:
            ctrl, proc = self._ensure_child(child_idx)
        except ExecutorError:
            raise
        except Exception as e:
            logger.warning(
                "executor child %d failed to start (%r); running in-thread",
                child_idx,
                e,
            )
            yield from self._fallback(graph, ctx, affinity, offset)
            return

        req = _PoolRequest(child_idx, ctx, ctrl)
        inq = req.inq
        with self._lock:
            self._pending[rid] = req
        started = False
        yielded = 0
        uncredited = 0
        try:
            try:
                ctrl.put(
                    (
                        "start",
                        rid,
                        graph_blob,
                        ctx.seed,
                        offset,
                        ctx.default_parallelism,
                    )
                )
                started = True
            except Exception as e:  # unpicklable graph, dead queue, ...
                logger.warning(
                    "executor dispatch failed (%r); running in-thread", e
                )
                yield from self._fallback(graph, ctx, affinity, offset)
                return
            while True:
                if ctx.stop_event.is_set():
                    return
                try:
                    msg = inq.get(timeout=0.1)
                except queue.Empty:
                    if not proc.is_alive():
                        msg = ("died",)
                    else:
                        continue
                kind = msg[0]
                if kind == "elem":
                    _, _, seq, elem = msg
                    yield seq, elem
                    yielded += 1
                    uncredited += 1
                    if uncredited >= REPLENISH_EVERY:
                        try:
                            ctrl.put(("credit", rid, uncredited))
                        except Exception:
                            pass
                        uncredited = 0
                elif kind == "end":
                    return
                elif kind == "err":
                    _, _, err_repr, sent = msg
                    if yielded == 0 and sent == 0:
                        # failed before producing anything: the graph may
                        # reference state the fork predates — retry inline
                        logger.warning(
                            "executor child error before first element "
                            "(%s); running in-thread",
                            err_repr,
                        )
                        yield from self._fallback(graph, ctx, affinity, offset)
                        return
                    raise ExecutorError(f"pipeline failed in child: {err_repr}")
                elif kind == "died":
                    if yielded == 0:
                        logger.warning(
                            "executor child %d died before first element; "
                            "running in-thread",
                            child_idx,
                        )
                        yield from self._fallback(graph, ctx, affinity, offset)
                        return
                    raise ExecutorError(
                        f"executor child {child_idx} died mid-request"
                    )
        finally:
            with self._lock:
                self._pending.pop(rid, None)
            if started:
                try:
                    ctrl.put(("cancel", rid))
                except Exception:
                    pass

    def stop(self) -> None:
        self._stopping.set()
        with self._lock:
            pairs = [
                (self._children[i], self._ctrl[i]) for i in range(self.width)
            ]
        for proc, ctrl in pairs:
            if proc is None:
                continue
            try:
                ctrl.put(("shutdown",))
            except Exception:
                pass
        for proc, _ in pairs:
            if proc is None:
                continue
            proc.join(timeout=2.0)
            if proc.is_alive():
                proc.terminate()
                proc.join(timeout=1.0)
        with self._lock:
            queues = [q for q in self._ctrl + self._out if q is not None]
            self._children = [None] * self.width
            self._ctrl = [None] * self.width
            self._out = [None] * self.width
        for q in queues:
            try:
                q.cancel_join_thread()
                q.close()
            except Exception:
                pass


def make_executor(processes: int, registry: MetricsRegistry) -> PipelineExecutor:
    """Build the engine for ``worker_processes=N`` (0/1-thread semantics: 0
    keeps the paper's in-thread engine; N >= 1 runs an N-child pool whose
    fallback counter lives in ``registry``)."""
    if processes and processes > 0:
        return ProcessPoolExecutor(processes, registry)
    return InThreadExecutor()
