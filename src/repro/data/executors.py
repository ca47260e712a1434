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

Data path from child to parent: two routes, chosen per element by the
bytes of its arrays (``element_nbytes``):

* **small** elements (under ``RING_MIN_BYTES``, 1 MiB) are pickled through
  the child's ``multiprocessing.Queue`` pipe, as they always were;
* **large** elements are written by the child straight into a slot of its
  lane's shared-memory ring (``core.shm_ring.ShmRing``), as pickle protocol
  5 with every array's buffer out of band, and only the descriptor
  ``("elem_ring", rid, seq, slot, length)`` crosses the pipe.  ``iterate``
  copies the buffers out into memory it owns, unpickles, releases the slot
  and only then yields, so no yielded element borrows the ring, and the
  element's types are what the pipe would give.

Why 1 MiB: a pipe moves at most 64 KiB per ``read``, and the parent's router
thread must win the trainer's interpreter lock back after every read.  A
61 MB element took about 940 reacquisitions, each behind whatever the
worker's tcp threads held; at 1 MiB a pipe transfer is at most 16 reads.
The ring is made at the first large element, with ``RING_SLOTS`` slots of
that element's size plus a quarter, its pages reserved up front.  The free
slots are the lane's byte-bounded credit: a request that holds a leased
slot and finds none free waits for its own consumer, answering ``cancel``
and sending its op stats on their timer, and never falls back to the pipe
for that — it would bring the convoy back exactly when the consumer is
behind.  The element credits below still apply to both routes.  A large
element takes the pipe only where the ring cannot serve it: larger than a
slot (``too_large``), every slot leased to the lane's other requests, whose
consumers may never pull (``ring_busy``: one stalled request must not stop
its lane-mates), or no ring could be made (``no_ring``).  Each element the
parent receives counts on ``executor_ring_elements_total`` or on
``executor_pipe_elements_total{reason=small|too_large|ring_busy|no_ring}``,
the reason named by the child.

Ring ownership: the parent names each child's segment at the fork
(``SEGMENT_PREFIX`` included, so leak sweeps see it), the child creates it,
the parent attaches at the first descriptor, and the parent unlinks it when
the child dies or the executor stops.  The children share the parent's
resource tracker, which removes the segment if the whole process tree dies.

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
import os
import pickle
import queue
import struct
import threading
import time
import zlib
from multiprocessing import resource_tracker
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np

from ..core.shm_ring import (
    ShmRing,
    ShmRingError,
    new_segment_name,
    unlink_segment,
)
from ..obs.registry import MetricsRegistry
from ..obs.tracing import annotate
from .elements import element_nbytes
from .iterators import ExecContext, Knob, build_iterator

logger = logging.getLogger(__name__)

# Flow control: a child may have this many elements in flight before it
# blocks; the parent replenishes in batches so steady state costs one
# control message per REPLENISH_EVERY elements, not one per element.
INITIAL_CREDITS = 64
REPLENISH_EVERY = 32
STATS_INTERVAL_S = 0.2
# The child-to-parent data path (module docstring): elements of at least
# RING_MIN_BYTES go through a ring of RING_SLOTS slots, polled for a free
# slot every SLOT_POLL_S.
RING_MIN_BYTES = 1 << 20
RING_SLOTS = 4
SLOT_POLL_S = 0.002
# A slot holds: <u32 buffers> <u32 pickle bytes> <u64 buffer bytes>*, the
# pickle, then each out-of-band buffer at a multiple of _BUF_ALIGN.
_SLOT_HEAD = struct.Struct("<II")
_BUF_ALIGN = 64


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


class _ChildRing:
    """A child's end of its lane's element ring, made at the first large
    element under the name the parent gave at the fork and shared by the
    child's live requests.  It remembers which request leased each slot
    last, and keeps a free slot for every live request that holds none, so
    one request cannot take the whole ring from its lane-mates."""

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._ring: Optional[ShmRing] = None
        self._holder: List[Optional[str]] = [None] * RING_SLOTS
        self._live: set = set()
        self._failed = False

    def enter(self, rid: str) -> None:
        with self._lock:
            self._live.add(rid)

    def leave(self, rid: str) -> None:
        with self._lock:
            self._live.discard(rid)

    def for_element(self, nbytes: int) -> Tuple[Optional[ShmRing], str]:
        """The ring, if one of its slots can hold ``nbytes``; else None and
        why the element takes the pipe (``no_ring`` or ``too_large``)."""
        with self._lock:
            if self._ring is None and not self._failed:
                mib = 1 << 20
                slot_bytes = -(-(nbytes + nbytes // 4) // mib) * mib
                ring = None
                try:
                    ring = ShmRing.create(RING_SLOTS, slot_bytes, name=self.name)
                    _reserve(self.name)
                    self._ring = ring
                except (OSError, ShmRingError) as e:
                    if ring is not None:
                        ring.unlink()
                        ring.close()
                    logger.warning(
                        "executor ring of %d x %d bytes not made (%r); large "
                        "elements take the pipe", RING_SLOTS, slot_bytes, e,
                    )
                    self._failed = True
            ring = self._ring
        if ring is None:
            return None, "no_ring"
        return (ring, "") if nbytes <= ring.slot_bytes else (None, "too_large")

    def lease(self, rid: str) -> Optional[int]:
        """A free slot, now request ``rid``'s; else None while ``rid`` holds
        a slot (its own consumer frees one), or -1 while it holds none and
        every slot is leased to lane-mates, whose consumers may never pull.
        A request that holds a slot takes another only if one stays free
        for each live lane-mate that holds none."""
        ring = self._ring
        with self._lock:
            held: Dict[Optional[str], int] = {}
            free = 0
            for s, h in enumerate(self._holder):
                if ring.is_free(s):
                    free += 1
                else:
                    held[h] = held.get(h, 0) + 1
            mine = held.get(rid, 0)
            holding_none = sum(1 for r in self._live if r != rid and r not in held)
            take = free and (not mine or free > holding_none)
            slot = ring.try_acquire() if take else None
            if slot is not None:
                self._holder[slot] = rid
                return slot
        return None if mine or free else -1


def _reserve(name: str) -> None:
    """Allocate every page of segment ``name`` now, so a ``/dev/shm`` too
    small for the ring fails here with ``OSError`` rather than with
    ``SIGBUS`` at the first write past its end."""
    fd = os.open(os.path.join("/dev/shm", name), os.O_RDWR)
    try:
        os.posix_fallocate(fd, 0, os.fstat(fd).st_size)
    finally:
        os.close(fd)


def _lease_slot(
    lane_ring: _ChildRing, req: _ChildRequest, stats_due
) -> Tuple[Optional[int], str]:
    """Wait for a slot for ``req``: ``(slot, "")`` once one is free;
    ``(None, "ring_busy")`` while every slot is leased to lane-mates, whose
    consumers may never pull, so the element takes the pipe;
    ``(None, "")`` once the request is cancelled."""
    while True:
        slot = lane_ring.lease(req.rid)
        if slot is not None:
            return (slot, "") if slot >= 0 else (None, "ring_busy")
        if req.stop.wait(SLOT_POLL_S):
            return None, ""
        stats_due()


def _slot_layout(data_len: int, buf_lens: List[int]) -> List[int]:
    """Offsets in a slot of the pickle and of each buffer, then the end."""
    off = _SLOT_HEAD.size + 8 * len(buf_lens)
    offs = [off]
    off += data_len
    for n in buf_lens:
        off = -(-off // _BUF_ALIGN) * _BUF_ALIGN
        offs.append(off)
        off += n
    offs.append(off)
    return offs


class _SlotFrame:
    """An element as a slot holds it: pickle protocol 5 with each array's
    buffer out of band.  The pickle keeps every type the pipe would (numpy
    scalars, tuples, dict keys, memory order), and an array's bytes are
    copied once into the slot and once out of it."""

    __slots__ = ("data", "bufs", "offs")

    def __init__(self, elem: Any):
        bufs: List[pickle.PickleBuffer] = []
        self.data = pickle.dumps(elem, protocol=5, buffer_callback=bufs.append)
        self.bufs = [b.raw() for b in bufs]
        self.offs = _slot_layout(len(self.data), [b.nbytes for b in self.bufs])

    @property
    def nbytes(self) -> int:
        return self.offs[-1]

    def write(self, view: memoryview) -> int:
        """Write the frame at the start of ``view``; returns its length."""
        lens = [b.nbytes for b in self.bufs]
        _SLOT_HEAD.pack_into(view, 0, len(lens), len(self.data))
        struct.pack_into(f"<{len(lens)}Q", view, _SLOT_HEAD.size, *lens)
        at = self.offs[0]
        view[at : at + len(self.data)] = self.data
        for b, at in zip(self.bufs, self.offs[1:]):
            view[at : at + b.nbytes] = b
        return self.nbytes


def _read_frame(view: memoryview) -> Any:
    """The element of a :class:`_SlotFrame`, its buffers copied out of
    ``view`` into memory the caller owns."""
    n, data_len = _SLOT_HEAD.unpack_from(view, 0)
    lens = struct.unpack_from(f"<{n}Q", view, _SLOT_HEAD.size)
    offs = _slot_layout(data_len, list(lens))
    bufs = [
        np.frombuffer(view, np.uint8, k, at).copy() if k else np.empty(0, np.uint8)
        for k, at in zip(lens, offs[1:])
    ]
    return pickle.loads(view[offs[0] : offs[0] + data_len], buffers=bufs)


def _run_request(
    req: _ChildRequest, graph_blob, seed, offset, default_par, out_q, lane_ring
):
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
            ring, slot, reason = None, None, "small"
            if element_nbytes(elem) >= RING_MIN_BYTES:
                frame = _SlotFrame(elem)
                ring, reason = lane_ring.for_element(frame.nbytes)
                if ring is not None:
                    slot, reason = _lease_slot(lane_ring, req, stats_due)
                    if slot is None and not reason:
                        break  # cancelled
            if slot is None:
                out_q.put(("elem", req.rid, i + 1, elem, reason))
            else:
                try:
                    length = frame.write(ring.slot_view(slot))
                except BaseException:
                    ring.cancel(slot)
                    raise
                ring.commit(slot, length)
                out_q.put(("elem_ring", req.rid, i + 1, slot, length))
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


def _child_main(ctrl_q, out_q, ring_name: str) -> None:
    """Entry point of one executor child: a tiny request multiplexer.

    Runs each ``start`` request on its own thread so one child serves
    several affinity keys concurrently; ``credit``/``knob``/``cancel``
    messages are applied to the matching live request.  The requests share
    the lane's element ring, made under ``ring_name``.
    """
    lane_ring = _ChildRing(ring_name)
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
                lane_ring.enter(req.rid)
                try:
                    _run_request(req, blob, seed, offset, dp, out_q, lane_ring)
                finally:
                    lane_ring.leave(req.rid)
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
class _Lane:
    """One child process and the parent's ends of it: its queues and its
    element ring, which the child makes under ``ring_name``."""

    def __init__(self, proc: Any, ctrl: Any, out: Any, ring_name: str):
        self.proc = proc
        self.ctrl = ctrl
        self.out = out
        self.ring_name = ring_name
        self.router: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        self._ring: Optional[ShmRing] = None
        self._closed = False

    def ring(self) -> Optional[ShmRing]:
        """The child's ring, attached at the first call; None once it is
        gone (the executor stopped)."""
        with self._lock:
            if self._ring is None and not self._closed:
                try:
                    self._ring = ShmRing.attach(self.ring_name, adopt=True)
                except FileNotFoundError:
                    return None
            return self._ring

    def release(self, msg: Tuple[Any, ...]) -> None:
        """Give back the ring slot of a descriptor no request will copy out."""
        if msg[0] == "elem_ring":
            ring = self.ring()
            if ring is not None:
                ring.release(msg[3])

    def unlink_ring(self) -> None:
        """Remove the segment; an attached mapping stays readable, so
        descriptors already routed can still be copied out."""
        unlink_segment(self.ring_name)

    def close(self) -> None:
        """After the child has exited and the executor is stopping: wait
        for the router to see that, then release the ring and the queues."""
        if self.router is not None:
            self.router.join(timeout=1.0)
        self.unlink_ring()
        with self._lock:
            self._closed = True
            ring, self._ring = self._ring, None
        if ring is not None:
            ring.close()
        for q in (self.ctrl, self.out):
            try:
                q.cancel_join_thread()
                q.close()
            except Exception:
                pass


class _PoolRequest:
    """Parent-side state of one pipeline running in a child."""

    __slots__ = ("lane", "inq", "ctx", "last_knob")

    def __init__(self, lane: _Lane, ctx: ExecContext):
        self.lane = lane
        self.inq: "queue.Queue[Any]" = queue.Queue()  # elements and ends
        self.ctx = ctx
        self.last_knob: Dict[Tuple[int, str], int] = {}


def _copy_out(ring: ShmRing, slot: int, length: int) -> Any:
    """The element in ``slot``, copied into memory the caller owns; the slot
    goes back to the child."""
    try:
        return _read_frame(ring.payload(slot, length))
    finally:
        ring.release(slot)


class ProcessPoolExecutor(PipelineExecutor):
    """Run pipelines in ``processes`` forked children with request affinity."""

    def __init__(self, processes: int, registry: MetricsRegistry):
        import multiprocessing

        self.width = max(1, int(processes))
        self._fallbacks = registry.counter(
            "executor_inthread_fallbacks_total",
            "pipelines the process pool ran in the worker's own thread",
        )
        self._ring_elements = registry.counter(
            "executor_ring_elements_total",
            "elements pool children handed over through their shm ring",
        )
        self._pipe_elements = registry.counter(
            "executor_pipe_elements_total",
            "elements pool children sent through their pipe, by reason",
        )
        self._mp = multiprocessing.get_context("fork")
        self._lanes: List[Optional[_Lane]] = [None] * self.width
        self._lock = threading.Lock()
        # rid -> its request; the router reads it and puts to a request's
        # queue under the lock, so a request that has ended (and given back
        # the ring slots of its queue) receives nothing more
        self._pending: Dict[str, _PoolRequest] = {}
        self._rid_counter = itertools.count()
        self._stopping = threading.Event()
        self._inthread = InThreadExecutor()

    # -- child lifecycle ---------------------------------------------------
    def _ensure_child(self, i: int) -> _Lane:
        """Start (or restart after death) child ``i``; returns its lane."""
        with self._lock:
            lane = self._lanes[i]
            if lane is not None and lane.proc.is_alive():
                return lane
            if self._stopping.is_set():
                raise ExecutorError("executor is stopped")
            # the child registers its ring with this process's tracker
            resource_tracker.ensure_running()
            ctrl = self._mp.Queue()
            out = self._mp.Queue()
            ring_name = new_segment_name()
            proc = self._mp.Process(
                target=_child_main,
                args=(ctrl, out, ring_name),
                daemon=True,
                name=f"repro-exec-{i}",
            )
            proc.start()
            lane = self._lanes[i] = _Lane(proc, ctrl, out, ring_name)
            lane.router = threading.Thread(
                target=self._route,
                args=(lane,),
                daemon=True,
                name=f"exec-route-{i}",
            )
            lane.router.start()
            return lane

    def _route(self, lane: _Lane) -> None:
        """Demultiplex one child's output queue: op stats are applied here,
        as they arrive, even while the request's consumer is not pulling;
        everything else goes to the request's queue."""
        out_q = lane.out
        while not self._stopping.is_set():
            # wait outside the span: executor.recv is the pipe read and the
            # unpickling of one message (mp.Queue has no public blocking poll)
            if not out_q._reader.poll(0.2):
                if lane.proc.is_alive():
                    continue
                # child died: remove its ring, poison every request routed
                # to it, then exit
                lane.unlink_ring()
                with self._lock:
                    victims = [r.inq for r in self._pending.values() if r.lane is lane]
                for q in victims:
                    q.put(("died",))
                return
            try:
                with annotate("executor.recv"):
                    msg = out_q.get(timeout=0.2)
            except queue.Empty:
                continue
            if msg[0] == "elem_ring":
                lane.ring()  # attach before a death can unlink the segment
            with self._lock:
                req = self._pending.get(msg[1])
                if req is not None and msg[0] != "stats":
                    req.inq.put(msg)
            if req is None:
                lane.release(msg)
            elif msg[0] == "stats":
                self._apply_stats(req, msg[1], msg[2])

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
                        req.lane.ctrl.put(("knob", rid, idx, kind, knob.get()))
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
            lane = self._ensure_child(child_idx)
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

        ctrl, proc = lane.ctrl, lane.proc
        req = _PoolRequest(lane, ctx)
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
                if kind in ("elem", "elem_ring"):
                    if kind == "elem":
                        _, _, seq, elem, reason = msg
                        self._pipe_elements.labels(reason=reason).inc()
                    else:
                        _, _, seq, slot, length = msg
                        ring = lane.ring()
                        if ring is None:
                            raise ExecutorError(
                                f"executor child {child_idx}'s ring is gone"
                            )
                        with annotate("executor.copy_out"):
                            elem = _copy_out(ring, slot, length)
                        self._ring_elements.inc()
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
                    lane.unlink_ring()
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
            # descriptors routed before the pop still lease ring slots
            while True:
                try:
                    lane.release(inq.get_nowait())
                except queue.Empty:
                    break
            if started:
                try:
                    ctrl.put(("cancel", rid))
                except Exception:
                    pass

    def stop(self) -> None:
        self._stopping.set()
        with self._lock:
            lanes = [lane for lane in self._lanes if lane is not None]
            self._lanes = [None] * self.width
        for lane in lanes:
            try:
                lane.ctrl.put(("shutdown",))
            except Exception:
                pass
        for lane in lanes:
            lane.proc.join(timeout=2.0)
            if lane.proc.is_alive():
                lane.proc.terminate()
                lane.proc.join(timeout=1.0)
        for lane in lanes:
            lane.close()


def make_executor(processes: int, registry: MetricsRegistry) -> PipelineExecutor:
    """Build the engine for ``worker_processes=N`` (0/1-thread semantics: 0
    keeps the paper's in-thread engine; N >= 1 runs an N-child pool whose
    fallback counter lives in ``registry``)."""
    if processes and processes > 0:
        return ProcessPoolExecutor(processes, registry)
    return InThreadExecutor()
