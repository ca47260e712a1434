"""Cross-process tracing for the disaggregated data path.

A trace follows one job's data across the four processes the paper
disaggregates (client, dispatcher, worker, device feeder):

* the CLIENT mints the trace: one root context per iteration session
  (carried on ``get_or_create_job`` / ``client_heartbeat``), plus one
  child context per element-batch RPC (``get_elements``/``get_element``);
* contexts travel INSIDE the RPC payload dicts (see ``core/protocol.py``)
  — no side channel, so they survive every transport (inproc/tcp/grpc)
  and, because the job's root context is journaled with ``job_created``,
  dispatcher failover: a promoted standby keeps stamping spans with the
  same ``trace_id`` (asserted by the chaos suite);
* each process records its spans into its own :class:`Tracer` ring buffer;
  ``trace_dump`` drains them over RPC and ``repro.obs.export`` merges the
  buffers into one Chrome trace-event JSON viewable in Perfetto.

Sampling gates ALL of it: with ``sample_rate == 0`` (the default) the hot
path pays one attribute check per RPC; with ``0 < rate < 1`` each
element-batch is traced with that probability, bounding the data-plane
overhead (< 5% at the default rates, measured by ``benchmarks/obs.py``).

Span timestamps are wall-clock (``time.time``) ON PURPOSE: they must be
comparable across processes in one exported trace, which is exactly the
cross-process exception to this repo's perf_counter-for-intervals rule.
Durations are still measured with ``perf_counter`` by the callers.

Every :meth:`Tracer.span` also opens a ``jax.profiler.TraceAnnotation`` of
the same name, sampled or not, so a ``jax.profiler`` session in the
trainer's process puts the service's own spans (worker long-poll, transport,
client, feeder) on the device trace's clock.  Outside a profiler session,
and in a process that never imported JAX (a remote worker), the span pays
a ``nullcontext`` in its place.  ``span(name, None)`` is the
context-free form: annotated, never recorded.  :func:`annotate` is the same
annotation for code that holds no tracer (the transport's framing).
"""
from __future__ import annotations

import random
import sys
import threading
import time
import uuid
from collections import deque
from contextlib import nullcontext
from dataclasses import dataclass
from typing import Any, ContextManager, Dict, List, Optional

__all__ = ["TraceContext", "Span", "Tracer", "annotate"]

_NULL = nullcontext()
_annotation_cls: Any = None


def _annotation(name: str, attrs: Dict[str, Any]) -> ContextManager[Any]:
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        # never import JAX here: a process without it keeps its start-up
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        cls = getattr(profiler, "TraceAnnotation", None)
        if cls is None:
            return _NULL
        _annotation_cls = cls
    if not cls.is_enabled():  # no profiler session: nothing to annotate
        return _NULL
    return cls(name, **attrs)


def annotate(name: str, **attrs: Any) -> ContextManager[Any]:
    """A ``jax.profiler.TraceAnnotation`` named ``name`` with ``attrs`` as
    its metadata while this process runs a profiler session, else a
    ``nullcontext``: the profiler half of :meth:`Tracer.span`."""
    return _annotation(name, attrs)


def _new_id(nbytes: int = 8) -> str:
    return uuid.uuid4().hex[: nbytes * 2]


@dataclass(frozen=True)
class TraceContext:
    """What travels inside RPC payloads: ``{"trace_id", "span_id", "sample"}``.

    ``span_id`` identifies the SENDER's span; the receiver records its own
    spans with ``parent_id = span_id``.  ``sample`` carries the minting
    client's sample rate so downstream processes (worker pipeline spans)
    gate per-element instrumentation at the same rate.
    """

    trace_id: str
    span_id: str
    sample: float = 1.0

    def child(self) -> "TraceContext":
        return TraceContext(self.trace_id, _new_id(), self.sample)

    def to_wire(self) -> Dict[str, Any]:
        return {"trace_id": self.trace_id, "span_id": self.span_id, "sample": self.sample}

    @staticmethod
    def from_wire(d: Optional[Dict[str, Any]]) -> Optional["TraceContext"]:
        if not isinstance(d, dict) or "trace_id" not in d:
            return None
        return TraceContext(
            str(d["trace_id"]),
            str(d.get("span_id", "")),
            float(d.get("sample", 1.0)),
        )


@dataclass
class Span:
    """One finished span.  ``start_unix`` is wall-clock (cross-process
    alignment — see module docstring); ``duration_s`` is interval-measured
    by the caller with perf_counter."""

    name: str
    trace_id: str
    span_id: str
    parent_id: Optional[str]
    process: str
    start_unix: float
    duration_s: float
    attrs: Dict[str, Any]

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "process": self.process,
            "start_unix": self.start_unix,
            "duration_s": self.duration_s,
            "attrs": self.attrs,
        }


class _SpanScope:
    """The with-block of one :meth:`Tracer.span` (a class, not a generator:
    the unsampled arm runs on every batch)."""

    __slots__ = ("_tracer", "_name", "_ctx", "_attrs", "_ann", "_child", "_wall", "_t0")

    def __init__(self, tracer: "Tracer", name: str, ctx: Optional[TraceContext],
                 attrs: Dict[str, Any]):
        self._tracer = tracer
        self._name = name
        self._ctx = ctx
        self._attrs = attrs
        self._ann = _annotation(name, attrs)

    def __enter__(self) -> Optional[TraceContext]:
        self._ann.__enter__()
        ctx = self._ctx
        if ctx is None:
            return None
        self._child = ctx.child()
        self._wall = time.time()  # cross-process timestamp (see module docstring)
        self._t0 = time.perf_counter()
        return self._child

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> None:
        try:
            if self._ctx is not None:
                self._tracer.record(
                    self._name,
                    self._child,
                    self._wall,
                    time.perf_counter() - self._t0,
                    parent_id=self._ctx.span_id,
                    **self._attrs,
                )
        finally:
            self._ann.__exit__(exc_type, exc, tb)


class Tracer:
    """Per-process span recorder with a bounded ring buffer.

    Recording is O(1) under a short lock; the buffer drops the OLDEST spans
    at capacity (a long-running traced job keeps its recent history, which
    is what a dashboard scrape wants).  All methods are thread-safe.
    """

    def __init__(self, process: str = "", sample_rate: float = 0.0, capacity: int = 8192):
        self.process = process or f"proc-{_new_id(3)}"
        self.sample_rate = max(0.0, min(1.0, float(sample_rate)))
        self._spans: deque = deque(maxlen=max(16, int(capacity)))
        self._lock = threading.Lock()
        self._rng = random.Random()
        self.dropped = 0

    # -- sampling ---------------------------------------------------------
    def should_sample(self, rate: Optional[float] = None) -> bool:
        r = self.sample_rate if rate is None else rate
        if r <= 0.0:
            return False
        if r >= 1.0:
            return True
        return self._rng.random() < r

    def start_trace(self, sample: Optional[float] = None) -> Optional[TraceContext]:
        """Mint a new root context, or None when tracing is off.  The root
        is minted whenever ``sample_rate > 0`` (session-level identity);
        per-batch spans are then gated at ``should_sample()`` rate."""
        rate = self.sample_rate if sample is None else sample
        if rate <= 0.0:
            return None
        return TraceContext(_new_id(), _new_id(), rate)

    # -- recording --------------------------------------------------------
    def record(
        self,
        name: str,
        ctx: TraceContext,
        start_unix: float,
        duration_s: float,
        parent_id: Optional[str] = None,
        span_id: Optional[str] = None,
        **attrs: Any,
    ) -> None:
        span = Span(
            name=name,
            trace_id=ctx.trace_id,
            span_id=span_id or ctx.span_id,
            parent_id=parent_id,
            process=self.process,
            start_unix=start_unix,
            duration_s=max(0.0, duration_s),
            attrs=attrs,
        )
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(span)

    def span(
        self, name: str, ctx: Optional[TraceContext], **attrs: Any
    ) -> ContextManager[Optional[TraceContext]]:
        """Annotate the with-block for the profiler and, with a context,
        record it as a child span of ``ctx`` (the block gets the child).
        With ``ctx is None`` (tracing off / unsampled) nothing is recorded
        and the block gets None; the annotation alone stays."""
        return _SpanScope(self, name, ctx, attrs)

    # -- draining ---------------------------------------------------------
    def drain(self, max_spans: int = 0) -> List[Dict[str, Any]]:
        """Pop up to ``max_spans`` recorded spans (0 = all), oldest first."""
        out: List[Dict[str, Any]] = []
        with self._lock:
            n = len(self._spans) if max_spans <= 0 else min(max_spans, len(self._spans))
            for _ in range(n):
                out.append(self._spans.popleft().to_dict())
        return out

    def peek(self) -> List[Dict[str, Any]]:
        """Non-destructive copy of the buffer (tests, dashboards)."""
        with self._lock:
            return [s.to_dict() for s in self._spans]

    def __len__(self) -> int:
        return len(self._spans)
