"""The program's own spans in a profiler trace, reduced over the traced
window, and the program's counters beside them.

``repro.obs.tracing.Tracer.span`` and ``repro.obs.tracing.annotate`` open
a ``jax.profiler.TraceAnnotation`` for every span, so a traced run's host
planes hold the service's spans beside the benchmark's (``bench/trace.py``),
one line per thread, on the device's clock.  The program names every span
``layer.what`` in dotted lowercase (:data:`PROGRAM_NAME`); :func:`load`
keeps every host event so named, with its thread, except the benchmark's
own spans.  The Python tracer's frames (``$file.py:12 fn``) and the
runtime's events (``PjitFunction(f)``, ``Foo::Bar``) do not match.

:func:`reduce` gives, for every span name, its count, total seconds, p90
duration and the first device's idle time under it (``spans``); the idle
times again as ``program_gaps``, the ten longest; and what the readers
``feed.next_ms_p90``, ``device.idle_fetch_share``,
``transport.recv_ms_per_batch`` and ``worker.wait_ms_per_batch`` read.
:func:`counters` reads every counter family of every worker's registry and
of the process's default registry, labelled series included as
``name{k=v}``.  Where the program has no such span or counter the number is
None, never an error.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Sequence, Tuple

from . import stats
from . import trace as T

PROGRAM_NAME = re.compile(r"^[a-z][a-z0-9_]*(?:\.[a-z][a-z0-9_]*)+$")
DATA_PLANE = ("get_elements", "get_element")  # RPCs whose responses carry batches
# name, start_ns, duration_ns, thread (plane and line), RPC method or ""
ProgramEvent = Tuple[str, float, float, str, str]


def is_program_span(name: str) -> bool:
    return bool(PROGRAM_NAME.match(name)) and name not in T.HOST_SPANS


def load(data: Any) -> List[ProgramEvent]:
    """The program's spans of a loaded trace (``bench.trace.profile``)."""
    out: List[ProgramEvent] = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}#{i}"
            for e in line.events:
                if is_program_span(e.name):
                    method = dict(e.stats).get("method", "")
                    out.append((e.name, float(e.start_ns), float(e.duration_ns), thread,
                                str(method)))
    return out


def idle_under(gaps: Sequence[T.Interval], spans: Sequence[T.Interval]) -> float:
    """Time of the (disjoint, sorted) ``gaps`` that ``spans`` cover."""
    return T.length(gaps) - T.length(T.subtract(gaps, spans))


def reduce(program: Sequence[ProgramEvent], events: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    """The program's numbers over the window of ``events`` (what
    ``bench.trace.load`` gives), in seconds."""
    lo, hi = T.window_of(events["host"])
    first = sorted(events["devices"])[0]
    busy0 = T.union(T.clip([(s, s + d) for _, s, d in events["devices"][first]], lo, hi))
    gaps = T.subtract([(lo, hi)], busy0)
    by_name: Dict[str, List[T.Interval]] = {}
    for name, s, d, _, _ in program:
        by_name.setdefault(name, []).append((s, s + d))
    idle = {n: idle_under(gaps, T.clip(ivs, lo, hi)) for n, ivs in by_name.items()}
    ns = 1e-9

    def within(names: Sequence[str], methods: Sequence[str] = ()) -> Any:
        picked = [(s, d) for n, s, d, _, m in program
                  if n in names and (not methods or m in methods)]
        if not picked:
            return None
        return sum(T.length(T.clip([(s, s + d)], lo, hi)) for s, d in picked) * ns

    spans = {}
    for name, ivs in sorted(by_name.items()):
        inside = [b - a for a, b in ivs if lo <= a < hi]
        spans[name] = {"count": len(inside), "total_s": T.length(T.clip(ivs, lo, hi)) * ns,
                       "p90_s": stats.nearest_rank(inside, 0.9) * ns if inside else None,
                       "idle_s": idle[name] * ns}
    return {
        "spans": spans,
        "program_gaps": [[n, t * ns] for n, t in sorted(idle.items(), key=lambda kv: -kv[1])
                         if t > 0][:top],
        "idle_fetch_s": idle["feed.fetch"] * ns if "feed.fetch" in idle else None,
        "feed_next_s": [d * ns for n, s, d, _, _ in program if n == "feed.next" and lo <= s < hi],
        "transport_recv_s": within(("transport.recv", "transport.decode"), DATA_PLANE),
        "worker_wait_s": within(("worker.wait",)),
    }


def registry_values(snapshot: Dict[str, Dict[str, Any]]) -> Dict[str, float]:
    """A registry snapshot's counters, flat: ``name`` for the unlabelled
    series, ``name{k=v,...}`` for each labelled one; a histogram gives its
    ``name_count`` and ``name_sum``.  Gauges are levels, not counts, and
    are left out."""
    out: Dict[str, float] = {}
    for name, fam in snapshot.items():
        kind = fam.get("kind")
        if kind == "counter":
            out[name] = float(fam.get("value", 0.0))
            for labels, v in fam.get("series", {}).items():
                out[f"{name}{{{labels}}}"] = float(v)
        elif kind == "histogram":
            for labels, h in [("", fam.get("value", {})), *fam.get("series", {}).items()]:
                tag = f"{{{labels}}}" if labels else ""
                out[f"{name}_count{tag}"] = float(h.get("count", 0))
                out[f"{name}_sum{tag}"] = float(h.get("sum", 0.0))
    return out


def counters(orchestrator: Any) -> Dict[str, Any]:
    """Every counter of every worker's registry and of the process's
    default registry, summed; ``worker_batches_served`` reads 0 where no
    worker served a batch."""
    from repro.obs.registry import get_registry

    out: Dict[str, float] = {}
    snaps = [w.registry.snapshot() for w in orchestrator.workers] + [get_registry().snapshot()]
    for snap in snaps:
        for k, v in registry_values(snap).items():
            out[k] = out.get(k, 0.0) + v
    out.setdefault("worker_batches_served", 0.0)
    return out
