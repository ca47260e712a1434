"""The program's own spans in a profiler trace, reduced over the traced
window, and the worker counters beside them.

``repro.obs.tracing.Tracer.span`` opens a ``jax.profiler.TraceAnnotation``
for every span, so a traced run's host planes hold the service's spans
beside the benchmark's (``bench/trace.py``), one line per thread, on the
device's clock.  :func:`load` reads them with their thread; :func:`reduce`
gives what the per-layer readers ``feed.next_ms_p90``,
``device.idle_fetch_share``, ``transport.recv_ms_per_batch`` and
``worker.wait_ms_per_batch`` read, and ``program_gaps``: the first
device's idle time in the window under each program span name open on any
thread.  :func:`counters` reads the workers' batches served and their
shm fallbacks for a full ring.  Where the program has no such span or
counter (one older than them) the number is None, never an error.
"""
from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Sequence, Tuple

from . import trace as T

PROGRAM_SPANS = (
    "worker.wait", "worker.encode", "executor.recv",
    "transport.encode", "transport.send", "transport.recv", "transport.decode",
    "client.fetch", "client.decode", "client.enqueue",
    "feed.fetch", "feed.device_put", "feed.queue_put", "feed.next",
)
DATA_PLANE = ("get_elements", "get_element")  # RPCs whose responses carry batches
# name, start_ns, duration_ns, thread (plane and line), RPC method or ""
ProgramEvent = Tuple[str, float, float, str, str]


def load(trace_dir: str) -> List[ProgramEvent]:
    """The program's spans of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out: List[ProgramEvent] = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}#{i}"
            for e in line.events:
                if e.name in PROGRAM_SPANS:
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

    return {
        "program_gaps": [[n, t * ns] for n, t in sorted(idle.items(), key=lambda kv: -kv[1])
                         if t > 0][:top],
        "idle_fetch_s": idle["feed.fetch"] * ns if "feed.fetch" in idle else None,
        "feed_next_s": [d * ns for n, s, d, _, _ in program if n == "feed.next" and lo <= s < hi],
        "transport_recv_s": within(("transport.recv", "transport.decode"), DATA_PLANE),
        "worker_wait_s": within(("worker.wait",)),
    }


def counters(orchestrator: Any) -> Dict[str, Any]:
    """Batches the workers served and the shm-channel fetches they answered
    inline because the ring was full, summed over the workers (None where
    the program does not count them)."""
    served = ring_full = 0.0
    counted = False
    for w in orchestrator.workers:
        snap = w.registry.snapshot()
        served += snap.get("worker_batches_served", {}).get("value", 0.0)
        inline = snap.get("worker_shm_inline_total")
        if inline is not None:
            counted = True
            ring_full += inline.get("series", {}).get("reason=ring_full", 0.0)
    return {"worker_batches_served": served,
            "worker_shm_ring_full": ring_full if counted else None}
