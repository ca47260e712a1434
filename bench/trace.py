"""Reduction of a profiler trace to the benchmark's device numbers.

Two stages, so that the second can be checked on a small recorded trace:

* :func:`load` reads a ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
  plain lists: for each device plane, the events of its ``XLA Ops`` line;
  on the host, the benchmark's own spans (``jax.profiler.TraceAnnotation``
  in ``bench/harness.py``).  Times are nanoseconds on the trace's clock.
* :func:`reduce` takes those lists and the traced window and gives the
  busy time of each device (the union of its op intervals), the device ops
  that took the most time (self time: a loop's op less the ops nested in
  it), the idle gaps of the first device attributed to
  the host span that overlaps each gap the most, and the time collectives
  ran on the first device with no other op beside them.
"""
from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]  # name, start_ns, duration_ns

HOST_SPANS = ("bench.window", "feeder.next", "step.dispatch", "loss.wait")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute", "all-to-all")
OPS_LINE = "XLA Ops"


def load(trace_dir: str) -> Dict[str, Any]:
    """Device op events and the benchmark's host spans of the newest trace."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (op_name(e.name), float(e.start_ns), float(e.duration_ns))
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events if e.name in HOST_SPANS)
    return {"devices": devices, "host": host}


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...), ...`` → ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(base: Sequence[Interval], cover: Sequence[Interval]) -> List[Interval]:
    """Parts of the (disjoint, sorted) ``base`` that ``cover`` leaves open."""
    cover = union(cover)
    out: List[Interval] = []
    j = 0
    for a, b in base:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def self_times(events: Sequence[Event], lo: float, hi: float) -> Dict[str, float]:
    """Time of each op inside [lo, hi] not covered by ops nested in it (a
    loop's op encloses the ops of its body), summed by op name."""
    out: Dict[str, float] = {}
    stack: List[List[Any]] = []  # [name, end, own time]

    def close(item: List[Any]) -> None:
        out[item[0]] = out.get(item[0], 0.0) + item[2]

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        a, b = max(s, lo), min(s + d, hi)
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        own = max(0.0, b - a)
        if stack and own > 0:
            stack[-1][2] -= own
        stack.append([name, s + d, own])
    while stack:
        close(stack.pop())
    return out


def window_of(host: Sequence[Event]) -> Interval:
    spans = [(s, s + d) for n, s, d in host if n == "bench.window"]
    if not spans:
        raise ValueError("the trace has no bench.window span")
    return max(spans, key=lambda iv: iv[1] - iv[0])


def reduce(events: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    """Device numbers over the traced window, in seconds."""
    lo, hi = window_of(events["host"])
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace has no device with an XLA Ops line")
    names = sorted(devices)
    busy = {}
    op_time: Dict[str, float] = {}
    for name in names:
        ivs = [(s, s + d) for _, s, d in devices[name]]
        busy[name] = length(union(clip(ivs, lo, hi)))
        for op, t in self_times(devices[name], lo, hi).items():
            op_time[op] = op_time.get(op, 0.0) + t
    first = [(s, s + d, op) for op, s, d in devices[names[0]]]
    busy0 = union(clip([(a, b) for a, b, _ in first], lo, hi))
    gaps = subtract([(lo, hi)], busy0)
    spans = [(n, s, s + d) for n, s, d in events["host"] if n != "bench.window"]
    by_span: Dict[str, float] = {}
    for a, b in gaps:
        best, most = "other", 0.0
        for n, s, e in spans:
            o = min(b, e) - max(a, s)
            if o > most:
                best, most = n, o
        by_span[best] = by_span.get(best, 0.0) + (b - a)
    coll = [(a, b) for a, b, op in first if any(c in op for c in COLLECTIVES)]
    other = [(a, b) for a, b, op in first if not any(c in op for c in COLLECTIVES)]
    exposed = length(subtract(clip(union(coll), lo, hi), other))
    n_dev = len(names)
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy.values()) / n_dev * ns,
        "device_ops": [[op, t / n_dev * ns] for op, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, t * ns] for n, t in sorted(by_span.items(), key=lambda kv: -kv[1])[:top]],
        "collective_s": length(clip(union(coll), lo, hi)) * ns,
        "collective_exposed_s": exposed * ns,
        "devices": n_dev,
    }
