"""Reduction of a profiler trace to the benchmark's device numbers.

Two stages, so that the second can be checked on a small recorded trace:

* :func:`load` reads a trace (a ``.xplane.pb`` that :func:`profile` opens
  with ``jax.profiler.ProfileData``) into plain lists: for each device
  plane, the events of its ``XLA Ops`` line; on the host, the benchmark's
  own spans (``jax.profiler.TraceAnnotation`` in ``bench/harness.py``).
  Times are nanoseconds on the trace's clock.  A run on the CPU backend
  has no device plane: there, and only where the caller says so, each
  host thread that ran XLA ops (events with an ``hlo_op`` stat) stands
  for a device, which checks the plumbing and measures nothing.
* :func:`reduce` takes those lists and the traced window and gives the
  busy time of each device (the union of its op intervals), the device ops
  that took the most time (self time: a loop's op less the ops nested in
  it), the idle gaps of the first device attributed to
  the host span that overlaps each gap the most, the time collectives
  ran on the first device with no other op beside them, and the self time
  of every named scope (:func:`scopes_of`) over all devices.

The ops' JAX name stacks come from the compiled program's HLO text
(:func:`hlo_stacks`): instruction name to the ``op_name`` of its metadata,
or, for an instruction the compiler made without one, of the computation
it calls, its async partner or the instruction that calls its computation.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]  # name, start_ns, duration_ns

HOST_SPANS = ("bench.window", "feeder.next", "step.dispatch", "loss.wait")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute", "all-to-all")
OPS_LINE = "XLA Ops"
# autodiff, batching and remat wrappers around a name-stack segment
WRAPPER = re.compile(
    r"^(?:jvp|transpose|vmap|checkpoint|remat|remat2|rematted_computation)\((.*)\)$")
BARE_WRAPPERS = ("checkpoint", "remat", "remat2", "rematted_computation", "")
HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=%]+) = ")
HLO_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+) .*\{\s*$")
HLO_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
HLO_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply|branch_computations|called_computations)"
                       r"=(\{[^}]*\}|%?[\w.\-]+)")
HLO_NAMES = re.compile(r"%?([\w.\-]+)")


def profile(trace_dir: str) -> Any:
    """``jax.profiler.ProfileData`` of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(paths[-1])


def load(data: Any, cpu_stand_in: bool = False) -> Dict[str, Any]:
    """Device op events and the benchmark's host spans of a loaded trace
    (:func:`profile`).  ``cpu_stand_in`` is for a run on the CPU backend
    alone: its host threads that ran XLA ops stand for devices.  Elsewhere
    a trace with no device ``XLA Ops`` line reads no device, and
    :func:`reduce` refuses it."""
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
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append((e.name, float(e.start_ns), float(e.duration_ns)))
                    elif cpu_stand_in and plane.name == "/host:CPU" \
                            and not e.name.startswith("end: ") and "hlo_op" in dict(e.stats):
                        devices.setdefault(f"{plane.name}#{i}", []).append(
                            (e.name, float(e.start_ns), float(e.duration_ns)))
    return {"devices": devices, "host": host}


def hlo_stacks(text: str) -> Dict[str, str]:
    """Instruction name → ``op_name`` of its metadata, over every
    computation of a compiled program's HLO text.  An instruction the
    compiler made without metadata takes, in this order: the ``op_name``
    of the computation it calls (an async collective's fusion: its root's,
    else its first); that of its async partner (``x-start.3`` and
    ``x-done.3``); that of the instruction that calls its computation (a
    prefetch inside a loop's body: the loop's).  Ops of the entry
    computation that none of these names stay unknown."""
    # computation → its instructions (name, op_name or "", is the root)
    comps: Dict[str, List[Tuple[str, str, bool]]] = {}
    calls: Dict[str, List[str]] = {}  # instruction → computations it calls
    caller: Dict[str, str] = {}  # computation → an instruction that calls it
    cur = None
    for line in text.splitlines():
        m = HLO_INSTR.match(line)
        if m is None:
            h = HLO_HEADER.match(line)
            if h:
                cur = h.group(1)
                comps[cur] = []
            continue
        name = m.group(1)
        op = HLO_OP_NAME.search(line)
        if cur is not None:
            comps[cur].append((name, op.group(1) if op else "", line.lstrip().startswith("ROOT")))
        called = [c for group in HLO_CALLS.findall(line) for c in HLO_NAMES.findall(group)]
        if called:
            calls[name] = called
            for c in called:
                caller.setdefault(c, name)
    out = {name: op for instrs in comps.values() for name, op, _ in instrs if op}

    def inner(comp: str) -> str:
        instrs = comps.get(comp, [])
        ops = [op for _, op, root in instrs if op and root] + [op for _, op, _ in instrs if op]
        return ops[0] if ops else ""

    def partner(name: str) -> str:
        for a, b in (("-start", "-done"), ("-done", "-start")):
            if a in name:
                return out.get(name.replace(a, b, 1), "")
        return ""

    for name in [n for instrs in comps.values() for n, op, _ in instrs if not op]:
        op = next((inner(c) for c in calls.get(name, []) if inner(c)), "") or partner(name)
        if op:
            out[name] = op

    home = {name: comp for comp, instrs in comps.items() for name, _, _ in instrs}

    def enclosing(comp: str, seen: Tuple[str, ...] = ()) -> str:
        call = caller.get(comp)
        if call is None or comp in seen:
            return ""
        if call in out:
            return out[call]
        return enclosing(home[call], seen + (comp,)) if call in home else ""

    for comp, instrs in comps.items():
        missing = [name for name, _, _ in instrs if name not in out]
        stack = enclosing(comp) if missing else ""
        if stack:
            out.update(dict.fromkeys(missing, stack))
    return out


def segments(stack: str) -> List[str]:
    """A name stack's ``/``-separated segments (a ``/`` inside brackets
    does not separate)."""
    out, depth, cur = [], 0, []
    for ch in stack:
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    out.append("".join(cur))
    return out


def scopes_of(stack: str) -> List[str]:
    """The scopes an op ran under: each segment of its name stack with the
    autodiff and remat wrappers taken off (``transpose(jvp(moe))`` →
    ``moe``), so that a ``jax.named_scope`` counts forward and backward."""
    out: List[str] = []
    for seg in segments(stack):
        m = WRAPPER.match(seg)
        while m:
            seg = m.group(1)
            m = WRAPPER.match(seg)
        if seg not in BARE_WRAPPERS and seg not in out:
            out.append(seg)
    return out


def scope_times(devices: Dict[str, Sequence[Event]], stacks: Dict[str, str], lo: float,
                hi: float) -> Tuple[Dict[str, float], float, float]:
    """Self time of every scope summed over the devices (device-time: a
    scope that runs on four chips at once counts four times), the busy
    time whose op has a known name stack, and all busy time."""
    table: Dict[str, float] = {}
    known = total = 0.0
    for evs in devices.values():
        for op, t in self_times(evs, lo, hi).items():
            total += t
            stack = stacks.get(op)
            if not stack:
                continue
            known += t
            for scope in scopes_of(stack):
                table[scope] = table.get(scope, 0.0) + t
    return table, known, total


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


def reduce(events: Dict[str, Any], top: int = 10,
           stacks: Optional[Dict[str, str]] = None) -> Dict[str, Any]:
    """Device numbers over the traced window, in seconds.  ``stacks`` gives
    each op's name stack (:func:`hlo_stacks`), for the scopes."""
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
    scopes, known, total = scope_times(devices, stacks or {}, lo, hi)
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy.values()) / n_dev * ns,
        "device_ops": [[op, t / n_dev * ns] for op, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, t * ns] for n, t in sorted(by_span.items(), key=lambda kv: -kv[1])[:top]],
        "collective_s": length(clip(union(coll), lo, hi)) * ns,
        "collective_exposed_s": exposed * ns,
        "devices": n_dev,
        "scopes": {k: t * ns for k, t in sorted(scopes.items(), key=lambda kv: -kv[1])},
        "coverage": known / total if total > 0 else None,
    }
