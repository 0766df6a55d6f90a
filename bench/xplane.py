"""Reduce a worker's profiler trace to device busy time, op time and idle
gaps, on the worker's own clock.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` (only
a process that imports JAX calls it).  Everything else is plain Python
over ``(name, start_ns, end_ns)`` tuples, so it is tested on the CPU
against a recorded trace and hand-made intervals.

The worker wraps each ``execute`` in a ``TraceAnnotation`` named
``EXECUTE`` with its step id, and notes the host clock at each entry.
Matching the two gives the offset from trace time to the host clock, so
the measured window can be cut out of the trace exactly.  An idle gap on
the device is split by what the worker was doing meanwhile: inside
``execute`` (the backend's host work: gathering pages, uploading,
reading back) or between executes (waiting for the engine's next plan).
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence, Tuple

EXECUTE = "bench.execute"
DEVICE = "/device:TPU:"
OPS_LINE = "XLA Ops"
IN_EXECUTE = "in_execute"
BETWEEN = "between_executes"

Interval = Tuple[int, int]


def load(path: str) -> dict:
    """Device op events per device plane, and the host's execute
    annotations, from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[tuple]] = {}
    executes: List[tuple] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE):
            evs = devices.setdefault(plane.name, [])
            for ln in plane.lines:
                if ln.name != OPS_LINE:
                    continue
                for ev in ln.events:
                    s = int(ev.start_ns)
                    evs.append((op_name(ev.name), s,
                                s + int(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == EXECUTE:
                        step = dict(ev.stats).get("step")
                        s = int(ev.start_ns)
                        executes.append((step, s, s + int(ev.duration_ns)))
    return {"devices": devices, "executes": executes}


def op_name(hlo: str) -> str:
    """An op event's name is its HLO instruction; keep the instruction's
    name, and a custom call's target (a Pallas kernel is a
    ``tpu_custom_call``)."""
    name = hlo.split(" = ", 1)[0]
    if "custom_call_target=" in hlo:
        target = hlo.split("custom_call_target=", 1)[1].split(",", 1)[0]
        name += f" ({target.strip(chr(34))})"
    return name


def clock_offset(executes: Sequence[tuple],
                 host_starts_ns: Dict[int, int]) -> Optional[int]:
    """Host-clock ns minus trace ns, the median over executes found both
    in the trace (by step id) and in the worker's own record."""
    diffs = [host_starts_ns[step] - s for step, s, _ in executes
             if step in host_starts_ns]
    return int(statistics.median(diffs)) if diffs else None


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], w0: int, w1: int) -> List[Interval]:
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if e > w0 and s < w1]


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> int:
    """Total length of the intersection of two sorted disjoint lists."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def reduce_device(ops: Sequence[tuple], executes: Sequence[Interval],
                  w0: int, w1: int, top: int = 10) -> dict:
    """One device's busy time, time per op name, and idle gaps within
    the window [w0, w1) (all in trace ns)."""
    busy = union(clip([(s, e) for _, s, e in ops], w0, w1))
    op_ns: Dict[str, int] = {}
    for name, s, e in ops:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            op_ns[name] = op_ns.get(name, 0) + e - s
    gaps: List[Interval] = []
    t = w0
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if w1 > t:
        gaps.append((t, w1))
    ex = union(clip(executes, w0, w1))
    idle_in = overlap(gaps, ex)
    idle = sum(e - s for s, e in gaps)
    labelled = []
    for s, e in gaps:
        inside = overlap([(s, e)], ex)
        labelled.append((IN_EXECUTE if 2 * inside >= e - s else BETWEEN,
                         e - s))
    labelled.sort(key=lambda p: -p[1])
    return {"window_ns": w1 - w0,
            "busy_ns": sum(e - s for s, e in busy),
            "op_ns": op_ns,
            "idle_ns": {IN_EXECUTE: idle_in, BETWEEN: idle - idle_in},
            "longest_gaps": labelled[:top]}
