"""Reduction from a ``jax.profiler`` trace to the benchmark's device
numbers.  Every PR computes them with this code.

A trace is read into plain tuples, ``(plane, line, name, start_ns,
dur_ns)``, so the reduction can be checked on a small recorded trace
(``benchmark/tests/data``) without a chip.

* busy: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
  clipped to the traced window and averaged over the devices.
* kernel time: the summed device time of the ``XLA Modules`` events
  whose name contains a given program name (a jitted function's HLO
  module is ``jit_<function name>``).
* idle gaps: the device's idle intervals inside the window, each named
  by the program span (``engine.*``, ``plane.*``, ``native.*``,
  ``procs.*``, ``setup.*``: the program's layer spans, on whichever host
  thread ran them) that is innermost over most of the gap, else
  ``host.round``.  At each instant the innermost span is the one, of
  those open then, that opened last (so a span nested inside another
  wins over it, on one thread or across two).
"""

from __future__ import annotations

import glob
import heapq
import os
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, str, str, float, float]   # plane, line, name, start, dur

WINDOW_ANNOTATION = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("engine.", "plane.", "native.", "procs.", "setup.")
NO_SPAN = "host.round"


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and "NON_CORE" not in name


def is_program_span(name: str) -> bool:
    return name.startswith(SPAN_PREFIXES)


def read_xplane(log_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    data = ProfileData.from_file(paths[-1])
    out: List[Event] = []
    for plane in data.planes:
        keep_all = is_device_plane(plane.name)
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if keep_all or name == WINDOW_ANNOTATION \
                        or is_program_span(name):
                    out.append((plane.name, line.name, name,
                                float(ev.start_ns), float(ev.duration_ns)))
    return out


def _union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def window_of(events: Sequence[Event]) -> Optional[Tuple[float, float]]:
    for _p, _l, name, start, dur in events:
        if name == WINDOW_ANNOTATION:
            return start, start + dur
    return None


def reduce(events: Sequence[Event], kernels: Dict[str, str]) -> Optional[dict]:
    """The traced window's device numbers, or None where the trace holds
    no window or no device operation.  ``kernels`` maps a result name to
    the program name its modules contain; a kernel with no event is left
    out, never reported as 0."""
    win = window_of(events)
    if win is None:
        return None
    w0, w1 = win
    per_device: Dict[str, List[Tuple[float, float]]] = {}
    op_time: Dict[str, float] = {}
    kernel_ns: Dict[str, float] = {}
    host: List[Tuple[float, float, str]] = []
    for plane, line, name, start, dur in events:
        s, e = max(start, w0), min(start + dur, w1)
        if is_device_plane(plane):
            if e <= s:
                continue
            if line == OPS_LINE:
                per_device.setdefault(plane, []).append((s, e))
                op = name.split(" = ", 1)[0]     # "%while.36 = (...) while(...)"
                op_time[op] = op_time.get(op, 0.0) + (e - s)
            elif line == MODULES_LINE:
                for key, prog in kernels.items():
                    if prog in name:
                        kernel_ns[key] = kernel_ns.get(key, 0.0) + (e - s)
        elif is_program_span(name) and e > s:
            host.append((s, e, name))
    if not per_device:
        return None
    busy = {p: _union(iv) for p, iv in per_device.items()}
    busy_ns = sum(sum(e - s for s, e in iv) for iv in busy.values()) \
        / len(busy)
    # idle gaps of the first device (one chip per cell today), each named
    # by the span innermost over most of it
    first = busy[sorted(busy)[0]]
    idle: List[Tuple[float, float]] = []
    cursor = w0
    for s, e in first + [[w1, w1]]:
        if s > cursor:
            idle.append((cursor, s))
        cursor = max(cursor, e)
    gaps: Dict[str, float] = {}
    for (s, e), who in zip(idle, _name_gaps(idle, _innermost(host))):
        gaps[who] = gaps.get(who, 0.0) + (e - s)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "kernel_s": {k: v / 1e9 for k, v in kernel_ns.items()},
        "devices": len(busy),
        "device_ops": [[n, t / 1e9] for n, t in top_ops],
        "idle_gaps": [[n, t / 1e9] for n, t in top_gaps],
    }


def _innermost(spans: Sequence[Tuple[float, float, str]]
               ) -> List[Tuple[float, float, str]]:
    """The time line cut where any span opens or closes, each piece under
    the innermost span open over it: of those open, the one that opened
    last, then the one that closes first.  Pieces under no span are left
    out.  Sorted, disjoint ``(start, end, name)``."""
    marks = sorted({t for s, e, _n in spans for t in (s, e)})
    order = sorted(range(len(spans)), key=lambda i: spans[i][0])
    heap: List[Tuple[float, float, int]] = []
    out: List[Tuple[float, float, str]] = []
    j = 0
    for a, b in zip(marks, marks[1:]):
        while j < len(order) and spans[order[j]][0] <= a:
            i = order[j]
            heapq.heappush(heap, (-spans[i][0], spans[i][1], i))
            j += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if heap:
            # a closed span deeper in the heap is dropped when it surfaces
            name = spans[heap[0][2]][2]
            if out and out[-1][1] == a and out[-1][2] == name:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def _name_gaps(gaps: Sequence[Tuple[float, float]],
               pieces: Sequence[Tuple[float, float, str]]) -> List[str]:
    """For each of the sorted, disjoint ``gaps``, the name that holds most
    of it among ``pieces`` (``_innermost``), or ``NO_SPAN``."""
    names: List[str] = []
    k = 0
    for s, e in gaps:
        while k < len(pieces) and pieces[k][1] <= s:
            k += 1
        held: Dict[str, float] = {}
        m = k
        while m < len(pieces) and pieces[m][0] < e:
            ps, pe, name = pieces[m]
            held[name] = held.get(name, 0.0) + min(pe, e) - max(ps, s)
            m += 1
        names.append(max(held, key=held.get) if held else NO_SPAN)
    return names
