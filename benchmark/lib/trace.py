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
  by the host annotation (``bench.*``, opened by the harness around the
  engine's calls) that overlaps it most, else ``host.round``.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, str, str, float, float]   # plane, line, name, start, dur

WINDOW_ANNOTATION = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and "NON_CORE" not in name


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
                if keep_all or name.startswith("bench."):
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
        elif name.startswith("bench.") and name != WINDOW_ANNOTATION \
                and e > s:
            host.append((s, e, name))
    if not per_device:
        return None
    busy = {p: _union(iv) for p, iv in per_device.items()}
    busy_ns = sum(sum(e - s for s, e in iv) for iv in busy.values()) \
        / len(busy)
    # idle gaps of the first device (one chip per cell today), named by
    # the host annotation that overlaps each most
    first = busy[sorted(busy)[0]]
    host.sort()
    starts = [h[0] for h in host]
    gaps: Dict[str, float] = {}
    cursor = w0
    for s, e in first + [[w1, w1]]:
        if s > cursor:
            who = _who(host, starts, cursor, s)
            gaps[who] = gaps.get(who, 0.0) + (s - cursor)
        cursor = max(cursor, e)
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


def _who(host: Sequence[Tuple[float, float, str]], starts: List[float],
         s: float, e: float) -> str:
    """The host annotation that overlaps [s, e) most.  The harness's
    annotations are sequential calls on one thread, so the scan walks back
    from the last one that starts before ``e`` until one ends before
    ``s``."""
    best, best_ns = "host.round", 0.0
    j = bisect.bisect_left(starts, e) - 1
    while j >= 0:
        hs, he, name = host[j]
        ov = min(he, e) - max(hs, s)
        if ov > best_ns:
            best, best_ns = name, ov
        if he <= s:
            break
        j -= 1
    return best
