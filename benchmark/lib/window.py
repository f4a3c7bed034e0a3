"""The measured window, taken between two round boundaries of
``Engine.run``, through the engine's ``on_boundary`` hook.

A boundary is the point where the previous round's host work is done and
its device dispatch has been collected, and ``scheduler.window_end`` is
the simulated time the simulation has reached.

* The window opens at the first boundary at or after ``warm_ns``.
* It closes at whichever comes first: the first boundary at or after
  ``seconds`` of wall time past the opening (``closed_by`` "wall"), or
  the last boundary at or before ``last_ns``, the scenario's last offered
  arrival (``closed_by`` "traffic_end"), past which the traffic is no
  longer stationary.  For the second, the engine's stop time is lowered
  to ``last_ns`` at the opening, so that no round reaches past it, and a
  boundary is the last at or before it where the engine's next window
  would start there or later (``Engine._advance_window``'s rule: the
  earliest host event or device-plane need).  A stop time below
  ``last_ns`` is left as it is.  The hook then returns False, and the
  engine stops there as a stop time would.
* Simulated and wall time are both read at those two boundaries.
* A run whose loop ends by itself (its own stop time, or no event left)
  before the window closed has no window: ``closed`` stays False.

Two facts of the window's host side, read at its close: the longest wall
time between two consecutive boundaries (``longest_round_ns``), and the
wall time spent in Python's collector (``gc_ns`` over ``gc_collections``
collections, from ``gc.callbacks`` registered at the opening and removed
at the close).
"""

from __future__ import annotations

import gc
import sys
import time
from typing import Callable, Optional

NOTE_EVERY_NS = 30_000_000_000


class _Notes:
    """A progress line on stderr every 30 s of wall: where a slow run was."""

    def __init__(self):
        self._next_ns = 0

    def __call__(self, text: str) -> None:
        now = time.perf_counter_ns()
        if now >= self._next_ns:
            self._next_ns = now + NOTE_EVERY_NS
            print(f"bench: progress: {text}", file=sys.stderr, flush=True)


class _GcClock:
    """Wall time in Python's collector while registered in
    ``gc.callbacks``."""

    def __init__(self):
        self.ns = 0
        self.collections = 0
        self._start: Optional[int] = None

    def __call__(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._start = time.perf_counter_ns()
        elif self._start is not None:
            self.ns += time.perf_counter_ns() - self._start
            self.collections += 1
            self._start = None

    def start(self) -> None:
        gc.callbacks.append(self)

    def stop(self) -> None:
        if self in gc.callbacks:
            gc.callbacks.remove(self)


class Window:
    def __init__(self, engine, warm_ns: int, seconds: float, last_ns: int,
                 on_open: Optional[Callable[[int], None]] = None,
                 on_close: Optional[Callable[[int], None]] = None,
                 first_boundary: Optional[Callable[[], None]] = None):
        self.engine = engine
        self.warm_ns = int(warm_ns)
        self.seconds_ns = int(seconds * 1e9)
        self.last_ns = int(last_ns)
        self.on_open = on_open
        self.on_close = on_close
        self.first_boundary = first_boundary
        self.boundaries = 0
        self.t0_ns: Optional[int] = None
        self.t1_ns: Optional[int] = None
        self.sim0_ns: Optional[int] = None
        self.sim1_ns: Optional[int] = None
        self.closed_by: Optional[str] = None
        self.last_boundary: Optional[int] = None
        self.longest_round_ns = 0
        self.gc = _GcClock()
        self._to_traffic_end = False
        self._prev_ns = 0
        self._note = _Notes()
        engine.on_boundary(self._hook)

    @property
    def closed(self) -> bool:
        return self.t1_ns is not None

    @property
    def wall_s(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9

    @property
    def sim_s(self) -> float:
        return (self.sim1_ns - self.sim0_ns) / 1e9

    def _hook(self, boundary: int) -> bool:
        now = time.perf_counter_ns()
        if self.boundaries == 0 and self.first_boundary is not None:
            self.first_boundary()
        self.boundaries += 1
        self.last_boundary = boundary
        self._note(f"boundary {self.boundaries} at sim {boundary / 1e9:.6f} "
                   f"s, window {'open' if self.t0_ns else 'not open'}")
        if self.t0_ns is None:
            if boundary >= self.warm_ns:
                self._open(boundary)
            return True
        self.longest_round_ns = max(self.longest_round_ns,
                                    now - self._prev_ns)
        self._prev_ns = now
        if now - self.t0_ns >= self.seconds_ns:
            self._close(now, boundary, "wall")
            return False
        if self._to_traffic_end and (boundary >= self.last_ns
                                     or self._next_start() >= self.last_ns):
            self._close(now, boundary, "traffic_end")
            return False
        return True

    def _next_start(self) -> int:
        engine = self.engine
        t = engine.scheduler.next_event_time()
        if engine.device_plane is not None:
            t = min(t, engine.device_plane.next_time())
        return t

    def _open(self, boundary: int) -> None:
        if self.on_open is not None:
            self.on_open(boundary)
        self._to_traffic_end = boundary < self.last_ns <= self.engine.end_time
        if self._to_traffic_end:
            self.engine.end_time = self.last_ns
        self.sim0_ns = boundary
        self.gc.start()
        self.t0_ns = self._prev_ns = time.perf_counter_ns()

    def _close(self, now: int, boundary: int, why: str) -> None:
        self.t1_ns = now
        self.gc.stop()
        self.sim1_ns = boundary
        self.closed_by = why
        if self.on_close is not None:
            self.on_close(boundary)
