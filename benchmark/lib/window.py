"""The measured window, taken between two round boundaries of
``Engine.run``.

The program has no public stop hook yet (PERF.md lists one for the
``tracing`` issue), so the harness wraps the engine's per-round
``_advance_window`` call on the one engine instance it drives.  That call
is the round boundary: the previous round's host work is done and its
device dispatch has been collected, and ``scheduler.window_end`` is the
simulated time the simulation has reached.

* The window opens at the first boundary at or after ``warm_ns``.
* It closes at the first boundary at or after ``seconds`` of wall time
  past the opening, where the hook lowers ``engine.end_time`` to that
  boundary and ends the loop as a stop time there would.
* Simulated and wall time are both read at those two boundaries.
* A run whose loop ends by itself (the stop time, or no event left)
  before the window closed has no window: ``closed`` stays False.
"""

from __future__ import annotations

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


class Window:
    def __init__(self, engine, warm_ns: int, seconds: float,
                 on_open: Optional[Callable[[int], None]] = None,
                 on_close: Optional[Callable[[int], None]] = None,
                 first_boundary: Optional[Callable[[], None]] = None):
        self.engine = engine
        self.warm_ns = int(warm_ns)
        self.seconds_ns = int(seconds * 1e9)
        self.on_open = on_open
        self.on_close = on_close
        self.first_boundary = first_boundary
        self.boundaries = 0
        self.t0_ns: Optional[int] = None
        self.t1_ns: Optional[int] = None
        self.sim0_ns: Optional[int] = None
        self.sim1_ns: Optional[int] = None
        self.ended_by_itself_at: Optional[int] = None
        self._note = _Notes()
        self._advance = engine._advance_window
        engine._advance_window = self._hook

    @property
    def closed(self) -> bool:
        return self.t1_ns is not None

    @property
    def wall_s(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9

    @property
    def sim_s(self) -> float:
        return (self.sim1_ns - self.sim0_ns) / 1e9

    def _hook(self, lookahead: int) -> bool:
        boundary = self.engine.scheduler.window_end
        if self.boundaries == 0 and self.first_boundary is not None:
            self.first_boundary()
        self.boundaries += 1
        self._note(f"boundary {self.boundaries} at sim {boundary / 1e9:.6f} "
                   f"s, window {'open' if self.t0_ns else 'not open'}")
        if self.t0_ns is None:
            if boundary >= self.warm_ns:
                if self.on_open is not None:
                    self.on_open(boundary)
                self.sim0_ns = boundary
                self.t0_ns = time.perf_counter_ns()
        elif self.t1_ns is None \
                and time.perf_counter_ns() - self.t0_ns >= self.seconds_ns:
            self.t1_ns = time.perf_counter_ns()
            self.sim1_ns = boundary
            self.engine.end_time = boundary
            if self.on_close is not None:
                self.on_close(boundary)
            return False
        more = self._advance(lookahead)
        if not more:
            self.ended_by_itself_at = boundary
        return more

