"""XLA compiles of this process, counted where JAX reports them.

One process-wide ``jax.monitoring`` listener on
``/jax/core/compile/backend_compile_duration``, the event JAX records
around each backend compile; a program loaded from the persistent
compilation cache is recorded the same way, so it counts too.  JAX's
listeners cannot be removed, so there is one listener per process and its
totals are the process's: ``jit.compiles`` and ``jit.compile_sec`` in
every registry's scrape (:func:`scrape`).  A reader wanting compiles
inside an interval takes the difference of two scrapes.

The listener is installed by the first :func:`compile_clock` call after
JAX has been imported (``shadow_tpu.ops`` calls it as it imports JAX, and
``configure_observability`` does where JAX is already loaded); it never
imports JAX itself.
"""

from __future__ import annotations

import sys
import threading
from typing import Dict, Optional

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Counts the compile events JAX reports to :meth:`on_duration`."""

    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0
        self.sec = 0.0

    def on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.count += 1
                self.sec += duration

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {"jit.compiles": self.count,
                    "jit.compile_sec": round(self.sec, 6)}


_clock: Optional[CompileClock] = None
_install_lock = threading.Lock()


def compile_clock() -> Optional[CompileClock]:
    """The process's clock, its listener registered on the first call
    made once JAX is loaded; None before that."""
    global _clock
    if _clock is None and "jax" in sys.modules:
        with _install_lock:
            if _clock is None:
                import jax.monitoring
                clock = CompileClock()
                jax.monitoring.register_event_duration_secs_listener(
                    clock.on_duration)
                _clock = clock
    return _clock


def scrape() -> Dict[str, float]:
    """The ``jit`` registry source: empty until JAX is loaded."""
    clock = compile_clock()
    return clock.snapshot() if clock is not None else {}
