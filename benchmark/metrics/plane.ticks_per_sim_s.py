"""Span-flush kernel ticks executed in the window (``plane.ticks_stepped``:
each dispatch's reached step less its base; idle ticks the plane banked
while empty are not executed) per simulated second.  None where the
program has no such counter or it did not move."""


def read(run):
    d = run.delta("plane.ticks_stepped")
    return d / run.sim_s if d and run.sim_s > 0 else None
