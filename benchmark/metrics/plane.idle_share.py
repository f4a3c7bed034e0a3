"""Wall time with no device-plane dispatch in flight, from a collect's
readback to the next launch's return, over the window's wall time: the
change in ``plane.idle_sec`` (host clock) across the window.  None where
the program has no such counter or it did not move."""


def read(run):
    d = run.delta("plane.idle_sec")
    return d / run.wall_s if d and run.wall_s > 0 else None
