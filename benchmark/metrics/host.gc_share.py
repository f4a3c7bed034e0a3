"""Wall time spent in Python's cyclic collector over the window's wall
(host clock, from ``gc.callbacks`` registered at the window's opening
and removed at its close).  0 where no collection ran in the window."""


def read(run):
    return run.gc_s / run.wall_s if run.wall_s > 0 else None
