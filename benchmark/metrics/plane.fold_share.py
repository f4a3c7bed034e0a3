"""The host fold of each collected flush (parse, window bookkeeping, node
byte deltas, wakes) over the window's wall time: the change in
``plane.fold_sec`` (host clock) across the window.  None where the
program has no such counter or it did not move."""


def read(run):
    d = run.delta("plane.fold_sec")
    return d / run.wall_s if d and run.wall_s > 0 else None
