"""The engine's flush phase (collect, launch, round flush) over the window's wall time: the change in ``engine.flush_sec``
(host clock) across the window."""


def read(run):
    d = run.delta("engine.flush_sec")
    return d / run.wall_s if d is not None and run.wall_s > 0 else None
