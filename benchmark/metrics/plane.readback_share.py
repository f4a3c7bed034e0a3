"""The device-to-host copy of each dispatch's flush buffer over the
window's wall time: the change in ``plane.readback_sec`` (host clock,
timed in the program after the wait for the kernel) across the window.
None where the program has no such counter or it did not move."""


def read(run):
    d = run.delta("plane.readback_sec")
    return d / run.wall_s if d and run.wall_s > 0 else None
