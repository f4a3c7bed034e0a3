"""The host blocked on the device traffic plane's collect over the window's wall time: the change in ``plane.plane_device_sec``
(host clock) across the window."""


def read(run):
    d = run.delta("plane.plane_device_sec")
    return d / run.wall_s if d is not None and run.wall_s > 0 else None
