"""Flush bytes the device traffic plane copied from the device to the host
in the window (``plane.flush_bytes_read``) per dispatch
(``plane.dispatches``), in KiB.  None where the program has no such
counter or either did not move."""


def read(run):
    read_bytes = run.delta("plane.flush_bytes_read")
    dispatches = run.delta("plane.dispatches")
    if not read_bytes or not dispatches:
        return None
    return read_bytes / dispatches / 1024
