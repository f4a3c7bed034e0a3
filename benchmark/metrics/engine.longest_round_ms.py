"""The longest wall time between two consecutive round boundaries in the
window, in ms (host clock, taken in the window's boundary hook): a run
that stalled once shows it here, where its rate only reads lower."""


def read(run):
    return run.longest_round_ms if run.wall_s > 0 else None
