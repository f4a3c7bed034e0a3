"""Simulated seconds advanced between the window's first and last round
boundary, over the wall seconds between them (host clock)."""


def read(run):
    return run.sim_s / run.wall_s if run.wall_s > 0 else None
