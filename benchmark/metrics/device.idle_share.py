"""1 - (union of the intervals in which an operation ran on the device)
/ (the traced window), from the ``jax.profiler`` trace of the window,
averaged over the chips used."""


def read(run):
    t = run.trace or {}
    if not t.get("window_s"):
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
