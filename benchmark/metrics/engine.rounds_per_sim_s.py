"""Engine rounds (``engine.rounds``) run in the window per simulated
second."""


def read(run):
    d = run.delta("engine.rounds")
    return d / run.sim_s if d is not None and run.sim_s > 0 else None
