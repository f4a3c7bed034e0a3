"""Process start to the window's opening boundary (host clock): imports,
the native-plane check, the scenario build, the plane build, compiles or
cache loads, the kernel warm-up, and the warm-up to ``warm_sim_s``."""


def read(run):
    return run.setup_s
