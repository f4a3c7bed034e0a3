"""The program's own build of the run, read at the window's opening:
``setup.build_sec``, the wall seconds ``Controller.run`` spent in
``setup()`` (hosts, host table, topology finalize) and in building the
device plane (flow table layout).  None where the program has no such
gauge."""


def read(run):
    v = run.before.get("setup.build_sec")
    return float(v) if v else None
