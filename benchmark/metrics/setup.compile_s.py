"""Seconds of XLA compiles (persistent-cache loads included) the process
made before the window opened: ``jit.compile_sec``, from the program's
listener on JAX's ``/jax/core/compile/backend_compile_duration`` events.
None where the program has no such counter or it reads 0."""


def read(run):
    v = run.before.get("jit.compile_sec")
    return float(v) if v else None
