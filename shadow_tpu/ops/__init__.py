"""Device data plane: JAX kernels for the per-round packet step.

Importing this package enables jax x64 mode: simulation timestamps are
nanoseconds since boot (int64 — a one-hour simulation is 3.6e12 ns, far past
int32), and event-order parity with the CPU policies requires exact integer
time math on device.  TPUs support int64; we use float32/bfloat16 for all
non-time quantities so the MXU/VPU paths stay fast.
"""

import jax

from ..obs.jit import compile_clock

jax.config.update("jax_enable_x64", True)
# count this process's XLA compiles (obs/jit.py) from its first kernel on
compile_clock()
