"""Device time of the span-flush kernel per simulated second: the summed
duration of every HLO module whose name contains ``_step_span_flush_impl``
in the traced window (all of its donating, non-donating and capped jit
variants wrap that function, ops/torcells_device.py), in ms, over the
window's simulated seconds (the trace spans the whole window)."""

KERNELS = {"spanflush": "_step_span_flush_impl"}


def read(run):
    sec = (run.trace or {}).get("kernel_s", {}).get("spanflush")
    if sec is None or run.sim_s <= 0:
        return None
    return sec * 1e3 / run.sim_s
