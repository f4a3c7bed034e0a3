"""Device time of the span-flush kernel per simulated second: the summed
duration of every HLO module whose name contains ``_step_span_flush_impl``
in the traced window (the full-width program's donating and
non-donating jit wrappers, and the compacted program's, whose function
``_compact_step_span_flush_impl`` holds that name too;
ops/torcells_device.py), in ms, over the window's simulated seconds (the
trace spans the whole window)."""

KERNELS = {"spanflush": "_step_span_flush_impl"}


def read(run):
    sec = (run.trace or {}).get("kernel_s", {}).get("spanflush")
    if sec is None or run.sim_s <= 0:
        return None
    return sec * 1e3 / run.sim_s
