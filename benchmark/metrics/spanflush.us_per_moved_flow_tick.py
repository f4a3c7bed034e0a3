"""Device time of the span-flush kernel per unit of useful work: the
summed duration of the HLO modules whose name contains
``_step_span_flush_impl`` in the traced window (as
``spanflush.device_ms_per_sim_s`` reads it), in microseconds, over the
(flow, tick) pairs in which a flow moved at least one cell
(``plane.flow_ticks_moved``, counted by the kernel into its flush
header) across the window.  None where either is missing or zero."""

KERNELS = {"spanflush": "_step_span_flush_impl"}


def read(run):
    sec = (run.trace or {}).get("kernel_s", {}).get("spanflush")
    moved = run.delta("plane.flow_ticks_moved")
    if not sec or not moved:
        return None
    return sec * 1e6 / moved
