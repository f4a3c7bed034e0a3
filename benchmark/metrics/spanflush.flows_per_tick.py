"""Flows the span-flush kernel steps per tick: the flow-ticks it stepped
in the window (``plane.flow_ticks_stepped``: each dispatch's kernel
width, the compacted width where it ran one, times the ticks it
executed) over the ticks it executed (``plane.ticks_stepped``).  None
where the program has no such counter or no tick ran."""


def read(run):
    stepped = run.delta("plane.flow_ticks_stepped")
    ticks = run.delta("plane.ticks_stepped")
    if not stepped or not ticks:
        return None
    return stepped / ticks
