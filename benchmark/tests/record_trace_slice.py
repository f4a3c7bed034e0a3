#!/usr/bin/env python3
"""Record ``benchmark/tests/data/v5e_chains_slice.json`` on the chip: one
traced run of the harness (``benchmark/run.py``, same arguments, with
``--trace 1``), whose trace is cut to the device's longest idle gap in
the window with ``PAD_NS`` on each side, a ``bench.window`` annotation
laid over the cut, and what ``lib/trace.reduce`` gives for the cut.

    python3 benchmark/tests/record_trace_slice.py --workload <cell> \\
        --seed <n> --seconds <s> --trace 1 [--out <path>]

The benchmark's own runs never run this."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import run as bench  # noqa: E402
from benchmark.lib import trace as tr  # noqa: E402

PAD_NS = 20e6
KERNELS = {"spanflush": "_step_span_flush_impl"}


def cut(events):
    """The events around the longest idle gap of the first device."""
    w0, w1 = tr.window_of(events)
    dev = sorted(p for p, *_ in events if tr.is_device_plane(p))[0]
    busy = tr._union([(max(s, w0), min(s + d, w1))
                      for p, line, _n, s, d in events
                      if p == dev and line == tr.OPS_LINE
                      and s + d > w0 and s < w1])
    g0, g1 = max(((a[1], b[0]) for a, b in zip(busy, busy[1:])),
                 key=lambda g: g[1] - g[0])
    a, b = g0 - PAD_NS, g1 + PAD_NS
    kept = [e for e in events if e[2] != tr.WINDOW_ANNOTATION
            and e[3] < b and e[3] + e[4] > a]
    return [("/host:CPU", "python3", tr.WINDOW_ANNOTATION, a, b - a)] + kept


def main(argv):
    out = os.path.join(HERE, "data", "v5e_chains_slice.json")
    if "--out" in argv:
        i = argv.index("--out")
        out = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    seen = {}
    read = tr.read_xplane

    def keep(log_dir):
        seen["events"] = read(log_dir)
        return seen["events"]
    tr.read_xplane = keep
    rc = bench.main(argv)
    if rc != 0 or "events" not in seen:
        return rc or 1
    events = cut(seen["events"])
    red = tr.reduce(events, KERNELS)
    seed = argv[argv.index("--seed") + 1]
    rec = {"about": (f"Events of a traced {argv[argv.index('--workload') + 1]}"
                     f" run on one {bench.find_devices(1)[0].device_kind} "
                     f"chip (seed {seed}), as benchmark/lib/trace.read_xplane "
                     "returns them, cut to the device's longest idle gap in "
                     "the window and 20 ms on each side, under a "
                     "bench.window laid over the cut (record_trace_slice.py);"
                     " 'expect' holds what lib/trace.reduce gave for the cut"
                     " when it was recorded."),
           "expect": {"window_s": red["window_s"], "busy_s": red["busy_s"],
                      "spanflush_s": red["kernel_s"].get("spanflush"),
                      "idle_gaps": red["idle_gaps"]},
           "events": [list(e) for e in events]}
    with open(out, "w") as f:
        json.dump(rec, f)
    print(f"slice: {len(events)} events, {os.path.getsize(out)} bytes, "
          f"gaps {red['idle_gaps']}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
