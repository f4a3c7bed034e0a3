#!/usr/bin/env python3
"""The control, at the cell's own size, on the chip: one whole run of the
harness (``benchmark/run.py``, same arguments) with the timed plane
stepped at twice the granule the configuration states.  The reference
keeps the stated granule, so ``correct`` has to come out false; the
result line gives each number compared, which sets the upper reading of
its limit (PERF.md).  The benchmark's own runs never run this."""

import copy
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench  # noqa: E402

_build = bench.build_controller


def coarser(cell, scenario, flags, tmpdir):
    c2 = copy.copy(cell)
    c2.config = dict(cell.config, plane=dict(cell.config["plane"]))
    c2.config["plane"]["granule_ms"] *= 2
    return _build(c2, scenario, flags, tmpdir)


if __name__ == "__main__":
    bench.build_controller = coarser
    sys.exit(bench.main())
