"""The trace reduction (lib/trace.py) on small traces: a hand-made one
whose numbers are known, and a slice recorded on a v5e chip."""

import json
import os

import pytest

from benchmark.lib import trace as tr

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _ev(plane, line, name, start, dur):
    return (plane, line, name, float(start), float(dur))


def test_reduce_hand_made():
    events = [
        _ev(HOST, "python", "bench.window", 1000, 10000),
        # ops: [500, 1500) clips to [1000, 1500); [2000, 3000) and the
        # overlapping [2500, 4000) merge into [2000, 4000); [10500, 12000)
        # clips to [10500, 11000)
        _ev(DEV, "XLA Ops", "fusion.1", 500, 1000),
        _ev(DEV, "XLA Ops", "fusion.2", 2000, 1000),
        _ev(DEV, "XLA Ops", "scatter.3", 2500, 1500),
        _ev(DEV, "XLA Ops", "fusion.1", 10500, 1500),
        _ev(DEV, "XLA Modules", "jit__step_span_flush_impl(7)", 2000, 2000),
        _ev(DEV, "XLA Modules", "jit_step(9)", 10500, 1500),
        # host annotations name the gaps [1500, 2000), [4000, 10500)
        _ev(HOST, "python", "bench.launch", 1400, 700),
        _ev(HOST, "python", "bench.collect", 4200, 3000),
        # outside the window: ignored
        _ev(DEV, "XLA Ops", "fusion.9", 20000, 500),
    ]
    red = tr.reduce(events, {"spanflush": "_step_span_flush_impl",
                             "absent": "no_such_program"})
    assert red["window_s"] == pytest.approx(10000 / 1e9)
    busy = 500 + 2000 + 500
    assert red["busy_s"] == pytest.approx(busy / 1e9)
    assert red["kernel_s"] == {"spanflush": pytest.approx(2000 / 1e9)}
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx(1000 / 1e9)
    assert ops["scatter.3"] == pytest.approx(1500 / 1e9)
    gaps = dict(red["idle_gaps"])
    assert gaps["bench.launch"] == pytest.approx(500 / 1e9)
    assert gaps["bench.collect"] == pytest.approx(6500 / 1e9)
    assert sum(gaps.values()) == pytest.approx((10000 - busy) / 1e9)


def test_reduce_without_window_or_device():
    assert tr.reduce([_ev(DEV, "XLA Ops", "f", 0, 10)], {}) is None
    assert tr.reduce([_ev(HOST, "python", "bench.window", 0, 10)], {}) \
        is None


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_chains_slice.json")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace slice")
def test_reduce_recorded_v5e_slice():
    """A slice of a traced tor-chains-100k.waves run on one v5e chip (my
    chip run, PR 22), with the numbers the reduction gave on the chip."""
    with open(RECORDED) as f:
        rec = json.load(f)
    events = [tuple(e) for e in rec["events"]]
    red = tr.reduce(events, {"spanflush": "_step_span_flush_impl"})
    for key in ("window_s", "busy_s"):
        assert red[key] == pytest.approx(rec["expect"][key], rel=1e-9)
    assert red["kernel_s"]["spanflush"] == pytest.approx(
        rec["expect"]["spanflush_s"], rel=1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    # the span-flush holds the device through this slice: its module time
    # and the union of the ops inside it agree to within 0.1%
    assert red["kernel_s"]["spanflush"] == pytest.approx(red["busy_s"],
                                                         rel=1e-3)
