"""The trace reduction (lib/trace.py) on small traces: a hand-made one
whose numbers are known, and a slice recorded on a v5e chip."""

import json
import os

import pytest

from benchmark.lib import trace as tr

DEV = "/device:TPU:0"
HOST = "/host:CPU"
MAIN = "python3"            # the engine's thread
COLLECT = "plane-collect"   # the device plane's collect thread


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
        # program spans name the gaps [1500, 2000), [4000, 10500)
        _ev(HOST, MAIN, "engine.launch", 1400, 700),
        _ev(HOST, MAIN, "engine.collect", 4200, 3000),
        # the harness's old wrappers and other host events name nothing
        _ev(HOST, MAIN, "bench.collect", 1000, 10000),
        _ev(HOST, MAIN, "PjitFunction(step)", 1000, 10000),
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
    assert gaps == {"engine.launch": pytest.approx(500 / 1e9),
                    "engine.collect": pytest.approx(6500 / 1e9)}
    assert sum(gaps.values()) == pytest.approx((10000 - busy) / 1e9)


def test_reduce_without_window_or_device():
    assert tr.reduce([_ev(DEV, "XLA Ops", "f", 0, 10)], {}) is None
    assert tr.reduce([_ev(HOST, "python", "bench.window", 0, 10)], {}) \
        is None


def _gaps(spans, idle=((1000, 2000),)):
    """The named idle gaps of a window [0, 10000) whose device is busy
    but for ``idle``."""
    events = [_ev(HOST, MAIN, "bench.window", 0, 10000)]
    cursor = 0
    for s, e in list(idle) + [(10000, 10000)]:
        if s > cursor:
            events.append(_ev(DEV, "XLA Ops", "fusion.1", cursor, s - cursor))
        cursor = e
    events += [_ev(HOST, line, name, s, e - s) for line, name, s, e in spans]
    red = tr.reduce(events, {})
    return {n: round(t * 1e9) for n, t in red["idle_gaps"]}


@pytest.mark.parametrize("spans, named", [
    # one thread: the plane's spans nested in the engine's collect; the
    # readback holds most of the gap, the wait the rest
    ([(MAIN, "engine.collect", 500, 2500), (MAIN, "plane.wait", 600, 1300),
      (MAIN, "plane.readback", 1300, 2100)], "plane.readback"),
    # the collect span holds the part of the gap nothing nested covers,
    # but the readback is innermost over more of it
    ([(MAIN, "engine.collect", 900, 2100),
      (MAIN, "plane.readback", 1400, 2000)], "plane.readback"),
    ([(MAIN, "engine.collect", 900, 2100),
      (MAIN, "plane.readback", 1700, 2000)], "engine.collect"),
    # a nested span with the same start is the inner one
    ([(MAIN, "engine.collect", 1000, 2000),
      (MAIN, "plane.fold", 1000, 1800)], "plane.fold"),
], ids=["wait_and_readback", "readback_most", "collect_most", "same_start"])
def test_gap_named_by_innermost_span_on_one_thread(spans, named):
    assert _gaps(spans) == {named: 1000}


@pytest.mark.parametrize("spans, named", [
    # the readback on the collect thread, inside the engine's collect on
    # the engine's thread
    ([(MAIN, "engine.collect", 500, 2500),
      (COLLECT, "plane.wait", 600, 1200),
      (COLLECT, "plane.readback", 1200, 2050)], "plane.readback"),
    # the collect thread's spans listed before the engine's
    ([(COLLECT, "plane.readback", 1100, 1900),
      (MAIN, "engine.collect", 900, 2200)], "plane.readback"),
    # spans on two threads that overlap without nesting: the one opened
    # last is innermost while both are open
    ([(MAIN, "engine.round", 900, 1500),
      (COLLECT, "plane.readback", 1200, 2100)], "plane.readback"),
    ([(MAIN, "engine.round", 900, 1700),
      (COLLECT, "plane.readback", 1600, 2100)], "engine.round"),
], ids=["nested_across_threads", "listed_out_of_order", "overlap_later",
        "overlap_earlier"])
def test_gap_named_by_innermost_span_across_threads(spans, named):
    assert _gaps(spans) == {named: 1000}


def test_gap_no_span_covers_reads_host_round():
    spans = [(MAIN, "engine.launch", 200, 900),
             (COLLECT, "plane.readback", 2100, 3000),
             (MAIN, "bench.collect", 0, 10000)]
    assert _gaps(spans, idle=((1000, 2000), (4000, 4500))) == {
        "host.round": 1500}
    # a gap partly covered is named by the span, whole
    spans.append((MAIN, "engine.flush", 4400, 4700))
    assert _gaps(spans, idle=((1000, 2000), (4000, 4500))) == {
        "host.round": 1000, "engine.flush": 500}


def test_each_gap_named_on_its_own():
    spans = [(MAIN, "engine.collect", 0, 10000),
             (COLLECT, "plane.readback", 1000, 2000),
             (MAIN, "engine.launch", 5000, 5600),
             (COLLECT, "plane.readback", 8000, 8700)]
    assert _gaps(spans, idle=((1000, 2000), (5000, 5500), (6000, 7000),
                              (8000, 9000))) == {
        "plane.readback": 2000, "engine.launch": 500, "engine.collect": 1000}


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_chains_slice.json")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace slice")
def test_reduce_recorded_v5e_slice():
    """A slice of a traced tor-chains-100k.waves run on one v5e chip
    (record_trace_slice.py: the device's longest idle gap in the window,
    20 ms on each side), with the numbers the reduction gave on the chip:
    the gap is the flush readback, named by the program's own span on the
    plane's collect thread."""
    with open(RECORDED) as f:
        rec = json.load(f)
    events = [tuple(e) for e in rec["events"]]
    red = tr.reduce(events, {"spanflush": "_step_span_flush_impl"})
    for key in ("window_s", "busy_s"):
        assert red[key] == pytest.approx(rec["expect"][key], rel=1e-9)
    assert red["kernel_s"]["spanflush"] == pytest.approx(
        rec["expect"]["spanflush_s"], rel=1e-9)
    assert red["idle_gaps"] == rec["expect"]["idle_gaps"]
    assert 0 < red["busy_s"] < red["window_s"]
    # the span-flush module is all the device does here: its module time
    # and the union of the device's ops agree
    assert red["kernel_s"]["spanflush"] == pytest.approx(red["busy_s"],
                                                         rel=1e-3)
    # the engine's thread and the collect thread both carry the
    # process's name in the xplane: spans are told apart by time alone
    spans = {name for _p, _l, name, _s, _d in events
             if tr.is_program_span(name)}
    assert {"engine.collect", "plane.wait", "plane.readback"} <= spans
    gaps = dict(red["idle_gaps"])
    idle = red["window_s"] - red["busy_s"]
    assert max(gaps, key=gaps.get) == "plane.readback"
    named = sum(t for n, t in gaps.items() if tr.is_program_span(n))
    assert named >= 0.95 * idle
    assert not any(n.startswith("bench.") for n in gaps)
