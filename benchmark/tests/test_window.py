"""The window: it opens and closes at round boundaries, the rate is taken
between those two boundaries, a window whose wall budget outlasts the
offered traffic closes at the last boundary at or before the last
arrival, and a run whose stop time ends it inside the window fails with
no result."""

import gc
import json

import pytest

from benchmark.lib.window import Window

from conftest import result_of

CELL = "tor-chains-100k.waves"


def _args(cell, seconds=1.0):
    return ["--workload", cell, "--seed", "2147483711",
            "--seconds", str(seconds), "--trace", "0"]


def _watch(monkeypatch):
    """The boundaries the window's hook saw, and the window."""
    seen = {"boundaries": []}
    real_hook = Window._hook

    def hook(self, boundary):
        assert boundary == self.engine.scheduler.window_end
        seen["boundaries"].append(boundary)
        seen["window"] = self
        return real_hook(self, boundary)
    monkeypatch.setattr(Window, "_hook", hook)
    return seen


def test_window_closes_at_round_boundaries(bench, monkeypatch, capsys):
    seen = _watch(monkeypatch)
    assert bench.main(_args(CELL, seconds=0.5)) == 0
    captured = capsys.readouterr()
    res = result_of(captured.out)
    assert res is not None and res["correct"] is True
    win = seen["window"]
    bounds = seen["boundaries"]
    assert win.closed_by == "wall"
    # both ends are round boundaries the engine reached, the first at or
    # after warm_sim_s, and the run stopped at the closing one
    assert win.sim0_ns in bounds and win.sim1_ns == bounds[-1]
    warm = 2.2e9                     # traffic/waves.json warm_sim_s
    assert win.sim0_ns >= warm
    assert [b for b in bounds if b >= warm][0] == win.sim0_ns
    assert win.engine.scheduler.window_end == win.sim1_ns
    assert win.wall_s >= 0.5
    rate = res["metrics"]["sim_s_per_wall_s"]["value"]
    assert rate == (win.sim1_ns - win.sim0_ns) / 1e9 / win.wall_s
    # the longest round is one of the window's rounds, and the untraced
    # run's window line carries it with the collector's share
    assert 0 < win.longest_round_ns <= win.t1_ns - win.t0_ns
    facts = _window_line(captured.err)
    assert facts["longest_round_ms"] == win.longest_round_ns / 1e6
    assert facts["gc_share"] == win.gc.ns / 1e9 / win.wall_s
    assert facts["dispatches"] > 0
    assert win.gc not in gc.callbacks


def _window_line(err: str) -> dict:
    line = [x for x in err.splitlines() if x.startswith("bench: window: ")]
    assert len(line) == 1
    return json.loads(line[0][len("bench: window: "):])


def test_stop_time_inside_window_fails(bench, capsys):
    bench.sizes = {"stoptime_s": 12}
    assert bench.main(_args(CELL, seconds=60)) != 0
    captured = capsys.readouterr()
    assert result_of(captured.out) is None
    assert "ended by itself" in captured.err


@pytest.mark.parametrize("start_s", [2.0, 2.035])
def test_window_closes_at_traffic_end(bench, monkeypatch, capsys, start_s):
    """20 waves 0.1 s apart end at 3.9 s, on a round boundary, or at
    3.935 s, between two; the window opens near 2.2 s with a wall budget
    far past what the CPU needs to get there, so it closes at the last
    boundary at or before the last arrival, with a result."""
    bench.traffic = {"waves": 20, "start_s": start_s}
    seen = _watch(monkeypatch)
    assert bench.main(_args(CELL, seconds=600)) == 0
    captured = capsys.readouterr()
    res = result_of(captured.out)
    assert res is not None
    if start_s == 2.0:
        assert res["correct"] is True
    # (starts off the 10 ms granule differ from the reference on the CPU
    # however the window closes: PERF.md, Open questions)
    win = seen["window"]
    last = round((start_s + 19 * 0.1) * 1e9)
    assert win.closed_by == "traffic_end"
    assert win.sim1_ns == seen["boundaries"][-1]
    assert all(b <= last for b in seen["boundaries"])
    assert last - 10_000_000 < win.sim1_ns <= last
    assert win.wall_s < 600
    rate = res["metrics"]["sim_s_per_wall_s"]["value"]
    assert rate == win.sim_s / win.wall_s
    assert _window_line(captured.err)["closed_by"] == "traffic_end"


def test_no_chip_no_result():
    """The unpatched harness on this CPU, started as every benchmark run
    starts it (``python3 benchmark/run.py`` from the checkout's root): it
    gets as far as the look for a chip, then exits non-zero with no
    result."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    r = subprocess.run([sys.executable, "benchmark/run.py", *_args(CELL)],
                       cwd=root, capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert result_of(r.stdout) is None
    assert "not a TPU" in r.stderr
