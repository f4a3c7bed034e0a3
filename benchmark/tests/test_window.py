"""The window: it opens and closes at round boundaries, the rate is taken
between those two boundaries, and a run whose stop time or offered
traffic runs out inside the window fails with no result."""

from benchmark.lib.window import Window

from conftest import result_of

CELL = "tor-chains-100k.waves"


def _args(cell, seconds=1.0):
    return ["--workload", cell, "--seed", "2147483711",
            "--seconds", str(seconds), "--trace", "0"]


def test_window_closes_at_round_boundaries(bench, monkeypatch, capsys):
    seen = {}
    real_hook = Window._hook

    def hook(self, lookahead):
        seen.setdefault("boundaries", []).append(
            self.engine.scheduler.window_end)
        seen["window"] = self
        return real_hook(self, lookahead)
    monkeypatch.setattr(Window, "_hook", hook)
    assert bench.main(_args(CELL)) == 0
    res = result_of(capsys.readouterr().out)
    assert res is not None and res["correct"] is True
    win = seen["window"]
    bounds = seen["boundaries"]
    # both ends are round boundaries the engine reached, the first at or
    # after warm_sim_s, and the run stopped at the closing one
    assert win.sim0_ns in bounds and win.sim1_ns == bounds[-1]
    warm = 2.2e9                     # traffic/waves.json warm_sim_s
    assert win.sim0_ns >= warm
    assert [b for b in bounds if b >= warm][0] == win.sim0_ns
    assert win.engine.scheduler.window_end == win.sim1_ns
    assert win.wall_s >= 1.0
    rate = res["metrics"]["sim_s_per_wall_s"]["value"]
    assert rate == (win.sim1_ns - win.sim0_ns) / 1e9 / win.wall_s


def test_stop_time_inside_window_fails(bench, capsys):
    bench.sizes = {"stoptime_s": 12}
    assert bench.main(_args(CELL, seconds=60)) != 0
    captured = capsys.readouterr()
    assert result_of(captured.out) is None
    assert "ended by itself" in captured.err


def test_traffic_running_out_fails(bench, capsys):
    # 6 waves end at 7 s; the window opens at 3 s and runs far past them
    bench.traffic = {"waves": 6, "step_s": 1.0}
    assert bench.main(_args(CELL, seconds=3)) != 0
    captured = capsys.readouterr()
    assert result_of(captured.out) is None
    assert "last offered arrival" in captured.err \
        or "ended by itself" in captured.err


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
