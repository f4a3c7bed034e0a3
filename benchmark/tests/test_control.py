"""The comparison that decides ``correct`` for the chains configuration
(``benchmark/comparisons/chains.py``) fails when the timed path is
wrong: the control (the plane stepped at twice the configured granule,
the approximation a later PR could be tempted by) and faults planted in
the timed path once the window has opened.  Each run goes through the
whole harness, with the look for a chip skipped, and must print a result
whose ``correct`` is false."""

import pytest

from benchmark.lib.window import Window

from conftest import result_of

CHAINS = "tor-chains-100k.waves"
# numbers only comparisons/chains.py compares
CHAINS_CHECKS = {"plane_lag_ticks", "flows_differing", "nodes_differing",
                 "completions_differing"}


def _args(cell, seed=4294967311, seconds=1.0):
    return ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", "0"]


def _once(real, alter):
    done = []

    def call(*a, **kw):
        if not done and a[8].any():
            done.append(1)
            return alter(a, real, kw)
        return real(*a, **kw)
    return call


def _plane(engine):
    plane = engine.device_plane
    if plane._flush_step is None:
        from shadow_tpu.ops.torcells_device import \
            step_window_flush_for_backend
        plane._flush_step = step_window_flush_for_backend()
    return plane


def plane_state_unchanged(engine):
    """A dispatch that injects cells hands back the state it was given."""
    plane = _plane(engine)

    def alter(a, real, kw):
        out = real(*a, **kw)
        return (*a[:8], *out[8:])
    plane._flush_step = _once(plane._flush_step, alter)


def plane_half_injections_dropped(engine):
    """A dispatch takes in only every other circuit it was handed."""
    plane = _plane(engine)

    def alter(a, real, kw):
        import numpy as np
        a = list(a)
        keep = np.arange(len(a[8])) % 2 == 0
        a[8] = np.where(keep, np.asarray(a[8]), 0)
        a[9] = np.where(keep, np.asarray(a[9]), 0)
        return real(*a, **kw)
    plane._flush_step = _once(plane._flush_step, alter)


def plane_cell_altered(engine):
    """A dispatch reports one more cell delivered on one flow."""
    plane = _plane(engine)

    def alter(a, real, kw):
        out = list(real(*a, **kw))
        out[4] = out[4].at[int(plane.last_flow[0])].add(1)
        return tuple(out)
    plane._flush_step = _once(plane._flush_step, alter)


def _plant(monkeypatch, fault):
    real_hook = Window._hook

    def hook(self, lookahead):
        more = real_hook(self, lookahead)
        if self.t0_ns is not None and not getattr(self, "_planted", False):
            self._planted = True
            fault(self.engine)
        return more
    monkeypatch.setattr(Window, "_hook", hook)


@pytest.mark.parametrize("fault", [
    plane_state_unchanged, plane_half_injections_dropped,
    plane_cell_altered], ids=lambda f: f.__name__)
def test_fault_makes_correct_false(bench, monkeypatch, capsys, fault):
    _plant(monkeypatch, fault)
    assert bench.main(_args(CHAINS)) == 0
    res = result_of(capsys.readouterr().out)
    assert res is not None and res["correct"] is False
    assert CHAINS_CHECKS <= set(res["checks"])
    assert res["failed"] > 0
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_coarser_granule_control_is_not_correct(bench, monkeypatch, capsys):
    """The control: the timed path steps the plane at twice the granule
    the configuration states; the reference keeps the stated one."""
    real_build = bench.build_controller

    def build(c, scenario, flags, tmpdir):
        import copy
        c2 = copy.copy(c)
        c2.config = dict(c.config, plane=dict(c.config["plane"]))
        c2.config["plane"]["granule_ms"] *= 2
        return real_build(c2, scenario, flags, tmpdir)
    monkeypatch.setattr(bench, "build_controller", build)
    assert bench.main(_args(CHAINS, seconds=0.5)) == 0
    res = result_of(capsys.readouterr().out)
    assert res is not None and res["correct"] is False
    assert CHAINS_CHECKS <= set(res["checks"])


@pytest.mark.parametrize("seed", [4294967311, 2**31 + 5])
def test_sound_run_is_correct(bench, capsys, seed):
    assert bench.main(_args(CHAINS, seed)) == 0
    res = result_of(capsys.readouterr().out)
    assert res is not None and res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
