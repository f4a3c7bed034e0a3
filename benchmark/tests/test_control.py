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


def _plant_once(plane, full, compact):
    """The plane's next dispatch that injects cells goes through ``full``
    (the full-width program, ``plane._flush_step``, whose argument 8 is
    the inject vector) or ``compact`` (the compacted program,
    ``plane._compact_step``, whose argument 8 is the ``live`` table with
    the injected cells in row 1), whichever runs it; every other
    dispatch runs as it is.  Each alteration is ``alter(a, real, kw)``."""
    if plane._flush_step is None:
        from shadow_tpu.ops.torcells_device import \
            step_window_flush_for_backend
        plane._flush_step = step_window_flush_for_backend()
    if plane._compact_step is None:
        from shadow_tpu.ops.torcells_device import compact_flush_for_backend
        plane._compact_step = compact_flush_for_backend()
    done = []

    def once(real, alter, injects):
        def call(*a, **kw):
            if not done and injects(a[8]):
                done.append(1)
                return alter(a, real, kw)
            return real(*a, **kw)
        return call
    plane._flush_step = once(plane._flush_step, full,
                             lambda inject: inject.any())
    plane._compact_step = once(plane._compact_step, compact,
                               lambda live: live[1].any())


def _state_unchanged(a, real, kw):
    out = real(*a, **kw)
    return (*a[:8], *out[8:])


def plane_state_unchanged(engine):
    """A dispatch that injects cells hands back the state it was given."""
    _plant_once(engine.device_plane, _state_unchanged, _state_unchanged)


def plane_half_injections_dropped(engine):
    """A dispatch takes in only about half of the chains it was handed:
    those whose entry (and exit) flow index is even on the full-width
    program, the even chains on the compacted one."""
    import numpy as np

    def full(a, real, kw):
        a = list(a)
        keep = np.arange(len(a[8])) % 2 == 0
        a[8] = np.where(keep, np.asarray(a[8]), 0)
        a[9] = np.where(keep, np.asarray(a[9]), 0)
        return real(*a, **kw)

    def compact(a, real, kw):
        a = list(a)
        live = np.array(a[8])
        drop = live[3] % 2 == 1
        live[1:3, drop] = 0
        a[8] = live
        return real(*a, **kw)
    _plant_once(engine.device_plane, full, compact)


def plane_cell_altered(engine):
    """A dispatch reports one more cell delivered on one flow."""
    plane = engine.device_plane

    def alter(a, real, kw):
        out = list(real(*a, **kw))
        out[4] = out[4].at[int(plane.last_flow[0])].add(1)
        return tuple(out)
    _plant_once(plane, alter, alter)


def _plant(monkeypatch, fault):
    real_hook = Window._hook

    def hook(self, boundary):
        more = real_hook(self, boundary)
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
