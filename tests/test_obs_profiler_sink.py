"""The profiler sink and the counters at the work (obs/trace.py, obs/jit.py,
parallel/device_plane.py).

1. Under a ``jax.profiler`` session a device-plane run writes its layer
   spans (``engine.*``, ``plane.*``, ``setup.*``) into the session's
   xplane, on the host timeline the device trace shares; with no session
   and no ``--trace`` nothing is recorded anywhere.
2. The plane's wall split adds up: ``launch_sec + fold_sec`` is
   ``plane_host_sec`` and ``wait_sec + readback_sec`` is
   ``plane_device_sec``, to the scrape's rounding.
3. ``flow_ticks_moved`` and ``ticks_stepped`` count the kernel's work:
   10 moved (flow, tick) pairs per uncontended circuit on both execution
   modes, and the ticks the kernel executed, banked idle ticks excluded.
4. ``jit.compiles`` counts a fresh jit once and its repeat call not at
   all.
5. The public round-boundary hook sees every boundary and ends the run
   when it returns False.
6. The span-flush jit wrappers, the compacted ones too, lower to HLO
   modules named after ``_step_span_flush_impl``, the name the
   benchmark's kernel-time reader matches.
"""

import glob
import io
import os

import numpy as np
import pytest

from shadow_tpu.core import configuration
from shadow_tpu.core.checkpoint import state_digest
from shadow_tpu.core.controller import Controller
from shadow_tpu.core.logger import SimLogger, set_logger
from shadow_tpu.core.options import Options
from shadow_tpu.scale import genscen
from shadow_tpu.tools import workloads

# 35 process-less circuits in 10 waves 1.5 s apart from 2 s; every bucket
# holds a wave's cells many times over at the 10 ms granule, so each of a
# circuit's 5 download and 5 upload stages moves its cells on one tick.
# The 1 s heartbeat sweeps run rounds between waves, while the plane is
# empty: those rounds bank idle ticks instead of dispatching.
N_HOSTS, WAVES = 40, 10


def _chains(mode="device", stop=20, **opt_kw):
    set_logger(SimLogger(stream=io.StringIO(), level="warning"))
    try:
        cfg = genscen.tor(N_HOSTS, stoptime=stop, stagger_waves=WAVES,
                          stagger_step_sec=1.5)
        ctrl = Controller(Options(scheduler_policy="global", workers=0,
                                  stop_time_sec=stop, seed=5,
                                  host_table="on", heartbeat_interval_sec=1,
                                  device_plane=mode,
                                  device_plane_granule_ms=10, **opt_kw), cfg)
        assert ctrl.run() == 0
    finally:
        set_logger(SimLogger())
    return ctrl


_CACHE: dict = {}


def _chains_cached(mode):
    if mode not in _CACHE:
        _CACHE[mode] = _chains(mode)
    return _CACHE[mode]


def _xplane_events(log_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)
    assert len(path) == 1, path
    out = {}
    for plane in ProfileData.from_file(path[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                if "." in ev.name:
                    stats = dict(ev.stats) if ev.name == "engine.round" \
                        else {}
                    out.setdefault(ev.name, []).append(
                        (float(ev.duration_ns), stats))
    return out


def test_layer_spans_land_in_the_profiler_session(tmp_path):
    import jax.profiler
    with jax.profiler.trace(str(tmp_path / "xplane")):
        ctrl = _chains(stop=6)
    assert ctrl.engine.tracer.events() == []     # the ring stayed off
    evs = _xplane_events(str(tmp_path / "xplane"))
    for name in ("engine.collect", "engine.round", "engine.launch",
                 "plane.launch", "plane.wait", "plane.readback",
                 "plane.fold", "setup.hosts", "setup.plane"):
        assert name in evs, f"no {name} span in the xplane"
        assert max(d for d, _ in evs[name]) > 0, name
    # the virtual clock rides along as a TraceMe argument
    assert any(st.get("sim_ns", 0) > 0 for _, st in evs["engine.round"])
    # per-event and ring-only spans stay out of the profiler sink
    assert "device.collect" not in evs and "round" not in evs


def test_no_session_and_no_trace_records_nothing(monkeypatch):
    from shadow_tpu.obs import trace as trace_mod
    made = []
    real = trace_mod._trace_me
    monkeypatch.setattr(trace_mod, "_trace_me",
                        lambda *a: made.append(a) or real(*a))
    ctrl = _chains(stop=4)
    assert made == []
    assert ctrl.engine.tracer.events() == []
    assert ctrl.engine.device_plane.dispatches > 0


@pytest.mark.parametrize("mode", ["device", "numpy"])
def test_plane_wall_split_adds_up(mode):
    st = _chains_cached(mode).engine.metrics.scrape()
    assert st["plane.dispatches"] > 0
    # plane_*_sec round to 3 decimals, the split to 6
    tol = 0.0005 + 2e-6
    assert abs(st["plane.launch_sec"] + st["plane.fold_sec"]
               - st["plane.plane_host_sec"]) <= tol
    assert abs(st["plane.wait_sec"] + st["plane.readback_sec"]
               - st["plane.plane_device_sec"]) <= tol
    assert st["plane.idle_sec"] > 0
    assert st["plane.fold_sec"] > 0


def test_flow_ticks_moved_hand_count_both_modes():
    dev = _chains_cached("device").engine.device_plane.stats()
    twin = _chains_cached("numpy").engine.device_plane.stats()
    assert dev["completed"] == dev["circuits"] == N_HOSTS - 5
    # 5 download + 5 upload stages, one tick each, per circuit
    assert dev["flow_ticks_moved"] == 10 * dev["circuits"]
    assert twin["flow_ticks_moved"] == dev["flow_ticks_moved"]
    assert twin["ticks_stepped"] == dev["ticks_stepped"]


def test_ticks_stepped_excludes_banked_idle_ticks():
    """Each dispatch runs its kernel from its base step to t_stop; the
    next base is that t_stop plus the idle ticks banked in between
    (logged per dispatch), so the executed ticks are the span from the
    first base to the final step less every later dispatch's banked
    ticks."""
    plane = _chains_cached("device").engine.device_plane
    log = plane._dispatch_log
    banked = sum(int(e[3]) for e in log[1:])
    t_last = int(np.asarray(plane._state[0]))
    assert banked > 0               # the plane emptied between waves
    assert plane.ticks_stepped == t_last - int(log[0][0]) - banked


def test_jit_compiles_counts_a_fresh_program_once():
    import jax
    import jax.numpy as jnp

    from shadow_tpu.obs.jit import compile_clock
    clock = compile_clock()
    assert clock is not None
    x = jnp.arange(7)
    x.block_until_ready()
    before = clock.snapshot()

    @jax.jit
    def fresh(v):
        return v * 3 + 1

    fresh(x).block_until_ready()
    once = clock.snapshot()
    fresh(x).block_until_ready()
    again = clock.snapshot()
    assert once["jit.compiles"] == before["jit.compiles"] + 1
    assert once["jit.compile_sec"] > before["jit.compile_sec"]
    assert again == once
    # every run's registry carries the process's totals
    assert _chains_cached("device").engine.metrics.scrape()[
        "jit.compiles"] >= 1


def _star_ctrl():
    cfg = configuration.parse_xml(
        workloads.star_bulk(3, stoptime=10, bulk_bytes=4096))
    cfg.stop_time_sec = 10
    return Controller(Options(scheduler_policy="global", workers=0,
                              stop_time_sec=10, log_level="warning"), cfg)


def test_boundary_hook_sees_every_boundary_and_stops_the_run():
    set_logger(SimLogger(stream=io.StringIO(), level="warning"))
    try:
        plain = _star_ctrl()
        assert plain.run() == 0
        watched, seen = _star_ctrl(), []
        watched.engine.on_boundary(seen.append)
        assert watched.run() == 0
        stopped, upto = _star_ctrl(), []

        def stop_at_fifth(boundary):
            upto.append(boundary)
            return len(upto) < 5

        stopped.engine.on_boundary(stop_at_fifth)
        assert stopped.run() == 0
    finally:
        set_logger(SimLogger())
    # a hook that returns None changes nothing, and is called at the top
    # of every round and at the boundary that ends the run
    assert state_digest(watched.engine) == state_digest(plain.engine)
    assert len(seen) == watched.engine.rounds_executed + 1
    assert seen == sorted(seen) and seen[-1] > 0
    # False ends the run there, as a stop time at that boundary would
    assert len(upto) == 5 and upto == seen[:5]
    assert stopped.engine.rounds_executed == 4
    assert stopped.engine.end_time == upto[-1]


@pytest.mark.parametrize("wrapper", ["torcells_step_window_flush",
                                     "torcells_step_window_flush_nodonate"])
def test_span_flush_module_names(wrapper):
    import jax.numpy as jnp

    from shadow_tpu.ops import torcells_device as td
    f, h = 3, 2
    args = (np.int64(0), jnp.zeros(f, jnp.int64),
            jnp.zeros((4, f), jnp.int32), jnp.zeros(h, jnp.int64),
            jnp.zeros(f, jnp.int64), jnp.zeros(f, jnp.int64),
            jnp.full(f, -1, jnp.int64), jnp.zeros(h, jnp.int64),
            np.zeros(f, np.int64), np.zeros(f, np.int64),
            np.array([2, 2]), np.int64(0), np.array([0, 1, 1]),
            np.array([1, 1, 0]), np.array([1, 2, -1]), np.array([0, 1, 1]),
            np.array([5, 5]), np.array([9, 9]), np.array([2]),
            np.array([-1, 0, 1]), np.array([[0, 1], [1, 3]]))
    text = getattr(td, wrapper).lower(*args, ring_len=4).as_text()
    module = next(ln for ln in text.splitlines() if ln.startswith("module"))
    assert "_step_span_flush_impl" in module, module


@pytest.mark.parametrize("wrapper", ["torcells_step_compact_flush",
                                     "torcells_step_compact_flush_nodonate"])
def test_compact_span_flush_module_names(wrapper):
    """The compacted span-flush's modules carry the same name, so the
    benchmark's kernel time counts every dispatch whichever program ran."""
    import jax.numpy as jnp

    from shadow_tpu.ops import torcells_device as td
    f, h = 3, 2
    live = np.array([[0, 1, 2, 3], [0] * 4, [0] * 4, [0] * 4])
    args = (np.int64(0), jnp.zeros(f, jnp.int64),
            jnp.zeros((4, f), jnp.int32), jnp.zeros(h, jnp.int64),
            jnp.zeros(f, jnp.int64), jnp.zeros(f, jnp.int64),
            jnp.full(f, -1, jnp.int64), jnp.zeros(h, jnp.int64), live,
            np.array([2, 2]), np.int64(0), np.array([0, 1, 1]),
            np.array([1, 1, 0]), np.array([1, 2, -1]), np.array([0, 1, 1]),
            np.array([5, 5]), np.array([9, 9]), np.array([-1, 0, 1]))
    text = getattr(td, wrapper).lower(*args, ring_len=4).as_text()
    module = next(ln for ln in text.splitlines() if ln.startswith("module"))
    assert "_step_span_flush_impl" in module, module
