"""Device-resident traffic plane (parallel/device_plane.py) gates.

Three contracts:
1. The span-flush program, one boundary per dispatch, advances the model
   IDENTICALLY to the run-to-completion loop (DeviceTorCells) and to its
   numpy twin (torcells_step_span_numpy), bit for bit, across arbitrary
   window splits and idle-gap folds.
2. A full engine simulation produces identical state digests whether the
   bulk flows run on the device plane or its numpy twin, and whether the
   scheduler policy is serial or tpu.
3. Conservation: every injected cell is delivered exactly once when the
   simulation runs long enough.
"""

import numpy as np
import pytest

from shadow_tpu.core import configuration
from shadow_tpu.core.checkpoint import state_digest
from shadow_tpu.core.controller import Controller
from shadow_tpu.core.options import Options
from shadow_tpu.tools import workloads


def _run(policy="global", mode="device", n_relays=8, n_clients=5, stop=60):
    cfg = configuration.parse_xml(workloads.tor_network(
        n_relays, n_clients=n_clients, n_servers=2, stoptime=stop,
        stream_spec="512:20200", device_data=True))
    cfg.stop_time_sec = stop
    ctrl = Controller(Options(scheduler_policy=policy, workers=0, seed=3,
                              stop_time_sec=stop, log_level="warning",
                              device_plane=mode), cfg)
    rc = ctrl.run()
    assert rc == 0
    return ctrl


# ---------------------------------------------------------------------------
# kernel-level parity
# ---------------------------------------------------------------------------

def _toy_instance():
    from shadow_tpu.ops.torcells_device import DeviceTorCells
    return DeviceTorCells(n_relays=6, n_circuits=20, seed=5,
                          relay_bw_kibps=512, max_latency_ms=20)


def _span_tables(inst):
    """The flow tables after the per-dispatch operands, numpy twin first,
    then the device program's (with the gather tables)."""
    fl = inst.flows
    twin = (fl["flow_node"], fl["flow_lat"], fl["flow_succ"],
            fl["seg_start"], inst.refill, inst.capacity,
            np.flatnonzero(fl["flow_succ"] < 0))
    return twin, tuple(np.asarray(a) for a in (*twin, fl["flow_pred"],
                                               fl["node_seg"]))


def _zero_state(inst):
    from shadow_tpu.ops.torcells_device import RING_DTYPE
    f = inst.n_flows
    return [np.int64(0), np.zeros(f, np.int64),
            np.zeros((inst.ring_len, f), RING_DTYPE),
            inst.capacity.copy().astype(np.int64),
            np.zeros(f, np.int64), np.zeros(f, np.int64),
            np.full(f, -1, np.int64), np.zeros(len(inst.refill), np.int64)]


def test_windowed_kernel_matches_run_to_completion():
    """The span-flush program over one single-boundary window == the
    run-to-completion loop on the numpy twin (many dispatches, halting
    at completions): the same cells delivered, forwards and completion
    ticks, bit for bit."""
    from shadow_tpu.ops.torcells_device import (
        parse_flush, torcells_step_window_flush_nodonate)
    inst = _toy_instance()
    fl = inst.flows
    queued0 = np.where(fl["flow_stage"] == 0, 40, 0).astype(np.int64)
    target0 = np.where(fl["flow_succ"] < 0, 40, 0).astype(np.int64)
    ref_del, ref_ticks, ref_fwd = inst.run_numpy(40, max_ticks=5000)
    assert ref_ticks < 5000

    _twin, tables = _span_tables(inst)
    out = torcells_step_window_flush_nodonate(
        *_zero_state(inst), queued0, target0,
        np.array([ref_ticks], np.int64), np.int64(0), *tables,
        ring_len=inst.ring_len)
    np.testing.assert_array_equal(np.asarray(out[4]), ref_del)
    assert int(out[8]) == ref_fwd
    fwd, _, t_stop, chains, steps, _, _ = parse_flush(
        np.asarray(out[9]), len(tables[6]), len(inst.refill))
    assert (fwd, t_stop) == (ref_fwd, ref_ticks)
    assert len(chains) == len(tables[6]) and steps.max() == ref_ticks - 1


def test_windowed_kernel_split_and_idle_invariance():
    """Many small windows + an idle-gap fold == one big window (numpy twin
    vs device, both ways), through the span-flush program with one
    boundary per dispatch."""
    from shadow_tpu.ops.torcells_device import (
        torcells_step_span_numpy, torcells_step_window_flush_nodonate)
    inst = _toy_instance()
    fl = inst.flows
    f = inst.n_flows
    queued0 = np.where(fl["flow_stage"] == 0, 25, 0).astype(np.int64)
    twin, tables = _span_tables(inst)
    flow_args = twin[:6]

    def np_span(state, inject, end, idle=0):
        return torcells_step_span_numpy(
            *state, inject, inject, np.array([end], np.int64),
            np.int64(idle), *flow_args, inst.ring_len)

    def dev_span(state, inject, end):
        return torcells_step_window_flush_nodonate(
            *state, inject, inject, np.array([end], np.int64), np.int64(0),
            *tables, ring_len=inst.ring_len)

    zeros = np.zeros(f, np.int64)
    # one 600-tick window
    big = np_span(_zero_state(inst), queued0, 600)
    # split: 7 + 93 + 500 with injection only in the first
    out = np_span(_zero_state(inst), queued0, 7)
    out = np_span(out[:8], zeros, 100)
    out = np_span(out[:8], zeros, 600)
    for i in (1, 3, 4, 5, 6, 7):
        np.testing.assert_array_equal(out[i], big[i])

    # device twin of the split run
    dout = dev_span(_zero_state(inst), queued0, 7)
    dout = dev_span(dout[:8], zeros, 100)
    dout = dev_span(dout[:8], zeros, 600)
    for i in (1, 3, 4, 5, 6, 7):
        np.testing.assert_array_equal(np.asarray(dout[i]), big[i])

    # idle fold: running 100 empty ticks == banking them as idle_ticks
    idle_a = np_span([x.copy() for x in out[:8]], zeros, 700)
    idle_b = np_span([x.copy() for x in out[:8]], zeros, 600, idle=100)
    np.testing.assert_array_equal(idle_a[3], idle_b[3])   # tokens
    np.testing.assert_array_equal(idle_a[4], idle_b[4])   # delivered


# ---------------------------------------------------------------------------
# engine-level parity + conservation
# ---------------------------------------------------------------------------

def test_engine_device_vs_numpy_plane_digest_parity():
    # NOTE: under the 8-virtual-device test mesh, mode="device" runs the
    # SHARDED layout by default (tpu_devices=0 -> all local devices), so
    # this is simultaneously the sharded-engine vs single-host-twin gate.
    a = _run(mode="device")
    b = _run(mode="numpy")
    assert a.engine.device_plane._shard is not None, \
        "expected the sharded layout under the 8-device test mesh"
    assert state_digest(a.engine) == state_digest(b.engine)
    assert a.engine.device_plane.stats()["forwards"] == \
        b.engine.device_plane.stats()["forwards"]


def test_engine_sharded_vs_single_device_plane_digest_parity():
    """Force single-device layout (tpu_devices=1) and compare against the
    default sharded run: identical digests — multichip is semantics-free."""
    from shadow_tpu.core import configuration
    from shadow_tpu.core.options import Options
    from shadow_tpu.core.controller import Controller

    def run(n_dev):
        cfg = configuration.parse_xml(workloads.tor_network(
            8, n_clients=5, n_servers=2, stoptime=60,
            stream_spec="512:20200", device_data=True))
        cfg.stop_time_sec = 60
        ctrl = Controller(Options(scheduler_policy="global", workers=0,
                                  seed=3, stop_time_sec=60,
                                  log_level="warning", tpu_devices=n_dev),
                          cfg)
        assert ctrl.run() == 0
        return ctrl

    single = run(1)
    sharded = run(8)
    assert single.engine.device_plane._shard is None
    assert sharded.engine.device_plane._shard is not None
    assert state_digest(single.engine) == state_digest(sharded.engine)


def test_engine_policy_parity_with_device_plane():
    a = _run(policy="global")
    b = _run(policy="tpu")
    assert state_digest(a.engine) == state_digest(b.engine)


def test_cell_conservation_and_completion():
    ctrl = _run(stop=120)
    st = ctrl.engine.device_plane.stats()
    assert st["completed"] == st["circuits"], \
        f"only {st['completed']}/{st['circuits']} flows completed"
    # each injected cell is forwarded exactly once per stage (5 stages)
    assert st["forwards"] == st["injected_cells"] * 5
    plane = ctrl.engine.device_plane
    delivered, _done, _sent = plane._read_summaries()
    assert int(delivered[plane.last_flow].sum()) == st["injected_cells"]


def test_varying_dispatch_sizes_preserve_arrivals():
    """The kernel's carried step counter must track the plane's synced step
    exactly across dispatches of VARYING size (round windows are
    event-driven, so n differs every dispatch).  A wrong re-base
    desynchronizes the arrival ring's absolute slots — in-flight cells get
    skipped and arrive a ring revolution late (r4 review repro)."""
    ctrl = _run(stop=120)
    plane = ctrl.engine.device_plane
    # kernel step counter + idle steps banked since the last dispatch ==
    # the plane's synced step (with the off-by-n re-base this diverges by
    # the final dispatch's size)
    assert (int(np.asarray(plane._state[0])) + plane._idle_ticks_banked
            == plane._ticks_synced)


# (test_sharded_windowed_kernel_bit_parity migrated to
# tests/test_meshplane.py: the PR-7 replicated-ring sharded kernel was
# retired by the mesh plane, whose parity suite pins the same contract
# against the partition/exchange kernels.)


def test_auto_consensus_device_clients():
    """auto: consensus clients work on the device plane (VERDICT r4 next
    #6a): the plane predicts each client's path at startup by replaying
    its derived draw over the config-determined consensus; the runtime
    fetch + route cross-check agree, circuits complete, and digests match
    the numpy twin."""
    from shadow_tpu.core.checkpoint import state_digest
    xml = workloads.tor_network(8, n_clients=4, n_servers=1, stoptime=120,
                                stream_spec="512:20200", dirauth=True,
                                device_data=True)
    runs = {}
    for mode in ("numpy", "device"):
        cfg = configuration.parse_xml(xml)
        ctrl = Controller(Options(scheduler_policy="global", workers=0,
                                  seed=3, stop_time_sec=120,
                                  log_level="warning", device_plane=mode),
                          cfg)
        rc = ctrl.run()
        assert rc == 0
        st = ctrl.engine.device_plane.stats()
        assert st["completed"] == st["circuits"] == 4
        runs[mode] = state_digest(ctrl.engine)
    assert runs["numpy"] == runs["device"]


def test_star_bulk_device_plane():
    """Workload #2 on the device plane (VERDICT r4 next #6b): 2-hop
    star-bulk chains, >=90% of traffic on-device, digest parity across
    execution modes."""
    from shadow_tpu.core.checkpoint import state_digest
    xml = workloads.star_bulk(20, stoptime=120, bulk_bytes=262144,
                              device_data=True)
    runs = {}
    for mode in ("numpy", "device"):
        cfg = configuration.parse_xml(xml)
        ctrl = Controller(Options(scheduler_policy="global", workers=0,
                                  seed=7, stop_time_sec=120,
                                  log_level="warning", device_plane=mode),
                          cfg)
        rc = ctrl.run()
        assert rc == 0
        eng = ctrl.engine
        st = eng.device_plane.stats()
        assert st["completed"] == st["circuits"] == 20
        total = st["forwards"] + eng.events_executed
        assert st["forwards"] / total >= 0.9, \
            f"device fraction {st['forwards'] / total:.3f} < 0.9"
        runs[mode] = state_digest(eng)
    assert runs["numpy"] == runs["device"]


def test_check_route_rejects_divergence():
    from shadow_tpu.parallel.device_plane import (DeviceTrafficPlane,
                                                  parse_device_client)

    class FakeEngine:
        shard_count = 1
        options = type("O", (), {})()

    spec = parse_device_client(
        "c0", ["client", "9050", "g0,m0,e0", "dest0", "80", "1",
               "512:51200", "device"])
    plane = object.__new__(DeviceTrafficPlane)
    plane._by_client = {"c0": spec}
    plane.check_route("c0", ["g0", "m0", "e0"])   # matching: no raise
    with pytest.raises(RuntimeError, match="diverged"):
        plane.check_route("c0", ["g0", "m0", "eX"])


def test_plane_refuses_sharded_engines():
    from shadow_tpu.parallel.device_plane import DeviceTrafficPlane

    class FakeEngine:
        shard_count = 2

    with pytest.raises(RuntimeError):
        DeviceTrafficPlane(FakeEngine(), [], mode="device")


def test_parse_device_client_defaults_with_nstreams_omitted():
    """ADVICE r4: 'client 9050 <path> dest 80 device' (nstreams omitted)
    must fall back to the defaults, not crash on int('device')."""
    from shadow_tpu.parallel.device_plane import parse_device_client
    spec = parse_device_client(
        "c0", ["client", "9050", "g0,m0,e0", "dest0", "80", "device"])
    assert spec is not None
    assert spec.cells_down > 0 and spec.cells_up > 0
    assert spec.route_down == ["dest0", "e0", "m0", "g0", "c0"]


def test_duplicate_device_clients_on_one_host_rejected():
    """ADVICE r4 (medium): two device-mode clients on one host would
    silently share a flow keyed by host name — must raise instead."""
    from shadow_tpu.parallel.device_plane import (DeviceTrafficPlane,
                                                  parse_device_client)

    class FakeEngine:
        shard_count = 1
        options = Options = type("O", (), {})()

    spec_a = parse_device_client(
        "c0", ["client", "9050", "g0,m0,e0", "dest0", "80", "1",
               "512:51200", "device"])
    spec_b = parse_device_client(
        "c0", ["client", "9051", "g1,m1,e1", "dest0", "80", "1",
               "512:51200", "device"])
    with pytest.raises(ValueError, match="multiple device-mode"):
        DeviceTrafficPlane(FakeEngine(), [spec_a, spec_b], mode="numpy")


def test_activate_zero_cells_rejected():
    """ADVICE r4: activate(cells=0) could never complete (target>0 gate) —
    the joining client would hang to end_time; reject loudly instead."""
    from shadow_tpu.core import configuration
    from shadow_tpu.core.controller import Controller
    from shadow_tpu.core.options import Options
    from shadow_tpu.parallel.device_plane import build_plane_from_engine
    from shadow_tpu.tools import workloads

    xml = workloads.tor_network(8, n_clients=2, n_servers=1, stoptime=10,
                                stream_spec="512:5120", device_data=True)
    cfg = configuration.parse_xml(xml)
    ctrl = Controller(Options(scheduler_policy="global", workers=0,
                              stop_time_sec=10), cfg)
    ctrl.setup()
    plane = build_plane_from_engine(ctrl.engine, mode="numpy")
    assert plane is not None
    client = plane.specs[0].client_name
    with pytest.raises(ValueError, match="at least 1 cell"):
        plane.activate(client, cells=0)


def test_segment_greedy_int32_prefix_is_exact_past_int32_totals():
    """The kernel's prefix sum runs in int32 (v5e refuses the int64 scan
    inside the tick loop): with per-flow backlogs whose running total
    passes 2**31 but whose per-node backlog fits, the wrapped int32
    differences equal the int64 greedy exactly."""
    import jax.numpy as jnp
    from shadow_tpu.ops.torcells_device import segment_greedy

    rng = np.random.default_rng(21)
    sizes = [7, 1, 30, 12, 50, 45, 50, 40]
    seg_start = np.concatenate(
        [np.full(n, s) for n, s in zip(sizes, np.r_[0, np.cumsum(sizes)])])
    queued = rng.integers(0, 2 ** 31 // 60, size=len(seg_start))
    queued[[3, 40, 90]] = 2 ** 31 // 55        # totals wrap several times
    cap_cells = rng.integers(0, 2 ** 31 // 2, size=len(seg_start))
    csum = np.cumsum(queued)
    before = csum - queued - np.where(seg_start > 0, csum[seg_start - 1], 0)
    want = np.clip(cap_cells - before, 0, queued)
    assert csum[-1] > 2 ** 31
    got = segment_greedy(jnp.asarray(queued), jnp.asarray(cap_cells),
                         jnp.asarray(seg_start))
    assert np.array_equal(np.asarray(got), want)


def test_plane_refuses_injections_past_the_int32_bound(monkeypatch):
    """More cells in flight than the int32 prefix sums can hold is refused
    loudly at dispatch, identically on the device and the numpy twin."""
    import shadow_tpu.ops.torcells_device as td
    monkeypatch.setattr(td, "MAX_CELLS_IN_FLIGHT", 10)
    for mode in ("device", "numpy"):
        with pytest.raises(ValueError, match="cells in flight"):
            _run(mode=mode)


def test_flow_pred_inverts_flow_succ():
    """The plane's gather tables: flow_pred is the inverse of flow_succ,
    and node_seg bounds exactly each node's flows."""
    plane = _run(mode="numpy", stop=5).engine.device_plane
    succ, pred = plane.flow_succ, plane.flow_pred
    has = np.flatnonzero(succ >= 0)
    assert np.array_equal(pred[succ[has]], has)
    assert np.count_nonzero(pred >= 0) == len(has)
    lo, hi = plane.node_seg
    assert np.array_equal(hi - lo, np.bincount(plane.flow_node,
                                               minlength=plane.n_nodes))
    for node in np.flatnonzero(hi > lo):
        assert (plane.flow_node[lo[node]:hi[node]] == node).all()


def test_collects_share_one_thread(monkeypatch):
    """Every watchdog-bounded flush read runs on one collect thread, across
    dispatches and planes (a thread per collect grew host RSS by a malloc
    arena's worth each dispatch on v5e)."""
    import threading

    from shadow_tpu.parallel.device_plane import DeviceTrafficPlane
    seen = []
    real = DeviceTrafficPlane._read_flush

    def read(self, handle):
        seen.append(threading.current_thread())
        return real(self, handle)

    monkeypatch.setattr(DeviceTrafficPlane, "_read_flush", read)
    planes = [_run(mode="device", stop=20).engine.device_plane
              for _ in range(2)]
    assert all(p._watchdog_sec > 0 for p in planes)
    assert len(seen) == sum(p.dispatches for p in planes) >= 4
    assert len(set(seen)) == 1
    assert seen[0].name == "device-dispatch-collect"
    assert seen[0].is_alive()


# ---------------------------------------------------------------------------
# the compacted span-flush: a dispatch steps its live chains alone
# ---------------------------------------------------------------------------

def _waves(mode, waves, step, stop, **opt_kw):
    """genscen's tor deployment at 250 hosts on one device: 223
    process-less circuits, 2,230 flows (one compacted width, 1,024), in
    ``waves`` waves ``step`` seconds apart from 2 s.  The 1 s heartbeat
    runs rounds while the plane is empty, which bank idle ticks."""
    from shadow_tpu.scale import genscen
    cfg = genscen.tor(250, stoptime=stop, stagger_waves=waves,
                      stagger_step_sec=step)
    ctrl = Controller(Options(scheduler_policy="global", workers=0, seed=5,
                              stop_time_sec=stop, log_level="warning",
                              host_table="on", heartbeat_interval_sec=1,
                              device_plane=mode, tpu_devices=1,
                              device_plane_granule_ms=10, **opt_kw), cfg)
    assert ctrl.run() == 0
    return ctrl


def _same_plane_state(a, b):
    for i, (x, y) in enumerate(zip(a.engine.device_plane._state,
                                   b.engine.device_plane._state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"state {i}")
    assert state_digest(a.engine) == state_digest(b.engine)


def test_compacted_plane_matches_the_twin():
    """Waves 0.5 s apart: each dispatch holds one wave's ~250 live flows,
    so every dispatch runs the 1,024-wide program, idle ticks banked
    between waves fold into each, and the plane ends in the twin's state
    with every circuit done and no chain live."""
    dev, twin = _waves("device", 10, 0.5, 10), _waves("numpy", 10, 0.5, 10)
    _same_plane_state(dev, twin)
    plane = dev.engine.device_plane
    st = plane.stats()
    assert plane._compact_widths == (1024,)
    assert st["compact_dispatches"] == st["dispatches"] == 10
    assert st["idle_rounds_skipped"] > 0
    assert st["completed"] == st["circuits"] == 223
    assert not plane._live and plane._live_flows == 0
    assert st["flow_ticks_stepped"] == 1024 * st["ticks_stepped"]
    assert st["flow_ticks_moved"] == twin.engine.device_plane.stats()[
        "flow_ticks_moved"]
    assert twin.engine.device_plane.stats()["compact_dispatches"] == 0
    # each dispatch read back a flush packed at (1,024, 1,024); the twin's
    # flush is host memory, read from no device
    from shadow_tpu.ops.torcells_device import flush_len
    assert st["flush_bytes_read"] == 10 * 8 * flush_len(1024, 1024)
    assert twin.engine.device_plane.stats()["flush_bytes_read"] == 0


def test_recovered_compacted_dispatch_folds_the_full_length_flush(
        monkeypatch):
    """The third dispatch, a compacted one, fails at its collect: the numpy
    twin replays the log and hands back a full-length flush, which the
    fold parses at (C, H), not at the failed launch's (1,024, 1,024).  The
    run still ends in the twin's state with every circuit done."""
    from shadow_tpu.ops.torcells_device import flush_len
    from shadow_tpu.parallel.device_plane import DeviceTrafficPlane
    folds = []
    real = DeviceTrafficPlane._fold

    def fold(self, engine, flush, sizes, t0, t1):
        folds.append((len(flush), sizes, self.mode))
        return real(self, engine, flush, sizes, t0, t1)

    monkeypatch.setattr(DeviceTrafficPlane, "_fold", fold)
    dev = _waves("device", 10, 0.5, 10, fault_inject="device-dispatch:3")
    monkeypatch.setattr(DeviceTrafficPlane, "_fold", real)
    twin = _waves("numpy", 10, 0.5, 10)
    _same_plane_state(dev, twin)
    plane = dev.engine.device_plane
    st = plane.stats()
    assert plane.recoveries == 1 and plane.demoted
    assert st["completed"] == st["circuits"] == 223
    c, h = plane.n_chains, plane.n_nodes
    assert folds[:2] == [(flush_len(1024, 1024), (1024, 1024),
                          "device")] * 2
    assert folds[2] == (flush_len(c, h), (c, h), "numpy")
    assert st["flush_bytes_read"] == 2 * 8 * flush_len(1024, 1024)


def test_plane_falls_to_full_width_past_the_top_width(monkeypatch):
    """Waves 70 ms apart overlap, so a dispatch holds one or two waves'
    flows (190 or ~380): with a top width of 256 the plane alternates
    between the compacted and the full-width program, and still ends in
    the twin's state."""
    import shadow_tpu.ops.torcells_device as td
    monkeypatch.setattr(td, "compact_widths", lambda n_flows: (256,))
    dev, twin = _waves("device", 12, 0.07, 4), _waves("numpy", 12, 0.07, 4)
    _same_plane_state(dev, twin)
    st = dev.engine.device_plane.stats()
    assert 0 < st["compact_dispatches"] < st["dispatches"]
    f = dev.engine.device_plane.n_flows
    assert (256 * st["ticks_stepped"] < st["flow_ticks_stepped"]
            < f * st["ticks_stepped"])
