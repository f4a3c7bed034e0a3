"""Superwindow scheduling gates (ISSUE 7): one device launch covers K
consecutive lookahead rounds whenever no host-side event falls inside
them, digest-identical to per-round dispatch.

1. Digest parity pinned at every cut: K=1 vs K=8 (the acceptance gate),
   device vs numpy twin, pipelined vs --device-plane-sync oracle, and
   threaded vs serial — all at K=8, all bit-identical.
2. Edge cases: an injection landing exactly on a superwindow boundary
   (kernel-level AND a staggered-wave integration run), K clamped when a
   host event falls mid-window (negotiate unit gates), and checkpoint/
   --resume round-stamp alignment when rounds advance K at a time.
3. The halt-at-completion rule: a K-round launch stops at the end of the
   first sub-window in which any chain completed, so completion wakes
   clamp to the launching round's barrier exactly as K=1 would.
4. Satellites: _run_threaded folds the native C plane's counters through
   the same helper _run_serial uses (regression), Tracker.heartbeat skips
   the format/values work when both the log line and the registry are
   off, and NativePlane.bulk_sync's one-call snapshot matches per-host C
   reads row for row.
"""

import glob
import textwrap
from contextlib import contextmanager

import numpy as np
import pytest

from shadow_tpu.core import configuration
from shadow_tpu.core.checkpoint import load_snapshot, state_digest
from shadow_tpu.core.controller import Controller
from shadow_tpu.core.options import Options
from shadow_tpu.ops.torcells_device import (CELL_WIRE_BYTES,
                                            torcells_step_span_numpy)
from shadow_tpu.tools import workloads

# few circuits + long transfers => the bulk phase is a host-quiet stretch
# deep enough for multi-round merges (the tor10k-class regime)
STAR_KW = dict(n_clients=8, stoptime=120, bulk_bytes=256 * 1024 * 1024,
               device_data=True)


# deterministic repeat runs shared via a module cache (the
# test_meshplane pattern, holding the tier-1 wall): the DEFAULT star
# run at a given (K, policy, workers, mode, sync, stop) is identical
# every time — several parity tests use the same K=8 baseline, which
# used to re-execute per test.  Runs with custom xml or extra options
# (checkpoint dirs etc.) are never cached.
_RUN_CACHE: dict = {}


def _run(superwindow_rounds, policy="global", workers=0, mode="device",
         sync=False, stop=120, xml=None, **opt_kw):
    key = (superwindow_rounds, policy, workers, mode, sync, stop)
    cacheable = xml is None and not opt_kw
    if cacheable and key in _RUN_CACHE:
        return _RUN_CACHE[key]
    cfg = configuration.parse_xml(xml or workloads.star_bulk(**STAR_KW))
    cfg.stop_time_sec = stop
    ctrl = Controller(Options(scheduler_policy=policy, workers=workers,
                              seed=3, stop_time_sec=stop,
                              log_level="warning", device_plane=mode,
                              device_plane_sync=sync,
                              superwindow_rounds=superwindow_rounds,
                              **opt_kw), cfg)
    assert ctrl.run() == 0
    if cacheable:
        _RUN_CACHE[key] = ctrl
    return ctrl


# -- digest parity at every cut -------------------------------------------

def test_digest_parity_k1_vs_k8():
    """The acceptance gate: K=8 merges multiple rounds per launch
    (rounds_per_launch well past 1, dispatch count cut) and ends in the
    bit-identical state K=1 reaches one round at a time."""
    k1 = _run(1)
    k8 = _run(8)
    s1, s8 = k1.engine.device_plane.stats(), k8.engine.device_plane.stats()
    assert s8["superwindows"] > 0, "superwindows never engaged"
    assert s8["rounds_per_launch"] >= 2.0, s8
    assert s8["dispatches"] < s1["dispatches"]
    assert s1["rounds_per_launch"] == 1.0
    assert s1["completed"] == s8["completed"] == 8
    # the round counter counts VIRTUAL rounds: merged launches advance it
    # by the rounds they covered, so both runs agree
    assert k1.engine.rounds_executed == k8.engine.rounds_executed
    assert state_digest(k1.engine) == state_digest(k8.engine)


def test_digest_parity_with_host_chatter():
    """tor-shaped control chatter (circuit TCP, timers) lands host events
    in most windows: negotiation must clamp around every one of them and
    still produce the K=1 digest."""
    xml = workloads.tor_network(8, n_clients=5, n_servers=2, stoptime=60,
                                stream_spec="512:2020000", device_data=True)
    k1 = _run(1, xml=xml, stop=60)
    k8 = _run(8, xml=xml, stop=60)
    assert state_digest(k1.engine) == state_digest(k8.engine)


def test_device_vs_numpy_twin_at_k8():
    dev = _run(8, mode="device")
    twin = _run(8, mode="numpy")
    assert dev.engine.device_plane.stats()["superwindows"] > 0
    assert state_digest(dev.engine) == state_digest(twin.engine)


def test_pipelined_vs_sync_oracle_at_k8():
    """--device-plane-sync (block on the dispatch at launch) generalizes
    from K=1: the serial oracle and the pipelined default agree at K=8."""
    piped = _run(8, sync=False)
    serial = _run(8, sync=True)
    assert piped.engine.device_plane.stats()["superwindows"] > 0
    assert state_digest(piped.engine) == state_digest(serial.engine)


def test_threaded_vs_serial_at_k8():
    serial = _run(8, policy="global", workers=0)
    threaded = _run(8, policy="steal", workers=2)
    assert threaded.engine.device_plane.stats()["superwindows"] > 0
    assert state_digest(serial.engine) == state_digest(threaded.engine)


# -- negotiation clamps (K drops to 1 around host events) ------------------

def _negotiation_plane():
    """A set-up (not run) star engine whose plane is forced busy, so
    negotiate_superwindow's replay can be probed with synthetic host/cap
    times."""
    cfg = configuration.parse_xml(workloads.star_bulk(**STAR_KW))
    cfg.stop_time_sec = 120
    ctrl = Controller(Options(scheduler_policy="global", workers=0, seed=3,
                              stop_time_sec=120, log_level="warning",
                              superwindow_rounds=8), cfg)
    ctrl.setup()
    eng = ctrl.engine
    from shadow_tpu.parallel.device_plane import build_plane_from_engine
    eng.device_plane = build_plane_from_engine(eng, mode="device")
    plane = eng.device_plane
    plane._init_state()
    plane._cells_dispatched = 1000          # busy: undelivered cells
    plane._cells_delivered_seen = 0
    return eng, plane


def test_negotiation_full_depth_when_quiet():
    from shadow_tpu.parallel.device_plane import TICK_NS

    eng, plane = _negotiation_plane()
    grid = TICK_NS * plane.granule
    q = plane.min_dispatch_steps
    la = eng.lookahead_ns
    nxt = q * grid
    far = 1 << 60
    end = eng.end_time
    merged = plane.negotiate_superwindow(nxt, la, far, end, None, 8)
    assert merged is not None
    plan = plane._pending_plan
    assert len(plan.bounds) == 8
    assert plan.targets == sorted(plan.targets)
    assert merged == plan.bounds[-1][1]
    # every merged round ends before the host event, every target is a
    # dispatch-cadence point the K=1 recurrence would have picked
    assert all(we <= far for _, we in plan.bounds)
    assert all(t * grid <= merged for t in plan.targets)


def test_negotiation_k1_when_host_event_in_first_window():
    """A plugin timer (or any host event) inside the next lookahead round:
    no merge — the round runs K=1."""
    from shadow_tpu.parallel.device_plane import TICK_NS

    eng, plane = _negotiation_plane()
    grid = TICK_NS * plane.granule
    nxt = plane.min_dispatch_steps * grid
    la = eng.lookahead_ns
    assert plane.negotiate_superwindow(nxt, la, nxt + la // 2, eng.end_time,
                                       None, 8) is None
    assert plane._pending_plan is None


def test_negotiation_clamps_at_mid_span_host_event():
    """A host event inside round i clamps the merge to the rounds before
    it (K shrinks, never skips the event's round)."""
    from shadow_tpu.parallel.device_plane import TICK_NS

    eng, plane = _negotiation_plane()
    grid = TICK_NS * plane.granule
    q = plane.min_dispatch_steps
    la = eng.lookahead_ns
    nxt = q * grid
    full = plane.negotiate_superwindow(nxt, la, 1 << 60, eng.end_time,
                                       None, 8)
    plan_full = plane._pending_plan
    plane._pending_plan = None
    # place the host event inside the 4th merged round's window
    ws3, we3 = plan_full.bounds[3]
    merged = plane.negotiate_superwindow(nxt, la, ws3 + la // 2,
                                         eng.end_time, None, 8)
    assert merged is not None and merged < full
    assert len(plane._pending_plan.bounds) == 3
    assert plane._pending_plan.bounds[-1][1] <= ws3 + la // 2


def test_negotiation_respects_checkpoint_cap():
    """cap_time (a checkpoint/resume boundary) stops the merge BEFORE the
    round containing it, so the snapshot digest lands on an exact visited
    round boundary."""
    from shadow_tpu.parallel.device_plane import TICK_NS

    eng, plane = _negotiation_plane()
    grid = TICK_NS * plane.granule
    q = plane.min_dispatch_steps
    la = eng.lookahead_ns
    nxt = q * grid
    full = plane.negotiate_superwindow(nxt, la, 1 << 60, eng.end_time,
                                       None, 8)
    plan_full = plane._pending_plan
    plane._pending_plan = None
    cap = plan_full.bounds[2][1]            # boundary after round 2
    merged = plane.negotiate_superwindow(nxt, la, 1 << 60, eng.end_time,
                                         cap, 8)
    assert merged is not None and merged <= cap < full
    for ws, we in plane._pending_plan.bounds:
        assert we <= cap


# -- kernel-level span semantics ------------------------------------------

def _chain_fixture():
    """One 2-hop chain (relay node 0 -> exit node 1), numpy arrays in the
    step-window layout."""
    cell = CELL_WIRE_BYTES
    return dict(
        queued=np.array([60, 0], dtype=np.int64),
        ring=np.zeros((6, 2), dtype=np.int64),
        tokens=np.array([4 * cell, 3 * cell], dtype=np.int64),
        delivered=np.zeros(2, dtype=np.int64),
        target=np.array([0, 40], dtype=np.int64),
        done_tick=np.full(2, -1, dtype=np.int64),
        node_sent=np.zeros(2, dtype=np.int64),
        flow_node=np.array([0, 1], dtype=np.int64),
        flow_lat=np.array([2, 0], dtype=np.int64),
        flow_succ=np.array([1, -1], dtype=np.int64),
        seg_start=np.array([0, 1], dtype=np.int64),
        refill=np.array([4 * cell, 3 * cell], dtype=np.int64),
        capacity=np.array([8 * cell, 6 * cell], dtype=np.int64),
    )


def _run_span(fx, t0, targets, inject=(0, 0), idle=0):
    f = fx
    return torcells_step_span_numpy(
        np.int64(t0), f["queued"].copy(), f["ring"].copy(),
        f["tokens"].copy(), f["delivered"].copy(), f["target"].copy(),
        f["done_tick"].copy(), f["node_sent"].copy(),
        np.array(inject, dtype=np.int64), np.zeros(2, dtype=np.int64),
        np.array(targets, dtype=np.int64), np.int64(idle),
        f["flow_node"], f["flow_lat"], f["flow_succ"], f["seg_start"],
        f["refill"], f["capacity"], 6)


def _run_sequential(fx, t0, targets, inject=(0, 0)):
    """The K=1 oracle: one single-boundary span per boundary, halting
    after the first window in which a chain newly completed (exactly the
    per-round engine behavior a completion wake imposes)."""
    f = fx
    state = (np.int64(t0), f["queued"].copy(), f["ring"].copy(),
             f["tokens"].copy(), f["delivered"].copy(), f["target"].copy(),
             f["done_tick"].copy(), f["node_sent"].copy())
    inj = np.array(inject, dtype=np.int64)
    forwards = 0
    for tgt in targets:
        done_before = state[6].copy()
        out = torcells_step_span_numpy(
            *state, inj, np.zeros(2, dtype=np.int64),
            np.array([tgt], dtype=np.int64), np.int64(0),
            f["flow_node"], f["flow_lat"], f["flow_succ"], f["seg_start"],
            f["refill"], f["capacity"], 6)
        inj = np.zeros(2, dtype=np.int64)   # injections fold at base only
        state = out[:8]
        forwards += int(out[8])
        if ((done_before < 0) & (state[6] >= 0)).any():
            break                           # K=1: the wake halts the run
    return (*state, np.int64(forwards))


def _assert_states_equal(a, b):
    assert int(a[0]) == int(b[0])           # reached boundary
    for i in range(1, 8):
        np.testing.assert_array_equal(np.asarray(a[i]), np.asarray(b[i]))
    assert int(a[8]) == int(b[8])           # forwards


def test_span_matches_sequential_windows_no_completion():
    fx = _chain_fixture()
    fx["target"] = np.array([0, 10 ** 9], dtype=np.int64)  # never completes
    targets = [4, 9, 13, 20]
    _assert_states_equal(_run_span(fx, 0, targets),
                         _run_sequential(fx, 0, targets))


def test_span_halts_at_completion_boundary():
    """The chain completes mid-plan: the span stops at that sub-window's
    boundary with state equal to the sequential windows run to the same
    point — never past it."""
    fx = _chain_fixture()
    targets = [4, 9, 13, 20, 30]
    span = _run_span(fx, 0, targets)
    seq = _run_sequential(fx, 0, targets)
    _assert_states_equal(span, seq)
    assert int(span[0]) in targets[:-1], \
        f"completion did not halt the span (reached {int(span[0])})"
    assert (np.asarray(span[6]) >= 0).any()


def test_injection_exactly_on_span_boundary():
    """An injection staged to a superwindow boundary folds at the NEXT
    dispatch's base step: span [0..a] then span [a..] with the injection
    equals the sequential windows with the same base-step fold."""
    fx = _chain_fixture()
    fx["target"] = np.array([0, 10 ** 9], dtype=np.int64)
    first = _run_span(fx, 0, [4, 9])
    fx2 = dict(fx, queued=np.asarray(first[1]), ring=np.asarray(first[2]),
               tokens=np.asarray(first[3]), delivered=np.asarray(first[4]),
               target=np.asarray(first[5]), done_tick=np.asarray(first[6]),
               node_sent=np.asarray(first[7]))
    span = _run_span(fx2, 9, [13, 20], inject=(25, 0))
    seq = _run_sequential(fx2, 9, [13, 20], inject=(25, 0))
    _assert_states_equal(span, seq)
    # the injected cells actually entered the first sub-window's service
    assert int(np.asarray(span[7]).sum()) > int(np.asarray(first[7]).sum())


def test_staggered_wave_injection_parity():
    """Integration form of the boundary-injection case: a second client
    wave activates (socket write -> plane injection) while the first
    wave's transfers sit in merged superwindows."""
    lines = ['<shadow stoptime="120">',
             '  <plugin id="tgen" path="python:tgen" />',
             '  <host id="server" bandwidthdown="1048576" '
             'bandwidthup="1048576">',
             '    <process plugin="tgen" starttime="1" '
             'arguments="server 80" />',
             '  </host>']
    for i in range(6):
        start = 2 if i < 3 else 40          # second wave mid-quiet-stretch
        lines.append(
            f'  <host id="client{i}" bandwidthdown="102400" '
            f'bandwidthup="51200">\n'
            f'    <process plugin="tgen" starttime="{start}" '
            f'arguments="client server 80 256:67108864 device" />\n'
            '  </host>')
    lines.append('</shadow>')
    xml = "\n".join(lines) + "\n"
    k1 = _run(1, xml=xml)
    k8 = _run(8, xml=xml)
    assert k8.engine.device_plane.stats()["superwindows"] > 0
    assert k8.engine.device_plane.stats()["completed"] == 6
    assert state_digest(k1.engine) == state_digest(k8.engine)


# -- checkpoint / resume alignment ----------------------------------------

def test_checkpoint_round_stamps_align_k1_vs_k8(tmp_path):
    """--checkpoint-every N with rounds advancing K at a time: the merge
    budget stops short of every cadence point, so K=8 writes the same
    round-stamped snapshot files with the same digests as K=1."""
    digests = {}
    for k in (1, 8):
        ckdir = str(tmp_path / f"ck{k}")
        _run(k, stop=72, checkpoint_every_rounds=40, checkpoint_dir=ckdir)
        snaps = sorted(glob.glob(ckdir + "/checkpoint_r*.ckpt"))
        assert snaps, f"K={k} wrote no snapshots"
        digests[k] = [(p.rsplit("/", 1)[1], load_snapshot(p)["digest"],
                       load_snapshot(p)["rounds"]) for p in snaps]
    assert digests[1] == digests[8]


def test_resume_from_superwindow_run(tmp_path):
    """A K=8 run resumed from one of its own mid-run snapshots replays to
    the digest an uninterrupted K=8 run reaches."""
    ckdir = str(tmp_path / "ck")
    full = _run(8, stop=72, checkpoint_every_rounds=40,
                checkpoint_dir=ckdir)
    want = state_digest(full.engine)
    snaps = sorted(glob.glob(ckdir + "/checkpoint_r*.ckpt"))
    assert len(snaps) >= 1
    resumed = _run(8, stop=72, resume_path=snaps[-1])
    assert state_digest(resumed.engine) == want


# -- satellite: threaded native-counter fold ------------------------------

class _FakeNativePlane:
    """Stand-in C plane (the real one is serial-only): fixed counters plus
    the window/teardown surface the engine touches."""

    def __init__(self):
        self.windows = []

    def counters(self):
        return (7, 5, 2, 123)               # sched, execd, drops, last

    def set_window(self, end):
        self.windows.append(end)

    @contextmanager
    def bulk_sync(self):
        yield

    def sync_tracker(self, hid, tracker):
        pass


ECHO_XML = textwrap.dedent("""\
    <shadow stoptime="30">
      <plugin id="echo" path="python:echo" />
      <host id="u1"><process plugin="echo" starttime="1" arguments="udp server 9000" /></host>
      <host id="u2"><process plugin="echo" starttime="2" arguments="udp client u1 9000 5 700" /></host>
    </shadow>
""")


@pytest.mark.parametrize("policy,workers", [("global", 0), ("steal", 2)])
def test_native_fold_in_both_runners(policy, workers):
    """_run_threaded used to skip the native-counter fold entirely
    (engine.py: only _run_serial folded) — both runners now route through
    _fold_native_events: events_executed includes the C plane's executed
    count and the ObjectCounter ledger carries its event lifecycle."""
    cfg = configuration.parse_xml(ECHO_XML)
    cfg.stop_time_sec = 30
    ctrl = Controller(Options(scheduler_policy=policy, workers=workers,
                              seed=3, stop_time_sec=30,
                              log_level="warning", dataplane="python"), cfg)
    ctrl.setup()
    eng = ctrl.engine
    eng.native_plane = _FakeNativePlane()
    assert eng.run() == 0
    scrape = eng.metrics.scrape()
    assert scrape["native.events_executed"] == 5
    # the fold ran: engine totals include the C plane's executed events...
    assert eng.events_executed == scrape["engine.events"]
    assert eng.events_executed >= 5
    # ...and the ledger absorbed its lifecycle (5 of the 7 scheduled
    # executed => 2 still live in C, plus the drop count)
    assert eng.counters._new.get("packet_drop", 0) >= 2


# -- satellite: heartbeat format gated behind the log level ---------------

def test_heartbeat_work_gated_when_silent(monkeypatch):
    """With the heartbeat log level filtered out AND the metrics registry
    disabled, a host heartbeat never computes heartbeat_values nor
    formats the line — 10k silent hosts pay only the counter pulls."""
    from shadow_tpu.host.tracker import Tracker

    calls = {"n": 0}
    orig = Tracker.heartbeat_values

    def counting(self):
        calls["n"] += 1
        return orig(self)

    monkeypatch.setattr(Tracker, "heartbeat_values", counting)
    cfg = configuration.parse_xml(ECHO_XML)
    cfg.stop_time_sec = 30
    ctrl = Controller(Options(scheduler_policy="global", workers=0, seed=3,
                              stop_time_sec=30, log_level="warning"), cfg)
    assert ctrl.run() == 0
    assert calls["n"] == 0, \
        "filtered heartbeats still computed their payload"


def test_heartbeat_values_flow_when_metrics_on(monkeypatch, tmp_path):
    """Same run with --metrics: the registry still records every host's
    closing heartbeat even though the log line stays filtered."""
    from shadow_tpu.host.tracker import Tracker

    calls = {"n": 0}
    orig = Tracker.heartbeat_values

    def counting(self):
        calls["n"] += 1
        return orig(self)

    monkeypatch.setattr(Tracker, "heartbeat_values", counting)
    cfg = configuration.parse_xml(ECHO_XML)
    cfg.stop_time_sec = 30
    mpath = str(tmp_path / "m.jsonl")
    ctrl = Controller(Options(scheduler_policy="global", workers=0, seed=3,
                              stop_time_sec=30, log_level="warning",
                              metrics_path=mpath), cfg)
    assert ctrl.run() == 0
    assert calls["n"] >= 2                   # closing sweep, one per host
    from shadow_tpu.obs.metrics import read_metrics_file
    summary = [r for r in read_metrics_file(mpath) if r.get("summary")][-1]
    assert any(k.startswith("tracker.") for k in summary["metrics"])


# -- satellite: bulk tracker snapshot parity ------------------------------

def test_native_bulk_sync_matches_per_host_reads():
    """NativePlane.tracker_all (one C call) row-for-row equals the
    per-host c.tracker() reads it replaces on the heartbeat/teardown
    sweeps."""
    from shadow_tpu.parallel import native_plane as npl

    if not npl.native_available():
        pytest.skip("native extension unavailable")
    cfg = configuration.parse_xml(ECHO_XML)
    cfg.stop_time_sec = 30
    ctrl = Controller(Options(scheduler_policy="global", workers=0, seed=3,
                              stop_time_sec=30, log_level="warning",
                              dataplane="native"), cfg)
    ctrl.setup()
    eng = ctrl.engine
    assert eng.native_plane is not None, "native plane did not engage"
    assert eng.run() == 0
    plane = eng.native_plane
    rows = np.frombuffer(plane.c.tracker_all(),
                         dtype=np.int64).reshape(-1, 34)
    assert len(rows) == len(eng.hosts)
    for row in rows:
        hid = int(row[0])
        assert tuple(int(x) for x in row[1:]) == tuple(plane.c.tracker(hid))
