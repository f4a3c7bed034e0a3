"""Supervised execution (ISSUE 2): the fault-injection matrix.

Every way a real process can wedge the simulator gets a deterministic
injection and a pinned recovery:

* a native plugin SIGSTOP'd mid-syscall-stream -> the plugin watchdog kills
  it, its simulated process is marked exited, the host and round loop
  continue (and the other hosts' work completes);
* a poisoned / hung in-flight device dispatch -> the dispatch guard replays
  the window history on the numpy twin, permanently demotes the backend,
  and the final state digest matches a clean run bit for bit;
* a shard hard-killed mid-protocol -> the parent's dead-shard detection
  produces a clean diagnostic abort, never a hang;
* a run SIGKILLed between checkpoints -> ``--resume`` replays to the last
  good snapshot, digest-verifies there, and finishes in a state identical
  to an uninterrupted run.
"""

import glob
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from shadow_tpu.core import configuration
from shadow_tpu.core.checkpoint import (find_last_good_snapshot,
                                        load_snapshot, state_digest)
from shadow_tpu.core.controller import Controller
from shadow_tpu.core.options import Options
from shadow_tpu.core.supervision import parse_fault_inject
from shadow_tpu.tools import workloads

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# harness plumbing
# ---------------------------------------------------------------------------

def test_fault_inject_spec_parsing():
    assert parse_fault_inject("") is None
    assert parse_fault_inject("device-dispatch:3") == {
        "kind": "device-dispatch", "dispatch": 3}
    assert parse_fault_inject("device-dispatch-hang:1") == {
        "kind": "device-dispatch-hang", "dispatch": 1}
    assert parse_fault_inject("plugin-stall:victim:6") == {
        "kind": "plugin-stall", "name": "victim", "nreq": 6}
    assert parse_fault_inject("shard-exit:1:3") == {
        "kind": "shard-exit", "shard": 1, "round": 3}
    # the self-healing drills (ISSUE 17)
    assert parse_fault_inject("shard-exit-resurrect:1:3") == {
        "kind": "shard-exit-resurrect", "shard": 1, "round": 3}
    assert parse_fault_inject("device-lost:4") == {
        "kind": "device-lost", "round": 4}
    assert parse_fault_inject("demote-repromote:2") == {
        "kind": "demote-repromote", "dispatch": 2}
    for bad in ("nope", "device-dispatch", "plugin-stall:x",
                "shard-exit:1", "shard-exit-resurrect:1",
                "device-lost", "demote-repromote"):
        with pytest.raises(ValueError):
            parse_fault_inject(bad)


def test_kill_stragglers_reaps_no_zombies():
    """Satellite: straggler teardown is terminate -> grace -> kill with a
    reaping wait — even a SIGSTOP'd child (immune to SIGTERM) is gone and
    REAPED afterwards, no defunct entries survive."""
    import shadow_tpu.process.native as native_mod

    p1 = subprocess.Popen(["sleep", "30"])
    p2 = subprocess.Popen(["sleep", "30"])
    os.kill(p2.pid, signal.SIGSTOP)   # SIGTERM can't act until SIGCONT
    native_mod._live_children.extend([p1, p2])
    try:
        native_mod._kill_stragglers(grace_sec=1.0)
        assert p1.poll() is not None
        assert p2.poll() is not None
        for p in (p1, p2):
            # reaped means the pid no longer exists — a zombie would still
            # accept signal 0
            with pytest.raises(ProcessLookupError):
                os.kill(p.pid, 0)
    finally:
        for p in (p1, p2):
            if p in native_mod._live_children:
                native_mod._live_children.remove(p)


# ---------------------------------------------------------------------------
# seam 1: plugin watchdog (SIGSTOP'd native plugin)
# ---------------------------------------------------------------------------

def test_sigstopped_plugin_killed_host_survives(native_bin):
    """A native plugin frozen (SIGSTOP) mid-syscall-stream: the RPC
    watchdog kills it within --plugin-watchdog-sec, its simulated process
    is marked exited with the logged reason, and the rest of the
    simulation — including a pure-Python echo pair on other hosts —
    completes normally with exit code 0 (a supervised kill is a counted
    recovery, not a plugin error)."""
    xml = textwrap.dedent(f"""\
        <shadow stoptime="40">
          <plugin id="app" path="{native_bin}" />
          <plugin id="echo" path="python:echo" />
          <host id="victim" bandwidthdown="10240" bandwidthup="10240">
            <process plugin="app" starttime="1" arguments="udpserver 8000 5" />
          </host>
          <host id="noisy" bandwidthdown="10240" bandwidthup="10240">
            <process plugin="app" starttime="2"
                     arguments="udpclient victim 8000 5 512" />
          </host>
          <host id="pysrv"><process plugin="echo" starttime="1"
                     arguments="udp server 9000" /></host>
          <host id="pycli"><process plugin="echo" starttime="2"
                     arguments="udp client pysrv 9000 5 300" /></host>
        </shadow>
    """)
    cfg = configuration.parse_xml(xml)
    cfg.stop_time_sec = 40
    ctrl = Controller(Options(scheduler_policy="global", workers=0,
                              stop_time_sec=40, log_level="warning",
                              plugin_watchdog_sec=2.0,
                              fault_inject="plugin-stall:victim:6"), cfg)
    t0 = time.monotonic()
    rc = ctrl.run()
    wall = time.monotonic() - t0
    assert wall < 60, "simulator froze on a SIGSTOP'd plugin"
    eng = ctrl.engine
    victim = eng.host_by_name("victim").processes[0]
    assert victim.exited and victim.exit_code == 124
    assert victim.supervised_kill and "watchdog" in victim.supervised_kill
    assert eng.supervision.plugin_watchdog_kills == 1
    # the python pair on other hosts completed untouched
    pycli = eng.host_by_name("pycli").processes[0]
    assert pycli.exit_code == 0
    # a supervised kill is a recovery, not a failure: the run exits 0
    assert rc == 0 and eng.plugin_errors == 0


# ---------------------------------------------------------------------------
# seam 2: dispatch guard (poisoned / hung device dispatch)
# ---------------------------------------------------------------------------

def _device_run(mode="device", expect_rc=0, **opt_kw):
    cfg = configuration.parse_xml(workloads.tor_network(
        8, n_clients=3, n_servers=2, stoptime=60,
        stream_spec="512:20200", device_data=True))
    cfg.stop_time_sec = 60
    ctrl = Controller(Options(scheduler_policy="global", workers=0, seed=3,
                              stop_time_sec=60, log_level="warning",
                              device_plane=mode, **opt_kw), cfg)
    assert ctrl.run() == expect_rc
    return ctrl


def test_poisoned_dispatch_numpy_fallback_digest_parity():
    """Poison one in-flight dispatch mid-run: the guard replays the window
    history on the numpy twin, demotes the backend permanently, and the
    run finishes in EXACTLY the clean run's state (digest parity — the
    degradation preserves correctness, forfeits only device speed)."""
    clean = _device_run(mode="device")
    assert clean.engine.device_plane.dispatches >= 2
    d_clean = state_digest(clean.engine)

    faulted = _device_run(mode="device",
                          fault_inject="device-dispatch:2")
    plane = faulted.engine.device_plane
    assert plane.demoted and plane.mode == "numpy"
    assert plane.recoveries == 1
    assert faulted.engine.supervision.dispatch_recoveries == 1
    assert faulted.engine.supervision.unrequested_dispatch_recoveries == 0
    assert state_digest(faulted.engine) == d_clean


def test_unrequested_dispatch_recovery_exits_nonzero(monkeypatch):
    """A dispatch failure that no --fault-inject asked for is still
    recovered on the numpy twin (the digest stays available), but the run
    exits non-zero: a run that quietly left the device is not a pass."""
    clean = _device_run(mode="numpy", tpu_devices=1)
    import shadow_tpu.ops.torcells_device as td
    real = td.step_window_flush_for_backend
    launches = []

    class _Broken:
        def __array__(self, dtype=None, copy=None):
            raise RuntimeError("device lost mid-dispatch")

    def failing_second_launch():
        step = real()

        def run(*args, **kw):
            out = step(*args, **kw)
            launches.append(1)
            return (*out[:9], _Broken()) if len(launches) == 2 else out
        return run

    monkeypatch.setattr(td, "step_window_flush_for_backend",
                        failing_second_launch)
    faulted = _device_run(mode="device", expect_rc=1, tpu_devices=1)
    plane = faulted.engine.device_plane
    sup = faulted.engine.supervision
    assert plane.demoted and plane.recoveries == 1
    assert sup.unrequested_dispatch_recoveries == 1
    assert sup.summary()["unrequested_dispatch_recoveries"] == 1
    assert state_digest(faulted.engine) == state_digest(clean.engine)


def test_hung_dispatch_watchdog_recovers_digest_parity():
    """Same recovery driven by the collect TIMEOUT instead of an
    exception: a dispatch that never completes is abandoned after
    --device-watchdog-sec and the numpy replay takes over."""
    clean = _device_run(mode="numpy")
    d_clean = state_digest(clean.engine)

    t0 = time.monotonic()
    faulted = _device_run(mode="device", device_watchdog_sec=1.0,
                          fault_inject="device-dispatch-hang:2")
    wall = time.monotonic() - t0
    plane = faulted.engine.device_plane
    assert plane.demoted and plane.recoveries == 1
    assert state_digest(faulted.engine) == d_clean
    assert wall < 60, "collect watchdog did not bound the hung dispatch"


# ---------------------------------------------------------------------------
# seam 3: shard supervision (hard-killed shard)
# ---------------------------------------------------------------------------

PROCS_XML = textwrap.dedent("""\
    <shadow stoptime="30">
      <plugin id="tgen" path="python:tgen" />
      <plugin id="echo" path="python:echo" />
      <host id="server"><process plugin="tgen" starttime="1" arguments="server 80" /></host>
      <host id="c1"><process plugin="tgen" starttime="2" arguments="client server 80 1024:102400" /></host>
      <host id="u1"><process plugin="echo" starttime="1" arguments="udp server 9000" /></host>
      <host id="u2"><process plugin="echo" starttime="2" arguments="udp client u1 9000 8 300" /></host>
    </shadow>
""")


def _procs_cfg(stop=30):
    cfg = configuration.parse_xml(PROCS_XML)
    cfg.stop_time_sec = stop
    return cfg


def test_dead_shard_clean_abort_not_hang():
    """A shard that hard-exits mid-protocol (os._exit — what a SIGKILL/OOM
    kill looks like: no error report, pipe just goes dead) surfaces as a
    diagnostic RuntimeError in the parent, promptly.  The run is driven
    from a guard thread so a regression to the old behavior (parent parked
    in Connection.recv forever) FAILS the test instead of hanging it."""
    from shadow_tpu.parallel.procs import ProcsController

    ctrl = ProcsController(
        Options(scheduler_policy="global", workers=0, seed=7,
                stop_time_sec=30, processes=2, log_level="warning",
                fault_inject="shard-exit:1:3"), _procs_cfg())
    result = {}

    def drive():
        try:
            ctrl.run()
            result["outcome"] = "completed"
        except RuntimeError as e:
            result["outcome"] = str(e)

    t = threading.Thread(target=drive, daemon=True)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive(), "parent hung on a dead shard"
    # the death surfaces through whichever check wins the race — process
    # liveness or pipe EOF — both carry the shard id and exit code
    outcome = result.get("outcome", "")
    assert "shard 1" in outcome and (
        "died" in outcome or "closed its pipe" in outcome), result
    assert ctrl.supervision.shard_deaths_detected == 1


# ---------------------------------------------------------------------------
# seam 4: crash-recoverable checkpoints (--checkpoint-every / --resume)
# ---------------------------------------------------------------------------

CKPT_XML = textwrap.dedent("""\
    <shadow stoptime="60">
      <plugin id="tgen" path="python:tgen" />
      <plugin id="echo" path="python:echo" />
      <host id="server"><process plugin="tgen" starttime="1" arguments="server 80" /></host>
      <host id="c1"><process plugin="tgen" starttime="2" arguments="client server 80 1024:204800" /></host>
      <host id="u1"><process plugin="echo" starttime="1" arguments="udp server 9000" /></host>
      <host id="u2"><process plugin="echo" starttime="2" arguments="udp client u1 9000 10 700" /></host>
    </shadow>
""")


def _ckpt_run(seed=5, stop=60, **opt_kw):
    cfg = configuration.parse_xml(CKPT_XML)
    cfg.stop_time_sec = stop
    ctrl = Controller(Options(scheduler_policy="global", workers=0,
                              seed=seed, stop_time_sec=stop,
                              log_level="warning", **opt_kw), cfg)
    rc = ctrl.run()
    return rc, ctrl


def test_sigkill_between_checkpoints_resume_digest_identical(tmp_path):
    """The acceptance-criteria crash drill: a real run, SIGKILLed from
    outside between checkpoint writes, resumes from --resume (the last
    good snapshot in the dir) and finishes with a state digest identical
    to a run that was never interrupted."""
    rc, clean = _ckpt_run()
    assert rc == 0
    d_clean = state_digest(clean.engine)

    ckdir = str(tmp_path / "ck")
    cfg_path = tmp_path / "cfg.xml"
    cfg_path.write_text(CKPT_XML)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.Popen(
        [sys.executable, "-c",
         "import sys; from shadow_tpu.cli import main; "
         "sys.exit(main(sys.argv[1:]))",
         str(cfg_path), "--checkpoint-every", "20",
         "--checkpoint-dir", ckdir, "--stop-time", "60", "--seed", "5",
         "--log-level", "warning"],
        cwd=str(tmp_path), env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        # SIGKILL as soon as the first snapshot lands — mid-run, between
        # checkpoint writes (if the run wins the race and finishes, the
        # resume contract below must hold all the same)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if glob.glob(ckdir + "/checkpoint_*.ckpt") \
                    or proc.poll() is not None:
                break
            time.sleep(0.05)
        assert glob.glob(ckdir + "/checkpoint_*.ckpt"), \
            "no checkpoint ever appeared"
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait()

    rc, resumed = _ckpt_run(resume_path=ckdir)
    assert rc == 0
    assert resumed.engine.supervision.resume_verified
    assert state_digest(resumed.engine) == d_clean


LOSSY_TOPO = """<topology><![CDATA[<?xml version="1.0" encoding="UTF-8"?>
<graphml xmlns="http://graphml.graphdrawing.org/xmlns">
<key id="d0" for="edge" attr.name="latency" attr.type="double"/>
<key id="d1" for="edge" attr.name="packetloss" attr.type="double"/>
<key id="d2" for="node" attr.name="bandwidthdown" attr.type="int"/>
<key id="d3" for="node" attr.name="bandwidthup" attr.type="int"/>
<graph edgedefault="undirected">
  <node id="n0"><data key="d2">10240</data><data key="d3">10240</data></node>
  <edge source="n0" target="n0"><data key="d0">25.0</data><data key="d1">0.03</data></edge>
</graph></graphml>]]></topology>"""


def _lossy_ckpt_run(seed, stop=30, **opt_kw):
    # lossy topology so the seed changes which packets drop — a divergent
    # seed then produces a genuinely different state (on a loss-free
    # topology different seeds legitimately converge, test_checkpoint.py)
    cfg = configuration.parse_xml(
        CKPT_XML.replace("<plugin", LOSSY_TOPO + "\n  <plugin", 1))
    cfg.stop_time_sec = stop
    ctrl = Controller(Options(scheduler_policy="global", workers=0,
                              seed=seed, stop_time_sec=stop,
                              log_level="warning", **opt_kw), cfg)
    return ctrl.run(), ctrl


def test_resume_divergent_seed_aborts(tmp_path):
    """A --resume whose replay does NOT reproduce the snapshot state
    (different seed = different run) must abort loudly at the verification
    boundary, never continue silently."""
    ckdir = str(tmp_path / "ck")
    rc, _ = _lossy_ckpt_run(seed=5, checkpoint_every_rounds=10,
                            checkpoint_dir=ckdir)
    assert rc == 0 and glob.glob(ckdir + "/checkpoint_*.ckpt")
    with pytest.raises(RuntimeError, match="resume verification failed"):
        _lossy_ckpt_run(seed=6, resume_path=ckdir)


def test_resume_skips_corrupt_snapshot(tmp_path):
    """'Last GOOD snapshot': a truncated snapshot (torn disk, partial
    copy) is detected by its digest, skipped with a warning, and resume
    proceeds from the newest one that verifies."""
    ckdir = str(tmp_path / "ck")
    rc, ctrl = _ckpt_run(stop=30, checkpoint_every_rounds=10,
                         checkpoint_dir=ckdir)
    assert rc == 0
    snaps = sorted(glob.glob(ckdir + "/checkpoint_*.ckpt"))
    assert len(snaps) >= 2
    newest = max(snaps, key=lambda p: load_snapshot(p)["sim_time_ns"])
    with open(newest, "r+b") as f:
        f.truncate(os.path.getsize(newest) // 2)
    with pytest.raises(Exception):
        load_snapshot(newest, verify=True)
    snap, resolved = find_last_good_snapshot(ckdir)
    assert resolved != newest
    rc, resumed = _ckpt_run(stop=30, resume_path=ckdir)
    assert rc == 0 and resumed.engine.supervision.resume_verified


def test_checkpoint_every_rounds_and_resume_sharded(tmp_path):
    """--checkpoint-every under --processes: the parent writes round-
    stamped snapshots at the same boundaries as a serial run (shared
    CheckpointWriter cadence -> identical names + digests), and a sharded
    --resume replays and digest-verifies over the ASSEMBLED state."""
    from shadow_tpu.parallel.procs import ProcsController

    d_serial = str(tmp_path / "ck_serial")
    rc, serial = _ckpt_run(stop=30, checkpoint_every_rounds=25,
                           checkpoint_dir=d_serial)
    assert rc == 0
    serial_names = sorted(os.path.basename(p) for p in
                          glob.glob(d_serial + "/checkpoint_r*.ckpt"))
    assert serial_names, "rounds-based writer produced no snapshots"

    d_procs = str(tmp_path / "ck_procs")
    cfg = configuration.parse_xml(CKPT_XML)
    cfg.stop_time_sec = 30
    sharded = ProcsController(
        Options(scheduler_policy="global", workers=0, seed=5,
                stop_time_sec=30, processes=2, log_level="warning",
                checkpoint_every_rounds=25, checkpoint_dir=d_procs), cfg)
    assert sharded.run() == 0
    procs_names = sorted(os.path.basename(p) for p in
                         glob.glob(d_procs + "/checkpoint_r*.ckpt"))
    assert procs_names == serial_names
    for name in serial_names:
        s = load_snapshot(os.path.join(d_serial, name), verify=True)
        p = load_snapshot(os.path.join(d_procs, name), verify=True)
        assert s["digest"] == p["digest"], name

    cfg2 = configuration.parse_xml(CKPT_XML)
    cfg2.stop_time_sec = 30
    resumed = ProcsController(
        Options(scheduler_policy="global", workers=0, seed=5,
                stop_time_sec=30, processes=2, log_level="warning",
                resume_path=d_procs), cfg2)
    assert resumed.run() == 0
    assert resumed.resume_verified
    assert resumed.digest == state_digest(serial.engine)
