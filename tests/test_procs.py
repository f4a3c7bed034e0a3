"""Process-parallel scale-out (parallel/procs.py, --processes N).

The strongest gate in the repo's determinism arsenal applied to the sharded
engine: a run partitioned over 2 / 3 OS processes must finish in the SAME
state digest as the single-process serial run — interior event order,
per-socket protocol state, tracker counters, bucket fills, all of it.
"""

import textwrap

import pytest

from shadow_tpu.core import configuration
from shadow_tpu.core.checkpoint import state_digest
from shadow_tpu.core.controller import Controller
from shadow_tpu.core.options import Options
from shadow_tpu.parallel.procs import ProcsController

LOSSY_TOPO = """<topology><![CDATA[<?xml version="1.0" encoding="UTF-8"?>
<graphml xmlns="http://graphml.graphdrawing.org/xmlns">
<key id="d0" for="edge" attr.name="latency" attr.type="double"/>
<key id="d1" for="edge" attr.name="packetloss" attr.type="double"/>
<key id="d2" for="node" attr.name="bandwidthdown" attr.type="int"/>
<key id="d3" for="node" attr.name="bandwidthup" attr.type="int"/>
<graph edgedefault="undirected">
  <node id="n0"><data key="d2">10240</data><data key="d3">10240</data></node>
  <edge source="n0" target="n0"><data key="d0">25.0</data><data key="d1">0.02</data></edge>
</graph></graphml>]]></topology>"""

# Lossy TCP bulk + UDP mix spread over 7 hosts so every 2- and 3-way
# partition has cross-shard flows in both directions.
XML = textwrap.dedent("""\
    <shadow stoptime="60">
      {topo}
      <plugin id="tgen" path="python:tgen" />
      <plugin id="echo" path="python:echo" />
      <host id="server"><process plugin="tgen" starttime="1" arguments="server 80" /></host>
      <host id="c1"><process plugin="tgen" starttime="2" arguments="client server 80 1024:204800" /></host>
      <host id="c2"><process plugin="tgen" starttime="3" arguments="client server 80 2048:102400" /></host>
      <host id="c3"><process plugin="tgen" starttime="4" arguments="client server 80 4096:51200" /></host>
      <host id="u1"><process plugin="echo" starttime="1" arguments="udp server 9000" /></host>
      <host id="u2"><process plugin="echo" starttime="2" arguments="udp client u1 9000 12 700" /></host>
      <host id="u3"><process plugin="echo" starttime="3" arguments="udp client u1 9000 8 300" /></host>
    </shadow>
""").format(topo=LOSSY_TOPO)


def _cfg(stop=60):
    cfg = configuration.parse_xml(XML)
    cfg.stop_time_sec = stop
    return cfg


def _serial(stop=60, policy="global"):
    ctrl = Controller(Options(scheduler_policy=policy, workers=0, seed=7,
                              stop_time_sec=stop), _cfg(stop))
    assert ctrl.run() == 0
    return ctrl


def _sharded(n, stop=60, policy="global", **opt_kw):
    ctrl = ProcsController(Options(scheduler_policy=policy, workers=0,
                                   seed=7, stop_time_sec=stop, processes=n,
                                   **opt_kw), _cfg(stop))
    assert ctrl.run() == 0
    return ctrl


def test_two_shards_match_serial():
    serial = _serial()
    sharded = _sharded(2)
    assert sharded.digest == state_digest(serial.engine)
    assert sharded.events_executed == serial.engine.events_executed
    assert sharded.rounds_executed == serial.engine.rounds_executed


def test_three_shards_match_serial():
    serial = _serial()
    sharded = _sharded(3)
    assert sharded.digest == state_digest(serial.engine)
    assert sharded.events_executed == serial.engine.events_executed


def test_sharded_checkpoint_matches_serial(tmp_path):
    """Parent-assembled mid-run snapshots carry the same digest as the
    serial CheckpointWriter's at the same virtual-time boundary."""
    from shadow_tpu.core.checkpoint import load_snapshot

    d_serial = tmp_path / "ck_serial"
    ctrl = Controller(Options(scheduler_policy="global", workers=0, seed=7,
                              stop_time_sec=60, checkpoint_interval_sec=2,
                              checkpoint_dir=str(d_serial)), _cfg())
    assert ctrl.run() == 0
    d_procs = tmp_path / "ck_procs"
    sharded = _sharded(2, checkpoint_interval_sec=2,
                       checkpoint_dir=str(d_procs))
    serial_written = sorted(p.name for p in d_serial.iterdir())
    procs_written = sorted(p.name for p in d_procs.iterdir())
    assert serial_written == procs_written and serial_written
    for name in serial_written:
        s = load_snapshot(str(d_serial / name))
        p = load_snapshot(str(d_procs / name))
        assert s["digest"] == p["digest"], name


def test_tpu_policy_shards_match_serial():
    """Each shard runs the batched device-step policy; cross-shard hops
    leave through the tpu flush's outbox branch.  Digest must still equal
    the serial global run."""
    serial = _serial()
    sharded = _sharded(2, policy="tpu")
    assert sharded.digest == state_digest(serial.engine)
    assert sharded.events_executed == serial.engine.events_executed


def test_tpu_policy_shards_refused_off_the_host_cpu(monkeypatch, tmp_path):
    """--processes N under the tpu policy would have every shard open the
    chip, which serves one process at a time: refused at startup with a
    message — by the coordinator and by the CLI (rc 2) — unless the shards
    are explicitly put on the host CPU (JAX_PLATFORMS=cpu, as here)."""
    from shadow_tpu.cli import main

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(ValueError, match="one process holds at a time"):
        ProcsController(Options(scheduler_policy="tpu", processes=2),
                        _cfg())
    cfg_path = tmp_path / "cfg.xml"
    cfg_path.write_text(XML)
    assert main([str(cfg_path), "--processes", "2",
                 "--scheduler-policy", "tpu"]) == 2


def test_procs_requires_two():
    with pytest.raises(ValueError):
        ProcsController(Options(processes=1), _cfg())


def test_shard_failure_surfaces_not_hangs():
    """A shard that dies during setup (unknown plugin) must surface as a
    RuntimeError in the parent promptly — not deadlock the barrier
    protocol or leave orphan children."""
    bad = XML.replace('path="python:tgen"', 'path="python:nosuchapp"')
    cfg = configuration.parse_xml(bad)
    cfg.stop_time_sec = 30
    ctrl = ProcsController(Options(scheduler_policy="global", workers=0,
                                   seed=7, stop_time_sec=30, processes=2),
                           cfg)
    with pytest.raises(RuntimeError, match="shard failed"):
        ctrl.run()


def test_cli_dispatch(tmp_path):
    """The user-facing path: `shadow-tpu config.xml --processes 2` routes
    through run_simulation to the sharded coordinator and exits 0."""
    from shadow_tpu.cli import main

    cfg_path = tmp_path / "cfg.xml"
    cfg_path.write_text(XML)
    rc = main([str(cfg_path), "--processes", "2", "--stop-time", "30",
               "--log-level", "warning"])
    assert rc == 0
