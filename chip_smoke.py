#!/usr/bin/env python3
"""Bring-up check on the chip: the simulator's main path, end to end.

    python chip_smoke.py              # one chip: the tor10k stand-in
    python chip_smoke.py --multichip  # four chips: genscen tor100k, 4 vs 1

One chip (no arguments).  Builds the native planes from the committed
sources, then runs the tor10k stand-in that bench.py's flagship rows run
(``workloads.tor_network(10000, device_data=True)``: 10 000 relays,
10 000 device-plane clients, 500 servers) through the CLI's own
``prepare`` and ``Controller`` with ``--scheduler-policy=tpu
--tpu-devices 1 --device-plane device``.  The stop time is the shortest
of STOP_TIMES at which the plane dispatched and at least one flow
completed on the device.  The run passes when it exits 0 with no
supervision recovery, the plane is still in device mode, the hop kernel
ran on the device only, and its ``state_digest`` equals that of the same
config under ``--scheduler-policy=global --device-plane=numpy`` (the
host reference, run in this process after it).

Four chips (``--multichip``).  Runs genscen ``tor100k`` through
``tools/mkscenario``'s options with ``--tpu-devices 4`` (the flow table
sharded over the mesh) and with ``--tpu-devices 1``, both at the 10 ms
plane granule bench.py's tor100k row uses, and compares the two state
digests.  No other phase.

Exits non-zero, with no result line, when JAX finds no TPU or when any
phase fails.  The last line of standard output is the one JSON result.
One process holds the chip: the native build runs in a child that never
imports JAX, and everything after it runs here.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
STOP_TIMES = (8, 16, 32, 64)        # seconds; BENCH_TOR10K_STOPTIME is 8
TOR10K_RELAYS = 10_000
# genscen tor100k: 10 s at bench.py's 10 ms plane granule (its
# scen_tor100k row).  At the 1 ms default granule the 4-chip run alone
# took 600.730 s on v5e (my chip run, PR 21): ~10 000 kernel ticks.
TOR100K_STOP = 10
TOR100K_GRANULE_MS = 10


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def build_native() -> None:
    """``make -C native -B`` in a child that never imports JAX."""
    if not os.path.isfile(os.path.join(HERE, "native", "Makefile")):
        raise SmokeFailure("no native/Makefile next to chip_smoke.py: run "
                           "it from the root of a shadow-tpu checkout")
    t0 = time.perf_counter()
    r = subprocess.run(["make", "-C", os.path.join(HERE, "native"), "-B"],
                       capture_output=True, text=True)
    if r.returncode != 0:
        raise SmokeFailure(f"native build failed (rc {r.returncode}): "
                           f"{(r.stdout + r.stderr)[-2000:]}")
    say(f"native planes built in {time.perf_counter() - t0:.1f} s")


def require_tpu():
    """The device list, or SmokeFailure when the platform is not a TPU
    (no CPU fallback)."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SmokeFailure(f"JAX found platform {platform!r} "
                           f"({devices[0].device_kind}), not 'tpu'")
    return devices


class CompileClock:
    """Seconds XLA spent compiling, from JAX's own compile events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.sec = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.sec += duration


def run_config(xml: str, args, tmpdir: str):
    """Run one config through the CLI's own parse/validate step and the
    Controller that run_simulation builds; returns (rc, controller)."""
    from shadow_tpu.cli import prepare
    from shadow_tpu.core.controller import Controller

    path = os.path.join(tmpdir, "config.xml")
    with open(path, "w") as f:
        f.write(xml)
    prepared = prepare([path, "--log-level", "warning", *args])
    check(not isinstance(prepared, int),
          f"the CLI refused {args}: rc {prepared}")
    ctrl = Controller(*prepared)
    return ctrl.run(), ctrl


def device_run_facts(rc: int, ctrl) -> dict:
    eng = ctrl.engine
    scrape = eng.metrics.scrape()
    plane = eng.device_plane
    st = plane.stats() if plane is not None else {}
    forwards = st.get("forwards", 0)
    total = forwards + eng.events_executed
    return {
        "rc": rc,
        "recoveries": eng.supervision.recoveries,
        "plane_mode": st.get("mode"),
        "plane_demoted": st.get("demoted"),
        "dispatches": st.get("dispatches", 0),
        "flows_completed": st.get("completed", 0),
        "flows": st.get("circuits", 0),
        "hop_device_calls": scrape.get("policy.device_calls", 0),
        "hop_host_calls": scrape.get("policy.host_calls", 0),
        "device_traffic_fraction": round(forwards / total, 6)
        if total else 0.0,
        "costmodel": getattr(plane, "_costmodel_status", None),
    }


def check_device_run(facts: dict) -> None:
    check(facts["rc"] == 0, f"run exited {facts['rc']}")
    check(facts["recoveries"] == 0,
          f"supervision.recoveries = {facts['recoveries']}")
    check(facts["plane_mode"] == "device" and not facts["plane_demoted"],
          f"plane mode {facts['plane_mode']}, "
          f"demoted {facts['plane_demoted']}")
    check(facts["dispatches"] > 0, "the plane never dispatched")
    check(facts["hop_device_calls"] > 0,
          "the hop kernel made no device call")
    check(facts["hop_host_calls"] == 0,
          f"the hop kernel took the host path {facts['hop_host_calls']} "
          "times")


def tor10k_phase(devices, n_relays: int = TOR10K_RELAYS,
                 stop_times=STOP_TIMES) -> None:
    """The one-chip phase (see the module docstring)."""
    from shadow_tpu.core.checkpoint import state_digest
    from shadow_tpu.tools import workloads

    clock = CompileClock()
    tpu_args = ["--scheduler-policy=tpu", "--tpu-devices", "1",
                "--device-plane", "device"]
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
        for stop in stop_times:
            xml = workloads.tor_network(n_relays, stoptime=stop,
                                        device_data=True)
            t0 = time.perf_counter()
            rc, ctrl = run_config(xml, tpu_args, tmp)
            wall = time.perf_counter() - t0
            facts = device_run_facts(rc, ctrl)
            say(f"stop {stop} s: {json.dumps(facts)}")
            check_device_run(facts)
            if facts["flows_completed"] > 0:
                break
        else:
            raise SmokeFailure(f"no flow completed on the device by stop "
                               f"time {stop_times[-1]} s")
        digest = state_digest(ctrl.engine)
        say(f"stop time chosen: {stop} s")
        say(f"compile seconds (XLA, all runs so far): {clock.sec:.3f}")
        say(f"wall seconds (this run, its compiles included): {wall:.3f}")
        say(f"sim-sec/wall-sec (smoke figure, not a benchmark): "
            f"{stop / wall:.4f}")
        say(f"device_traffic_fraction: {facts['device_traffic_fraction']}")
        stats = devices[0].memory_stats() or {}
        say(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
        say(f"COSTMODEL.json: {facts['costmodel']} (loaded = used; "
            "refused = fingerprint of another machine)")
        del ctrl
        rc_ref, ref = run_config(
            xml, ["--scheduler-policy=global", "--device-plane=numpy"], tmp)
        check(rc_ref == 0, f"reference run exited {rc_ref}")
        ref_digest = state_digest(ref.engine)
    say(f"state_digest tpu+device   {digest}")
    say(f"state_digest global+numpy {ref_digest}")
    check(digest == ref_digest, "state digests differ")


def tor100k_phase(n_chips: int = 4, build=None,
                  stop: int = TOR100K_STOP) -> None:
    """The four-chip phase: the same generated scenario sharded over
    ``n_chips`` and on one chip, digests compared."""
    from shadow_tpu.core.checkpoint import state_digest
    from shadow_tpu.core.controller import Controller
    from shadow_tpu.scale import genscen
    from shadow_tpu.tools.mkscenario import scenario_options

    digests = {}
    for n in (n_chips, 1):
        cfg = build() if build is not None else genscen.build("tor100k")
        opts = scenario_options(cfg, ["--stop-time", str(stop),
                                      "--tpu-devices", str(n),
                                      "--device-plane-granule-ms",
                                      str(TOR100K_GRANULE_MS),
                                      "--log-level", "warning"])
        t0 = time.perf_counter()
        ctrl = Controller(opts, cfg)
        rc = ctrl.run()
        wall = time.perf_counter() - t0
        eng = ctrl.engine
        plane = eng.device_plane
        st = plane.stats() if plane is not None else {}
        scrape = eng.metrics.scrape()
        sharded = plane is not None and plane._shard is not None
        say(f"{n} chip(s): rc {rc}, wall {wall:.3f} s, sharded {sharded}, "
            f"dispatches {st.get('dispatches')}, completed "
            f"{st.get('completed')}/{st.get('circuits')}, recoveries "
            f"{eng.supervision.recoveries}, mesh.host_bounces "
            f"{scrape.get('mesh.host_bounces')}")
        check(rc == 0, f"{n}-chip run exited {rc}")
        check(eng.supervision.recoveries == 0,
              f"{n}-chip run recovered a dispatch")
        check(st.get("mode") == "device" and st.get("dispatches", 0) > 0,
              f"{n}-chip run: plane {st.get('mode')}, "
              f"{st.get('dispatches')} dispatches")
        check(sharded == (n > 1), f"{n}-chip run sharded={sharded}")
        digests[n] = state_digest(eng)
        say(f"state_digest at {n} chip(s): {digests[n]}")
        del ctrl, eng, plane
    check(digests[n_chips] == digests[1],
          f"{n_chips}-chip and 1-chip digests differ")


def main(argv) -> int:
    multichip = "--multichip" in argv
    try:
        build_native()
        sys.path.insert(0, HERE)
        devices = require_tpu()
        from shadow_tpu.utils.compile_cache import setup_compile_cache
        say(f"compile cache: {setup_compile_cache()}")
        say(f"devices: {len(devices)} x {devices[0].device_kind}")
        if multichip:
            check(len(devices) >= 4,
                  f"--multichip needs 4 chips, found {len(devices)}")
            tor100k_phase()
        else:
            tor10k_phase(devices)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
