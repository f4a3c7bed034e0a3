#!/usr/bin/env python3
"""shadow-tpu's chip benchmark: one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  Everything else is found by name:

* ``benchmark/configs/<config>.json``: the deployment's sizes, the
  device plane's stated granule and cell size, the program options it
  runs with, the generator that renders it
  (``benchmark/generators/<generator>.py``, the benchmark's own copy;
  its ``build`` returns the scenario, with ``"offered"``, a count and
  its unit, and ``"last_arrival_s"``), and the comparison that decides
  ``correct`` (``benchmark/comparisons/<comparison>.py``: ``snapshot(
  engine, scenario, config)`` after the window, ``compare(snap,
  scenario, boundary_ns, config)`` returning ``(checks, attempted,
  failed)``, each check ``{"value", "limit"}``);
* ``benchmark/traffic/<traffic>.json``: the traffic mix's parameters,
  read by that generator, and ``warm_sim_s``;
* ``benchmark/metrics/<metric>.py``: one reader per metric, ``read(run)``
  returning a number, or None where it finds nothing to read.

One run: build the native planes (a child that never imports JAX),
refuse anything but a TPU with the cell's chips, render the scenario
from ``--seed``, build it through the program's normal path
(``cli.prepare`` for XML, ``tools/mkscenario.scenario_options`` for a
generated ``Configuration``; then ``Controller``) with
``--scheduler-policy=tpu --tpu-devices <chips> --device-plane device``,
warm every kernel shape the scenario can use, run to ``warm_sim_s``,
then measure the window (``lib/window.py``: ``--seconds`` of wall, or
less where the traffic's last arrival comes first).  ``--trace 1``
records a ``jax.profiler`` trace of the window (``lib/trace.py``).
After the window: the configuration's comparison with its plain
reference, then the result, the last line of standard output.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TIMED_FLAGS = ["--scheduler-policy=tpu", "--device-plane", "device"]
HOP_WARM_BATCH = 1 << 16          # the hop kernel's largest batch bucket


class RunFailed(Exception):
    """The run has no result: it prints none and exits non-zero."""


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise RunFailed(f"{os.path.relpath(path, ROOT)} does not exist")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise RunFailed(f"{os.path.relpath(path, ROOT)} does not exist")
    with open(path) as f:
        return json.load(f)


class Cell:
    """A cell of BENCHMARK.json with its files, found by name."""

    def __init__(self, name: str):
        bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise RunFailed(f"no cell {name!r} in BENCHMARK.json")
        self.name = name
        self.spec = cells[name]
        self.chips = int(self.spec["chips"])
        self.config = load_json(os.path.join(
            HERE, "configs", self.spec["config"] + ".json"))
        self.traffic = load_json(os.path.join(
            HERE, "traffic", self.spec["traffic"] + ".json"))
        self.generator = load_module(
            os.path.join(HERE, "generators",
                         self.config["generator"] + ".py"),
            "bench_gen_" + self.config["generator"])
        if "comparison" not in self.config:
            raise RunFailed(f"configuration {self.spec['config']!r} names "
                            "no \"comparison\": nothing decides correct")
        self.comparison = load_module(
            os.path.join(HERE, "comparisons",
                         self.config["comparison"] + ".py"),
            "bench_cmp_" + self.config["comparison"])
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]
        self.readers = {}
        for m in self.end_to_end + self.per_layer:
            self.readers[m["name"]] = load_module(
                os.path.join(HERE, "metrics", m["name"] + ".py"),
                "bench_metric_" + m["name"].replace(".", "_"))

    def scenario(self, seed: int) -> dict:
        return self.generator.build(self.config["sizes"], self.traffic, seed)


def build_native() -> None:
    """``make -C native`` in a child that never imports JAX: rebuilds
    only what is older than its source."""
    t0 = time.perf_counter()
    r = subprocess.run(["make", "-C", os.path.join(ROOT, "native")],
                       capture_output=True, text=True, timeout=600)
    if r.returncode != 0:
        raise RunFailed(f"native build failed (rc {r.returncode}): "
                        f"{(r.stdout + r.stderr)[-2000:]}")
    say(f"native planes checked/built in {time.perf_counter() - t0:.3f} s")


def find_devices(chips: int):
    """The chips this cell asks for, or RunFailed: no CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RunFailed(f"JAX found {devices[0].platform!r} "
                        f"({devices[0].device_kind}), not a TPU")
    if len(devices) < chips:
        raise RunFailed(f"the cell asks for {chips} chips; JAX found "
                        f"{len(devices)}")
    return devices


class CompileClock:
    """XLA compiles, from JAX's own events (as chip_smoke.py counts)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.sec = 0.0
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **_kw) -> None:
        # recorded around compile_or_get_cached: a cache load counts too
        if event == self.EVENT:
            self.sec += duration
            self.count += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def summary(self) -> str:
        return (f"{self.count} XLA programs, {self.cache_hits} of them "
                f"from the persistent cache, {self.sec:.3f} s")


def build_controller(cell: Cell, scenario: dict, flags, tmpdir: str):
    """The program's normal path: cli.prepare for XML, mkscenario's
    options for a generated Configuration; then a Controller."""
    from shadow_tpu.core.controller import Controller
    argv = [*flags, *cell.config.get("options", []), "--log-level", "warning"]
    if "plane" in cell.config:
        argv += ["--device-plane-granule-ms",
                 str(cell.config["plane"]["granule_ms"])]
    if scenario["kind"] == "xml":
        from shadow_tpu.cli import prepare
        path = os.path.join(tmpdir, "scenario.xml")
        with open(path, "w") as f:
            f.write(scenario["xml"])
        prepared = prepare([path, *argv])
        if isinstance(prepared, int):
            raise RunFailed(f"the CLI refused {argv}: rc {prepared}")
        return Controller(*prepared)
    from shadow_tpu.tools.mkscenario import scenario_options
    return Controller(scenario_options(scenario["config"], argv),
                      scenario["config"])


def scenario_digest(scenario: dict) -> str:
    if scenario["kind"] == "xml":
        import hashlib
        return hashlib.sha256(scenario["xml"].encode()).hexdigest()
    from shadow_tpu.scale.genscen import config_digest
    return config_digest(scenario["config"])


class Run:
    """What the metric readers read: the window's counters, times and
    trace.  ``delta(key)`` is a registry counter's change over the
    window, None where the registry has no such counter."""

    def __init__(self):
        self.before: dict = {}
        self.after: dict = {}
        self.sim_s = 0.0
        self.wall_s = 0.0
        self.setup_s = 0.0
        self.rss_peak_mb = 0.0
        self.longest_round_ms = 0.0
        self.gc_s = 0.0
        self.trace: dict = {}

    def delta(self, key: str):
        if key not in self.before or key not in self.after:
            return None
        return float(self.after[key]) - float(self.before[key])


def measure(cell: Cell, args, scenario: dict, clock: CompileClock,
            tmpdir: str):
    """Set-up, warm-up and the window; returns the run, the state snapshot,
    the window facts and the closing boundary (sim ns)."""
    from benchmark.lib.window import Window
    flags = [*TIMED_FLAGS, "--tpu-devices", str(cell.chips)]
    ctrl = build_controller(cell, scenario, flags, tmpdir)
    engine = ctrl.engine
    run = Run()
    trace_dir = os.path.join(tmpdir, "trace")
    state = {}

    def warm() -> None:
        # every kernel shape the window can meet, before the window: the
        # plane's span-flush, and the hop kernel's batch buckets where a
        # host runs a process that sends packets through it
        say(f"scenario and plane built at {time.perf_counter() - T_START:.3f}"
            " s")
        pol = engine.scheduler.policy
        if hasattr(pol, "warmup") and scenario.get("processes", True):
            pol.warmup(engine, max_batch=HOP_WARM_BATCH)
        if engine.device_plane is not None:
            engine.device_plane.warmup()
        say(f"kernels warm at {time.perf_counter() - T_START:.3f} s "
            f"({clock.summary()})")

    def opened(_boundary: int) -> None:
        if args.trace:
            import jax.profiler as prof
            prof.start_trace(trace_dir)
            state["ann"] = prof.TraceAnnotation("bench.window")
            state["ann"].__enter__()
        run.before = engine.metrics.scrape()
        state["compiles0"] = clock.count

    def closed(_boundary: int) -> None:
        run.after = engine.metrics.scrape()
        run.rss_peak_mb = resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss / 1024.0
        state["compiles1"] = clock.count
        if args.trace:
            import jax.profiler as prof
            state["ann"].__exit__(None, None, None)
            prof.stop_trace()

    win = Window(engine, int(cell.traffic["warm_sim_s"] * 1e9),
                 args.seconds, round(scenario["last_arrival_s"] * 1e9),
                 on_open=opened, on_close=closed, first_boundary=warm)
    try:
        rc = ctrl.run()
    finally:
        win.gc.stop()
    if not win.closed:
        raise RunFailed(
            f"the simulation ended by itself at sim "
            f"{(win.last_boundary or 0) / 1e9:.3f} s (rc {rc}) before "
            f"the window closed: the stop time or the offered traffic ran "
            "out inside the window, so there is no rate")
    if rc != 0:
        raise RunFailed(f"the timed run exited {rc}")
    if win.sim1_ns / 1e9 > scenario["last_arrival_s"]:
        raise RunFailed(
            f"the window reached sim {win.sim1_ns / 1e9:.3f} s, past the "
            f"last offered arrival at {scenario['last_arrival_s']:.3f} s: "
            "the traffic is no longer stationary there")
    run.sim_s, run.wall_s = win.sim_s, win.wall_s
    run.setup_s = (win.t0_ns / 1e9) - T_START
    run.longest_round_ms = win.longest_round_ns / 1e6
    run.gc_s = win.gc.ns / 1e9
    facts = {
        "window_sim_s": [win.sim0_ns / 1e9, win.sim1_ns / 1e9],
        "window_wall_s": win.wall_s,
        "closed_by": win.closed_by,
        "compiles_in_window": state["compiles1"] - state["compiles0"],
        "rounds": run.delta("engine.rounds"),
        "dispatches": run.delta("plane.dispatches"),
        "longest_round_ms": run.longest_round_ms,
        "gc_share": run.gc_s / run.wall_s,
        "gc_collections": win.gc.collections,
    }
    import jax
    facts["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[:cell.chips])
    if args.trace:
        from benchmark.lib import trace as tr
        t0 = time.perf_counter()
        kernels = {}
        for reader in cell.readers.values():
            kernels.update(getattr(reader, "KERNELS", {}))
        red = tr.reduce(tr.read_xplane(trace_dir), kernels)
        run.trace = red or {}
        say(f"trace read in {time.perf_counter() - t0:.3f} s")
    snap = cell.comparison.snapshot(engine, scenario, cell.config)
    return run, snap, facts, win.sim1_ns


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        return _main(args)
    except RunFailed as e:
        say(f"FAIL: {e}")
        return 1


def _main(args) -> int:
    # the benchmark's own modules (a comparison imports benchmark.lib)
    # and the program under test, both from the checkout's root
    sys.path.insert(0, ROOT)
    cell = Cell(args.workload)
    if not os.path.isfile(os.path.join(ROOT, "native", "Makefile")):
        raise RunFailed("no shadow-tpu checkout around the benchmark")
    build_native()
    # the compile cache: the driver's JAX_COMPILATION_CACHE_DIR where it
    # sets one, else a fixed directory inside the checkout (the program's
    # own default, shadow_tpu/utils/compile_cache.py)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    devices = find_devices(cell.chips)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()
    say(f"devices: {len(devices)} x {devices[0].device_kind}; host RSS "
        f"after the runtime's start "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024:.1f} MB")
    scenario = cell.scenario(args.seed)
    count, unit = scenario["offered"]
    say(f"cell {cell.name}: seed {args.seed}, scenario "
        f"{scenario_digest(scenario)}, {count} {unit} offered up to sim "
        f"{scenario['last_arrival_s']:.3f} s")
    with tempfile.TemporaryDirectory(prefix="bench-") as tmpdir:
        run, timed, facts, end_ns = measure(cell, args, scenario, clock,
                                            tmpdir)
        say(f"compile: {clock.summary()} (all before the window: "
            f"{facts['compiles_in_window'] == 0})")
        say(f"window: {json.dumps(facts)}")
        metrics = {}
        wanted = cell.per_layer if args.trace else cell.end_to_end
        for m in wanted:
            v = cell.readers[m["name"]].read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    gc.collect()
    t0 = time.perf_counter()
    checks, attempted, failed = cell.comparison.compare(
        timed, scenario, end_ns, cell.config)
    say(f"plain reference and comparison "
        f"({cell.config['comparison']}): {time.perf_counter() - t0:.3f} s")
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    if facts["compiles_in_window"]:
        say(f"WARNING: {facts['compiles_in_window']} compiles inside the "
            "window")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": facts["memory_peak_bytes"]}
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace:
        red = run.trace
        if not red or red.get("busy_s", 0) <= 0:
            raise RunFailed("the trace holds no device operation in the "
                            "window")
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
