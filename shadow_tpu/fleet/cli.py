"""simfleet CLI (ISSUE 18): drive the vmapped many-scenarios-per-chip
fleet plane.

Usage::

    simfleet smoke [--lanes 8] [--seeds 8] [--seed-base 0] [--numpy]
                   [--out PATH]

``smoke`` is the CI gate (``make fleet-smoke``): draw a bounded mixed
scenario set from the fuzz generator, run each scenario's base mode
twice — serially (the reference) and as fleet lanes over ONE shared
vmapped plane — and require bit-identical digests plus a real batched
launch count.  Prints ONE summary JSON line last, like bench.py; exit
0 = digest-gated pass, 1 = mismatch or no launches, 2 = usage errors.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time as _walltime
from typing import List, Optional


def _say(msg: str) -> None:
    print(f"simfleet: {msg}", file=sys.stderr, flush=True)


def setup_fleet_env(n_dev: int = 8) -> None:
    """In-process twin of ``fuzz.runner.child_env``: force the virtual
    device mesh for a CPU backend BEFORE jax initializes, so phase-2 mesh
    modes run anywhere.  The platform is the caller's choice; a process
    that already imported jax is left alone."""
    if "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_dev}"
        ).strip()


def cmd_smoke(args) -> int:
    setup_fleet_env()
    from ..fuzz.gen import draw_spec
    from ..fuzz.runner import mode_batchable, run_one_mode
    from .driver import FleetDriver

    t0 = _walltime.monotonic()
    picks = []
    for i in range(args.seeds):
        seed = args.seed_base + i
        spec = draw_spec(seed)
        mode = next((m for m in spec["modes"]
                     if mode_batchable(spec, m) and not m.get("resume")),
                    None)
        if mode is None:
            _say(f"seed {seed} [{spec['family']}]: no batchable mode, "
                 "skipped")
            continue
        picks.append((seed, spec, mode))
    if not picks:
        _say("no batchable scenarios drawn; widen --seeds")
        return 2
    _say(f"{len(picks)} scenarios "
         f"({', '.join(sorted({s['family'] for _, s, _ in picks}))}): "
         "serial reference pass")
    serial = [run_one_mode(spec, mode) for _, spec, mode in picks]
    t1 = _walltime.monotonic()
    _say(f"fleet pass: {args.lanes} lanes"
         + (" (numpy twin)" if args.numpy else ""))
    driver = FleetDriver(lanes=args.lanes, use_numpy=args.numpy)
    jobs = [lambda lane, s=spec, m=mode: run_one_mode(s, m, lane=lane)
            for _, spec, mode in picks]
    fleet = driver.run(jobs)
    t2 = _walltime.monotonic()
    rows = []
    matched = True
    for (seed, spec, mode), ref, got in zip(picks, serial, fleet):
        ok = (ref["digest"] == got["digest"] and ref["rc"] == got["rc"]
              and ref["events"] == got["events"])
        matched = matched and ok
        rows.append({"seed": seed, "family": spec["family"],
                     "mode": mode["name"], "rc": got["rc"],
                     "digest_match": ok})
        if not ok:
            _say(f"seed {seed} [{spec['family']}] DIGEST MISMATCH: "
                 f"serial rc={ref['rc']} digest={ref['digest']} vs "
                 f"fleet rc={got['rc']} digest={got['digest']}")
    stats = driver.plane.metrics()
    launched = stats["fleet.launches"] > 0
    if not launched:
        _say("no batched launches fired — the fleet plane was never "
             "exercised (gate fails closed)")
    # the runtime half of the SIM305 compile-budget contract: measured
    # cache counts vs the checked-in [tool.simjit.budget] table, failing
    # on either direction of drift (growth past the budget, or a
    # budgeted metric the run no longer reports)
    from ..analysis.simjit import crosscheck_budget, load_runtime_budget
    from ..parallel.device_plane import DeviceTrafficPlane
    budget = load_runtime_budget(os.getcwd())
    measured = {
        "fleet.compiles": int(stats.get("fleet.compiles", 0)),
        "device_plane.sharded_variants":
            int(DeviceTrafficPlane.sharded_variants_high_water),
    }
    if args.numpy:
        # the numpy twin compiles nothing by design — the budget
        # contract is about the jit path
        budget_problems: List[str] = []
    elif not budget:
        _say("no [tool.simjit.budget] runtime entries found; "
             "compile-budget cross-check skipped")
        budget_problems = []
    else:
        budget_problems = crosscheck_budget(
            measured, budget, require_nonzero=("fleet.compiles",))
        for p in budget_problems:
            _say(f"compile-budget drift: {p}")
    ok = matched and launched and not budget_problems
    summary = {"simfleet": {
        "lanes": args.lanes,
        "scenarios": len(picks),
        "families": sorted({s["family"] for _, s, _ in picks}),
        "digest_match": matched,
        "serial_wall_sec": round(t1 - t0, 2),
        "fleet_wall_sec": round(t2 - t1, 2),
        "numpy": bool(args.numpy),
        "rows": rows,
        "budget_measured": measured,
        "budget_problems": budget_problems,
        **stats},
        "pass": ok}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1, sort_keys=True)
            f.write("\n")
    print(json.dumps(summary), flush=True)
    return 0 if ok else 1


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="simfleet",
        description="many simulations per chip: N scenarios advanced by "
                    "one vmapped device program (ROADMAP 3)")
    sub = p.add_subparsers(dest="cmd", required=True)
    sm = sub.add_parser(
        "smoke", help="bounded mixed fleet, digest-gated against serial")
    sm.add_argument("--lanes", type=int, default=8,
                    help="concurrent fleet lanes")
    sm.add_argument("--seeds", type=int, default=8,
                    help="scenarios to draw (fuzz generator seeds)")
    sm.add_argument("--seed-base", type=int, default=0, dest="seed_base")
    sm.add_argument("--numpy", action="store_true",
                    help="drive the batched numpy twin instead of the "
                         "vmapped jit program (kernel-parity debugging)")
    sm.add_argument("--out", default=None,
                    help="also write the summary JSON here")
    sm.set_defaults(fn=cmd_smoke)
    return p


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
