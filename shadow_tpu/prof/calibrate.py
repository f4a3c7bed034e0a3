"""``simprof calibrate``: microbenchmark the actual backend into a
digest-stamped per-box cost model.

Methodology (arXiv 1912.03413's IPU microbenchmarking, applied to this
engine's three device cost centers):

* **per-collective launch cost** — one jitted ``shard_map`` program per
  (kind, D, width) whose ``fori_loop`` issues N collectives back to
  back; per-launch cost is wall/N.  The loop body carries a data
  dependence through the collective result so XLA cannot DCE it (the
  PR-9 trap: multiplying a collective by 0 deletes it).  Kinds are
  exactly what the mesh kernel issues: ``ppermute``, tiled
  ``all_to_all``, and the fused stats ``psum``;
* **step-kernel cost vs flows** — the production superwindow flush
  kernel (ops/torcells_device) timed at measured flow counts, so the
  model predicts the per-tick cost of the table the engine actually
  dispatches;
* **dispatch/flush transfer cost** — host->device upload of an [F]
  inject vector plus device->host materialization of a flush-sized
  buffer, the fixed per-launch transfer the pipeline amortizes.

Execution is the bench-multichip pattern: the parent spawns ONE bounded
child with the virtual device mesh forced on CPU (a real accelerator
environment is left alone), kills it on overrun, and wraps the child's
measurements with fingerprint + git sha + digest (model.build_model)
into an atomically-written ``COSTMODEL.json``.  The child checks a wall
deadline between probes and marks the model ``truncated`` when it had
to stop early — a truncated model is still valid for the points it
measured.
"""

from __future__ import annotations

import json
import os
import time as _walltime
from typing import Dict, List, Optional, Tuple

# default probe grids (ISSUE 15: D in {2,3,4,8} and slot widths); quick
# mode trims to the endpoints for the wall-capped CI smoke
DEVICES = (2, 3, 4, 8)
WIDTHS = (24, 240, 4080)
QUICK_DEVICES = (2, 8)
QUICK_WIDTHS = (24, 960)
# step-kernel sweep in CIRCUITS (flow rows = 5x).  The top points exist
# so the calibrated range covers flagship-scale tables (ISSUE 16: tor10k
# dispatches ~100k flow rows; under the two-sided no-extrapolation guard
# an uncovered table gets neither launch attribution NOR auto-tuning) —
# 24k circuits = 120k flows, covering 240k under the 2x slack.  Large
# points run proportionally fewer steps (_steps_for) so the sweep's wall
# stays bounded.
FLOW_POINTS = (200, 1000, 4000, 12000, 24000)
QUICK_FLOW_POINTS = (200, 2000)


def _steps_for(n_circ: int, steps: int) -> int:
    """Scale the timed step count down for large tables (cost per step
    grows ~linearly with flows; the per-step quotient stays accurate with
    fewer, longer steps) — never below 60 steps so launch overhead stays
    amortized out of the quotient."""
    if n_circ <= 4000:
        return steps
    return max(60, steps * 4000 // n_circ)


def _deadline_left(deadline: Optional[float]) -> float:
    if deadline is None:
        return float("inf")
    return deadline - _walltime.monotonic()


def measure_collectives(devices, widths, iters: int,
                        deadline: Optional[float]) -> Tuple[Dict, bool]:
    """Per-launch cost tables {kind: {"DxW": us}}; bool = truncated."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import device_mesh

    out: Dict[str, Dict[str, float]] = {"ppermute": {},
                                        "all_to_all": {},
                                        "psum": {}}
    truncated = False
    n_avail = len(jax.devices())
    for d in devices:
        if d > n_avail:
            continue
        mesh = device_mesh(d, axis_names=("x",))
        for width in widths:
            # per-shard width; all_to_all tiles over it, so keep it a
            # multiple of d (floor d)
            w = max((int(width) // d) * d, d)
            for kind in ("ppermute", "all_to_all", "psum"):
                if _deadline_left(deadline) <= 0:
                    truncated = True
                    return out, truncated
                perm = [(s, (s + 1) % d) for s in range(d)]

                def body(i, x, kind=kind, perm=perm):
                    if kind == "ppermute":
                        y = jax.lax.ppermute(x, "x", perm=perm)
                    elif kind == "all_to_all":
                        y = jax.lax.all_to_all(x, "x", 0, 0, tiled=True)
                    else:
                        y = x + jax.lax.psum(x[0], "x")
                    # the +i data dependence keeps every iteration (and
                    # the collective inside it) live under XLA
                    return y + i

                @jax.jit
                @partial(jax.shard_map, mesh=mesh, in_specs=P("x"),
                         out_specs=P("x"), check_vma=False)
                def run(x, body=body, iters=iters):
                    return jax.lax.fori_loop(0, iters, body, x)

                x = jnp.zeros(d * w, jnp.int64)
                jax.block_until_ready(run(x))          # compile
                t0 = _walltime.perf_counter()
                jax.block_until_ready(run(x))
                t1 = _walltime.perf_counter()
                out[kind][f"{d}x{w}"] = round(
                    (t1 - t0) / iters * 1e6, 2)
    return out, truncated


def measure_step_kernel(flow_points, steps: int,
                        deadline: Optional[float]) -> Tuple[Dict, bool]:
    """Per-tick cost of the production span-flush kernel at measured
    circuit counts (points carry the padded flow-row count the engine's
    predictor is keyed by)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops.torcells_device import (
        RING_DTYPE, DeviceTorCells, torcells_step_window_flush_nodonate)

    points: List[Dict] = []
    truncated = False
    for n_circ in flow_points:
        if _deadline_left(deadline) <= 0:
            truncated = True
            break
        pt_steps = _steps_for(int(n_circ), steps)
        inst = DeviceTorCells(n_relays=max(8, n_circ // 10),
                              n_circuits=n_circ, seed=11,
                              relay_bw_kibps=4096, max_latency_ms=30)
        fl = inst.flows
        f = inst.n_flows
        h = len(inst.refill)
        last_flow = np.flatnonzero(fl["flow_succ"] < 0)
        queued0 = jnp.asarray(
            (fl["flow_stage"] == 0).astype("int64") * 50)
        target0 = jnp.asarray(
            (fl["flow_succ"] < 0).astype("int64") * 50)
        state = (jnp.int64(0), jnp.zeros(f, jnp.int64),
                 jnp.zeros((inst.ring_len, f), RING_DTYPE),
                 jnp.asarray(inst.capacity), jnp.zeros(f, jnp.int64),
                 jnp.zeros(f, jnp.int64), jnp.full(f, -1, jnp.int64),
                 jnp.zeros(h, jnp.int64))
        args = (jnp.asarray(fl["flow_node"]), jnp.asarray(fl["flow_lat"]),
                jnp.asarray(fl["flow_succ"]), jnp.asarray(fl["seg_start"]),
                jnp.asarray(inst.refill), jnp.asarray(inst.capacity),
                jnp.asarray(last_flow), jnp.asarray(fl["flow_pred"]),
                jnp.asarray(fl["node_seg"]))
        targets = np.array([pt_steps], dtype=np.int64)
        out = torcells_step_window_flush_nodonate(
            *state, queued0, target0, targets, np.int64(0), *args,
            ring_len=inst.ring_len)
        jax.block_until_ready(out)                    # compile
        t0 = _walltime.perf_counter()
        out = torcells_step_window_flush_nodonate(
            *state, queued0, target0, targets, np.int64(0), *args,
            ring_len=inst.ring_len)
        jax.block_until_ready(out)
        t1 = _walltime.perf_counter()
        points.append({"flows": int(f),
                       "us_per_step": round((t1 - t0) / pt_steps * 1e6,
                                            3)})
    return {"points": points}, truncated


def measure_batched_step_kernel(widths=(1, 2, 4, 8), n_circ: int = 1000,
                                steps: int = 200,
                                deadline: Optional[float] = None
                                ) -> Tuple[Dict, bool]:
    """Fleet-plane width sweep (ISSUE 18): per-lane per-tick cost of the
    VMAPPED span-flush kernel at widths 1..W — the measured answer to
    "how many co-resident simulations does one ~320 us launch amortize
    over before the compute wall bites".  Reported in the calibrate
    status row ONLY; the stamped COSTMODEL stays the single-lane model
    every existing consumer (autotune, launch attribution) is keyed by."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops.torcells_device import (
        RING_DTYPE, DeviceTorCells, torcells_step_span_flush_batched)

    inst = DeviceTorCells(n_relays=max(8, n_circ // 10),
                          n_circuits=n_circ, seed=11,
                          relay_bw_kibps=4096, max_latency_ms=30)
    fl = inst.flows
    f = inst.n_flows
    h = len(inst.refill)
    last_flow = np.flatnonzero(fl["flow_succ"] < 0)
    queued0 = (fl["flow_stage"] == 0).astype("int64") * 50
    target0 = (fl["flow_succ"] < 0).astype("int64") * 50
    lane_state = (np.int64(0), np.zeros(f, np.int64),
                  np.zeros((inst.ring_len, f), RING_DTYPE),
                  np.asarray(inst.capacity), np.zeros(f, np.int64),
                  np.zeros(f, np.int64), np.full(f, -1, np.int64),
                  np.zeros(h, np.int64))
    tables = (np.asarray(fl["flow_node"]), np.asarray(fl["flow_lat"]),
              np.asarray(fl["flow_succ"]), np.asarray(fl["seg_start"]),
              np.asarray(inst.refill), np.asarray(inst.capacity),
              np.asarray(last_flow), np.asarray(fl["flow_pred"]),
              np.asarray(fl["node_seg"]))
    points: List[Dict] = []
    truncated = False
    base_us = None
    for w in widths:
        if _deadline_left(deadline) <= 0:
            truncated = True
            break
        lane = (*lane_state, queued0, target0,
                np.array([steps], dtype=np.int64), np.int64(0), *tables)
        batch = tuple(jnp.asarray(np.stack([np.asarray(a)] * w))
                      for a in lane)
        out = torcells_step_span_flush_batched(
            *batch, ring_len=inst.ring_len)
        jax.block_until_ready(out)                    # compile
        t0 = _walltime.perf_counter()
        out = torcells_step_span_flush_batched(
            *batch, ring_len=inst.ring_len)
        jax.block_until_ready(out)
        t1 = _walltime.perf_counter()
        lane_us = (t1 - t0) / steps / w * 1e6
        if base_us is None:
            base_us = lane_us
        points.append({"width": int(w), "flows": int(f),
                       "us_per_lane_step": round(lane_us, 3),
                       "speedup_vs_serial": round(base_us / lane_us, 2)
                       if lane_us > 0 else 0.0})
    return {"points": points}, truncated


def measure_transfer(reps: int = 30, flows: int = 4096,
                     big_flows: int = 65536) -> Dict:
    """Fixed per-launch transfer cost: inject upload + flush readback.
    The readback is measured at TWO buffer sizes, and their slope is
    recorded as ``flush_us_per_mb``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def readback_us(n: int) -> float:
        dev = jnp.arange(n, dtype=jnp.int64)
        np.asarray(dev)
        t0 = _walltime.perf_counter()
        for _ in range(reps):
            np.asarray(dev + 1)  # +1: a fresh buffer per materialization
        return (_walltime.perf_counter() - t0) / reps * 1e6

    host = np.zeros(flows, dtype=np.int64)
    jax.block_until_ready(jnp.asarray(host))          # warm the path
    t0 = _walltime.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(jnp.asarray(host))
    up_us = (_walltime.perf_counter() - t0) / reps * 1e6
    down_us = readback_us(flows)
    down_big_us = readback_us(big_flows)
    mb = (big_flows - flows) * 8 / 2 ** 20
    slope = max((down_big_us - down_us) / mb, 0.0) if mb > 0 else 0.0
    return {"dispatch_us": round(up_us, 2), "flush_us": round(down_us, 2),
            "flush_us_per_mb": round(slope, 2)}


def calibrate_child(out_path: str, quick: bool, wall_cap_sec: float,
                    devices: Optional[List[int]] = None,
                    batched: bool = False) -> int:
    """The in-subprocess half: run every probe under the wall deadline
    and write raw measurements (+ truncated flag + wall) as JSON."""
    t0 = _walltime.monotonic()
    deadline = t0 + wall_cap_sec if wall_cap_sec > 0 else None
    devs = tuple(devices) if devices else (
        QUICK_DEVICES if quick else DEVICES)
    widths = QUICK_WIDTHS if quick else WIDTHS
    flow_points = QUICK_FLOW_POINTS if quick else FLOW_POINTS
    iters = 200 if quick else 500
    steps = 200 if quick else 400
    coll, trunc_c = measure_collectives(devs, widths, iters, deadline)
    step, trunc_s = measure_step_kernel(flow_points, steps, deadline)
    transfer = measure_transfer()
    payload = {
        "collectives": coll,
        "step_kernel": step,
        "transfer": transfer,
        "truncated": bool(trunc_c or trunc_s),
        "wall_sec": round(_walltime.monotonic() - t0, 2),
    }
    if batched:
        fleet, trunc_b = measure_batched_step_kernel(
            n_circ=200 if quick else 1000,
            steps=100 if quick else 200, deadline=deadline)
        fleet["truncated"] = trunc_b
        payload["fleet_batched"] = fleet
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, out_path)
    return 0


def run_calibration(out_path: str, quick: bool = False,
                    wall_cap_sec: float = 600.0,
                    devices: Optional[List[int]] = None,
                    n_dev_env: int = 8, batched: bool = False) -> Dict:
    """Parent orchestration: spawn the bounded child with the virtual
    device mesh forced on CPU, wrap its measurements into the stamped
    model, write ``out_path`` atomically.  Returns a status row
    ({"ok": bool, ...}); a wedged child is killed and reported, never a
    hang."""
    import subprocess
    import sys
    import tempfile

    from . import model as _model
    from ..fuzz.runner import child_env

    t0 = _walltime.monotonic()
    with tempfile.TemporaryDirectory(prefix="simprof-") as td:
        mpath = os.path.join(td, "measurements.json")
        args = [sys.executable, "-m", "shadow_tpu.prof", "calibrate",
                "--child", mpath, "--wall-cap-sec", str(wall_cap_sec)]
        if quick:
            args.append("--quick")
        if batched:
            args.append("--batched")
        if devices:
            args += ["--devices", ",".join(str(d) for d in devices)]
        try:
            proc = subprocess.run(
                args, env=child_env(n_dev_env), capture_output=True,
                text=True, timeout=wall_cap_sec + 120)
        except subprocess.TimeoutExpired:
            return {"ok": False,
                    "reason": f"calibration child exceeded the "
                              f"{wall_cap_sec + 120:.0f}s bound and was "
                              "killed"}
        if proc.returncode != 0 or not os.path.exists(mpath):
            return {"ok": False, "rc": proc.returncode,
                    "reason": "calibration child failed",
                    "tail": (proc.stdout + proc.stderr)[-800:]}
        with open(mpath) as f:
            meas = json.load(f)
    # the fleet width sweep rides in the STATUS ROW only — popped before
    # build_model so the stamped COSTMODEL stays the single-lane model
    # (its digest/schema consumers are all keyed by one-lane costs)
    fleet_batched = meas.pop("fleet_batched", None)
    data = _model.build_model(
        meas, wall_sec=_walltime.monotonic() - t0,
        truncated=bool(meas.get("truncated")))
    save_dir = os.path.dirname(os.path.abspath(out_path))
    if save_dir and not os.path.isdir(save_dir):
        os.makedirs(save_dir, exist_ok=True)
    _model.save_model(out_path, data)
    n_coll = sum(len(t) for t in data["collectives"].values())
    return {"ok": True, "path": out_path,
            **({"fleet_batched": fleet_batched} if fleet_batched else {}),
            "wall_sec": round(_walltime.monotonic() - t0, 1),
            "collective_points": n_coll,
            "step_points": len(data["step_kernel"]["points"]),
            "truncated": data["truncated"],
            "fingerprint": data["fingerprint"],
            "git_sha": data["git_sha"]}
