"""Fleet plane gates (shadow_tpu/fleet/, ISSUE 18): the vmapped
many-scenarios-per-chip traffic plane.

ONE cached mixed fleet (module fixture) drives most gates: star + tor +
phold scenarios — three different table shapes — ride concurrent lanes
over a single shared plane, with one lane running the checkpoint+resume
drill mid-fleet, referenced bit-for-bit against the serial in-process
twin.  The re-arm drill then reuses the same plane to pin the
compile-free lane recycle, and the ops-level test pins the vmapped
kernel against the unbatched program it wraps.

Results are compared on digest/rc/events/scrape/skipped — NOT the full
supervision dict, whose watchdog/mttr fields are wall-clock and differ
between ANY two runs (serial twins included)."""

import numpy as np
import pytest

from shadow_tpu.fleet.driver import FleetDriver
from shadow_tpu.fuzz.gen import draw_spec
from shadow_tpu.fuzz.runner import mode_batchable, run_one_mode

# star, tor, phold — mixed families, distinct shape classes, one fleet
SEEDS = (11, 21, 3)

# the per-result keys that must match bit for bit across the two paths
PARITY_KEYS = ("digest", "rc", "events", "skipped", "scrape")


def _mode(spec, resume=False):
    for m in spec["modes"]:
        if mode_batchable(spec, m) and bool(m.get("resume")) == resume:
            return m
    raise AssertionError(
        f"seed {spec['seed']}: no batchable mode with resume={resume}")


@pytest.fixture(scope="module")
def fleet_run():
    specs = {s: draw_spec(s) for s in SEEDS}
    meta = [(s, _mode(specs[s])) for s in SEEDS]
    # the resumed lane: checkpoint, detach, re-attach — mid-fleet
    meta.append((3, _mode(specs[3], resume=True)))
    serial = [run_one_mode(specs[s], m) for s, m in meta]
    driver = FleetDriver(lanes=4)
    jobs = [lambda lane, s=specs[s], m=m: run_one_mode(s, m, lane=lane)
            for s, m in meta]
    fleet = driver.run(jobs)
    return {"specs": specs, "meta": meta, "serial": serial,
            "fleet": fleet, "driver": driver}


def test_mixed_fleet_digest_parity(fleet_run):
    """Acceptance: every lane of the mixed star/tor/phold fleet lands
    the exact digest (and rc/events/scrape) of its serial twin."""
    fams = {fleet_run["specs"][s]["family"] for s, _ in fleet_run["meta"]}
    assert fams == {"star", "tor", "phold"}
    for (seed, mode), ref, got in zip(fleet_run["meta"],
                                      fleet_run["serial"],
                                      fleet_run["fleet"]):
        for key in PARITY_KEYS:
            assert got[key] == ref[key], \
                (seed, mode["name"], key, ref[key], got[key])


def test_resume_lane_parity(fleet_run):
    """The checkpoint+--resume drill on a LANE (two engine passes, the
    second re-attaching the same lane) matches its serial twin while
    other lanes run concurrently."""
    seed, mode = fleet_run["meta"][-1]
    assert mode.get("resume")
    ref, got = fleet_run["serial"][-1], fleet_run["fleet"][-1]
    assert not got.get("skipped")
    for key in PARITY_KEYS:
        assert got[key] == ref[key], (seed, key)


def test_fleet_really_batched(fleet_run):
    """Fail-closed companion to parity: the fleet pass must have gone
    through the batched plane — real vmapped launches over multiple
    shape classes, amortization and occupancy coherent."""
    stats = fleet_run["driver"].plane.metrics()
    assert stats["fleet.launches"] > 0
    assert stats["fleet.lane_dispatches"] >= stats["fleet.launches"]
    assert stats["fleet.shape_classes"] >= 2
    assert stats["fleet.launches_amortized"] >= 1.0
    assert 0.0 < stats["fleet.lane_occupancy"] <= 1.0


def test_rearm_without_recompile(fleet_run):
    """ISSUE 18 drill: a finished lane is detached and a NEW lane with a
    same-class scenario re-armed on the same plane — zero recompiles
    (the jit cache key is (shape class, sticky width), and the sticky
    width never shrinks)."""
    driver = fleet_run["driver"]
    spec = fleet_run["specs"][11]
    mode = _mode(spec)
    before = driver.plane.metrics()
    got = driver.run([lambda lane: run_one_mode(spec, mode, lane=lane)])[0]
    after = driver.plane.metrics()
    assert got["digest"] == fleet_run["serial"][0]["digest"]
    assert after["fleet.compiles"] == before["fleet.compiles"]
    assert after["fleet.launches"] > before["fleet.launches"]


def test_vmapped_kernel_matches_unbatched():
    """Ops-level pin: the [W]-leading-axis program is bit-identical per
    lane to the unbatched span/flush kernel — including lanes at
    DIFFERENT t_stops, where the batched while-cond keeps running the
    long lane while the short one sits select()-frozen."""
    from shadow_tpu.ops.torcells_device import (
        RING_DTYPE, DeviceTorCells, torcells_step_span_flush_batched,
        torcells_step_window_flush_nodonate)
    inst = DeviceTorCells(n_relays=8, n_circuits=24, seed=5,
                          relay_bw_kibps=1024, max_latency_ms=20)
    fl = inst.flows
    f, h = inst.n_flows, len(inst.refill)
    last_flow = np.flatnonzero(fl["flow_succ"] < 0)
    tables = (fl["flow_node"], fl["flow_lat"], fl["flow_succ"],
              fl["seg_start"], inst.refill, inst.capacity, last_flow,
              fl["flow_pred"], fl["node_seg"])
    lanes = []
    for k in (1, 3):          # different injections AND different spans
        inject = (fl["flow_stage"] == 0).astype("int64") * 40 * k
        target = (fl["flow_succ"] < 0).astype("int64") * 40 * k
        lanes.append((np.int64(0), np.zeros(f, np.int64),
                      np.zeros((inst.ring_len, f), RING_DTYPE),
                      np.asarray(inst.capacity), np.zeros(f, np.int64),
                      np.zeros(f, np.int64), np.full(f, -1, np.int64),
                      np.zeros(h, np.int64), inject, target,
                      np.array([50 * k], np.int64), np.int64(0), *tables))
    singles = [torcells_step_window_flush_nodonate(
        *lane, ring_len=inst.ring_len) for lane in lanes]
    batch = tuple(np.stack([np.asarray(lane[i]) for lane in lanes])
                  for i in range(21))
    batched = torcells_step_span_flush_batched(*batch,
                                               ring_len=inst.ring_len)
    for i in range(10):
        got = np.asarray(batched[i])
        for w, single in enumerate(singles):
            np.testing.assert_array_equal(got[w], np.asarray(single[i]),
                                          err_msg=f"output {i} lane {w}")


def test_cli_parser_surface():
    from shadow_tpu.fleet.cli import build_parser
    args = build_parser().parse_args(["smoke", "--lanes", "2",
                                      "--seeds", "3"])
    assert args.lanes == 2 and args.seeds == 3 and not args.numpy
