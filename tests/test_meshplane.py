"""meshplane parity suite (ISSUE 9): the multi-chip sharded traffic plane
(shadow_tpu/parallel/mesh/) on the 8-virtual-device CPU mesh.

1. Partition + exchange statics: the chain partitioner is deterministic,
   segment-aligned, balanced, and never cuts more hops than the old
   contiguous split; the BvN schedule covers every cross-shard successor
   edge exactly once with <= D-1 permutation legs.
2. Kernel bit parity (migrated from test_device_plane's PR-7 sharded
   kernel gate): the mesh superwindow kernel — shard-local arrival ring,
   ppermute exchange legs — is bit-identical to the single-device span
   kernel, packed flush included, at D=8 and uneven D=3.
3. Engine digest parity sharded-vs-single-device-vs-numpy-twin-vs-serial
   (--device-plane-sync) on a generated star scenario and a tor network,
   at K=1 and K=8, with the acceptance metrics: mesh.host_bounces == 0
   (cross-shard forwards never transit the host), cross_shard_cells > 0
   (the legs actually carried traffic), and <= 3 device calls per
   dispatch (the single-device plane's pipeline budget).
4. Composition: K=8 superwindows engage with the halt flag psum'd across
   shards, checkpoint/resume mid-superwindow on a sharded run, and the
   device-dispatch fault drill demoting the sharded plane to the numpy
   twin with digest parity preserved.
"""

import glob

import numpy as np
import pytest

from shadow_tpu.core import configuration
from shadow_tpu.core.checkpoint import state_digest
from shadow_tpu.core.controller import Controller
from shadow_tpu.core.options import Options
from shadow_tpu.tools import workloads

STAR_XML = workloads.star_bulk(6, stoptime=120, bulk_bytes=192 * 1024 * 1024,
                               device_data=True)
TOR_XML = workloads.tor_network(8, n_clients=5, n_servers=2, stoptime=60,
                                stream_spec="512:20200", device_data=True)


def _run(xml, n_dev=8, k=1, mode="device", policy="global", sync=False,
         stop=120, **opt_kw):
    cfg = configuration.parse_xml(xml)
    cfg.stop_time_sec = stop
    ctrl = Controller(Options(scheduler_policy=policy, workers=0, seed=3,
                              stop_time_sec=stop, log_level="warning",
                              device_plane=mode, device_plane_sync=sync,
                              superwindow_rounds=k, tpu_devices=n_dev,
                              **opt_kw), cfg)
    assert ctrl.run() == 0
    return ctrl


# several gates compare against the same star configurations; runs are
# deterministic, so repeat configurations are executed once and shared
# (keeps the suite's tier-1 wall share down — each run is a few seconds)
_STAR_CACHE: dict = {}


def _star(n_dev=8, k=1, mode="device"):
    key = (n_dev, k, mode)
    if key not in _STAR_CACHE:
        _STAR_CACHE[key] = _run(STAR_XML, n_dev=n_dev, k=k, mode=mode)
    return _STAR_CACHE[key]


def _mesh_scrape(ctrl):
    return {k: v for k, v in ctrl.engine.metrics.scrape().items()
            if k.startswith("mesh.")}


# -- partition + exchange statics ------------------------------------------

def _toy_flows():
    from shadow_tpu.ops.torcells_device import DeviceTorCells
    inst = DeviceTorCells(n_relays=6, n_circuits=20, seed=5,
                          relay_bw_kibps=512, max_latency_ms=20)
    return inst


def test_chain_partition_deterministic_balanced_and_no_worse():
    from shadow_tpu.parallel.mesh.partition import (chain_partition,
                                                    contiguous_partition)
    inst = _toy_flows()
    fl = inst.flows
    a, cross_a = chain_partition(fl["flow_node"], fl["flow_succ"], 8)
    b, cross_b = chain_partition(fl["flow_node"], fl["flow_succ"], 8)
    np.testing.assert_array_equal(a, b)
    # segment alignment: every flow's node maps to exactly one shard by
    # construction; balance: no shard exceeds budget + one max segment
    f = len(fl["flow_node"])
    sizes = np.bincount(a[fl["flow_node"]], minlength=8)
    seg_max = np.bincount(fl["flow_node"]).max()
    assert sizes.max() <= -(-f // 8) + seg_max
    # the chain walker must not cut more hops than the pre-mesh
    # contiguous split (its baseline) on the same table
    contig = contiguous_partition(fl["flow_node"], 8)
    valid = fl["flow_succ"] >= 0
    cut = np.count_nonzero(
        contig[fl["flow_node"][valid]]
        != contig[fl["flow_node"][fl["flow_succ"][valid]]])
    assert cross_a <= cut


def test_exchange_schedule_is_a_bvn_decomposition():
    """Every cross-shard successor edge rides exactly one leg slot; each
    leg is a rotation permutation (shard s talks only to (s+r) % D), and
    the leg count is bounded by D-1 (offset 0 is local traffic)."""
    from shadow_tpu.parallel.mesh.partition import build_mesh_layout
    inst = _toy_flows()
    fl = inst.flows
    for n_dev in (8, 3):
        lay = build_mesh_layout(fl["flow_node"], fl["flow_lat"],
                                fl["flow_succ"], fl["seg_start"],
                                inst.refill, inst.capacity, n_dev)
        sched = lay["exchange"]
        assert 1 <= sched.legs <= n_dev - 1
        assert all(0 < r < n_dev for r in sched.offsets)
        pad = lay["pad"]
        succ = lay["succ_global"]
        # reconstruct (src shard, local src, local dst) triples from the
        # leg tables and compare against the raw cross edges
        from_tables = set()
        for r, w, snd, rcv in zip(sched.offsets, sched.widths,
                                  sched.send_src, sched.recv_dst):
            for s in range(n_dev):
                d = (s + r) % n_dev
                for slot in range(w):
                    src_row = snd[s * w + slot]
                    dst_row = rcv[d * w + slot]
                    assert (src_row < 0) == (dst_row < 0), \
                        "sender/receiver slot tables out of step"
                    if src_row >= 0:
                        from_tables.add((s, int(src_row), d, int(dst_row)))
        expect = set()
        for i in np.flatnonzero(succ >= 0).tolist():
            s, d = i // pad, int(succ[i]) // pad
            if s != d:
                expect.add((s, i - s * pad, d, int(succ[i]) - d * pad))
        assert from_tables == expect
        assert sched.cross_edges == len(expect)


def test_pad_state_contract():
    """pad_state is the one original->padded translation: real rows land
    at inv positions, padding rows keep the fill value."""
    from shadow_tpu.parallel.mesh.partition import (build_mesh_layout,
                                                    pad_state)
    inst = _toy_flows()
    fl = inst.flows
    lay = build_mesh_layout(fl["flow_node"], fl["flow_lat"],
                            fl["flow_succ"], fl["seg_start"],
                            inst.refill, inst.capacity, 8)
    a = np.arange(inst.n_flows, dtype=np.int64) + 7
    p = pad_state(lay, a, fill=-5)
    np.testing.assert_array_equal(p[lay["inv"]], a)
    assert (p[~lay["keep"]] == -5).all()


# -- kernel bit parity ------------------------------------------------------

@pytest.mark.parametrize("n_dev", [8, 3, 2])
def test_mesh_kernel_bit_parity(n_dev):
    """The mesh superwindow kernel (shard-local ring, fused on-device
    exchange) is bit-identical to the single-device span kernel across
    split windows, packed flush buffer included — at D=8 (fused
    all_to_all), uneven D=3 (N % D != 0 exercises per-shard padding),
    and D=2 (a single-leg schedule exercises the lone-ppermute path)."""
    import jax.numpy as jnp
    from shadow_tpu.ops.torcells_device import (
        RING_DTYPE, flush_len, torcells_step_window_flush_nodonate)
    from shadow_tpu.parallel.mesh import device_mesh
    from shadow_tpu.parallel.mesh.exchange import make_mesh_span_flush
    from shadow_tpu.parallel.mesh.partition import (build_mesh_layout,
                                                    pad_state)

    inst = _toy_flows()
    fl = inst.flows
    f = inst.n_flows
    h = len(inst.refill)
    c = len(np.flatnonzero(fl["flow_succ"] < 0))
    last_flow = np.flatnonzero(fl["flow_succ"] < 0)
    queued0 = np.where(fl["flow_stage"] == 0, 30, 0).astype(np.int64)
    target0 = np.where(fl["flow_succ"] < 0, 30, 0).astype(np.int64)
    zeros = np.zeros(f, np.int64)
    targets1 = np.array([40], dtype=np.int64)
    targets2 = np.array([140, 240, 540], dtype=np.int64)

    # single-device oracle: one window, then a 3-span superwindow
    sstate = (jnp.int64(0), jnp.zeros(f, jnp.int64),
              jnp.zeros((inst.ring_len, f), RING_DTYPE),
              jnp.asarray(inst.capacity), jnp.zeros(f, jnp.int64),
              jnp.zeros(f, jnp.int64), jnp.full(f, -1, jnp.int64),
              jnp.zeros(h, jnp.int64))
    args = (jnp.asarray(fl["flow_node"]), jnp.asarray(fl["flow_lat"]),
            jnp.asarray(fl["flow_succ"]), jnp.asarray(fl["seg_start"]),
            jnp.asarray(inst.refill), jnp.asarray(inst.capacity),
            jnp.asarray(last_flow), jnp.asarray(fl["flow_pred"]),
            jnp.asarray(fl["node_seg"]))
    ref = torcells_step_window_flush_nodonate(
        *sstate, queued0, target0, targets1, np.int64(0), *args,
        ring_len=inst.ring_len)
    ref = torcells_step_window_flush_nodonate(
        *ref[:8], zeros, zeros, targets2, np.int64(0), *args,
        ring_len=inst.ring_len)

    mesh = device_mesh(n_dev, axis_names=("flows",))
    lay = build_mesh_layout(fl["flow_node"], fl["flow_lat"],
                            fl["flow_succ"], fl["seg_start"],
                            inst.refill, inst.capacity, n_dev)
    fp = len(lay["src"])
    step = make_mesh_span_flush(mesh, "flows", inst.ring_len, lay,
                                lay["inv"][last_flow], lay["node_src"], h)
    statics = (lay["flow_node_local"], lay["succ_global"],
               lay["seg_start_local"], lay["refill"], lay["capacity"],
               lay["arr_lat"], lay["shard_base"])
    zp = np.zeros(fp, np.int64)
    mstate = (np.int64(0), jnp.asarray(pad_state(lay, zeros)),
              jnp.zeros((inst.ring_len, fp), RING_DTYPE),
              jnp.asarray(lay["capacity"]), jnp.zeros(fp, jnp.int64),
              jnp.zeros(fp, jnp.int64), jnp.full(fp, -1, jnp.int64),
              jnp.zeros(len(lay["refill"]), jnp.int64))
    out = step(*mstate, pad_state(lay, queued0), pad_state(lay, target0),
               targets1, np.int64(0), *statics)
    out = step(*out[:8], zp, zp, targets2, np.int64(0), *statics)

    inv = lay["inv"]
    for name, i in (("queued", 1), ("delivered", 4), ("target", 5),
                    ("done", 6)):
        np.testing.assert_array_equal(np.asarray(out[i])[inv],
                                      np.asarray(ref[i]), err_msg=name)
    assert int(out[0]) == int(ref[0])           # halt boundary agrees
    base = flush_len(c, h)
    np.testing.assert_array_equal(np.asarray(out[9])[:base],
                                  np.asarray(ref[9]))
    assert int(np.asarray(out[9])[base]) > 0    # legs carried cells


# -- engine digest parity (the acceptance gate) ----------------------------

def _assert_mesh_contract(ctrl, max_calls=3):
    plane = ctrl.engine.device_plane
    scrape = _mesh_scrape(ctrl)
    assert plane._shard is not None, "mesh layout did not engage"
    assert scrape["mesh.host_bounces"] == 0
    assert scrape["mesh.cross_shard_cells"] > 0, \
        "no cells crossed shards — the exchange gate is vacuous"
    assert scrape["mesh.exchange_legs"] >= 1
    assert scrape["mesh.devices"] == plane._meshinfo.n_devices
    st = plane.stats()
    assert st["device_calls"] / max(st["dispatches"], 1) <= max_calls, st


def test_star_parity_sharded_vs_single_vs_twin_k1_and_k8():
    """The acceptance gate on the generated star scenario: sharded(8),
    single-device, and the numpy twin end bit-identical at K=1 and K=8,
    with cross-shard forwards exchanged on-device (host_bounces == 0) and
    the per-dispatch device-call budget <= 3."""
    digests = {}
    for k in (1, 8):
        sharded = _star(n_dev=8, k=k)
        _assert_mesh_contract(sharded)
        single = _star(n_dev=1, k=k)
        assert single.engine.device_plane._shard is None
        twin = _star(n_dev=8, k=k, mode="numpy")
        d = state_digest(sharded.engine)
        assert d == state_digest(single.engine), f"K={k} sharded != single"
        assert d == state_digest(twin.engine), f"K={k} sharded != twin"
        st = sharded.engine.device_plane.stats()
        assert st["completed"] == st["circuits"] == 6
        digests[k] = d
    assert digests[1] == digests[8]


def test_star_parity_pipelined_vs_serial_schedule():
    """Sharded pipelined vs the --device-plane-sync serial oracle: the
    same digest, so overlap never reorders anything on the mesh either."""
    piped = _star(n_dev=8, k=8)
    serial = _run(STAR_XML, n_dev=8, k=8, sync=True)
    assert state_digest(piped.engine) == state_digest(serial.engine)


def test_tor_parity_sharded_vs_single_vs_twin():
    """tor-shaped control chatter (circuit TCP through the real engine)
    with the bulk phase sharded: digests match single-device and the twin
    at K=1 and K=8."""
    for k in (1, 8):
        sharded = _run(TOR_XML, n_dev=8, k=k, stop=60)
        _assert_mesh_contract(sharded)
        single = _run(TOR_XML, n_dev=1, k=k, stop=60)
        twin = _run(TOR_XML, n_dev=8, k=k, stop=60, mode="numpy")
        d = state_digest(sharded.engine)
        assert d == state_digest(single.engine), f"K={k}"
        assert d == state_digest(twin.engine), f"K={k}"


def test_uneven_partition_parity():
    """N % D != 0: 6 circuits over 3 and 5 devices — per-shard padding
    differs per shard and digests still match single-device."""
    single = _star(n_dev=1)
    for n_dev in (3, 5):
        sharded = _run(STAR_XML, n_dev=n_dev)
        assert sharded.engine.device_plane._shard is not None
        assert sharded.engine.device_plane._shard["n_shards"] == n_dev
        assert state_digest(sharded.engine) == state_digest(single.engine)


# -- composition: superwindows, checkpoints, fault drill -------------------

def test_superwindow_halt_flag_psum_across_shards():
    """K=8 on the mesh: superwindows engage (multi-round launches), the
    per-tick completion flag is psum'd so every shard halts at the same
    boundary — pinned by digest parity against K=1 and by the wake times
    all landing inside the run."""
    k8 = _star(n_dev=8, k=8)
    k1 = _star(n_dev=8, k=1)
    st = k8.engine.device_plane.stats()
    assert st["superwindows"] > 0, "superwindows never engaged on the mesh"
    assert st["rounds_per_launch"] > 1.0
    assert st["completed"] == 6
    assert state_digest(k8.engine) == state_digest(k1.engine)
    assert k8.engine.rounds_executed == k1.engine.rounds_executed


def test_checkpoint_resume_mid_superwindow_sharded(tmp_path):
    """--checkpoint-every on a sharded K=8 run: snapshots land on exact
    round boundaries (the superwindow budget stops merges short of every
    cadence point), and --resume replays to a digest-verified boundary
    and finishes bit-identical to the uninterrupted run."""
    d_clean = state_digest(_star(n_dev=8, k=8).engine)
    ckdir = str(tmp_path / "ck")
    _run(STAR_XML, n_dev=8, k=8, checkpoint_every_rounds=30,
         checkpoint_dir=ckdir)
    snaps = sorted(glob.glob(ckdir + "/checkpoint_r*.ckpt"))
    assert snaps, "sharded K=8 run wrote no snapshots"
    resumed = _run(STAR_XML, n_dev=8, k=8, resume_path=ckdir,
                   checkpoint_dir=str(tmp_path / "ck2"))
    assert resumed.engine.supervision.resume_verified
    assert state_digest(resumed.engine) == d_clean


def test_fault_drill_demotes_sharded_plane_to_numpy_twin():
    """--fault-inject device-dispatch:2 on the mesh: the failed in-flight
    dispatch replays on the numpy twin, the backend demotes permanently,
    and the final digest still matches the clean twin run.  The demoted
    windows' cross-shard forwards run HOST-side, so mesh.host_bounces
    goes NONZERO here — the proof that the steady-state == 0 gate is
    falsifiable, not a tautology."""
    dev = _run(STAR_XML, n_dev=8, fault_inject="device-dispatch:2")
    plane = dev.engine.device_plane
    assert plane.demoted and plane.mode == "numpy"
    assert plane.recoveries == 1
    assert dev.engine.supervision.recoveries == 1
    scrape = _mesh_scrape(dev)
    assert scrape["mesh.host_bounces"] > 0, \
        "demoted cross-shard windows must count as host bounces"
    assert scrape["mesh.demoted"] == 1
    twin = _star(n_dev=8, mode="numpy")
    assert state_digest(dev.engine) == state_digest(twin.engine)


# -- tor200 (the acceptance scale point; excluded from tier-1) -------------

@pytest.mark.slow
def test_tor200_parity_sharded_vs_single_vs_serial():
    """The ISSUE 9 acceptance gate at the tor200 scale point: digest
    parity sharded-vs-single-device-vs-serial-schedule at K=1 and K=8
    with on-device cross-shard exchange asserted."""
    xml = workloads.tor_network(200, stoptime=60, device_data=True)
    for k in (1, 8):
        sharded = _run(xml, n_dev=8, k=k, stop=60)
        _assert_mesh_contract(sharded)
        single = _run(xml, n_dev=1, k=k, stop=60)
        serial = _run(xml, n_dev=8, k=k, stop=60, sync=True)
        d = state_digest(sharded.engine)
        assert d == state_digest(single.engine), f"K={k} sharded != single"
        assert d == state_digest(serial.engine), f"K={k} sharded != serial"
