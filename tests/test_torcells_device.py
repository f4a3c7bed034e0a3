"""Device-resident onion-relay cell model (ops/torcells_device.py)."""

import numpy as np

from shadow_tpu.ops.torcells_device import (CELL_WIRE_BYTES, DeviceTorCells,
                                            bucket_params)


def test_device_matches_numpy_twin():
    m = DeviceTorCells(n_relays=20, n_circuits=60, seed=3,
                       relay_bw_kibps=512)
    d_dev, t_dev, f_dev = m.run_device(40, 40_000)
    d_np, t_np, f_np = m.run_numpy(40, 40_000)
    assert np.array_equal(d_dev, d_np)
    assert t_dev == t_np and f_dev == f_np


def test_cell_conservation_and_hops():
    """Every injected cell is delivered exactly once at its own client,
    and each traversed exactly 5 stages (server, e, m, g uplinks + client
    delivery counts as the 5th serve)."""
    c, per = 60, 40
    m = DeviceTorCells(n_relays=20, n_circuits=c, seed=3,
                       relay_bw_kibps=512)
    delivered, ticks, forwards = m.run_device(per, 40_000)
    st = m.flows["flow_stage"]
    circ = m.flows["flow_circ"]
    last = delivered[st == 4]
    assert last.sum() == c * per, "cells lost or duplicated"
    per_circ = np.zeros(c, dtype=np.int64)
    np.add.at(per_circ, circ[st == 4], delivered[st == 4])
    assert (per_circ == per).all(), "a circuit lost cells"
    assert forwards == c * per * 5
    assert ticks < 40_000, "did not converge"


def test_contention_slows_shared_relays():
    """Circuits sharing starved relays take longer than an uncontended
    run — bandwidth contention is real, not decorative."""
    fat = DeviceTorCells(n_relays=8, n_circuits=40, seed=5,
                         relay_bw_kibps=1 << 20)
    thin = DeviceTorCells(n_relays=8, n_circuits=40, seed=5,
                          relay_bw_kibps=256)
    _d1, t_fat, _ = fat.run_device(50, 200_000)
    _d2, t_thin, _ = thin.run_device(50, 200_000)
    assert t_thin > t_fat * 2, (t_thin, t_fat)
    # closed-form floor: 8 relays x 256 KiB/s must move 40*50*3 relay
    # serves of 552 B; the thin run cannot beat the aggregate-bandwidth
    # bound even with perfect pipelining
    total_relay_bytes = 40 * 50 * 3 * CELL_WIRE_BYTES
    refill, _cap = bucket_params(np.full(8, 256))
    floor_ticks = total_relay_bytes // int(refill.sum() + 1)
    assert t_thin >= floor_ticks // 2


# -- the flush header's moved (flow, tick) count ------------------------------

def _chain(cells, refill_cells, cap_cells):
    """A one-chain, two-stage table: flow 0 paced by node 0 forwards to
    flow 1 (node 1) two ticks later; ``cells`` enter at flow 0."""
    from shadow_tpu.ops.torcells_device import RING_DTYPE
    c = CELL_WIRE_BYTES
    state = (np.int64(0), np.zeros(2, np.int64),
             np.zeros((4, 2), RING_DTYPE),
             np.array(cap_cells, np.int64) * c, np.zeros(2, np.int64),
             np.zeros(2, np.int64), np.full(2, -1, np.int64),
             np.zeros(2, np.int64))
    inject = np.array([cells, 0], np.int64)
    inject_target = np.array([0, cells], np.int64)
    tables = (np.array([0, 1], np.int64), np.array([2, 0], np.int64),
              np.array([1, -1], np.int64), np.array([0, 1], np.int64),
              np.array(refill_cells, np.int64) * c,
              np.array(cap_cells, np.int64) * c, np.array([1], np.int64))
    return state, inject, inject_target, tables


def _moved_three_ways(cells, refill_cells, cap_cells, ticks=20):
    """flush_moved from the jitted span-flush, its numpy twin and the
    vmapped fleet program, on the same one-chain table."""
    import jax.numpy as jnp

    from shadow_tpu.ops.torcells_device import (
        flush_moved, torcells_step_span_flush_batched,
        torcells_step_window_flush_nodonate,
        torcells_step_window_numpy_flush)
    state, inj, inj_t, tables = _chain(cells, refill_cells, cap_cells)
    targets = np.array([ticks, ticks], np.int64)
    dev = torcells_step_window_flush_nodonate(
        *state, inj, inj_t, targets, np.int64(0), *tables, ring_len=4)
    twin = torcells_step_window_numpy_flush(
        *(np.array(a).copy() for a in state), inj, inj_t, targets,
        np.int64(0), *tables, 4)
    batched = torcells_step_span_flush_batched(
        *(jnp.asarray(np.asarray(a))[None] for a in
          (*state, inj, inj_t, targets, np.int64(0), *tables)),
        ring_len=4)
    out = [flush_moved(np.asarray(dev[9])), flush_moved(twin[9]),
           flush_moved(np.asarray(batched[9])[0])]
    assert int(np.asarray(dev[4])[1]) == cells     # every cell delivered
    return out


def test_flow_ticks_moved_uncontended_hand_count():
    """Buckets far above the batch: each stage moves all its cells in one
    tick, so the chain's two flows move on one tick each."""
    assert _moved_three_ways(20, [100, 100], [200, 200]) == [2, 2, 2]


def test_flow_ticks_moved_contended_hand_count():
    """A bucket runs short.  Node 0 (cap 8, refill 4 cells) serves 20
    cells on ticks 0-3: 8, 4, 4, 4.  Node 1 (cap 6, refill 3) receives
    them two ticks later and serves 6, 3, 3, 3, 3, 2 on ticks 2-7.  So
    4 + 6 = 10 moved (flow, tick) pairs."""
    assert _moved_three_ways(20, [4, 3], [8, 6]) == [10, 10, 10]
