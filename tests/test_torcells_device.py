"""Device-resident onion-relay cell model (ops/torcells_device.py)."""

import numpy as np
import pytest

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


def _gather(tables):
    """The kernel's gather tables for a twin-ordered table tuple (flow_node,
    flow_lat, flow_succ, seg_start, refill, capacity, last_flow)."""
    from shadow_tpu.ops.torcells_device import gather_tables
    return gather_tables(tables[0], tables[2], len(tables[4]))


def _moved_three_ways(cells, refill_cells, cap_cells, ticks=20):
    """flush_moved from the jitted span-flush, its numpy twin and the
    vmapped fleet program, on the same one-chain table."""
    import jax.numpy as jnp

    from shadow_tpu.ops.torcells_device import (
        flush_moved, torcells_step_span_flush_batched,
        torcells_step_window_flush_nodonate,
        torcells_step_window_numpy_flush)
    state, inj, inj_t, tables = _chain(cells, refill_cells, cap_cells)
    gather = _gather(tables)
    targets = np.array([ticks, ticks], np.int64)
    dev = torcells_step_window_flush_nodonate(
        *state, inj, inj_t, targets, np.int64(0), *tables, *gather,
        ring_len=4)
    twin = torcells_step_window_numpy_flush(
        *(np.array(a).copy() for a in state), inj, inj_t, targets,
        np.int64(0), *tables, 4)
    batched = torcells_step_span_flush_batched(
        *(jnp.asarray(np.asarray(a))[None] for a in
          (*state, inj, inj_t, targets, np.int64(0), *tables, *gather)),
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


# -- the scatter-free span-flush tick against the unchanged numpy twin -------

def _tor_table(route, n_nodes, refill_cells, cap_cells, lat=2):
    """build_flows tables for 5-hop ``route`` rows over ``n_nodes`` nodes
    (a node no route names paces no flow), every onward hop ``lat`` ticks,
    buckets given in whole cells per node."""
    from shadow_tpu.ops.torcells_device import build_flows
    route = np.asarray(route, np.int64)
    fl = build_flows(route, np.full((n_nodes, n_nodes), lat, np.int64))
    last_flow = np.array([np.flatnonzero((fl["flow_circ"] == c)
                                         & (fl["flow_succ"] < 0))[0]
                          for c in range(len(route))], np.int64)
    c = CELL_WIRE_BYTES
    tables = (fl["flow_node"], fl["flow_lat"], fl["flow_succ"],
              fl["seg_start"], np.asarray(refill_cells, np.int64) * c,
              np.asarray(cap_cells, np.int64) * c, last_flow)
    return fl, tables


def _zero_state(tables, ring_len=4):
    from shadow_tpu.ops.torcells_device import RING_DTYPE
    f, h = len(tables[0]), len(tables[4])
    return [np.int64(0), np.zeros(f, np.int64),
            np.zeros((ring_len, f), RING_DTYPE), tables[5].copy(),
            np.zeros(f, np.int64), np.zeros(f, np.int64),
            np.full(f, -1, np.int64), np.zeros(h, np.int64)]


def _span_parity(tables, state, inject, inject_target, targets, idle=0,
                 ring_len=4):
    """The jitted span-flush, through its gather tables, against the numpy
    twin from the same state: all ten outputs equal bit for bit.  Returns
    the twin's outputs."""
    from shadow_tpu.ops.torcells_device import (
        torcells_step_window_flush_nodonate, torcells_step_window_numpy_flush)
    args = (np.asarray(inject, np.int64), np.asarray(inject_target, np.int64),
            np.asarray(targets, np.int64), np.int64(idle))
    dev = torcells_step_window_flush_nodonate(
        *state, *args, *tables, *_gather(tables), ring_len=ring_len)
    twin = torcells_step_window_numpy_flush(
        *(np.array(a).copy() for a in state), *args, *tables, ring_len)
    for i in range(10):
        np.testing.assert_array_equal(np.asarray(dev[i]),
                                      np.asarray(twin[i]),
                                      err_msg=f"output {i}")
    return twin


# six circuits over odd nodes 1..25: nodes 0, the evens and 26 pace no flow;
# node 1 paces every circuit's stage 0 and node 25 every stage 4
_SHARED_ROUTE = [[1, 3 + 2 * (k % 2), 7 + 2 * (k % 3), 13 + 2 * k, 25]
                 for k in range(6)]
_EIGHT_SPANS = np.arange(1, 9, dtype=np.int64) * 3


def test_span_parity_empty_nodes_and_a_partial_serve():
    """Node 1 holds 6 x 5 queued cells in one segment and a 7-cell bucket
    refilled by 3: its tokens run out inside the segment (5 cells to the
    first flow, 2 to the second, none after) on the first tick and every
    tick after, so the node total is the bucket, not the backlog.  Half
    the nodes pace no flow at all."""
    n = 27
    refill = np.full(n, 50)
    cap = np.full(n, 100)
    refill[1], cap[1] = 3, 7
    fl, tables = _tor_table(_SHARED_ROUTE, n, refill, cap)
    assert (fl["node_seg"][0] == fl["node_seg"][1]).sum() == 14   # empty
    inject = np.where(fl["flow_stage"] == 0, 5, 0)
    target = np.where(fl["flow_succ"] < 0, 10 ** 9, 0)
    one = _span_parity(tables, _zero_state(tables), inject, target, [1])
    seg = slice(*fl["node_seg"][:, 1])
    assert list(one[1][seg]) == [0, 3, 5, 5, 5, 5]
    assert int(one[7][1]) == 7 * CELL_WIRE_BYTES
    out = _span_parity(tables, _zero_state(tables), inject, target,
                       _EIGHT_SPANS)
    assert int(out[0]) == _EIGHT_SPANS[-1]
    assert int(out[7][1]) == 30 * CELL_WIRE_BYTES


def test_span_parity_prefix_sum_wraps_int32():
    """Backlogs near 2**29 cells on each of six disjoint circuits: the
    int32 prefix sum over all flows wraps past 2**31 (twice over after the
    first hop) while each node's segment total fits, and buckets of 2**28
    cells serve part of each."""
    n = 32
    route = np.arange(30).reshape(6, 5)
    fl, tables = _tor_table(route, n, np.full(n, 2 ** 27),
                            np.full(n, 2 ** 28))
    inject = np.where(fl["flow_stage"] == 0,
                      2 ** 29 + fl["flow_circ"] * 977, 0)
    assert inject.sum() > 2 ** 31
    target = np.where(fl["flow_succ"] < 0, 2 ** 40, 0)
    out = _span_parity(tables, _zero_state(tables), inject, target,
                       np.arange(1, 9, dtype=np.int64) * 2)
    assert int(out[8]) > 2 ** 31                     # cells forwarded


def test_span_parity_idle_ticks_clear_a_stale_ring():
    """idle_ticks > 0 folds refill into half-empty buckets and clears a
    ring full of stale sends before the first tick."""
    n = 27
    fl, tables = _tor_table(_SHARED_ROUTE, n, np.full(n, 4), np.full(n, 9))
    state = _zero_state(tables)
    rng = np.random.default_rng(4)
    state[2] = rng.integers(1, 50, size=state[2].shape).astype(
        state[2].dtype)
    state[3] = tables[5] // 3
    inject = np.where(fl["flow_stage"] == 0, 6, 0)
    target = np.where(fl["flow_succ"] < 0, 10 ** 9, 0)
    out = _span_parity(tables, state, inject, target, _EIGHT_SPANS, idle=5)
    assert int(out[8]) > 0


def test_span_parity_eight_spans_halt_at_a_completion():
    """A K=8 span whose chains complete mid-plan halts at the boundary
    after the first completion, identically in both."""
    n = 27
    fl, tables = _tor_table(_SHARED_ROUTE, n, np.full(n, 40),
                            np.full(n, 80))
    inject = np.where(fl["flow_stage"] == 0, 4, 0)
    target = np.where(fl["flow_succ"] < 0, 4, 0)
    out = _span_parity(tables, _zero_state(tables), inject, target,
                       _EIGHT_SPANS)
    assert int(out[0]) in _EIGHT_SPANS[:-1]
    assert (np.asarray(out[6]) >= 0).any()


def test_fleet_kernel_padded_lanes_match_twin():
    """The vmapped fleet program through a lane's padding: padded flows
    (pred -1, their own empty segments), padded nodes (no segment) and
    three filler lanes, against the twin on the real shapes."""
    from types import SimpleNamespace

    from shadow_tpu.fleet.plane import FleetPlane
    from shadow_tpu.ops.torcells_device import torcells_step_window_numpy_flush
    n = 27
    refill = np.full(n, 6)
    refill[1] = 2
    fl, tables = _tor_table(_SHARED_ROUTE, n, refill, np.full(n, 12))
    plane = SimpleNamespace(
        n_flows=len(tables[0]), n_nodes=n, n_chains=6, superwindow_rounds=8,
        ring_len=4, flow_node=tables[0], flow_lat_steps=tables[1],
        flow_succ=tables[2], seg_start=tables[3], refill_step=tables[4],
        capacity_step=tables[5], last_flow=tables[6],
        flow_pred=fl["flow_pred"], node_seg=fl["node_seg"])
    lane = FleetPlane().lane("padded")
    lane.attach_plane(plane)
    assert lane.cls.f2 > plane.n_flows and lane.cls.h2 > n
    assert (lane._tables[7][plane.n_flows:] == -1).all()
    lane.cls.width = 4              # a class that once ran four lanes
    state = _zero_state(tables)
    inject = np.where(fl["flow_stage"] == 0, 7, 0)
    target = np.where(fl["flow_succ"] < 0, 7, 0)
    got = lane.dispatch(state, inject, target, _EIGHT_SPANS, 0)
    want = torcells_step_window_numpy_flush(
        *(np.array(a).copy() for a in state), inject, target, _EIGHT_SPANS,
        np.int64(0), *tables, 4)
    for i in range(10):
        np.testing.assert_array_equal(np.asarray(got[i]),
                                      np.asarray(want[i]),
                                      err_msg=f"output {i}")
    assert int(want[8]) > 0


def _while_body(text: str) -> str:
    """The ``do`` region of the tick loop in ``text``: of its
    ``stablehlo.while`` loops, the one whose body writes a ring row."""
    bodies = []
    at = text.find("stablehlo.while")
    while at >= 0:
        start = text.index("} do {", at) + 5
        depth = 0
        for j in range(start, len(text)):
            depth += {"{": 1, "}": -1}.get(text[j], 0)
            if depth == 0:
                bodies.append(text[start:j + 1])
                break
        at = text.find("stablehlo.while", at + 1)
    loops = [b for b in bodies if "dynamic_update_slice" in b]
    assert len(loops) == 1, f"{len(loops)} tick loops"
    return loops[0]


def test_span_flush_tick_loop_has_no_scatter():
    """No scatter inside the tick loop: on TPU a scatter-add's updates run
    one after another (the successor send and the per-node byte total
    took 188 ms of a 225 ms tick at 890k flows on v5e).  The flush
    pack's scatters run once per dispatch, after the loop."""
    import jax

    from shadow_tpu.ops.torcells_device import _step_span_flush_impl
    n = 27
    fl, tables = _tor_table(_SHARED_ROUTE, n, np.full(n, 4), np.full(n, 9))
    state = _zero_state(tables)
    z = np.zeros(len(tables[0]), np.int64)
    text = jax.jit(_step_span_flush_impl, static_argnames=("ring_len",)) \
        .lower(*state, z, z, _EIGHT_SPANS, np.int64(0), *tables,
               *_gather(tables), ring_len=4).as_text()
    body = _while_body(text)                          # writes the ring's row
    assert "scatter" not in body
    assert "scatter" in text.replace(body, "")       # the flush pack's


# -- the compacted span-flush: the live flows alone, bit for bit ------------

def _live(fl, tables, circuits, width, inject, inject_target):
    """The compacted program's live table for ``circuits`` of a build_flows
    layout: their flow positions, ascending and padded with F, with the
    injections there (none may fall elsewhere), and each flow's chain (the
    circuit whose exit flow is ``last_flow[chain]``)."""
    f = len(fl["flow_node"])
    last_flow = tables[6]
    on = np.isin(fl["flow_circ"], circuits)
    assert not (np.asarray(inject)[~on].any()
                or np.asarray(inject_target)[~on].any())
    pos = np.flatnonzero(on)
    chain_of_circ = np.empty(len(last_flow), np.int64)
    chain_of_circ[fl["flow_circ"][last_flow]] = np.arange(len(last_flow))
    live = np.zeros((4, width), np.int64)
    live[0] = f
    live[0, :len(pos)] = pos
    live[1, :len(pos)] = np.asarray(inject)[pos]
    live[2, :len(pos)] = np.asarray(inject_target)[pos]
    live[3, :len(pos)] = chain_of_circ[fl["flow_circ"][pos]]
    return live


def _same_flush(got, want, got_sizes, want_sizes):
    """Every field parse_flush reads, in order: the header values, the
    chain ids and done steps, the node ids and deltas."""
    from shadow_tpu.ops.torcells_device import flush_moved, parse_flush
    a = parse_flush(np.asarray(got), *got_sizes)
    b = parse_flush(np.asarray(want), *want_sizes)
    assert a[:3] == b[:3]
    assert flush_moved(np.asarray(got)) == flush_moved(np.asarray(want))
    for i in range(3, 7):
        np.testing.assert_array_equal(a[i], b[i], err_msg=f"field {i}")


def _compact_parity(fl, tables, state, inject, inject_target, targets,
                    circuits, idle=0, width=32, ring_len=4):
    """The compacted span-flush over ``circuits`` against the full-width
    program and the numpy twin (_span_parity) from the same state: the
    nine state outputs equal bit for bit, the ring included, and the
    flush, packed at (width, width), reads as the full one at (C, H).
    Returns the twin's outputs."""
    from shadow_tpu.ops.torcells_device import (
        flush_len, torcells_step_compact_flush_nodonate)
    twin = _span_parity(tables, state, inject, inject_target, targets, idle,
                        ring_len)
    comp = torcells_step_compact_flush_nodonate(
        *state, _live(fl, tables, circuits, width, inject, inject_target),
        np.asarray(targets, np.int64), np.int64(idle), *tables[:6],
        fl["flow_pred"], ring_len=ring_len)
    for i in range(9):
        np.testing.assert_array_equal(np.asarray(comp[i]),
                                      np.asarray(twin[i]),
                                      err_msg=f"output {i}")
    assert np.asarray(comp[9]).shape == (flush_len(width, width),)
    _same_flush(comp[9], twin[9], (width, width),
                (len(tables[6]), len(tables[4])))
    return twin


def _injected(fl, circuits, cells):
    on = np.isin(fl["flow_circ"], circuits)
    return (np.where(on & (fl["flow_stage"] == 0), cells, 0),
            np.where(on & (fl["flow_succ"] < 0), cells, 0))


def _after(out):
    """The carried state a flush program's outputs leave for the next."""
    return [np.asarray(a).copy() for a in out[:8]]


def test_compact_parity_live_and_quiet_flows_share_segments():
    """All six circuits run to completion, then three of them again: node
    1 paces every circuit's first stage and node 25 every last, so live
    and quiet flows share those segments.  Node 1's bucket, half full and
    refilled by 3, holds 6 cells: in greedy order the first live flow
    takes its 5, the second 1, the third none.  The quiet flows keep
    their columns and their nodes refill as the full program's do."""
    n = 27
    refill, cap = np.full(n, 50), np.full(n, 100)
    refill[1], cap[1] = 3, 7
    fl, tables = _tor_table(_SHARED_ROUTE, n, refill, cap)
    inject, target = _injected(fl, range(6), 4)
    first = _span_parity(tables, _zero_state(tables), inject, target,
                         [40, 40])
    assert (np.asarray(first[6])[fl["flow_succ"] < 0] >= 0).all()
    state = _after(first)
    state[3] = state[3] // 2               # buckets below capacity
    inject, target = _injected(fl, [1, 3, 4], 5)
    one = _compact_parity(fl, tables, state, inject, target, [41],
                          [1, 3, 4])
    seg = slice(*fl["node_seg"][:, 1])
    served = (state[1] + inject - one[1])[seg]
    assert list(served) == [0, 5, 0, 1, 0, 0]
    out = _compact_parity(fl, tables, state, inject, target, _EIGHT_SPANS + 40,
                          [1, 3, 4])
    assert int(out[8]) > 0


@pytest.mark.parametrize("gap", [1, 2, 3, 4])
def test_compact_parity_chain_injected_again_after_leaving(gap):
    """The stale-ring case: circuit 2 completes, a dispatch steps the other
    live circuit alone for ``gap`` - 1 ticks, and circuit 2 is injected
    again ``gap`` ticks after it left, with its ring columns holding its
    last run's sends.  Every dispatch matches the full-width program and
    the twin, the ring included."""
    n = 27
    fl, tables = _tor_table(_SHARED_ROUTE, n, np.full(n, 40),
                            np.full(n, 80))
    inj2, tgt2 = _injected(fl, [2], 3)
    inj5, tgt5 = _injected(fl, [5], 400)
    out = _compact_parity(fl, tables, _zero_state(tables), inj2 + inj5,
                          tgt2 + tgt5, np.arange(1, 9) * 3, [2, 5])
    t = int(out[0])
    assert np.asarray(out[6])[fl["flow_succ"] < 0].max() >= 0   # 2 is done
    assert np.asarray(out[2]).any()                 # sends left in the ring
    state = _after(out)
    zero = np.zeros_like(inj2)
    if gap > 1:
        state = _after(_compact_parity(fl, tables, state, zero, zero,
                                       [t + gap - 1], [5]))
    out = _compact_parity(fl, tables, state, inj2, tgt2,
                          np.arange(1, 9) * 3 + t + gap - 1, [2, 5])
    assert int(out[8]) > 0


def test_compact_parity_halts_mid_span():
    """A K=8 span halting at the boundary after a completion: the quiet
    nodes' buckets, a third full, refill in closed form over the ticks
    actually run, not the plan's."""
    n = 27
    fl, tables = _tor_table(_SHARED_ROUTE, n, np.full(n, 4), np.full(n, 80))
    state = _zero_state(tables)
    state[3] = tables[5] // 3
    inject, target = _injected(fl, [0, 4], 4)
    out = _compact_parity(fl, tables, state, inject, target, _EIGHT_SPANS,
                          [0, 4])
    assert int(out[0]) in _EIGHT_SPANS[:-1]
    assert (np.asarray(out[3]) < tables[5]).any()   # refill not yet capped


def test_compact_parity_idle_ticks_between_dispatches():
    """Banked idle ticks fold into every bucket and clear a ring full of
    stale sends before the compacted loop's first tick."""
    n = 27
    fl, tables = _tor_table(_SHARED_ROUTE, n, np.full(n, 4), np.full(n, 9))
    state = _zero_state(tables)
    rng = np.random.default_rng(4)
    state[2] = rng.integers(1, 50, size=state[2].shape).astype(
        state[2].dtype)
    state[3] = tables[5] // 3
    inject, target = _injected(fl, [1, 2], 6)
    out = _compact_parity(fl, tables, state, inject, target, _EIGHT_SPANS,
                          [1, 2], idle=5)
    assert int(out[8]) > 0


def test_compact_parity_fills_both_flush_sections():
    """Twelve one-hop chains on nodes 12..1, each its own node: eight are
    live in a width-8 dispatch, so every live slot is a real flow, all
    eight complete and all eight nodes send, filling both sections of the
    (8, 8) flush.  The live flows sit in node order, the reverse of chain
    order, so the chain section is put back in ascending chain id."""
    n = 14
    route = np.arange(12, 0, -1).reshape(12, 1)
    fl, tables = _tor_table(route, n, np.full(n, 10), np.full(n, 20))
    live = [0, 2, 3, 5, 6, 8, 9, 11]
    inject, target = _injected(fl, live, 3)
    out = _compact_parity(fl, tables, _zero_state(tables), inject, target,
                          [1], live, width=8)
    from shadow_tpu.ops.torcells_device import parse_flush
    _, _, _, chains, steps, nodes, deltas = parse_flush(out[9], 12, n)
    assert list(chains) == live and list(steps) == [0] * 8
    assert list(nodes) == sorted(12 - c for c in live)
    assert list(deltas) == [3 * CELL_WIRE_BYTES] * 8
    assert int(out[8]) == 24


def test_compact_flush_is_packed_from_live_sizes_without_scatter():
    """The compacted program's flush is flush_len(K, K) long, and nothing
    it reads comes from a scatter: the program cut down to its flush
    output lowers with no scatter, while the whole program keeps the
    K-long write-back scatters of its state."""
    import jax

    from shadow_tpu.ops.torcells_device import (_compact_step_span_flush_impl,
                                                flush_len)
    n = 127
    route = np.arange(25 * 5).reshape(25, 5)
    fl, tables = _tor_table(route, n, np.full(n, 4), np.full(n, 9))
    inject, target = _injected(fl, [3, 7], 2)
    args = (*_zero_state(tables), _live(fl, tables, [3, 7], 16, inject,
                                        target),
            _EIGHT_SPANS, np.int64(0), *tables[:6], fl["flow_pred"])

    def step(*a):
        return _compact_step_span_flush_impl(*a, ring_len=4)

    assert jax.eval_shape(step, *args)[9].shape == (flush_len(16, 16),)
    whole = jax.jit(step).lower(*args).as_text()
    flush_only = jax.jit(lambda *a: step(*a)[9]).lower(*args).as_text()
    assert "scatter" in whole
    assert "scatter" not in flush_only
    assert "stablehlo.sort" in flush_only


def test_compact_tick_loop_holds_nothing_table_wide():
    """The compacted program's tick loop works on the live flows and their
    nodes alone: no operand in its body is as long as the flow table or
    the node table, and it scatters nothing."""
    import re

    import jax

    from shadow_tpu.ops.torcells_device import _compact_step_span_flush_impl
    n = 127
    route = np.arange(25 * 5).reshape(25, 5)
    fl, tables = _tor_table(route, n, np.full(n, 4), np.full(n, 9))
    f = len(tables[0])
    state = _zero_state(tables)
    inject, target = _injected(fl, [3], 2)
    text = jax.jit(_compact_step_span_flush_impl,
                   static_argnames=("ring_len",)) \
        .lower(*state, _live(fl, tables, [3], 16, inject, target),
               _EIGHT_SPANS, np.int64(0), *tables[:6], fl["flow_pred"],
               ring_len=4).as_text()
    body = _while_body(text)
    assert "tensor<16x" in body
    wide = re.findall(r"tensor<(?:\d+x)*(?:%d|%d)x" % (f, n), body)
    assert not wide, wide[:3]
    assert "scatter" not in body
    assert re.search(r"tensor<(?:\d+x)*%dx" % f, text)   # outside it


@pytest.mark.parametrize("m", [1, 4, 66, CELL_WIRE_BYTES, 65535])
def test_floor_div_small_is_exact_over_int64(m):
    """The tick's 32-bit long division by a small constant equals numpy's
    int64 floor division across the whole range, negatives included."""
    import jax

    from shadow_tpu.ops.torcells_device import _floor_div_small, _rem_small
    rng = np.random.default_rng(m)
    x = np.concatenate([
        rng.integers(-2 ** 63, 2 ** 63 - 1, 20_000, dtype=np.int64),
        rng.integers(-10 ** 6, 10 ** 6, 20_000, dtype=np.int64),
        np.array([0, 1, -1, m - 1, m, m + 1, -m, -m - 1, 2 ** 32 - 1,
                  2 ** 32, 2 ** 63 - 1, -2 ** 63 + 1], np.int64)])
    q = np.asarray(jax.jit(lambda v: _floor_div_small(v, m))(x))
    np.testing.assert_array_equal(q, x // m)
    small = x[np.abs(x) < 2 ** 62]
    r = np.asarray(jax.jit(lambda v: _rem_small(v, m))(small))
    np.testing.assert_array_equal(r, small % m)


def test_flush_halves_round_trip():
    """The flush's int32 halves give back every int64 word on the host."""
    from shadow_tpu.ops.torcells_device import flush_from_halves, flush_halves
    x = np.random.default_rng(3).integers(-2 ** 63, 2 ** 63 - 1, 1_001,
                                          dtype=np.int64)
    halves = np.asarray(flush_halves(x))
    assert halves.dtype == np.int32 and halves.shape == (1_001, 2)
    np.testing.assert_array_equal(flush_from_halves(halves), x)


@pytest.mark.parametrize("n_flows,widths", [
    (890_000, (4096, 65536)),
    (100_000, (1024, 8192)),
    (2_000, (1024,)),
    (1_024, ()),
])
def test_compact_widths_follow_the_table(n_flows, widths):
    from shadow_tpu.ops.torcells_device import compact_widths
    assert compact_widths(n_flows) == widths


@pytest.mark.parametrize("flow_node,flow_succ,why", [
    ([0, 0, 1], [2, 2, -1], "not injective"),
    ([0, 1, 0], [1, 2, -1], "contiguous"),
])
def test_gather_tables_refuse_a_layout_the_kernel_cannot_read(
        flow_node, flow_succ, why):
    from shadow_tpu.ops.torcells_device import gather_tables
    with pytest.raises(ValueError, match=why):
        gather_tables(np.array(flow_node), np.array(flow_succ), 2)
