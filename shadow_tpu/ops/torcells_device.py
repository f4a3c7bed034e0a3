"""Device-resident onion-relay cell forwarding: the flagship workload's
traffic pattern with ALL state in HBM.

apps/tor.py models Tor's network behavior through the full engine (cells,
circuits, streams over the userspace TCP stack).  This module is the
device-resident counterpart for the dominant traffic term — bulk cell
delivery server→exit→middle→guard→client across circuits that CONTEND for
shared relay bandwidth — composing the three north-star kernels in one
``lax.while_loop`` tick loop (_span_loop):

* per-edge latency (cells in flight live in a [L, F] ring buffer indexed
  by arrival tick — the device analog of the delivery event queue);
* per-node token buckets (1 ms refill ticks, byte capacities from the same
  ``bucket_params`` the engine's interfaces use);
* bandwidth allocation across circuits sharing a relay: exact greedy in
  circuit-id order via STATIC segment cumsums — flows are grouped by
  receiving node at build time, so the per-tick allocation is one cumsum +
  two gathers, no sorting and no data-dependent shapes.

The tick loop runs in one family of span-flush programs: the full-width
step, the same step over a dispatch's live flows (compacted), and the
fleet's vmapped step, each returning the packed flush.  The numbers this
produces are honest about what they are: a model workload (no TCP control
loop, no cell crypto) showing the architecture's throughput when the host
is out of the per-event path.  Correctness gates: a bit-identical numpy
twin (torcells_step_span_numpy) and cell conservation (every injected
cell is delivered exactly once) in tests/test_torcells_device.py.

Shapes: C circuits × 5 stages = F flows.  Stage s of circuit c is paced by
node route[c, s] (route = [server, exit, middle, guard, client]); a cell
leaving stage s<4 arrives at stage s+1 after latency_ticks[node_s,
node_{s+1}]; leaving stage 4 means delivered.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..core import defs
from .bandwidth import bucket_params

CELL_WIRE_BYTES = 512 + defs.CONFIG_HEADER_SIZE_TCPIPETH

# Arrival-ring element dtype for the execution plane: per-step per-flow cell
# counts (bounded by bucket capacity / cell size — a 10 Gbit/s host at a
# 100 ms granule is ~230k cells, nowhere near 2**31).  int32 halves the
# [ring_len, F] state bytes, which is the fixed per-dispatch copy cost on
# backends where the carried state cannot alias (PJRT CPU).
RING_DTYPE = np.int32

# Bound on the cells a plane may hold at once: segment_greedy's prefix
# sums run in int32, exact while every node's backlog fits in it.
# DeviceTrafficPlane refuses an injection that could exceed it.
MAX_CELLS_IN_FLIGHT = 2 ** 31 - 1


def build_flows(route: np.ndarray,          # int32 [C, 5] node per stage
                latency_ticks: np.ndarray,  # int64 [H, H]
                ) -> dict:
    """Precompute the static flow layout: flows sorted by (paced node,
    circuit id), segment offsets per node, and each flow's onward hop
    latency.  Pure numpy; runs once at model build."""
    c, stages = route.shape
    flow_circ = np.repeat(np.arange(c, dtype=np.int64), stages)
    flow_stage = np.tile(np.arange(stages, dtype=np.int64), c)
    flow_node = route[flow_circ, flow_stage].astype(np.int64)
    # greedy allocation order: by paced node, then circuit id (a node never
    # paces two stages of the same circuit: servers/relays/clients occupy
    # disjoint node ranges and relay picks are distinct).  Onward latencies
    # are >= 1 tick, so a cell can never traverse two stages in one tick —
    # matching the engine, where a forwarded cell is a new arrival event.
    order = np.lexsort((flow_stage, flow_circ, flow_node))
    flow_circ, flow_stage, flow_node = (flow_circ[order], flow_stage[order],
                                        flow_node[order])
    # onward latency: stage s -> s+1 edge; last stage delivers (0)
    nxt = np.where(flow_stage < stages - 1,
                   route[flow_circ, np.minimum(flow_stage + 1, stages - 1)],
                   route[flow_circ, flow_stage])
    lat = latency_ticks[flow_node, nxt].astype(np.int64)
    lat = np.where(flow_stage < stages - 1, np.maximum(lat, 1), 0)
    # successor flow index (same circuit, next stage) in sorted space
    flat_id = flow_circ * stages + flow_stage
    pos_of = np.empty(c * stages, dtype=np.int64)
    pos_of[flat_id] = np.arange(c * stages)
    succ = np.where(flow_stage < stages - 1,
                    pos_of[np.minimum(flat_id + 1, c * stages - 1)], -1)
    # segment start offset of each flow's node group (for the cumsum trick)
    seg_start_of_flow = np.zeros(c * stages, dtype=np.int64)
    starts = np.flatnonzero(np.r_[True, flow_node[1:] != flow_node[:-1]])
    seg_id = np.cumsum(np.r_[0, (flow_node[1:] != flow_node[:-1])
                             .astype(np.int64)])
    seg_start_of_flow = starts[seg_id]
    flow_pred, node_seg = gather_tables(flow_node, succ,
                                        latency_ticks.shape[0])
    return {
        "flow_circ": flow_circ, "flow_stage": flow_stage,
        "flow_node": flow_node, "flow_lat": lat, "flow_succ": succ,
        "seg_start": seg_start_of_flow, "flow_pred": flow_pred,
        "node_seg": node_seg,
    }


def gather_tables(flow_node: np.ndarray, flow_succ: np.ndarray,
                  n_nodes: int) -> Tuple[np.ndarray, np.ndarray]:
    """The static tables the span-flush tick reads by gather where it
    would otherwise scatter, built once on the host from the flow layout:

    * ``flow_pred`` int64 [F]: the flow whose successor is this one, or -1
      (the inverse of the injective ``flow_succ``);
    * ``node_seg`` int64 [2, H]: each node's flow segment as [first, end)
      positions, [0, 0) for a node that paces no flow.

    Refuses a layout in which a node's flows are not one contiguous
    segment or two flows share a successor: the kernel's node totals and
    successor sends would then be wrong, not slow."""
    flow_node = np.asarray(flow_node, dtype=np.int64)
    flow_succ = np.asarray(flow_succ, dtype=np.int64)
    f = len(flow_node)
    has_succ = np.flatnonzero(flow_succ >= 0)
    flow_pred = np.full(f, -1, dtype=np.int64)
    flow_pred[flow_succ[has_succ]] = has_succ
    if np.count_nonzero(flow_pred >= 0) != len(has_succ):
        raise ValueError("flow_succ is not injective")
    starts = np.flatnonzero(np.r_[True, flow_node[1:] != flow_node[:-1]])
    nodes = flow_node[starts]
    if len(np.unique(nodes)) != len(nodes):
        raise ValueError("a node's flows are not one contiguous segment")
    node_seg = np.zeros((2, n_nodes), dtype=np.int64)
    node_seg[0, nodes] = starts
    node_seg[1, nodes] = np.r_[starts[1:], f]
    return flow_pred, node_seg


from functools import partial


def segment_greedy(queued, cap_cells, seg_start):
    """Exact greedy allocation in static flow order within each node
    segment: served = clip(capacity_at_segment - cells_before_me, 0,
    queued), the cells before a flow being a segment-relative prefix sum.

    The prefix sum runs in int32.  An int64 ``cumsum`` lowers on TPU to a
    reduce-window over a u32 pair, which the v5e compiler refuses inside
    the tick loop's ``while_loop`` body for want of VMEM at most widths
    (ISSUE 21: "Scoped allocation with size 19.10M and limit 16.00M" at
    F = 1k, 25k and 50k flows).  Two's-complement wraparound keeps the
    segment-relative difference exact whenever its true value fits in
    int32; it is at most the cells queued at one node, which the plane
    keeps below MAX_CELLS_IN_FLIGHT."""
    return _segment_greedy(queued, cap_cells, seg_start)[0]


def _prefix_before(csum, pos):
    """The int32 prefix total of the flows before position ``pos``."""
    return jnp.where(pos > 0, csum[jnp.maximum(pos - 1, 0)], jnp.int32(0))


def _segment_greedy(queued, cap_cells, seg_start):
    """segment_greedy's (served, int32 prefix sum)."""
    q32 = queued.astype(jnp.int32)
    csum = jnp.cumsum(q32)
    before = (csum - q32 - _prefix_before(csum, seg_start)).astype(jnp.int64)
    return jnp.clip(cap_cells - before, 0, queued), csum


def _floor_div_small(x, m: int):
    """``x // m`` for int64 ``x`` and a static ``0 < m < 2**16``, exact, in
    32-bit divisions: base-2**16 long division of ``|x|``'s 32-bit halves.
    XLA lowers an int64 division or remainder on TPU to a bitwise long
    division, ~500 KB of HLO each, and the runtime keeps every loaded
    program's HLO in host memory."""
    neg = x < 0
    ax = jnp.where(neg, -x - 1, x)        # x // m == -((-x - 1) // m) - 1
    hi = (ax >> 32).astype(jnp.uint32)
    lo = (ax & 0xFFFFFFFF).astype(jnp.uint32)
    m32 = jnp.uint32(m)
    z1 = (hi % m32 << 16) | (lo >> 16)
    z0 = (z1 % m32 << 16) | (lo & 0xFFFF)
    q = ((hi // m32).astype(jnp.int64) << 32
         | (z1 // m32).astype(jnp.int64) << 16
         | (z0 // m32).astype(jnp.int64))
    return jnp.where(neg, -q - 1, q)


def _rem_small(x, m: int):
    """``x mod m`` (floor) as int32, for int64 ``x`` and m as above."""
    return (x - m * _floor_div_small(x, m)).astype(jnp.int32)


def segment_greedy_totals(queued, node_cap, flow_node, seg_start, node_seg):
    """segment_greedy with ``cap_cells = node_cap[flow_node]``, plus the
    cells each node served, without a scatter.

    The greedy serves a node's segment in order until its capacity runs
    out, so for ``node_cap >= 0`` and ``queued >= 0`` the node's total is
    ``min(node_cap, segment's queued total)``, and that total is the
    difference of the same int32 prefix sum at the segment's two ends
    (``node_seg``, see gather_tables), exact under the same bound as the
    per-flow greedy.  A node with no flows has an empty segment and
    totals 0.  Returns (served [F], node_cells [H])."""
    served, csum = _segment_greedy(queued, node_cap[flow_node], seg_start)
    seg_total = (_prefix_before(csum, node_seg[1])
                 - _prefix_before(csum, node_seg[0])).astype(jnp.int64)
    return served, jnp.minimum(jnp.maximum(node_cap, 0), seg_total)


# ---------------------------------------------------------------------------
# Packed flush buffer: the dispatch's ENTIRE host-facing summary in one
# int64 vector, so collect is ONE device->host transfer instead of four
# (delivered + done_tick + node_sent + forwards).  A device-side cursor
# packs each section to its front: only chains that completed THIS window
# and only nodes whose sent-byte counter moved occupy slots, and the header
# carries how many.
#
# Layout ([6 + 2C + 2H] int64, C = chains, H = nodes):
#   [0] forwards this window
#   [1] cumulative delivered cells summed over chain-exit flows
#   [2] n_done   — chains newly completed this window
#   [3] n_nodes  — nodes with a nonzero sent-byte delta this window
#   [4] t_stop   — the absolute step the kernel actually advanced to (the
#                  final target, or an earlier sub-window boundary when the
#                  superwindow loop halted at a completion — see
#                  _step_span_impl); carried in the flush so the host never
#                  pays a second device read to learn where a multi-round
#                  dispatch stopped
#   [5] moved    — (flow, tick) pairs this window in which a flow moved at
#                  least one cell: the kernel's useful work, counted the
#                  same way whatever implements the tick (flush_moved)
#   [6        : 6+n_done]        newly-done chain indices (ascending)
#   [6+C      : 6+C+n_done]      their completion steps
#   [6+2C     : 6+2C+n_nodes]    touched node indices (ascending)
#   [6+2C+H   : 6+2C+H+n_nodes]  their sent-byte deltas
#
# C and H are the section capacities.  The full-width program, the fleet's
# vmapped program, the mesh program and the numpy twin pack at (C, H) =
# (chains, nodes) by a cursor scatter (_pack_flush_jnp / pack_flush_np).
# The compacted program packs at (K, K), K its width (_pack_live_flush):
# only its live chains can complete and only its live nodes can send, and
# it holds at most K of each.  The reader passes the capacities of the
# buffer it read (parse_flush).
# ---------------------------------------------------------------------------

FLUSH_HEADER = 6


def flush_len(n_chains: int, n_nodes: int) -> int:
    """Packed flush buffer length."""
    return FLUSH_HEADER + 2 * n_chains + 2 * n_nodes


def _pack_flush_jnp(forwards, delivered_sum, t_stop, newly, done_last,
                    sent_delta, moved=0):
    """newly bool [C], done_last int64 [C], sent_delta int64 [H] -> packed
    buffer.  Compaction is a cumsum-cursor scatter; out-of-range slots (the
    unselected lanes) are dropped on device."""
    c = newly.shape[0]
    h = sent_delta.shape[0]
    length = flush_len(c, h)
    touched = sent_delta != 0
    # int32 cursors: a count of chains or nodes is far below 2**31
    pos_c = jnp.cumsum(newly.astype(jnp.int32)) - 1
    pos_h = jnp.cumsum(touched.astype(jnp.int32)) - 1
    oob = jnp.int64(length)
    # a slot past its section's end is dropped, never written into the next
    sel_c = newly & (pos_c < c)
    sel_h = touched & (pos_h < h)
    buf = jnp.zeros(length, jnp.int64)
    buf = buf.at[0].set(forwards)
    buf = buf.at[1].set(delivered_sum)
    buf = buf.at[2].set(jnp.sum(newly.astype(jnp.int64)))
    buf = buf.at[3].set(jnp.sum(touched.astype(jnp.int64)))
    buf = buf.at[4].set(t_stop)
    buf = buf.at[5].set(moved)
    base = jnp.int64(FLUSH_HEADER)
    buf = buf.at[jnp.where(sel_c, base + pos_c, oob)].set(
        jnp.arange(c, dtype=jnp.int64), mode="drop")
    buf = buf.at[jnp.where(sel_c, base + c + pos_c, oob)].set(
        done_last, mode="drop")
    buf = buf.at[jnp.where(sel_h, base + 2 * c + pos_h, oob)].set(
        jnp.arange(h, dtype=jnp.int64), mode="drop")
    buf = buf.at[jnp.where(sel_h, base + 2 * c + h + pos_h, oob)].set(
        sent_delta, mode="drop")
    return buf


def _pack_live_flush(forwards, delivered_sum, t_stop, moved, chain, newly,
                     done_last, node, touched, sent_delta):
    """The packed flush at section capacities (K, K) from K-long candidates
    alone: ``chain``/``newly``/``done_last`` over the live flows (a chain
    id, whether it newly completed, its completion step), ``node``/
    ``touched``/``sent_delta`` over the live node slots.  The same layout
    and order as _pack_flush_jnp: each section is compacted by one sort on
    an int32 key, the selected ids ascending ahead of the rest, which
    carries the slot each came from; no scatter.  Slots past a section's
    count read 0, as in the full-length pack."""
    big = jnp.iinfo(jnp.int32).max
    slot = jnp.arange(chain.shape[0], dtype=jnp.int32)

    def section(sel, ids, vals):
        key, at = jax.lax.sort((jnp.where(sel, ids.astype(jnp.int32), big),
                                slot), num_keys=1)
        hit = key < big
        return (jnp.where(hit, key, 0).astype(jnp.int64),
                jnp.where(hit, vals[at], 0))

    header = jnp.stack([
        jnp.asarray(v, jnp.int64) for v in (
            forwards, delivered_sum, jnp.sum(newly.astype(jnp.int64)),
            jnp.sum(touched.astype(jnp.int64)), t_stop, moved)])
    return jnp.concatenate([header, *section(newly, chain, done_last),
                            *section(touched, node, sent_delta)])


def pack_flush_np(forwards, delivered_sum, t_stop, newly, done_last,
                  sent_delta, moved=0):
    """Bit-identical host twin of _pack_flush_jnp."""
    c = len(newly)
    h = len(sent_delta)
    buf = np.zeros(flush_len(c, h), np.int64)
    buf[0] = forwards
    buf[1] = delivered_sum
    ci = np.flatnonzero(newly)
    ni = np.flatnonzero(sent_delta)
    buf[2] = len(ci)
    buf[3] = len(ni)
    buf[4] = t_stop
    buf[5] = moved
    base = FLUSH_HEADER
    buf[base:base + len(ci)] = ci
    buf[base + c:base + c + len(ci)] = np.asarray(done_last)[ci]
    buf[base + 2 * c:base + 2 * c + len(ni)] = ni
    buf[base + 2 * c + h:base + 2 * c + h + len(ni)] = \
        np.asarray(sent_delta)[ni]
    return buf


@jax.jit
def flush_halves(flush):
    """The packed flush as its int32 halves, for the copy to the host
    (flush_from_halves undoes it there).  The runtime copies an int64
    array out through a host-side conversion (X64FromTuple), which took
    17-29 ms of each ~30 ms readback of an 890k-flow plane's 5.9 MB flush
    on v5e; an int32 array is copied as it is."""
    return jax.lax.bitcast_convert_type(flush, jnp.int32)


def flush_from_halves(halves: np.ndarray) -> np.ndarray:
    """The int64 flush from flush_halves' int32 pairs, on the host."""
    return np.ascontiguousarray(halves).view(np.int64).reshape(-1)


def flush_moved(buf: np.ndarray) -> int:
    """The flush header's count of (flow, tick) pairs that moved a cell."""
    return int(buf[5])


def parse_flush(buf: np.ndarray, n_chains: int, n_nodes: int):
    """(forwards, delivered_sum, t_stop, done_chains, done_steps, node_idx,
    node_delta) from a packed flush buffer — the ONE host-side reader.
    ``n_chains`` and ``n_nodes`` are the section capacities of the buffer
    read: (chains, nodes) for a full-length flush, (K, K) for one packed by
    the compacted program at width K.  The caller knows which program
    packed it; the length alone does not say (flush_len(K, K) can equal
    flush_len(C, H) of another table)."""
    c, h = n_chains, n_nodes
    base = FLUSH_HEADER
    n_done = int(buf[2])
    n_touch = int(buf[3])
    return (int(buf[0]), int(buf[1]), int(buf[4]),
            buf[base:base + n_done],
            buf[base + c:base + c + n_done],
            buf[base + 2 * c:base + 2 * c + n_touch],
            buf[base + 2 * c + h:base + 2 * c + h + n_touch])


def _span_loop(t0, targets, state, tables, ring_len: int):
    """The superwindow tick loop over one flow table, shared by the
    full-width program (_step_span_impl) and the compacted one
    (_compact_step_span_impl): advance ``state`` = (queued, ring, tokens,
    delivered, target, done_tick, node_sent) from ``t0`` through the
    ascending absolute step boundaries in ``targets``, halting at the end
    of the first sub-window in which any chain newly completed.

    ``tables`` = (flow_node, seg_start, node_seg, refill, capacity,
    flow_pred, arr_lat, is_last): each flow's node as an index into the
    node columns, its node segment's first flow, each node's [first, end)
    flow segment, the nodes' bucket refill and capacity, each flow's
    predecessor (-1 where none), the latency of the hop into it, and
    whether it is a chain's last stage.  Every operation in the loop
    takes the length of these tables.  Returns (t_stop, *state,
    forwards, moved)."""
    (flow_node, seg_start, node_seg, refill, capacity, flow_pred, arr_lat,
     is_last) = tables
    p = targets.shape[0]
    size = jnp.int64(CELL_WIRE_BYTES)
    has_pred = flow_pred >= 0
    pred = jnp.maximum(flow_pred, 0)
    cols = jnp.arange(flow_node.shape[0])
    end = targets[p - 1]
    # ring rows in int32: tick t's row, t mod ring_len, rides beside t in
    # the loop state (an int64 remainder is a long division on TPU, see
    # _floor_div_small)
    lat = arr_lat.astype(jnp.int32)

    def body(state):
        (t, row, idx, halt, span_done, queued, hist, tokens, delivered,
         target, done_tick, node_sent, forwards, moved) = state
        arr = hist[jnp.mod(row - lat, ring_len), cols]
        queued = queued + arr
        tokens = jnp.minimum(capacity, tokens + refill)
        node_cap = _floor_div_small(tokens, CELL_WIRE_BYTES)
        served, cells = segment_greedy_totals(queued, node_cap, flow_node,
                                              seg_start, node_seg)
        queued = queued - served
        spent = cells * size
        tokens = tokens - spent
        node_sent = node_sent + spent
        delivered = delivered + jnp.where(is_last, served, 0)
        newly_done = (is_last & (target > 0) & (done_tick < 0)
                      & (delivered >= target))
        done_tick = jnp.where(newly_done, t, done_tick)
        # cast before the gather: one gather in the ring dtype
        fwd = jnp.where(is_last, 0, served).astype(hist.dtype)
        v = jnp.where(has_pred, fwd[pred], jnp.zeros((), hist.dtype))
        hist = jax.lax.dynamic_update_slice(hist, v[None],
                                            (row, jnp.int32(0)))
        forwards = forwards + jnp.sum(served)
        moved = moved + jnp.sum((served > 0).astype(jnp.int64))
        # sub-window bookkeeping: at a boundary, halt iff this span saw a
        # completion; otherwise roll into the next span with a clean flag
        span_done = span_done | jnp.any(newly_done)
        boundary = (t + 1) == targets[jnp.minimum(idx, p - 1)]
        halt = boundary & span_done
        idx = jnp.where(boundary, idx + 1, idx)
        span_done = span_done & ~boundary
        row = jnp.where(row + 1 == ring_len, 0, row + 1)
        return (t + 1, row, idx, halt, span_done, queued, hist, tokens,
                delivered, target, done_tick, node_sent, forwards, moved)

    def cond(state):
        return (state[0] < end) & ~state[3]

    init = (t0, _rem_small(t0, ring_len), jnp.int64(0),
            jnp.bool_(False), jnp.bool_(False), *state, jnp.int64(0),
            jnp.int64(0))
    out = jax.lax.while_loop(cond, body, init)
    return (out[0], *out[5:])


def _step_span_impl(t0, queued, ring, tokens, delivered, target,
                    done_tick, node_sent, inject, inject_target,
                    targets, idle_ticks, flow_node, flow_lat,
                    flow_succ, seg_start, refill, capacity,
                    flow_pred, node_seg, ring_len: int):
    """The SUPERWINDOW step: advance the cell model from ``t0`` through the
    ascending absolute step boundaries in ``targets`` (padded by repeating
    the final boundary, so the array shape stays static), HALTING at the
    end of the first sub-window in which any chain newly completed.

    Each ``targets[i-1]..targets[i]`` span is one virtual engine round's
    dispatch (device_plane negotiates the list by replaying the K=1 round
    recurrence); running them fused amortizes the per-dispatch launch +
    state-copy cost K-fold.  The halt rule is what keeps a K-round launch
    bit-identical to K separate launches: a completion wakes its client at
    the launching round's barrier under K=1, and anything that client does
    (close a socket, activate another flow) must see plane state advanced
    exactly to that round — so the kernel refuses to run past it.  The
    reached boundary comes back in the flush header (t_stop), one transfer.

    The tick itself is _span_loop's, pinned bit for bit to the numpy
    twin torcells_step_span_numpy (tests/test_superwindow.py's
    span-vs-sequential-windows parity case), and formed without a
    scatter: ``flow_pred`` and ``node_seg``
    (gather_tables) turn the successor send and the per-node byte total
    into gathers.  A scatter-add's updates run one after another on TPU
    (~114 ms a tick for 890k flows on v5e); a gather of the same length
    takes a few ms.
    Returns the same 9-tuple, with [0] = the boundary actually reached,
    plus [9] = the (flow, tick) pairs in which a flow served a cell."""
    queued = queued + inject
    target = target + inject_target
    tokens = jnp.minimum(capacity, tokens + refill * idle_ticks)
    ring = jax.lax.cond(idle_ticks > 0,
                        lambda hh: jnp.zeros_like(hh),
                        lambda hh: hh, ring)
    arr_lat = jnp.where(flow_pred >= 0, flow_lat[jnp.maximum(flow_pred, 0)],
                        jnp.int64(0))
    return _span_loop(
        t0, targets,
        (queued, ring, tokens, delivered, target, done_tick, node_sent),
        (flow_node, seg_start, node_seg, refill, capacity, flow_pred,
         arr_lat, flow_succ < 0), ring_len)


def _compact_step_span_impl(t0, queued, ring, tokens, delivered, target,
                            done_tick, node_sent, live, targets, idle_ticks,
                            flow_node, flow_lat, flow_succ, seg_start,
                            refill, capacity, flow_pred, ring_len: int):
    """_step_span_impl over the live flows alone: the same 10-tuple, bit
    for bit, from a tick loop whose every operation is K long, and what
    the flush needs of the live flows and nodes (see the end).

    ``live`` int64 [4, K]: the ascending table positions of every flow
    that holds or receives a cell before the dispatch ends (whole chains;
    padded with F), the cells injected and the target added at each, and
    the chain each belongs to (read at chain exits).
    Every other flow has nothing queued or in flight and receives
    nothing, so it serves nothing and its columns stay as they are.  The
    kernel gathers the live columns, derives their tables (predecessor
    and segment starts by ``searchsorted`` of the positions; the live
    flows' nodes and each one's segment from the node-sorted order), runs
    the shared tick loop, then writes back:

    * the live columns and the live nodes' buckets and bytes sent;
    * every other node's bucket refilled in closed form over the ticks
      actually run, ``min(capacity, tokens + refill * n)`` (a capped
      refill composes);
    * the ring rows of those ticks zeroed in every other column, as the
      full program writes a quiet flow's empty sends, so the ring equals
      the full program's and a flow that turns live later reads no stale
      send.

    Only a live chain's exit can newly complete and only a live node's
    bytes sent can move, so the second value, all K long, holds every
    change the full-length flush could carry: (newly, done_last) over the
    live flows, (node, touched, sent_delta) over the live node slots, and
    the cells the live exits delivered in the dispatch."""
    f = queued.shape[0]
    h = refill.shape[0]
    k = live.shape[1]
    pos = live[0]
    valid = pos < f
    at = jnp.minimum(pos, f - 1)
    tokens = jnp.minimum(capacity, tokens + refill * idle_ticks)
    ring = jax.lax.cond(idle_ticks > 0,
                        lambda hh: jnp.zeros_like(hh),
                        lambda hh: hh, ring)

    def take(col, fill):
        return jnp.where(valid, col[at], jnp.asarray(fill, col.dtype))

    def find(p):
        return jnp.searchsorted(pos, p, method="scan")

    # the live flows' tables; padding is a last stage of node h, no cells
    node = take(flow_node, h)
    pred_at = take(flow_pred, -1)
    pred = jnp.where(pred_at >= 0, find(pred_at), -1)
    arr_lat = jnp.where(pred_at >= 0, flow_lat[jnp.maximum(pred_at, 0)],
                        jnp.int64(0))
    seg_start_k = find(take(seg_start, f))
    # the live nodes: node-sorted flows, numbered by segment
    first = jnp.concatenate([jnp.ones(1, bool), node[1:] != node[:-1]])
    flow_u = jnp.cumsum(first.astype(jnp.int32)) - 1
    u = jnp.arange(k, dtype=jnp.int32)
    useg = jnp.stack([jnp.searchsorted(flow_u, u, side="left",
                                       method="scan"),
                      jnp.searchsorted(flow_u, u, side="right",
                                       method="scan")])
    unode = jnp.where(useg[0] < useg[1],
                      node[jnp.minimum(useg[0], k - 1)], h)
    real = unode < h
    un = jnp.minimum(unode, h - 1)

    def take_node(col):
        return jnp.where(real, col[un], jnp.zeros((), col.dtype))

    hist = jnp.where(valid[None, :], ring[:, at], jnp.zeros((), ring.dtype))
    delivered_in = take(delivered, 0)
    done_in = take(done_tick, -1)
    sent_in = take_node(node_sent)
    is_last = take(flow_succ, -1) < 0
    exit_k = valid & is_last
    out = _span_loop(
        t0, targets,
        (take(queued, 0) + live[1], hist, take_node(tokens), delivered_in,
         take(target, 0) + live[2], done_in, sent_in),
        (flow_u, seg_start_k, useg, take_node(refill), take_node(capacity),
         pred, arr_lat, is_last), ring_len)
    (t_stop, queued_k, hist, tokens_u, delivered_k, target_k, done_k,
     sent_u, forwards, moved) = out
    sent_delta = sent_u - sent_in
    news = (exit_k & (done_k >= 0) & (done_in < 0), done_k,
            unode, real & (sent_delta != 0), sent_delta,
            jnp.sum(jnp.where(exit_k, delivered_k - delivered_in, 0)))
    ran = t_stop - t0
    wrote = jnp.mod(jnp.arange(ring_len, dtype=jnp.int32)
                    - _rem_small(t0, ring_len), ring_len) < ran

    def put(col, vals):
        return col.at[pos].set(vals, mode="drop")

    tokens = jnp.minimum(capacity, tokens + refill * ran) \
        .at[unode].set(tokens_u, mode="drop")
    ring = jnp.where(wrote[:, None], jnp.zeros((), ring.dtype), ring) \
        .at[:, pos].set(hist, mode="drop")
    return (t_stop, put(queued, queued_k), ring, tokens,
            put(delivered, delivered_k), put(target, target_k),
            put(done_tick, done_k),
            node_sent.at[unode].set(sent_u, mode="drop"), forwards,
            moved), news


def _with_flush(out, done_in_last, node_sent_in, last_flow):
    """A full-width span step's 10-tuple as its flush programs return it:
    the 9-tuple with the full-length packed flush buffer appended as [9]
    (its moved count rides in the flush header).  ``done_in_last`` and
    ``node_sent_in`` are the chains' exit-flow done ticks and the nodes'
    bytes sent before the step."""
    done_last = out[6][last_flow]
    newly = (done_last >= 0) & (done_in_last < 0)
    flush = _pack_flush_jnp(out[8], jnp.sum(out[4][last_flow]), out[0],
                            newly, done_last, out[7] - node_sent_in,
                            moved=out[9])
    return (*out[:9], flush)


def _step_span_flush_impl(t0, queued, ring, tokens, delivered, target,
                          done_tick, node_sent, inject, inject_target,
                          targets, idle_ticks, flow_node, flow_lat,
                          flow_succ, seg_start, refill, capacity,
                          last_flow, flow_pred, node_seg, ring_len: int):
    """Superwindow step + packed flush in ONE dispatch: the 9-tuple of
    _step_span_impl with the packed flush buffer appended as [9].
    ``last_flow`` [C] maps each chain to its exit flow row;
    ``flow_pred`` and ``node_seg`` come from gather_tables."""
    out = _step_span_impl(t0, queued, ring, tokens, delivered, target,
                          done_tick, node_sent, inject, inject_target,
                          targets, idle_ticks, flow_node, flow_lat,
                          flow_succ, seg_start, refill, capacity,
                          flow_pred, node_seg, ring_len)
    return _with_flush(out, done_tick[last_flow], node_sent, last_flow)


def _compact_step_span_flush_impl(t0, queued, ring, tokens, delivered,
                                  target, done_tick, node_sent, live,
                                  targets, idle_ticks, flow_node, flow_lat,
                                  flow_succ, seg_start, refill, capacity,
                                  flow_pred, ring_len: int):
    """_step_span_flush_impl through _compact_step_span_impl: the same
    9-tuple, from a tick loop over the ``live`` flows alone, and the flush
    packed from the live flows and nodes at section capacities (K, K)
    (_pack_live_flush): the same header, and the same chains and nodes in
    the same order, as the full-length flush.  Nothing the flush reads
    comes from a scatter or a table-long gather.  The cumulative
    delivered count (header [1]) is the exit flows' (``flow_succ < 0``,
    every chain's last stage) before the dispatch, a masked sum, plus
    what the live exits delivered; a gather of the C exit rows took ~2.2
    ms a dispatch on v5e."""
    out, (newly, done_k, unode, touched, sent_delta, gain) = \
        _compact_step_span_impl(t0, queued, ring, tokens, delivered,
                                target, done_tick, node_sent, live,
                                targets, idle_ticks, flow_node, flow_lat,
                                flow_succ, seg_start, refill, capacity,
                                flow_pred, ring_len)
    before = jnp.sum(jnp.where(flow_succ < 0, delivered, 0))
    flush = _pack_live_flush(out[8], before + gain, out[0], out[9],
                             live[3], newly, done_k, unode, touched,
                             sent_delta)
    return (*out[:9], flush)


# Two jit wrappers over the SAME flush program, picked by backend
# (step_window_flush_for_backend): donation aliases the carried state in
# place on TPU/GPU, but on the PJRT CPU client a donated call executes
# SYNCHRONOUSLY (measured: 114 ms launch vs 0.33 ms undonated for the same
# kernel) AND still copies the buffers — so the CPU backend uses the
# non-donating variant, which is what lets the dispatch actually compute
# behind the round's host work.
torcells_step_window_flush = partial(
    jax.jit, static_argnames=("ring_len",),
    donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7))(_step_span_flush_impl)

torcells_step_window_flush_nodonate = partial(
    jax.jit, static_argnames=("ring_len",))(_step_span_flush_impl)

# The compacted flush program over a dispatch's live flows, in the same
# two jit wrappers; each width of ``live`` is a program of its own.
torcells_step_compact_flush = partial(
    jax.jit, static_argnames=("ring_len",),
    donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7))(_compact_step_span_flush_impl)

torcells_step_compact_flush_nodonate = partial(
    jax.jit, static_argnames=("ring_len",))(_compact_step_span_flush_impl)


def step_window_flush_for_backend():
    """The flush-step jit appropriate for the default backend (see note
    above): donating on accelerators, non-donating on CPU."""
    if jax.default_backend() == "cpu":
        return torcells_step_window_flush_nodonate
    return torcells_step_window_flush


def compact_flush_for_backend():
    """The compacted flush program for the default backend, as above."""
    if jax.default_backend() == "cpu":
        return torcells_step_compact_flush_nodonate
    return torcells_step_compact_flush


# The compacted widths: powers of two at or above F/256 and F/16, never
# below COMPACT_FLOOR.  A dispatch runs the smallest that holds its live
# flows, and the full-width program beyond the larger.
COMPACT_FLOOR = 1024


def compact_widths(n_flows: int) -> Tuple[int, ...]:
    """The compacted program widths for an ``n_flows`` table, ascending;
    a width that is not below ``n_flows`` would save nothing and is left
    out."""
    widths = set()
    for share in (256, 16):
        need = max(-(-n_flows // share), 1)          # ceil(F / share)
        widths.add(max(COMPACT_FLOOR, 1 << (need - 1).bit_length()))
    return tuple(w for w in sorted(widths) if w < n_flows)


# Fleet plane (ISSUE 18): the SAME span/flush program vmapped over a
# leading batch axis so one launch advances W independent simulations.
# Every operand — carried state, injections, superwindow targets, AND the
# static flow tables — carries its own lane row (lanes are independent
# scenarios padded to a shared shape class; tables differ per lane).  The
# batching rules keep per-lane semantics exact: the while_loop's cond
# becomes "any lane still below its span end" with finished lanes
# select()-frozen at their halt state, and every body op is int64
# cumsum/min/clip/segment arithmetic — bit-identical per lane to the
# unbatched kernel, which is what lets the fleet digest-gate against the
# serial path.  Never donating: the fleet runs on the CPU dispatch path
# (see the backend note above) and the driver re-pads carried real-shaped
# state per dispatch.
@partial(jax.jit, static_argnames=("ring_len",))
def torcells_step_span_flush_batched(t0, queued, ring, tokens, delivered,
                                     target, done_tick, node_sent, inject,
                                     inject_target, targets, idle_ticks,
                                     flow_node, flow_lat, flow_succ,
                                     seg_start, refill, capacity, last_flow,
                                     flow_pred, node_seg, ring_len: int):
    """[W]-leading-axis twin of torcells_step_window_flush: 10-tuple with
    every element batched ([W] t_stop/forwards scalars, [W, F] columns,
    [W, L, F] rings, [W, flush_len] flush buffers)."""
    fn = partial(_step_span_flush_impl, ring_len=ring_len)
    return jax.vmap(fn)(t0, queued, ring, tokens, delivered, target,
                        done_tick, node_sent, inject, inject_target,
                        targets, idle_ticks, flow_node, flow_lat,
                        flow_succ, seg_start, refill, capacity, last_flow,
                        flow_pred, node_seg)


def torcells_step_span_batched_numpy(t0, queued, ring, tokens, delivered,
                                     target, done_tick, node_sent, inject,
                                     inject_target, targets, idle_ticks,
                                     flow_node, flow_lat, flow_succ,
                                     seg_start, refill, capacity, last_flow,
                                     ring_len: int):
    """Host twin of torcells_step_span_flush_batched: lanes looped through
    the unbatched numpy flush twin and re-stacked (same 10-tuple/leading-
    axis contract) — the parity oracle for the vmapped program."""
    outs = [torcells_step_window_numpy_flush(
        np.int64(t0[w]), queued[w], ring[w], tokens[w], delivered[w],
        target[w], done_tick[w], node_sent[w], inject[w], inject_target[w],
        targets[w], int(idle_ticks[w]), flow_node[w], flow_lat[w],
        flow_succ[w], seg_start[w], refill[w], capacity[w], last_flow[w],
        ring_len) for w in range(len(t0))]
    return tuple(np.stack([np.asarray(o[i]) for o in outs])
                 for i in range(10))


def torcells_step_span_numpy(t0, queued, ring, tokens, delivered, target,
                             done_tick, node_sent, inject, inject_target,
                             targets, idle_ticks, flow_node, flow_lat,
                             flow_succ, seg_start, refill, capacity,
                             ring_len: int):
    """Bit-identical host twin of _step_span_impl (same boundary/halt
    rule, same 10-tuple) — the parity oracle and the --device-plane=numpy
    execution mode's superwindow step."""
    f = len(queued)
    h = len(refill)
    size = CELL_WIRE_BYTES
    is_last = flow_succ < 0
    queued = queued + inject
    target = target + inject_target
    tokens = np.minimum(capacity, tokens + refill * int(idle_ticks))
    if int(idle_ticks) > 0:
        ring = np.zeros_like(ring)   # idle jump: stale send history cleared
    arr_lat = np.zeros(f, dtype=np.int64)
    np.add.at(arr_lat, np.maximum(flow_succ, 0),
              np.where(is_last, 0, flow_lat))
    cols = np.arange(f)
    bounds = [int(x) for x in np.asarray(targets)]
    end = bounds[-1]
    forwards = 0
    moved = 0
    t = int(t0)
    idx = 0
    span_done = False
    while t < end:
        arr = ring[(t - arr_lat) % ring_len, cols]
        queued = queued + arr
        tokens = np.minimum(capacity, tokens + refill)
        cap_cells = tokens[flow_node] // size
        csum = np.cumsum(queued)
        seg_base = np.where(seg_start > 0, csum[np.maximum(seg_start - 1, 0)],
                            0) * (seg_start > 0)
        before = csum - queued - seg_base
        served = np.clip(cap_cells - before, 0, queued)
        queued = queued - served
        spent = np.bincount(flow_node, weights=served * size,
                            minlength=h).astype(np.int64)
        tokens = tokens - spent
        node_sent = node_sent + spent
        delivered = delivered + np.where(is_last, served, 0)
        newly_done = (is_last & (target > 0) & (done_tick < 0)
                      & (delivered >= target))
        done_tick = np.where(newly_done, t, done_tick)
        v = np.zeros(f, dtype=np.int64)
        np.add.at(v, np.maximum(flow_succ, 0), np.where(is_last, 0, served))
        ring[t % ring_len] = v
        forwards += int(served.sum())
        moved += int(np.count_nonzero(served))
        span_done = span_done or bool(newly_done.any())
        t += 1
        if t == bounds[min(idx, len(bounds) - 1)]:
            idx += 1
            if span_done:
                break
            span_done = False
    return (np.int64(t), queued, ring, tokens, delivered, target, done_tick,
            node_sent, np.int64(forwards), np.int64(moved))


def torcells_step_window_numpy_flush(t0, queued, ring, tokens, delivered,
                                     target, done_tick, node_sent, inject,
                                     inject_target, targets, idle_ticks,
                                     flow_node, flow_lat, flow_succ,
                                     seg_start, refill, capacity, last_flow,
                                     ring_len: int):
    """Host twin of torcells_step_window_flush (same 10-tuple contract,
    same ``targets`` superwindow boundaries)."""
    done_in_last = np.asarray(done_tick)[last_flow].copy()
    node_sent_in = np.asarray(node_sent).copy()
    out = torcells_step_span_numpy(t0, queued, ring, tokens, delivered,
                                   target, done_tick, node_sent, inject,
                                   inject_target, targets, idle_ticks,
                                   flow_node, flow_lat, flow_succ,
                                   seg_start, refill, capacity, ring_len)
    done_last = out[6][last_flow]
    newly = (done_last >= 0) & (done_in_last < 0)
    flush = pack_flush_np(int(out[8]), int(out[4][last_flow].sum()),
                          int(out[0]), newly, done_last,
                          out[7] - node_sent_in, moved=int(out[9]))
    return (*out[:9], flush)


# ---------------------------------------------------------------------------
# Multi-chip execution plane: the flow table sharded over a device mesh
# lives in shadow_tpu/parallel/mesh/ (partition.py chain partitioner +
# padded layout, exchange.py BvN permutation-leg exchange + shard_map
# superwindow kernel, meshplane.py DeviceTrafficPlane attachment) — the
# single definition of the shard placement contract.  The PR-7
# replicated-ring/full-psum kernels that used to live here were retired by
# the mesh plane; tests/test_meshplane.py is their parity suite.
# ---------------------------------------------------------------------------


# DeviceTorCells' run to completion: each dispatch covers RUN_SPANS
# sub-windows of RUN_SPAN_TICKS ticks, and halts early at the end of one in
# which a chain completed.
RUN_SPAN_TICKS = 64
RUN_SPANS = 8


class DeviceTorCells:
    """Build a circuits-over-relays instance and run it device-resident."""

    def __init__(self, n_relays: int, n_circuits: int, seed: int = 7,
                 relay_bw_kibps: int = 2048, edge_bw_kibps: int = 1 << 20,
                 max_latency_ms: int = 120):
        rng = np.random.default_rng(seed)
        # nodes: [clients | relays | servers] — clients/servers effectively
        # unthrottled, relays are the contended resource
        n_clients = n_circuits
        n_servers = max(1, n_circuits // 50)
        h = n_clients + n_relays + n_servers
        lat = rng.integers(2, max_latency_ms, size=(h, h)).astype(np.int64)
        np.fill_diagonal(lat, 1)
        bw = np.full(h, edge_bw_kibps, dtype=np.int64)
        bw[n_clients:n_clients + n_relays] = relay_bw_kibps
        refill, cap = bucket_params(bw)
        self.refill = refill.astype(np.int64)
        self.capacity = cap.astype(np.int64)
        # routes: distinct guard/middle/exit per circuit
        route = np.empty((n_circuits, 5), dtype=np.int64)
        route[:, 4] = np.arange(n_circuits)                       # client
        route[:, 0] = n_clients + n_relays + rng.integers(
            0, n_servers, size=n_circuits)                        # server
        picks = rng.random((n_circuits, n_relays)).argsort(axis=1)[:, :3]
        route[:, 1:4] = n_clients + picks                         # e, m, g
        self.flows = build_flows(route, lat)
        self.last_flow = np.flatnonzero(self.flows["flow_succ"] < 0)
        self.ring_len = int(max_latency_ms) + 2
        self.n_flows = n_circuits * 5
        self.route = route

    def _run(self, step, tables, cells_per_circuit: int, max_ticks: int):
        """Inject ``cells_per_circuit`` at every chain's entry flow, the
        same count as its target, and dispatch ``step`` (a span-flush
        program or its numpy twin, with ``tables`` after the per-dispatch
        operands) over RUN_SPANS sub-windows of RUN_SPAN_TICKS ticks at a
        time until every chain is done or ``max_ticks``.  Returns
        (delivered [F], ticks, forwards), ``ticks`` one past the last
        chain's completion tick, or ``max_ticks`` when one never
        completed."""
        fl = self.flows
        f, h = self.n_flows, len(self.refill)
        last = self.last_flow
        inject = np.where(fl["flow_stage"] == 0, cells_per_circuit, 0) \
            .astype(np.int64)
        target = np.where(fl["flow_succ"] < 0, cells_per_circuit, 0) \
            .astype(np.int64)
        zeros = np.zeros(f, np.int64)
        state = (np.int64(0), zeros,
                 np.zeros((self.ring_len, f), RING_DTYPE),
                 self.capacity.copy(), zeros, zeros,
                 np.full(f, -1, np.int64), np.zeros(h, np.int64))
        spans = RUN_SPAN_TICKS * np.arange(1, RUN_SPANS + 1, dtype=np.int64)
        t = forwards = n_done = 0
        while n_done < len(last) and t < max_ticks:
            out = step(*state, inject, target,
                       np.minimum(t + spans, max_ticks), np.int64(0),
                       *tables)
            state = out[:8]
            inject = target = zeros
            fwd, _, t, _, steps, _, _ = parse_flush(np.asarray(out[9]),
                                                    len(last), h)
            forwards += fwd
            n_done += len(steps)
        ticks = max_ticks
        if n_done == len(last):
            ticks = int(np.asarray(state[6])[last].max()) + 1
        return np.asarray(state[4]), ticks, forwards

    def _tables(self):
        fl = self.flows
        return (fl["flow_node"], fl["flow_lat"], fl["flow_succ"],
                fl["seg_start"], self.refill, self.capacity, self.last_flow)

    def run_device(self, cells_per_circuit: int, max_ticks: int):
        """Run to completion on the device's span-flush program."""
        tables = tuple(jnp.asarray(a) for a in (
            *self._tables(), self.flows["flow_pred"], self.flows["node_seg"]))
        return self._run(partial(torcells_step_window_flush_nodonate,
                                 ring_len=self.ring_len),
                         tables, cells_per_circuit, max_ticks)

    def run_numpy(self, cells_per_circuit: int, max_ticks: int):
        """run_device on the numpy twin, bit for bit."""
        return self._run(torcells_step_window_numpy_flush,
                         (*self._tables(), self.ring_len),
                         cells_per_circuit, max_ticks)
