"""Cross-shard forward exchange: precomputed BvN permutation legs executed
as on-device collectives inside the sharded superwindow kernel.

Exactness argument (inherited from the PR-7 sharded kernel): the per-tick
greedy bandwidth allocation is independent ACROSS nodes, so with every
node's whole flow segment on one shard, per-shard segment cumsums are
bit-identical to the global ones.  The only cross-shard dataflow is cell
forwarding, and every flow has exactly one predecessor (circuits are
chains), so the per-tick arrival vector in successor space has exactly one
writer per slot — addition order cannot matter, and any exchange that
delivers the same (src value -> dst slot) pairs is bitwise-equivalent.

The PREVIOUS sharded kernel exchanged by scattering into a full [F] vector
and psum-ing it over the mesh every tick, with the whole arrival ring
REPLICATED on every shard: collective bytes and ring memory were O(F)
regardless of how little traffic actually crossed shards.  This module
replaces that with a minimal-round schedule in the all-to-all scheduling
literature's shape (FAST, arxiv 2505.09764; hierarchical BvN
decomposition, arxiv 2602.22756):

* at build time the static shard-to-shard cell-EDGE matrix M[s, d] (how
  many flow->successor hops go from shard s to shard d) is decomposed
  into <= D-1 rotation permutation legs — offset r covers every (s,
  (s+r) % D) entry of M's support, so the set of offsets actually present
  IS a Birkhoff-von-Neumann decomposition of the support into permutation
  matrices, and only offsets carrying traffic become legs (the FAST
  minimal-round property: a workload whose partition keeps chains local
  pays for exactly as many legs as it has distinct cross-shard offsets);
* at run time the legs execute FUSED: collective LAUNCHES dominate the
  per-tick wall (~320 us each on the 8-virtual-device CPU mesh, nearly
  size-independent at these widths), so a multi-leg schedule runs as ONE
  ``jax.lax.all_to_all`` over the superposed [D, pair_width] slot layout
  and a single-leg schedule as the bytes-minimal lone ``ppermute``; the
  sending shard gathers its served cells into its slots, the collective
  delivers them, the receiving shard scatter-adds them into its
  SHARD-LOCAL arrival ring.  No host transfer, no [F]-sized collective —
  ``mesh.host_bounces`` stays 0 by construction and the tick's exchange
  bytes are the actual cross-shard cell slots.  With the forwards/halt
  reductions fused into one psum, a tick costs 2 collective launches
  against the PR-7 replicated-ring kernel's 3 (measured ~20%
  faster/tick on the virtual mesh).

The kernel below (:func:`make_mesh_span_flush`) is otherwise the
superwindow step + packed flush of ops/torcells_device.py, byte-for-byte:
same tick math, same halt-at-completion rule (the per-tick completion
flag is psum'd so every shard halts at the same sub-window boundary), and
the packed flush buffer grows ONE trailing slot carrying the window's
cross-shard cell count so the host learns it with zero extra reads.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ...ops.torcells_device import (CELL_WIRE_BYTES, _pack_flush_jnp,
                                    flush_len, segment_greedy)


class ExchangeSchedule:
    """The precomputed cross-shard forward schedule.

    ``offsets[k]`` is leg k's rotation (shard s sends to (s+r) % D);
    ``send_src[k]`` is int64 [D * width_k]: for each sending shard, the
    shard-LOCAL rows whose served cells ride leg k (slot-padded with -1);
    ``recv_dst[k]`` is int64 [D * width_k]: for each RECEIVING shard, the
    shard-local successor rows the same slots scatter into (-1 = padding,
    dropped).  Slot order is ascending sender local row, so sender and
    receiver tables line up by construction.

    Execution fuses the legs: with more than one leg the per-tick
    collective is ONE ``all_to_all`` whose [D, W] slot layout
    (``pair_width``/``a2a_src``/``a2a_dst``) is the superposition of the
    rotation legs — same cells, same slots, one launch (collective-launch
    count is what the per-tick wall buys on any backend); a single-leg
    schedule keeps the bytes-minimal lone ``ppermute``."""

    __slots__ = ("n_shards", "offsets", "widths", "send_src", "recv_dst",
                 "cross_edges", "matrix", "pair_width", "a2a_src",
                 "a2a_dst")

    def __init__(self, n_shards: int, offsets: List[int],
                 widths: List[int], send_src: List[np.ndarray],
                 recv_dst: List[np.ndarray], cross_edges: int,
                 matrix: np.ndarray, pair_width: int,
                 a2a_src: np.ndarray, a2a_dst: np.ndarray):
        self.n_shards = n_shards
        self.offsets = offsets
        self.widths = widths
        self.send_src = send_src
        self.recv_dst = recv_dst
        self.cross_edges = cross_edges
        self.matrix = matrix
        self.pair_width = pair_width
        self.a2a_src = a2a_src
        self.a2a_dst = a2a_dst

    @property
    def legs(self) -> int:
        return len(self.offsets)


def shard_edge_matrix(succ_global: np.ndarray, pad: int,
                      n_shards: int) -> np.ndarray:
    """The static shard-to-shard cell-edge matrix M[s, d]: count of flow
    rows on shard s whose successor lives on shard d != s."""
    succ_global = np.asarray(succ_global, dtype=np.int64)
    rows = np.flatnonzero(succ_global >= 0)
    s_src = rows // pad
    s_dst = succ_global[rows] // pad
    m = np.zeros((n_shards, n_shards), dtype=np.int64)
    cross = s_src != s_dst
    np.add.at(m, (s_src[cross], s_dst[cross]), 1)
    return m


def build_exchange(succ_global: np.ndarray, pad: int,
                   n_shards: int) -> ExchangeSchedule:
    """Decompose the cross-shard successor edges into rotation legs.

    Every entry M[s, d] maps to offset r = (d - s) % D; the used offsets
    (sorted ascending, deterministic) are the legs, each leg's width the
    max edge count any shard contributes at that offset."""
    succ_global = np.asarray(succ_global, dtype=np.int64)
    m = shard_edge_matrix(succ_global, pad, n_shards)
    rows = np.flatnonzero(succ_global >= 0)
    s_src = rows // pad
    s_dst = succ_global[rows] // pad
    cross = rows[s_src != s_dst]
    # per (offset, sending shard): (local src row, receiver local dst row)
    # pairs in ascending src-row order — the slot order BOTH tables use
    by_leg: dict = {}
    for i in cross.tolist():
        s = i // pad
        d = int(succ_global[i]) // pad
        r = (d - s) % n_shards
        by_leg.setdefault(r, {}).setdefault(s, []).append(
            (i - s * pad, int(succ_global[i]) - d * pad))
    offsets = sorted(by_leg)
    widths, send_src, recv_dst = [], [], []
    for r in offsets:
        per_shard = by_leg[r]
        w = max(len(v) for v in per_shard.values())
        snd = np.full(n_shards * w, -1, dtype=np.int64)
        rcv = np.full(n_shards * w, -1, dtype=np.int64)
        for s, pairs in sorted(per_shard.items()):
            d = (s + r) % n_shards
            for k, (src_row, dst_row) in enumerate(pairs):
                snd[s * w + k] = src_row
                rcv[d * w + k] = dst_row
        widths.append(w)
        send_src.append(snd)
        recv_dst.append(rcv)
    # fused all_to_all layout: slot chunk d of sender s carries the
    # (s -> d) edges; receiver m's chunk s scatters sender s's slots.
    # pair_width is the max edge count over ordered shard pairs, so the
    # [D, W] buffer superposes every rotation leg into one collective.
    pair_width = max(1, int(m.max()) if m.size else 1)
    a2a_src = np.full((n_shards, n_shards * pair_width), -1, dtype=np.int64)
    a2a_dst = np.full((n_shards, n_shards * pair_width), -1, dtype=np.int64)
    for r in offsets:
        for s, pairs in sorted(by_leg[r].items()):
            d = (s + r) % n_shards
            for k, (src_row, dst_row) in enumerate(pairs):
                a2a_src[s, d * pair_width + k] = src_row
                a2a_dst[d, s * pair_width + k] = dst_row
    return ExchangeSchedule(n_shards, offsets, widths, send_src, recv_dst,
                            int(len(cross)), m, pair_width,
                            a2a_src.reshape(-1), a2a_dst.reshape(-1))


def leg_of_edges(succ_global: np.ndarray, pad: int,
                 schedule: ExchangeSchedule) -> np.ndarray:
    """Per PADDED flow row: the index of the exchange leg its successor
    edge rides, or -1 (no edge, intra-shard, or padding).  The quiet-tick
    leg mask is built from this: OR each chain's rows' legs into a bitmask
    and a span whose active chains touch only a subset of legs can compile
    the rest out (make_mesh_span_raw's ``leg_mask``)."""
    succ_global = np.asarray(succ_global, dtype=np.int64)
    n_shards = schedule.n_shards
    leg_of = np.full(len(succ_global), -1, dtype=np.int64)
    lut = np.full(n_shards, -1, dtype=np.int64)
    for k, r in enumerate(schedule.offsets):
        lut[r] = k
    rows = np.flatnonzero(succ_global >= 0)
    s_src = rows // pad
    s_dst = succ_global[rows] // pad
    cross = s_src != s_dst
    leg_of[rows[cross]] = lut[(s_dst[cross] - s_src[cross]) % n_shards]
    return leg_of


def choose_exchange_mode(schedule: ExchangeSchedule, model=None,
                         override: str = "auto"
                         ) -> Tuple[str, float, str]:
    """Pick the exchange execution mode for a schedule: ``fused`` (one
    all_to_all over the superposed [D, pair_width] slots), ``ppermute``
    (one collective per rotation leg — lone for a single leg, multi-leg
    otherwise), or ``none`` (no cross-shard edges).

    Returns ``(mode, predicted_tick_us, source)``.  ``source`` says what
    decided: ``static`` (no cross edges), ``forced`` (the
    ``--exchange-mode`` CLI override), ``model`` (the measured per-box
    cost model, ISSUE 15 — cheapest predicted per-tick collective cost
    wins), or ``heuristic`` (no calibration on this box: today's PR-9
    rule, fused when multi-leg, lone ppermute otherwise — exactly the
    pre-model behavior, so an uncalibrated box changes nothing).
    ``predicted_tick_us`` is the model's per-tick exchange cost for the
    CHOSEN mode (0.0 without a model) — recorded as
    ``mesh.predicted_us`` so the decision is auditable in every scrape.

    Every candidate delivers the identical (src value -> dst slot)
    pairs, so the choice can only ever change WHICH bit-identical kernel
    runs: digest parity across modes is by construction, and pinned by
    tests/test_simprof.py with the override forced each way."""
    d = schedule.n_shards

    def predicted(mode: str) -> float:
        if model is None:
            return 0.0
        return model.exchange_tick_us(d, mode, schedule.pair_width,
                                      schedule.widths)

    if schedule.legs == 0:
        # cross-free table: no exchange collective, but the mesh kernel
        # still issues the per-tick stats psum — predict THAT, so the
        # audit value (and the window predictor fed from it) is the
        # cost actually paid, not a flattering zero
        return "none", round(predicted("none"), 2), "static"

    if override in ("fused", "ppermute"):
        return override, round(predicted(override), 2), "forced"
    heuristic = "fused" if schedule.legs > 1 else "ppermute"
    if model is None:
        return heuristic, 0.0, "heuristic"
    cost_f, cost_p = predicted("fused"), predicted("ppermute")
    if cost_f == cost_p:
        mode = heuristic            # measured tie: keep the known shape
    else:
        mode = "fused" if cost_f < cost_p else "ppermute"
    return mode, round(min(cost_f, cost_p), 2), "model"


def make_mesh_span_raw(mesh, axis: str, ring_len: int, pad: int,
                       schedule: ExchangeSchedule,
                       mode: Optional[str] = None,
                       leg_mask: Optional[Tuple[bool, ...]] = None):
    """The shard_map-ed SUPERWINDOW step with device-side cross-shard
    exchange.  Same argument list as the engine-facing flush kernel minus
    the flush packing; the arrival ring and arr_lat are SHARD-LOCAL
    (sharded in_specs), unlike the PR-7 kernel's replicated ring.  Returns
    the usual 9-tuple plus [9] = cross-shard cells exchanged this window
    (psum'd, replicated) and [10] = the (flow, tick) pairs in which a flow
    served a cell, over every shard.

    ``leg_mask`` (ISSUE 16 quiet-tick fusion) is a STATIC per-leg bool
    tuple: a False leg issues NO collective this variant.  Safe whenever
    the masked legs provably carry zeros — a chain whose specs are not yet
    injected has queued=0 and an empty ring everywhere, so fwd=0 on all
    its rows; meshplane tracks which legs the ACTIVE chains can touch and
    compiles a variant per distinct superset mask.  Any SUPERSET of the
    truly-needed legs is bit-identical (extra legs exchange zeros), so the
    mask can only ever trade launches, never results.  With ``ppermute``
    each masked leg is one launch saved per tick; with ``fused`` the
    exchange is one launch regardless, so only the all-False mask (which
    degrades to ``none``: zero exchange collectives, stats psum only)
    changes the launch count."""
    from jax.sharding import PartitionSpec as P

    n_shards = schedule.n_shards
    if leg_mask is None:
        leg_mask = tuple(True for _ in range(schedule.legs))
    assert len(leg_mask) == schedule.legs, (len(leg_mask), schedule.legs)
    active_legs = [k for k in range(schedule.legs) if leg_mask[k]]
    # exchange tables are closed over as constants (the per-shard slice
    # is taken with dynamic_slice on the shard id).  Execution strategy
    # (``mode``; decided by choose_exchange_mode — measured cost model
    # when this box is calibrated, the PR-9 heuristic otherwise):
    # "fused" runs every leg as ONE all_to_all over the superposed
    # [D, pair_width] slot layout (one launch per tick — launches, not
    # bytes, dominate the per-tick wall at these widths); "ppermute"
    # runs one rotation collective PER leg (bytes-minimal: lone for a
    # single-leg schedule, multi-leg when the model says L launches
    # beat one wide all_to_all); a cross-free table pays no exchange.
    # Every mode delivers the identical (src value -> dst slot) pairs —
    # each slot has exactly one writer — so the choice is between
    # bit-identical kernels and digest parity holds by construction.
    if mode is None:
        mode = "fused" if schedule.legs > 1 else (
            "ppermute" if schedule.legs == 1 else "none")
    if schedule.legs == 0 or not active_legs:
        # cross-free table OR every leg masked quiet this variant: the
        # tick pays zero exchange collectives (stats psum still issues —
        # it is the halt synchronizer, not exchange traffic)
        mode = "none"
    assert mode in ("fused", "ppermute", "none"), mode
    if mode == "fused":
        ex_mode = "a2a"
        pw = schedule.pair_width
        a2a_src_tbl = jnp.asarray(schedule.a2a_src)
        a2a_dst_tbl = jnp.asarray(schedule.a2a_dst)
        chunk = n_shards * pw
    elif mode == "ppermute":
        ex_mode = "ppermute"
        # masked (quiet) legs compile out entirely: each is one saved
        # collective launch per tick in this variant
        leg_tbls = [(schedule.offsets[k], schedule.widths[k],
                     jnp.asarray(schedule.send_src[k]),
                     jnp.asarray(schedule.recv_dst[k]))
                    for k in active_legs]
    else:
        ex_mode = "none"

    def step(t0, queued, ring, tokens, delivered, target, done_tick,
             node_sent, inject, inject_target, targets, idle_ticks,
             flow_node_local, succ_global, seg_start_local,
             refill, capacity, arr_lat, shard_base):
        """All [*] args sharded on ``axis`` (including ring columns and
        arr_lat) except targets/scalars (replicated).  succ_global is the
        successor's GLOBAL padded index (-1 = chain end); whether it is
        local is decided against the shard's own row range."""

        def shard_body(t0, queued, ring, tokens, delivered, target,
                       done_tick, node_sent, inject, inject_target,
                       targets, idle_ticks, flow_node_local,
                       succ_global, seg_start_local, refill, capacity,
                       arr_lat, shard_base):
            fp = queued.shape[0]
            h_local = refill.shape[0]
            p = targets.shape[0]
            queued = queued + inject
            target = target + inject_target
            tokens = jnp.minimum(capacity, tokens + refill * idle_ticks)
            # idle jump: the local send history is stale — clear only when
            # ticks were actually banked (same rule as the 1-chip kernel)
            ring = jax.lax.cond(idle_ticks > 0,
                                lambda hh: jnp.zeros_like(hh),
                                lambda hh: hh, ring)
            end = targets[p - 1]
            size = jnp.int64(CELL_WIRE_BYTES)
            is_last = succ_global < 0
            base = shard_base[0]
            # intra-shard successor rows (cross-shard rows ride the legs)
            local_succ = succ_global - base
            intra = (succ_global >= 0) & (local_succ >= 0) \
                & (local_succ < fp)
            oob = jnp.int64(fp)
            intra_dst = jnp.where(intra, local_succ, oob)
            cols = jnp.arange(fp)
            shard = base // pad
            if ex_mode == "a2a":
                my_src = jax.lax.dynamic_slice(a2a_src_tbl,
                                               (shard * chunk,), (chunk,))
                my_dst = jax.lax.dynamic_slice(a2a_dst_tbl,
                                               (shard * chunk,), (chunk,))
                my_dst_slots = jnp.where(my_dst >= 0, my_dst, oob)
            elif ex_mode == "ppermute":
                # per-leg shard-local slices, hoisted out of the tick
                # loop (one (src rows, dst slots) pair per rotation leg)
                my_legs = []
                for leg_r, leg_w, snd_tbl, rcv_tbl in leg_tbls:
                    l_src = jax.lax.dynamic_slice(
                        snd_tbl, (shard * leg_w,), (leg_w,))
                    l_dst = jax.lax.dynamic_slice(
                        rcv_tbl, (shard * leg_w,), (leg_w,))
                    my_legs.append(
                        (leg_r, l_src, l_dst,
                         jnp.where(l_dst >= 0, l_dst, oob)))

            def body(state):
                (t, idx, halt, span_done, queued, ring, tokens, delivered,
                 target, done_tick, node_sent, forwards, cross,
                 moved) = state
                # arrivals: my rows' sends from arr_lat steps ago, out of
                # MY ring slice (columns with no predecessor gather zeros)
                arr = ring[jnp.mod(t - arr_lat, ring_len), cols]
                queued = queued + arr
                tokens = jnp.minimum(capacity, tokens + refill)
                cap_cells = tokens[flow_node_local] // size
                served = segment_greedy(queued, cap_cells,
                                        seg_start_local)
                queued = queued - served
                spent = jax.ops.segment_sum(served * size, flow_node_local,
                                            num_segments=h_local)
                tokens = tokens - spent
                node_sent = node_sent + spent
                delivered = delivered + jnp.where(is_last, served, 0)
                newly = (is_last & (target > 0) & (done_tick < 0)
                         & (delivered >= target))
                done_tick = jnp.where(newly, t, done_tick)
                fwd = jnp.where(is_last, jnp.int64(0), served)
                # successor-space send vector, SHARD-LOCAL: intra-shard
                # sends scatter directly; cross-shard sends ride the
                # precomputed exchange (one collective per tick)
                v = jnp.zeros(fp, jnp.int64).at[intra_dst].add(
                    jnp.where(intra, fwd, 0), mode="drop")
                if ex_mode == "a2a":
                    vals = jnp.where(my_src >= 0,
                                     fwd[jnp.clip(my_src, 0, fp - 1)],
                                     jnp.int64(0))
                    got = jax.lax.all_to_all(vals, axis, 0, 0, tiled=True)
                    v = v.at[my_dst_slots].add(got, mode="drop")
                    cross = cross + jnp.sum(
                        jnp.where(my_dst >= 0, got, jnp.int64(0)))
                elif ex_mode == "ppermute":
                    # one rotation collective per leg (L launches/tick;
                    # the cost model decided L beat one fused a2a here)
                    for leg_r, l_src, l_dst, l_dst_slots in my_legs:
                        vals = jnp.where(l_src >= 0,
                                         fwd[jnp.clip(l_src, 0, fp - 1)],
                                         jnp.int64(0))
                        got = jax.lax.ppermute(
                            vals, axis,
                            perm=[(s, (s + leg_r) % n_shards)
                                  for s in range(n_shards)])
                        v = v.at[l_dst_slots].add(got, mode="drop")
                        cross = cross + jnp.sum(
                            jnp.where(l_dst >= 0, got, jnp.int64(0)))
                ring = ring.at[jnp.mod(t, ring_len)].set(
                    v.astype(ring.dtype))
                # fused stats reduction: forwards, the moved flow count
                # and the global completion flag (any shard's newly-done
                # chain halts every shard at the same sub-window
                # boundary) ride ONE psum per tick
                stats = jax.lax.psum(
                    jnp.stack([jnp.sum(served),
                               jnp.sum(newly.astype(jnp.int64)),
                               jnp.sum((served > 0).astype(jnp.int64))]),
                    axis)
                forwards = forwards + stats[0]
                moved = moved + stats[2]
                span_done = span_done | (stats[1] > 0)
                boundary = (t + 1) == targets[jnp.minimum(idx, p - 1)]
                halt = boundary & span_done
                idx = jnp.where(boundary, idx + 1, idx)
                span_done = span_done & ~boundary
                return (t + 1, idx, halt, span_done, queued, ring, tokens,
                        delivered, target, done_tick, node_sent, forwards,
                        cross, moved)

            def cond(state):
                return (state[0] < end) & ~state[2]

            state = (t0, jnp.int64(0), jnp.bool_(False), jnp.bool_(False),
                     queued, ring, tokens, delivered, target,
                     done_tick, node_sent, jnp.int64(0), jnp.int64(0),
                     jnp.int64(0))
            out = jax.lax.while_loop(cond, body, state)
            # every exchanged cell was counted once, at its receiver
            cross_total = jax.lax.psum(out[12], axis)
            return (out[0], *out[4:12], cross_total, out[13])

        sharded = P(axis)
        repl = P()
        return jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(repl, sharded, P(None, axis), sharded, sharded,
                      sharded, sharded, sharded, sharded, sharded, repl,
                      repl, sharded, sharded, sharded, sharded, sharded,
                      sharded, sharded),
            out_specs=(repl, sharded, P(None, axis), sharded, sharded,
                       sharded, sharded, sharded, repl, repl, repl),
            check_vma=False)(
            t0, queued, ring, tokens, delivered, target, done_tick,
            node_sent, inject, inject_target, targets, idle_ticks,
            flow_node_local, succ_global, seg_start_local,
            refill, capacity, arr_lat, shard_base)

    return step


def make_mesh_span_flush(mesh, axis: str, ring_len: int, layout: dict,
                         last_flow_pad: np.ndarray, node_src: np.ndarray,
                         n_nodes: int, mode: Optional[str] = None,
                         leg_mask: Optional[Tuple[bool, ...]] = None):
    """Mesh superwindow step + packed flush in ONE dispatch: the engine's
    sharded kernel (DeviceTrafficPlane._sharded_step contract — same
    argument list as the PR-7 kernel, so advance()/warmup() are layout-
    agnostic).  ``mode`` picks the exchange execution strategy
    (choose_exchange_mode; None = the legacy heuristic); ``leg_mask``
    compiles quiet exchange legs out (make_mesh_span_raw).  The flush
    buffer is the standard packed layout (ops/torcells_device.
    _pack_flush_jnp) with ONE trailing slot appended: [flush_len(...)] =
    cross-shard cells exchanged this window (consume() folds it into the
    mesh metrics with no extra device read)."""
    raw = make_mesh_span_raw(mesh, axis, ring_len, layout["pad"],
                             layout["exchange"], mode=mode,
                             leg_mask=leg_mask)
    lf = np.asarray(last_flow_pad, dtype=np.int64)
    nsrc = np.asarray(node_src, dtype=np.int64)

    def global_sent(ns_padded):
        # padding slots (node_src < 0) scatter out of range and drop
        idx = jnp.where(nsrc >= 0, nsrc, jnp.int64(n_nodes))
        return jnp.zeros(n_nodes, jnp.int64).at[idx].add(ns_padded,
                                                         mode="drop")

    def step_flush(t0, queued, ring, tokens, delivered, target, done_tick,
                   node_sent, inject, inject_target, targets, idle_ticks,
                   flow_node_local, succ_global, seg_start_local,
                   refill, capacity, arr_lat, shard_base):
        done_in_last = done_tick[lf]
        sent_in = global_sent(node_sent)
        out = raw(t0, queued, ring, tokens, delivered, target, done_tick,
                  node_sent, inject, inject_target, targets, idle_ticks,
                  flow_node_local, succ_global, seg_start_local,
                  refill, capacity, arr_lat, shard_base)
        done_last = out[6][lf]
        newly = (done_last >= 0) & (done_in_last < 0)
        flush = _pack_flush_jnp(out[8], jnp.sum(out[4][lf]), out[0], newly,
                                done_last, global_sent(out[7]) - sent_in,
                                moved=out[10])
        flush = jnp.concatenate([flush, out[9][None]])
        return (*out[:9], flush)

    return jax.jit(step_flush)


def mesh_flush_extra(flush: np.ndarray, n_chains: int, n_nodes: int) -> int:
    """The mesh flush buffer's trailing cross-shard cell count, or 0 for a
    standard-length buffer (the numpy twin after a demotion)."""
    base = flush_len(n_chains, n_nodes)
    return int(flush[base]) if len(flush) > base else 0
