"""FleetPlane: the shared vmapped traffic plane (ISSUE 18, ROADMAP 3).

Every device-mode run today owns the whole chip and pays ~320 us of
launch overhead per dispatch; this module batches N independent
scenarios into ONE stacked program so one launch advances all of them.
The split that unlocks it: per-scenario plane *state* stays with each
lane (real-shaped, carried between dispatches by the lane's own
DeviceTrafficPlane), while the *compiled program* is shared per shape
class — scenarios whose padded shapes coincide ride the same jit entry.

Shape classes generalize the ``pad_state`` contract from
``mesh/partition.py`` to a leading batch axis: flows/nodes/chains and
the targets vector are padded up to power-of-two buckets with INERT
rows (padding flows are their own zero-cell segment with no successor
and target 0 — identically zero forever, so pad -> step -> unpad is
bit-exact), while ``ring_len`` stays EXACT per class (the arrival
ring's mod-slot layout is position-dependent; length-padding it would
re-address history carried between dispatches).  When chain padding is
needed the flow axis is padded by at least one row so the padded
``last_flow`` entries can point at a guaranteed-zero flow (keeping the
flush header's ``delivered_sum`` exact).

Batch width per class is STICKY (starts at the first launch's
power-of-two, only grows); under-full launches are topped up with
cached inert filler lanes whose targets equal their base step — the
vmapped while_loop freezes them before the first iteration.  Sticky
width + fillers is what makes lane re-arm compile-free: the jit cache
key (shapes, width, ring_len) never changes for a living class, and
``FleetPlane.compiles`` counts exactly the (class, width) pairs XLA
ever saw — the re-arm drill asserts on it.

Lanes at different rounds coexist in one program: each lane submits its
OWN superwindow targets vector and gets back its OWN ``t_stop``
(per-lane halt flag in the batched while cond), which the lane's
engine maps back through its own ``_SuperPlan`` exactly as in the
serial path.  All kernel math is int64 integer arithmetic, so each
batched lane is bit-identical to the unbatched kernel — the property
the fleet digest gate (``simfleet smoke``, ``simfuzz --batched``)
rides on.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

# torcells_step_span_flush_batched's array operands, ring_len aside
_N_OPERANDS = 21


def _pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << int(n - 1).bit_length()


def _pad_vec(a: np.ndarray, n: int, fill: int = 0) -> np.ndarray:
    a = np.asarray(a)
    out = np.full(n, fill, dtype=a.dtype)
    out[: len(a)] = a
    return out


def _repack_flush(buf: np.ndarray, pad_c: int, pad_h: int, c: int,
                  h: int) -> np.ndarray:
    """Re-section a padded-class flush buffer [5+2*pad_c+2*pad_h] to the
    lane's real [5+2c+2h] layout.  Padding chains never complete and
    padding nodes never carry deltas, so every recorded index is < c/h
    and the true header counts are <= c/h — a straight section copy."""
    from ..ops.torcells_device import FLUSH_HEADER, flush_len
    buf = np.asarray(buf)
    if pad_c == c and pad_h == h:
        return buf.copy()
    out = np.zeros(flush_len(c, h), np.int64)
    out[:FLUSH_HEADER] = buf[:FLUSH_HEADER]
    n_done = int(buf[2])
    n_touch = int(buf[3])
    base = FLUSH_HEADER
    out[base:base + n_done] = buf[base:base + n_done]
    out[base + c:base + c + n_done] = buf[base + pad_c:base + pad_c + n_done]
    out[base + 2 * c:base + 2 * c + n_touch] = \
        buf[base + 2 * pad_c:base + 2 * pad_c + n_touch]
    out[base + 2 * c + h:base + 2 * c + h + n_touch] = \
        buf[base + 2 * pad_c + pad_h:base + 2 * pad_c + pad_h + n_touch]
    return out


class _ShapeClass:
    """One padded shape bucket: (flows, nodes, chains, targets) padded to
    powers of two, ring_len exact.  Owns the sticky batch width and the
    cached inert filler row every under-full launch is topped up with."""

    __slots__ = ("key", "f2", "h2", "c2", "p2", "ring_len", "width",
                 "_filler")

    def __init__(self, f2: int, h2: int, c2: int, p2: int, ring_len: int):
        self.key = (f2, h2, c2, p2, ring_len)
        self.f2 = f2
        self.h2 = h2
        self.c2 = c2
        self.p2 = p2
        self.ring_len = ring_len
        self.width = 0          # sticky: set at first launch, only grows
        self._filler = None

    def filler_row(self) -> tuple:
        """The inert lane: zero state, targets all equal to the base step
        (the batched while cond is false for it before the first
        iteration), tables shaped like a member with no traffic."""
        if self._filler is None:
            from ..ops.torcells_device import RING_DTYPE
            f2, h2, c2, p2 = self.f2, self.h2, self.c2, self.p2
            i64 = np.int64
            self._filler = (
                i64(0),                                   # t0
                np.zeros(f2, i64),                        # queued
                np.zeros((self.ring_len, f2), RING_DTYPE),  # ring
                np.zeros(h2, i64),                        # tokens
                np.zeros(f2, i64),                        # delivered
                np.zeros(f2, i64),                        # target
                np.full(f2, -1, i64),                     # done_tick
                np.zeros(h2, i64),                        # node_sent
                np.zeros(f2, i64),                        # inject
                np.zeros(f2, i64),                        # inject_target
                np.zeros(p2, i64),                        # targets (== t0)
                i64(0),                                   # idle_ticks
                np.full(f2, h2 - 1, i64),                 # flow_node
                np.zeros(f2, i64),                        # flow_lat
                np.full(f2, -1, i64),                     # flow_succ
                np.arange(f2, dtype=i64),                 # seg_start
                np.zeros(h2, i64),                        # refill
                np.zeros(h2, i64),                        # capacity
                np.full(c2, f2 - 1, i64),                 # last_flow
                np.full(f2, -1, i64),                     # flow_pred
                np.zeros((2, h2), i64),                   # node_seg
            )
        return self._filler


class _Submit:
    """One lane's staged dispatch: the 21 padded kernel operands, filled
    in with its batch row (or an error) by the launching thread."""

    __slots__ = ("lane", "args", "result", "error")

    def __init__(self, lane: "FleetLane", args: tuple):
        self.lane = lane
        self.args = args
        self.result: Optional[tuple] = None
        self.error: Optional[BaseException] = None


class FleetLane:
    """Per-scenario handle: attaches to the scenario's DeviceTrafficPlane
    (via ``options._fleet_lane``), pads its real-shaped dispatches into
    the shape class, and blocks until the shared batched launch returns
    its row.  ``dispatch`` is synchronous — the already-digest-pinned
    ``--device-plane-sync`` shape — so the owning engine sees exactly
    the serial plane contract."""

    __slots__ = ("plane", "name", "cls", "shape", "_tables", "dispatches")

    def __init__(self, plane: "FleetPlane", name: str):
        self.plane = plane
        self.name = name
        self.cls: Optional[_ShapeClass] = None
        self.shape: Optional[Tuple[int, int, int, int, int]] = None
        self._tables = None
        self.dispatches = 0

    # -- driver-facing lifecycle ------------------------------------------
    def begin(self) -> None:
        self.plane._lane_begin()

    def end(self) -> None:
        self.plane._lane_end()

    # -- device-plane-facing ----------------------------------------------
    def attach_plane(self, dev_plane) -> None:
        """Join (or re-join: a --resume second pass re-attaches with the
        same shapes) the shape class for this plane's flow table and
        cache the padded static tables."""
        f, h, c = dev_plane.n_flows, dev_plane.n_nodes, dev_plane.n_chains
        p, ring_len = dev_plane.superwindow_rounds, dev_plane.ring_len
        self.shape = (f, h, c, p, ring_len)
        self.cls = self.plane._class_for(f, h, c, p, ring_len)
        f2, h2, c2 = self.cls.f2, self.cls.h2, self.cls.c2
        i64 = np.int64
        self._tables = (
            _pad_vec(np.asarray(dev_plane.flow_node, i64), f2, h2 - 1),
            _pad_vec(np.asarray(dev_plane.flow_lat_steps, i64), f2, 0),
            _pad_vec(np.asarray(dev_plane.flow_succ, i64), f2, -1),
            # padding flows are each their own (empty) segment
            np.concatenate([np.asarray(dev_plane.seg_start, i64),
                            np.arange(f, f2, dtype=i64)]),
            _pad_vec(np.asarray(dev_plane.refill_step, i64), h2, 0),
            _pad_vec(np.asarray(dev_plane.capacity_step, i64), h2, 0),
            # padded chains exit through a guaranteed-zero padding flow
            _pad_vec(np.asarray(dev_plane.last_flow, i64), c2, f2 - 1),
            # padding flows have no predecessor; padding nodes no segment
            _pad_vec(np.asarray(dev_plane.flow_pred, i64), f2, -1),
            np.pad(np.asarray(dev_plane.node_seg, i64),
                   ((0, 0), (0, h2 - h))),
        )

    def dispatch(self, state: tuple, inject, inject_target, tvec,
                 idle: int) -> tuple:
        """Pad the real-shaped dispatch into the class, ride the shared
        launch, return the real-shaped synchronous numpy 10-tuple the
        serial kernel call would have produced."""
        assert self.cls is not None, "lane dispatched before attach_plane"
        f, h, c, _p, ring_len = self.shape
        cls = self.cls
        f2, h2 = cls.f2, cls.h2
        i64 = np.int64
        ring = np.asarray(state[2])
        ring_p = np.zeros((ring_len, f2), ring.dtype)
        ring_p[:, :f] = ring
        tvec = np.asarray(tvec, i64)
        args = (
            i64(state[0]),
            _pad_vec(np.asarray(state[1], i64), f2),
            ring_p,
            _pad_vec(np.asarray(state[3], i64), h2),
            _pad_vec(np.asarray(state[4], i64), f2),
            _pad_vec(np.asarray(state[5], i64), f2),
            _pad_vec(np.asarray(state[6], i64), f2, -1),
            _pad_vec(np.asarray(state[7], i64), h2),
            _pad_vec(np.asarray(inject, i64), f2),
            _pad_vec(np.asarray(inject_target, i64), f2),
            # extra target slots repeat the final boundary (never
            # reached: the lane's span ends at its own targets[-1])
            _pad_vec(tvec, cls.p2, int(tvec[-1])),
            i64(idle),
            *self._tables,
        )
        sub = _Submit(self, args)
        self.plane._submit(sub)
        if sub.error is not None:
            raise sub.error
        r = sub.result
        flush = _repack_flush(r[9], cls.c2, cls.h2, c, h)
        self.dispatches += 1
        return (i64(r[0]),
                np.ascontiguousarray(r[1][:f]),
                np.ascontiguousarray(r[2][:, :f]),
                np.ascontiguousarray(r[3][:h]),
                np.ascontiguousarray(r[4][:f]),
                np.ascontiguousarray(r[5][:f]),
                np.ascontiguousarray(r[6][:f]),
                np.ascontiguousarray(r[7][:h]),
                i64(r[8]),
                flush)

    def metrics(self) -> Dict:
        """fleet.* scrape source (registered per engine by the device
        plane's lane hook; namespace documented in obs/metrics.py)."""
        return self.plane.metrics()


class FleetPlane:
    """The shared batching executor: shape classes, the all-live-lanes
    barrier, and the vmapped launches.

    Barrier contract: every live lane (begin()..end()) eventually either
    submits a dispatch or ends.  A submission parks its lane; when every
    live lane has one parked submission, the LAST parker launches the
    whole batch (grouped per shape class, one vmapped call each) with
    the lock released around the device work, distributes per-lane rows,
    and wakes everyone.  A lane ending mid-wait re-checks the barrier,
    so host-heavy lanes delay launches but can never deadlock them."""

    def __init__(self, use_numpy: bool = False):
        self._cv = threading.Condition(threading.Lock())
        self._live = 0
        self._pending: List[_Submit] = []
        self._launching = False
        self._classes: Dict[tuple, _ShapeClass] = {}
        self._compiled: set = set()
        self._use_numpy = bool(use_numpy)
        self._lanes_created = 0
        self.lanes_peak = 0
        self.launches = 0
        self.lane_dispatches = 0
        self.compiles = 0
        self._occupancy_sum = 0.0

    # -- lane construction -------------------------------------------------
    def lane(self, name: Optional[str] = None) -> FleetLane:
        with self._cv:
            self._lanes_created += 1
            label = name or f"lane-{self._lanes_created}"
        return FleetLane(self, label)

    def _class_for(self, f: int, h: int, c: int, p: int,
                   ring_len: int) -> _ShapeClass:
        c2 = _pow2(c)
        h2 = _pow2(h)
        # chain padding needs at least one guaranteed-zero flow row for
        # the padded last_flow entries (delivered_sum stays exact)
        f2 = _pow2(f + 1) if c2 > c else _pow2(f)
        p2 = _pow2(p)
        key = (f2, h2, c2, p2, ring_len)
        with self._cv:
            cls = self._classes.get(key)
            if cls is None:
                cls = self._classes[key] = _ShapeClass(f2, h2, c2, p2,
                                                       ring_len)
            return cls

    # -- barrier -----------------------------------------------------------
    def _lane_begin(self) -> None:
        with self._cv:
            self._live += 1
            self.lanes_peak = max(self.lanes_peak, self._live)

    def _lane_end(self) -> None:
        with self._cv:
            self._live -= 1
            self._maybe_launch_locked()

    def _submit(self, sub: _Submit) -> None:
        with self._cv:
            self._pending.append(sub)
            self.lane_dispatches += 1
            self._maybe_launch_locked()
            while sub.result is None and sub.error is None:
                self._cv.wait()

    def _maybe_launch_locked(self) -> None:
        """Launch when every live lane is parked (lock held on entry and
        exit; RELEASED around the device call — the batch is snapshotted
        first, so late submissions start the next generation)."""
        if self._launching or not self._pending \
                or len(self._pending) < self._live:
            return
        batch, self._pending = self._pending, []
        self._launching = True
        self._cv.release()
        try:
            self._run_batch(batch)
        finally:
            self._cv.acquire()
            self._launching = False
            self._cv.notify_all()
            # submissions that arrived during the launch may already
            # satisfy the next barrier (e.g. the last other lane ended)
            self._maybe_launch_locked()

    # -- launching ---------------------------------------------------------
    def _run_batch(self, batch: List[_Submit]) -> None:
        """One barrier generation: group per shape class, launch each
        group as one vmapped program, scatter rows back (called with the
        barrier lock released)."""
        groups: Dict[tuple, List[_Submit]] = {}
        for sub in batch:
            groups.setdefault(sub.lane.cls.key, []).append(sub)
        for key in sorted(groups):
            subs = groups[key]
            try:
                self._launch_class(self._classes[key], subs)
            except BaseException as e:  # noqa: BLE001 - scatter to lanes
                for sub in subs:
                    if sub.result is None:
                        sub.error = e

    def _launch_class(self, cls: _ShapeClass, subs: List[_Submit]) -> None:
        width = max(cls.width, _pow2(len(subs)))
        rows = [s.args for s in subs]
        filler = cls.filler_row()
        rows.extend([filler] * (width - len(rows)))
        stacked = tuple(
            np.asarray([r[i] for r in rows])
            if np.ndim(rows[0][i]) == 0
            else np.stack([r[i] for r in rows])
            for i in range(_N_OPERANDS))
        if self._use_numpy:
            # the twin takes every operand but the two gather tables
            from ..ops.torcells_device import torcells_step_span_batched_numpy
            out = torcells_step_span_batched_numpy(
                *stacked[:-2], ring_len=cls.ring_len)
        else:
            from ..ops.torcells_device import torcells_step_span_flush_batched
            out = torcells_step_span_flush_batched(
                *stacked, ring_len=cls.ring_len)
        out = tuple(np.asarray(a) for a in out)
        with self._cv:
            cls.width = width
            if (cls.key, width) not in self._compiled:
                self._compiled.add((cls.key, width))
                self.compiles += 1
            self.launches += 1
            self._occupancy_sum += len(subs) / width
        for w, sub in enumerate(subs):
            sub.result = tuple(a[w] for a in out)

    # -- stats -------------------------------------------------------------
    def metrics(self) -> Dict:
        """The fleet.* scrape namespace (see obs/metrics.py): how many
        lanes rode the plane, how full launches ran, and how many lane
        dispatches each device launch amortized."""
        with self._cv:
            launches = self.launches
            amortized = self.lane_dispatches / launches if launches else 0.0
            occupancy = self._occupancy_sum / launches if launches else 0.0
            return {
                "fleet.lanes": self.lanes_peak,
                "fleet.lane_occupancy": round(occupancy, 4),
                "fleet.launches": launches,
                "fleet.lane_dispatches": self.lane_dispatches,
                "fleet.launches_amortized": round(amortized, 4),
                "fleet.shape_classes": len(self._classes),
                "fleet.compiles": self.compiles,
            }

    def stats(self) -> Dict:
        return self.metrics()
