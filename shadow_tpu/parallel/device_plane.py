"""Device-resident traffic plane: bulk flows advance in HBM, Python keeps
only the control plane.

This is the execution-plane promotion of ops/torcells_device.py (r3's
VERDICT item #1): instead of every DATA cell crossing the Python TCP stack
as discrete events, a Tor client in device mode builds its circuit through
the REAL engine (TCP connects, CREATE/EXTEND cells through real relays —
the control plane stays fully simulated), then registers the bulk transfer
as a device flow.  From that point the cells live in device tensors:

* one [F] flow table (circuit stage -> paced node, onward latency ticks,
  successor), sorted by paced node so the per-tick bandwidth allocation is
  the torcells segment-cumsum (exact greedy in circuit order, no sorting on
  device);
* per-node token buckets (1 ms refill, byte capacities from the SAME
  bucket parameters the engine's NetworkInterfaces use — ops/bandwidth.py);
* a [ring_len, F] arrival ring indexed by tick (the device analog of the
  delivery event queue).

The device plane is a two-stage pipeline over the engine's round loop
(stage -> launch -> collect):

* **stage** — client activations buffer injections host-side
  (``activate``) during a round;
* **launch** — at the TOP of the next dispatching round (right after the
  engine computes the window), ONE windowed dispatch advances the plane
  to the round barrier (ops/torcells_device.torcells_step_window_flush;
  state donated, so it never leaves HBM).  The dispatch is asynchronous:
  it computes while the host drains the round's arrivals (plugin
  execution + the native C plane);
* **collect** — at the next loop iteration, before the next window is
  computed, the engine materializes the dispatch's ONE packed flush
  buffer (forwards + delivered cursor + newly-completed chains +
  per-node byte deltas, delta-compacted on device) and wakes completed
  flows.

Completed flows wake their client process through an ordinary scheduled
event, so determinism is exact: completion ticks are device-computed, wake
times are their tick times clamped to the launching round's barrier, and
digests are identical across scheduler policies, across the device/numpy
execution modes (--device-plane=numpy runs the bit-identical host twin;
tests/test_device_plane.py pins both), and across pipelined vs serial
(--device-plane-sync) execution — the engine commits round N's plane
state before round N+1's staged injections are folded in, so overlap
never reorders anything (tests/test_device_pipeline.py).

What is and is NOT modeled (honesty contract, same spirit as
ops/bandwidth.py's docstring): the plane models BOTH directions of each
stream as independent cell chains (download server->exit->middle->guard->
client and upload client->guard->middle->exit->server), store-and-forward
at relay granularity with per-direction bucket contention (each host
contributes an egress node on its up bucket for sending hops and an
ingress node on its down bucket for the delivering hop — the same
send/receive TokenBucket split the engine's interfaces use), and fixed
512B+header wire cells.  It does not model per-cell TCP control (windows,
retransmits) for the bulk phase — circuit setup DOES exercise the full
TCP stack.  Reference analog: the traffic pattern shadow-plugin-tor
measures (worker.c:243-304 + network_interface.c:421-579 per-cell work,
executed here as dense tensor ticks).
"""

from __future__ import annotations

import math
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..core import stime
from ..core.event import Event
from ..core.task import Task
from ..core.logger import get_logger

TICK_NS = 1_000_000          # 1 ms, = the interface refill interval

# The numpy twin's share of _flow_args(): every table but the device
# kernel's two gather tables (ops/torcells_device.gather_tables).
_TWIN_TABLES = 7


class _PoisonedFlush:
    """Fault-harness stand-in for an in-flight flush handle: materializing
    it raises (``device-dispatch:N``) or stalls (``device-dispatch-hang:N``,
    bounded so the abandoned guard thread cannot linger forever) — the
    deterministic stand-ins for a dispatch that failed or wedged."""

    def __init__(self, handle, hang: bool = False):
        self._handle = handle
        self._hang = hang

    def __array__(self, dtype=None, copy=None):
        if self._hang:
            import time as _wt
            # simlint: disable=SIM005 -- fault harness: a deliberate stall
            _wt.sleep(30.0)
        raise RuntimeError("fault injection: poisoned device dispatch")


class _CollectThread:
    """The process's flush-collect thread: runs each blocking flush read so
    that ``--device-watchdog-sec`` can bound it.  One thread serves every
    plane's collects instead of a new one per collect: on v5e each new
    thread's flush readback (~6 MB at 890k flows) landed in a fresh malloc
    arena, and host RSS grew by ~6 MB a dispatch.  A read that outlives
    the watchdog abandons the thread (``abandon``); it exits once the
    stuck read returns, and the next collect starts a fresh one."""

    _lock = threading.Lock()
    _live: Optional["_CollectThread"] = None

    def __init__(self):
        import queue
        self._jobs = queue.SimpleQueue()
        self.thread = threading.Thread(target=self._serve, daemon=True,
                                       name="device-dispatch-collect")
        self.thread.start()

    @classmethod
    def get(cls) -> "_CollectThread":
        with cls._lock:
            if cls._live is None:
                cls._live = cls()
            return cls._live

    def submit(self, job) -> None:
        self._jobs.put(job)

    def abandon(self) -> None:
        with self._lock:
            if _CollectThread._live is self:
                _CollectThread._live = None
        self._jobs.put(None)

    def _serve(self) -> None:
        while True:
            job = self._jobs.get()
            if job is None:
                return
            job()
            del job


class _SuperPlan:
    """One negotiated superwindow: the K=1 round recurrence replayed
    host-side (negotiate_superwindow), executed as ONE kernel launch.

    ``bounds`` is every merged virtual round's (window_start, window_end);
    ``targets`` the absolute step boundary each dispatching round's window
    maps to (ascending); ``round_of`` the bounds index that launched each
    target.  consume() maps the kernel's reached boundary (flush t_stop)
    back through ``round_of`` to learn which virtual round the plane — and
    therefore the engine's round counter and window bookkeeping — actually
    advanced to."""

    __slots__ = ("base", "targets", "bounds", "round_of")

    def __init__(self, base, targets, bounds, round_of):
        self.base = base
        self.targets = targets
        self.bounds = bounds
        self.round_of = round_of


class _FlowSpec:
    """One device-mode client = TWO independent cell chains, e.g. a tor
    download (server -> exit -> middle -> guard -> client) and upload
    (client -> guard -> middle -> exit -> server), or a star-bulk pair
    (server -> client / client -> server).  Chains may have different hop
    counts per spec — the flow table is built from the actual routes.  The
    client's flow is complete when BOTH chains have delivered.

    ``route_down`` may be None for an auto: consensus client; the plane
    resolves it at startup by replaying the client's derived path draw over
    the config-predicted consensus (resolve_auto_routes)."""

    __slots__ = ("client_name", "route_down", "route_up", "cells_down",
                 "cells_up", "circuit", "dirspec", "dest", "auto_start_ns")

    def __init__(self, client_name: str, route_down: Optional[List[str]],
                 route_up: Optional[List[str]], cells_down: int,
                 cells_up: int, dirspec: Optional[str] = None,
                 dest: Optional[str] = None):
        self.client_name = client_name
        self.route_down = route_down
        self.route_up = route_up
        self.cells_down = cells_down
        self.cells_up = cells_up
        self.circuit = -1
        self.dirspec = dirspec
        self.dest = dest
        # processless flow (scale tier): the plane self-activates it at
        # this sim time and completion needs no wake event — no plugin
        # ever joins, so the quiet client host stays a table row
        self.auto_start_ns: Optional[int] = None


def _cells_for(nstreams: int, specs: List[str]):
    from ..apps.tor import PAYLOAD_MAX
    cells_down = cells_up = 0
    for i in range(nstreams):
        up, down = (int(x) for x in specs[i % len(specs)].split(":"))
        cells_down += max(1, math.ceil(down / PAYLOAD_MAX))
        cells_up += max(1, math.ceil(up / PAYLOAD_MAX))
    return cells_down, cells_up


def parse_device_client(host_name: str, args: List[str]) -> Optional[_FlowSpec]:
    """Recognize a tor client process configured for device-plane data
    ('device' flag in its args).  args layout (apps/tor.py client role):
    client <socksport> <path> <dest> <destport> <nstreams> <spec...> device
    <path> is a static 3-hop list or 'auto:<dirhost>[:<dirport>]' (the
    consensus route is predicted at startup — resolve_auto_routes)."""
    if not args or args[0] != "client" or "device" not in args:
        return None
    # strip the mode token BEFORE positional parsing (client_main does the
    # same), so "client 9050 <path> dest 80 device" with nstreams omitted
    # falls back to the defaults instead of int("device") crashing
    args = [a for a in args if a != "device"]
    path_s = args[2]
    dest = args[3]
    nstreams = int(args[5]) if len(args) > 5 else 1
    specs = args[6:] or ["100:10000"]
    cells_down, cells_up = _cells_for(nstreams, specs)
    if path_s.startswith("auto:"):
        return _FlowSpec(host_name, None, None, cells_down, cells_up,
                         dirspec=path_s[len("auto:"):], dest=dest)
    path = [h.partition(":")[0] for h in path_s.split(",")]
    if len(path) != 3:
        raise ValueError(f"{host_name}: device-plane needs a 3-hop path")
    guard, middle, exit_ = path[0], path[1], path[2]
    return _FlowSpec(host_name,
                     [dest, exit_, middle, guard, host_name],
                     [host_name, guard, middle, exit_, dest],
                     cells_down, cells_up, dest=dest)


def parse_device_tgen(host_name: str, args: List[str]) -> Optional[_FlowSpec]:
    """Recognize a tgen client configured for device-plane data (workload
    #2, star bulk): client <server> <port> <spec...> device.  The flow is a
    2-hop pair: server->client download and client->server upload, paced by
    the two hosts' own up/down buckets."""
    if not args or args[0] != "client" or "device" not in args:
        return None
    args = [a for a in args if a != "device"]
    server = args[1]
    specs = args[3:] if len(args) > 3 else ["1024:65536"]
    cells_down, cells_up = _cells_for(len(specs), specs)
    return _FlowSpec(host_name, [server, host_name], [host_name, server],
                     cells_down, cells_up, dest=server)


def resolve_auto_routes(engine, specs: List[_FlowSpec]) -> None:
    """Fill in auto: specs' routes at startup by replaying each client's
    path draw: the consensus is config-determined (every relay publishes
    its name/orport/bw from its own args, and the authority serves them
    sorted by name), and device-mode clients draw from the DERIVED
    per-host stream host.random.spawn('device-circuit') — independent of
    execution order, so the replay here is exact.  The runtime cross-check
    (DeviceTrafficPlane.check_route via api.device_flow_start) fails
    loudly if the fetched consensus ever diverges from this prediction."""
    autos = [s for s in specs if s.route_down is None]
    if not autos:
        return
    from ..apps.tor import pick_weighted
    from ..core.rng import RandomSource, derive
    relays = {}
    for _hid, host_name, app, a in engine.iter_process_specs():
        if not app.endswith("tor"):
            continue
        # relay <orport> <dirauth_host:port> <bw>: publishes into the
        # consensus (apps/tor.py relay role)
        if a and a[0] == "relay" and len(a) > 2 and a[2]:
            orport = int(a[1]) if len(a) > 1 else 9001
            bw = int(a[3]) if len(a) > 3 else 100
            relays[host_name] = (orport, bw)
    consensus = [(n, p, w) for n, (p, w) in sorted(relays.items())]
    if not consensus:
        raise ValueError(
            "device plane: auto: clients configured but no publishing "
            "relays found (no dirauth-registered relay processes)")
    for s in autos:
        # the client's derived path stream, computed arithmetically so a
        # table-resident client needs no Host object to predict its route
        key = engine.host_stream_key(s.client_name)
        if key is None:
            raise ValueError(f"device plane: unknown host "
                             f"{s.client_name!r}")
        rng = RandomSource(derive(key, "device-circuit"))
        path = [name for name, _port in pick_weighted(rng, consensus)]
        if len(path) != 3:
            raise ValueError(
                f"{s.client_name}: consensus has only {len(path)} usable "
                "relays; device-plane circuits need 3 hops")
        guard, middle, exit_ = path[0], path[1], path[2]
        s.route_down = [s.dest, exit_, middle, guard, s.client_name]
        s.route_up = [s.client_name, guard, middle, exit_, s.dest]


class DeviceTrafficPlane:
    """Owns the device-resident state for all registered bulk flows and the
    engine-side activation/wake bookkeeping."""

    # process-wide high-water mark of the quiet-tick sharded-variant
    # cache, reported by `simfleet smoke` against the checked-in
    # [tool.simjit.budget] "device_plane.sharded_variants" entry (the
    # runtime half of the SIM305 compile-budget cross-check; the static
    # half pins the literal cap in _pick_sharded_step to the same value)
    sharded_variants_high_water = 0

    def __init__(self, engine, specs: List[_FlowSpec], mode: str = "device"):
        if engine.shard_count > 1:
            raise RuntimeError(
                "--device-plane is global state; it does not compose with "
                "--processes sharding (run the device plane single-process)")
        assert mode in ("device", "numpy")
        self.engine = engine
        self.mode = mode
        # dispatch cadence: accumulate at least this many steps before
        # launching a kernel dispatch (injections wait with them).  One
        # dispatch per engine round would pay a full state round trip per
        # round on backends without buffer donation (jax CPU copies the
        # donated state every call — measured ~7 ms at 50k flows); batching
        # K rounds' ticks into one dispatch amortizes it K-fold.  Wake
        # times are observed at the consuming barrier either way, and both
        # execution modes follow the identical cadence, so digests stay
        # parity-comparable.
        self.min_dispatch_steps = max(
            1, int(getattr(engine.options, "device_plane_batch_steps", 8)))
        # superwindow depth: how many consecutive lookahead rounds one
        # kernel launch may cover when no host-side event falls inside
        # them (engine._advance_window negotiates per round; ISSUE 7).
        # Also the static pad length of the kernel's targets vector.
        self.superwindow_rounds = max(
            1, int(getattr(engine.options, "superwindow_rounds", 8)))
        self._pending_plan: Optional[_SuperPlan] = None
        self._active_plan: Optional[_SuperPlan] = None
        self.superwindows = 0
        self._rounds_launched = 0    # virtual rounds covered by launches
        self._mesh = None
        self._shard = None           # layout dict when sharded
        self._sharded_step = None
        self.specs = specs
        for i, s in enumerate(specs):
            s.circuit = i
        # activate/check_route/join are keyed by host name, so the
        # one-flow-per-host rule holds for PLUGIN-driven specs only; auto
        # (processless) flows self-stage and wake by circuit index, never
        # through this dict — a swarm peer may carry many chains
        plugin_specs = [s for s in specs if s.auto_start_ns is None]
        self._by_client = {s.client_name: s for s in plugin_specs}
        if len(self._by_client) != len(plugin_specs):
            # two device-mode clients on one host would silently share a
            # circuit (the second spec wins) and one client's
            # activate/join would target the wrong flow, blocking until
            # end_time with no error
            seen: set = set()
            dup = next(s.client_name for s in plugin_specs
                       if s.client_name in seen or seen.add(s.client_name))
            raise ValueError(
                f"device plane: host {dup!r} has multiple device-mode tor "
                "clients; run at most one per host (flows are keyed by "
                "host name)")
        self._meshinfo = None        # set by attach_mesh when sharded
        # the measured per-box cost model (ISSUE 15, shadow_tpu/prof/):
        # consulted by attach_mesh for the exchange-mode decision and by
        # advance() for per-launch predicted cost.  A missing or
        # fingerprint-mismatched COSTMODEL.json degrades (loudly) to
        # None — the pre-model heuristics — never a crash.
        if mode == "device":
            from ..prof.model import load_for_engine
            self._costmodel, self._costmodel_status = load_for_engine(
                engine.options)
        else:
            self._costmodel, self._costmodel_status = None, "off"
        self._build_layout(engine)
        # COSTMODEL auto-tuner (ISSUE 16, prof/autotune.py): with a
        # loaded model covering this flow table, pick the effective
        # superwindow depth from measured costs.  Digest-NEUTRAL by
        # construction: K only merges rounds the halt rule maps back
        # exactly.  Cadence and granule are digest-BEARING and stay at
        # contract values.
        from ..prof.autotune import plan_dispatch
        self._tune_plan = plan_dispatch(
            self._costmodel, self._costmodel_status, engine.options,
            self.n_flows)
        if self._tune_plan.source == "model":
            self.superwindow_rounds = self._tune_plan.superwindow_rounds
            if self.superwindow_rounds > getattr(engine, "_superwindow", 1):
                engine._superwindow = self.superwindow_rounds
        engine.metrics.source("autotune", self._tune_plan.metrics)
        # quiet-tick exchange-leg fusion (ISSUE 16): set by attach_mesh —
        # per-chain leg bitmasks; dispatch picks a variant kernel with
        # the quiet legs compiled out (superset masks are bit-identical)
        self._chain_leg_bits = None
        self._full_leg_bits = 0
        self._active_leg_bits = 0
        self._sharded_variants: Dict[int, object] = {}
        # multi-chip: shard the flow table over a device mesh (same
        # --tpu-devices axis the scheduler policy scales on).  Exact — see
        # parallel/mesh/ (partition + BvN exchange); state/API stay in the
        # ORIGINAL flow space, translated at the dispatch boundary.
        if mode == "device":
            n_dev = int(getattr(engine.options, "tpu_devices", 1) or 0)
            if n_dev == 0:
                import jax
                n_dev = len(jax.devices())
            if n_dev > 1:
                self._setup_sharding(n_dev)
        self._state = None           # lazy: built at first activation
        # processless flows (scale tier): (start_ns, circuit) ascending;
        # the plane self-activates each at its start time — next_time()
        # keeps the engine's windows coming until the last one is staged
        self._auto = sorted(
            (s.auto_start_ns, i) for i, s in enumerate(specs)
            if s.auto_start_ns is not None)
        self._auto_pos = 0
        self._inflight = False
        self._flush_handle = None    # in-flight packed flush (1-deep slot)
        self._flush_step = None      # backend-selected flush kernel (lazy)
        self._compact_step = None    # ... and its compacted program
        self._ticks_synced = 0
        self._inject_buf: List[Tuple[int, int]] = []   # (circuit, cells)
        # the live chains (an insertion-ordered set): injected, and their
        # completion not yet folded from a flush.  No other chain holds or
        # receives a cell, so a dispatch may step these alone (the
        # compacted program); a chain whose second completion is never
        # reported stays in, which is slower and never wrong
        self._live: Dict[int, None] = {}
        self._live_flows = 0
        self._waiters: Dict[int, Tuple[object, object]] = {}
        self._done: Dict[int, int] = {}   # circuit -> wake sim time ns
        self._woken: set = set()
        self._chain_done: Optional[np.ndarray] = None  # [C] step or -1
        self._flow_args_cached = None
        self._zero_inject_cached = None   # device-resident, reused when the
                                          # staged inject buffer is empty
        self.total_forwards = 0
        self.total_injected_cells = 0
        self.dispatches = 0
        # the plane's wall, split where the work happens: host = launch_ns
        # (dispatch prep up to the jit call's return) + fold_ns (the fold
        # of a collected flush); device = wait_ns (blocked on the in-flight
        # dispatch) + readback_ns (the device->host copy of its flush).
        # idle_ns: wall with no dispatch in flight, from a collect's
        # readback to the next launch's return
        self.launch_ns = 0
        self.fold_ns = 0
        self.wait_ns = 0
        self.readback_ns = 0
        self.idle_ns = 0
        self._idle_from: Optional[int] = None
        # kernel ticks executed (t_stop - launch base; banked idle ticks
        # are a re-base jump, not ticks) and the (flow, tick) pairs in
        # which a flow moved a cell (the flush header's count)
        self.ticks_stepped = 0
        self.flow_ticks_moved = 0
        # flow-ticks the kernel stepped (its width x ticks executed) and
        # the dispatches that ran a compacted width
        self.flow_ticks_stepped = 0
        self.compact_dispatches = 0
        self._launch_width = 0
        # the section capacities (chains, nodes) of the in-flight
        # dispatch's flush: (K, K) from the compacted program at width K,
        # (C, H) from every other; and the flush bytes copied to the host
        self._launch_sizes = (0, 0)
        self.flush_bytes_read = 0
        # pipeline introspection: actual host<->device interactions (kernel
        # dispatch + inject upload + flush read) and the wall the in-flight
        # dispatch had to compute behind host round work
        self.device_calls = 0
        self.pipeline_overlap_ns = 0
        self._launch_wall = 0
        self._launch_pred = None     # (per_step_us, fixed_us) model
        self._launch_base = 0        # kernel t at launch (steps = t_stop-)
        # --device-plane-sync: block on the dispatch at launch time (the
        # serial oracle the pipelined run is digest-compared against)
        self._sync = bool(getattr(engine.options, "device_plane_sync",
                                  False))
        # idle fast path: when the plane provably has no cells anywhere
        # (every dispatched cell delivered, nothing buffered), rounds only
        # bank refill ticks instead of spinning the kernel; the next real
        # dispatch folds them in exactly (capped refill is idempotent)
        self._cells_dispatched = 0
        self._cells_delivered_seen = 0
        self._idle_ticks_banked = 0
        self.idle_rounds_skipped = 0
        # Dispatch supervision (ISSUE 2): every dispatch window is logged as
        # (base_ticks, inject pairs, n, idle) — a few ints per window — so
        # that a FAILED in-flight dispatch (exception at materialization, or
        # collect timeout via --device-watchdog-sec) can be recovered by
        # replaying the whole window history on the bit-identical numpy
        # twin.  Full-history replay rather than one-window replay because
        # the carried device state is donated on accelerators: after the
        # failed dispatch there is no pre-state buffer left to restart from.
        # On recovery the backend is PERMANENTLY demoted to the numpy twin
        # (graceful degradation: digest parity preserved, device speed
        # forfeited), counted in engine.supervision.
        self._dispatch_log: List[tuple] = []
        # observability hooks (shadow_tpu/obs/): dispatch/collect latency
        # histograms, bytes per flush, pipeline-overlap efficiency — all
        # no-ops (one attribute check) when tracing/metrics are off
        from ..obs.profiler import DeviceProfiler
        self._profiler = DeviceProfiler()
        self._watchdog_sec = float(
            getattr(engine.options, "device_watchdog_sec", 0) or 0)
        self.demoted = False
        self.recoveries = 0
        from ..core.supervision import parse_fault_inject
        fault = parse_fault_inject(
            getattr(engine.options, "fault_inject", "") or "")
        self._fault_dispatch = 0
        self._fault_hang = False
        if fault and fault["kind"] in ("device-dispatch",
                                       "device-dispatch-hang"):
            self._fault_dispatch = fault["dispatch"]
            self._fault_hang = fault["kind"] == "device-dispatch-hang"
        # self-healing (ISSUE 17): an injected device loss re-shards the
        # mesh onto D-1 devices at the next quiesced round boundary; a
        # demote-repromote poison fails like device-dispatch:N but the
        # demotion serves a probation (--repromote-after clean collects)
        # and then climbs back to the device rung once, replay guard armed
        self._fault_device_lost = 0
        if fault and fault["kind"] == "device-lost":
            self._fault_device_lost = fault["round"]
        if fault and fault["kind"] == "demote-repromote":
            self._fault_dispatch = fault["dispatch"]
        self._repromote_after = int(
            getattr(engine.options, "repromote_after", 0) or 0)
        self._probation_clean = 0
        self._repromoted = False
        self._replay_base = None   # state stash at re-promotion: a second
                                   # failure replays base + log, then the
                                   # numpy demotion is permanent
        # fleet lane (ISSUE 18): an engine run as a fleet batch lane
        # carries a FleetLane on its options; this plane's device
        # dispatches then ride the shared vmapped program (lane.dispatch
        # pads to the shape class, the batched launch advances every
        # parked lane at once, the lane unpads this plane's row).  The
        # lane path is synchronous (the digest-pinned --device-plane-sync
        # shape) and single-device only — sharded meshes keep their own
        # program.
        self._lane = None
        lane = getattr(engine.options, "_fleet_lane", None)
        if lane is not None and mode == "device" and self._shard is None:
            self._lane = lane
            lane.attach_plane(self)
            from ..obs.metrics import fleet_source
            engine.metrics.source("fleet", fleet_source(lane.plane))

    # -- static layout ----------------------------------------------------
    def _build_layout(self, engine) -> None:
        """Flow table from the static specs: the torcells layout (sorted by
        paced node, segment cumsum offsets) with per-flow onward latencies
        gathered from the engine's real topology rows — no [H, H] local
        matrix is ever materialized (10k-host graphs would not fit)."""
        topo = engine.topology
        # Every host contributes up to TWO plane nodes: its EGRESS node
        # (up-bandwidth bucket — paces stages 0..3, the sending hops) and
        # its INGRESS node (down-bandwidth bucket — paces stage 4, the
        # delivering hop).  Distinct buckets per direction mirror the
        # engine's send/receive TokenBuckets; a client uploading and
        # downloading concurrently contends on the right one each way.
        names: List[Tuple[str, str]] = []      # (host, "tx"|"rx")
        name_idx: Dict[Tuple[str, str], int] = {}

        def node_of(nm: str, kind: str) -> int:
            key = (nm, kind)
            if key not in name_idx:
                name_idx[key] = len(names)
                names.append(key)
            return name_idx[key]

        # chains: 2 per spec (download then upload), VARIABLE hop counts —
        # a tor circuit is 5 stages, a star-bulk pair is 2 (the flow table
        # is built from the actual routes, not a fixed grid)
        chains: List[List[int]] = []
        for s in self.specs:
            for rt in (s.route_down, s.route_up):
                chains.append([node_of(nm, "tx") for nm in rt[:-1]] +
                              [node_of(rt[-1], "rx")])
        self.node_names = names
        self.node_hosts = []
        self.node_kind = [k for (_nm, k) in names]
        self._has_upload = np.array([s.cells_up > 0 for s in self.specs],
                                    dtype=bool)
        rows = np.empty(len(names), dtype=np.int64)
        rates = np.empty(len(names), dtype=np.int64)
        table = getattr(engine, "host_table", None)
        for i, (nm, kind) in enumerate(names):
            # deliberately NOT engine.host_by_name: that would materialize
            # every table row the flow table references — the whole point
            # is that quiet hosts contribute array rows, so read the
            # table's columns instead
            host = engine.hosts_by_name.get(nm)
            if host is not None:
                self.node_hosts.append(host)
                rows[i] = host.topo_row
                rates[i] = (host.params.bw_up_kibps if kind == "tx"
                            else host.params.bw_down_kibps)
                continue
            info = table.plane_host_info(nm) if table is not None else None
            if info is None:
                raise ValueError(f"device plane: unknown host {nm!r}")
            self.node_hosts.append(None)
            topo_row, bw_up, bw_down = info
            rows[i] = topo_row
            rates[i] = bw_up if kind == "tx" else bw_down
        from ..ops.bandwidth import bucket_params
        refill, capacity = bucket_params(rates)
        self.refill = refill.astype(np.int64)
        self.capacity = capacity.astype(np.int64)
        # flatten chains into pre-sort flow arrays (chain-contiguous)
        c = len(chains)
        chain_len = np.array([len(rt) for rt in chains], dtype=np.int64)
        n_flows = int(chain_len.sum())
        flow_chain = np.repeat(np.arange(c, dtype=np.int64), chain_len)
        flow_stage = np.concatenate(
            [np.arange(m, dtype=np.int64) for m in chain_len])
        flow_node = np.concatenate(
            [np.asarray(rt, dtype=np.int64) for rt in chains])
        is_last_pre = flow_stage == chain_len[flow_chain] - 1
        nxt = np.where(is_last_pre, flow_node,
                       np.roll(flow_node, -1))       # next stage, same chain
        pre_succ = np.where(is_last_pre, -1,
                            np.arange(n_flows, dtype=np.int64) + 1)
        lat_ns = np.asarray(topo.latency_ns)[rows[flow_node], rows[nxt]]
        lat_pre = np.where(is_last_pre, 0,
                           np.maximum(lat_ns // TICK_NS, 1))
        # sort by (paced node, chain, stage): the per-tick allocation is a
        # segment cumsum in this order (exact greedy per node)
        order = np.lexsort((flow_stage, flow_chain, flow_node))
        pos_of = np.empty(n_flows, dtype=np.int64)
        pos_of[order] = np.arange(n_flows)
        flow_node = flow_node[order]
        lat = lat_pre[order]
        succ = np.where(pre_succ[order] >= 0,
                        pos_of[np.maximum(pre_succ[order], 0)], -1)
        starts = np.flatnonzero(np.r_[True, flow_node[1:] != flow_node[:-1]])
        seg_id = np.cumsum(np.r_[0, (flow_node[1:] != flow_node[:-1])
                                 .astype(np.int64)])
        self.flow_node = flow_node
        self.flow_lat = lat.astype(np.int64)
        self.flow_succ = succ
        self.seg_start = starts[seg_id]
        from ..ops.torcells_device import gather_tables
        self.flow_pred, self.node_seg = gather_tables(flow_node, succ,
                                                      len(names))
        self.flow_circ = flow_chain[order]
        self.flow_stage = flow_stage[order]
        # per-chain entry (stage 0) and exit (last stage) flow positions
        chain_base = np.r_[0, np.cumsum(chain_len)[:-1]]
        self.first_flow = pos_of[chain_base]
        self.last_flow = pos_of[chain_base + chain_len - 1]
        self.n_chains = len(chains)
        # chain c's flow positions: _chain_rows[b:b + n], b = _chain_base[c]
        # and n = _chain_len[c] (the pre-sort table is chain-contiguous)
        self._chain_rows = pos_of
        self._chain_base = chain_base
        self._chain_len = chain_len
        from ..ops.torcells_device import compact_widths
        self._compact_widths = compact_widths(n_flows)
        # Step granulation: the kernel's loop iteration covers ``granule``
        # milliseconds.  Chosen so the arrival ring stays <= ~64 slots even
        # on multi-second-latency topologies (the reference GraphML has
        # 2.3 s paths; a 1 ms-exact ring would be [2300, F] ~ 1 GB at 10k
        # circuits) AND the sequential step count stays low (state bytes x
        # steps is the device cost).  Bandwidth is exact at every granule
        # (refill and burst capacity scale with the step); per-hop latency
        # rounds UP to the next granule multiple — <= granule-1 ms late per
        # hop, never early — identically in both execution modes.
        max_lat = int(self.flow_lat.max()) if len(lat) else 1
        g = max(1, -(-(max_lat + 1) // 64))
        override = getattr(engine.options, "device_plane_granule_ms", 0)
        if override:
            g = int(override)
        self.granule = g
        lat_steps = -(-self.flow_lat // g)
        self.flow_lat_steps = np.where(self.flow_lat > 0,
                                       np.maximum(lat_steps, 1),
                                       0).astype(np.int64)
        self.ring_len = int(self.flow_lat_steps.max()) + 2
        self.refill_step = self.refill * g
        # rate preservation: a backlogged node must be able to spend a full
        # step's refill; burst capacity otherwise keeps the 1 ms bucket's
        self.capacity_step = np.maximum(self.capacity, self.refill_step)
        from ..ops.torcells_device import CELL_WIRE_BYTES
        if int(self.capacity_step.max()) // CELL_WIRE_BYTES >= 2 ** 31:
            # the int32 arrival ring (ops/torcells_device.RING_DTYPE) holds
            # per-step cell counts bounded by capacity/cell-size; a config
            # that could overflow it must fail loudly, not wrap
            raise ValueError(
                "device plane: a node's per-step burst capacity exceeds "
                "2**31 cells — the int32 arrival ring would overflow "
                "(lower --device-plane-granule-ms or the host bandwidth)")
        self.n_flows = n_flows
        self.n_nodes = len(names)
        # Vectorized tracker feed (ISSUE 7 control-plane cut): collects
        # fold each flush's per-node byte deltas into ONE numpy
        # scatter-add here; the per-host split into Tracker counter
        # objects happens lazily, only when something actually reads a
        # tracker (heartbeat, digest, teardown) — Tracker.pull_device().
        # 10k quiet hosts pay one np.add.at per collect instead of a
        # Python loop over every touched node.
        self._node_pending = np.zeros(self.n_nodes, dtype=np.int64)
        self._table = table
        name_nodes: Dict[str, List[int]] = {}
        for i, (nm, _kind) in enumerate(names):
            name_nodes.setdefault(nm, []).append(i)
        for nm, nodes in name_nodes.items():
            host = engine.hosts_by_name.get(nm)
            if host is not None:
                host.tracker._device_feed = (self, nodes)
            else:
                # table row: the table folds these nodes' deltas into its
                # tracker columns, and wires the feed at materialization
                table.set_device_nodes(nm, nodes, self)

    # -- state ------------------------------------------------------------
    def _init_state(self):
        if self._shard is not None:
            f = len(self._shard["src"])
            h = len(self._shard["refill"])
            tokens0 = self._shard["capacity"]
        else:
            f, h = self.n_flows, self.n_nodes
            tokens0 = self.capacity_step
        from ..ops.torcells_device import RING_DTYPE
        zeros_f = np.zeros(f, dtype=np.int64)
        state = (np.int64(self._ticks_synced),
                 zeros_f.copy(),                                   # queued
                 np.zeros((self.ring_len, f), dtype=RING_DTYPE),   # ring
                 tokens0.copy(),                                   # tokens
                 zeros_f.copy(),                                   # delivered
                 zeros_f.copy(),                                   # target
                 np.full(f, -1, dtype=np.int64),                   # done_tick
                 np.zeros(h, dtype=np.int64))                      # node_sent
        if self.mode == "device":
            import jax.numpy as jnp
            state = tuple(jnp.asarray(a) for a in state)
        self._state = state
        self._flow_args_cached = None
        self._zero_inject_cached = None
        self._chain_done = np.full(self.n_chains, -1, dtype=np.int64)

    def _setup_sharding(self, n_dev: int) -> None:
        """The ONE sharding entry point: the mesh plane (parallel/mesh/)
        owns partition, exchange schedule, kernel, and metrics."""
        from .mesh.meshplane import attach_mesh
        attach_mesh(self, n_dev)

    def _unshard_state(self, lay) -> tuple:
        """Translate the live padded state back to the ORIGINAL flow/node
        space under layout ``lay`` — the inverse of the pad_state
        translation: flow arrays gather through ``inv``, node arrays
        scatter through ``node_src`` (each global node lives on exactly
        one shard, so the scatter is an assignment)."""
        t, queued, ring, tokens, delivered, target, done_tick, node_sent = \
            (np.asarray(a) for a in self._state)
        inv = lay["inv"]
        node_src = lay["node_src"]
        valid = node_src >= 0
        tok = np.zeros(self.n_nodes, dtype=np.int64)
        sent = np.zeros(self.n_nodes, dtype=np.int64)
        tok[node_src[valid]] = tokens[valid]
        sent[node_src[valid]] = node_sent[valid]
        return (np.int64(t), queued[inv], np.ascontiguousarray(ring[:, inv]),
                tok, delivered[inv], target[inv], done_tick[inv], sent)

    @staticmethod
    def _state_digest(state) -> str:
        """Canonical digest of an original-space state tuple (dtype, shape,
        bytes per tensor) — the re-layout pin: translating state between
        device layouts must be the identity in the original space."""
        import hashlib
        h = hashlib.sha256()
        for a in state:
            arr = np.asarray(a)
            h.update(str(arr.dtype).encode())
            h.update(str(arr.shape).encode())
            h.update(np.ascontiguousarray(arr).tobytes())
        return h.hexdigest()

    def _reshard(self, engine) -> None:
        """Mid-run device loss on the sharded mesh (ROADMAP 4(b)): at a
        quiesced round boundary (no dispatch in flight), translate the
        live padded state back to the original flow space, re-run the
        chain partitioner and BvN exchange schedule for the surviving
        D-1 devices, translate the state into the new layout, and PIN the
        round trip — the original-space digest before the re-layout must
        equal the digest read back through the new layout, or the run
        aborts loudly.  The plane's mode, pipeline, superwindow and
        checkpoint contracts are untouched; only the layout moved.
        D=2 loses the mesh entirely and continues on the single-device
        kernel (same digest pin, identity translation)."""
        import time as _wt
        t0 = _wt.perf_counter_ns()
        old = self._shard
        n_old = int(old["n_shards"])
        n_new = n_old - 1
        old_info = self._meshinfo
        orig = self._unshard_state(old)
        digest_before = self._state_digest(orig)
        # old-layout kernels and caches die with the lost device
        self._sharded_variants.clear()
        self._flow_args_cached = None
        self._zero_inject_cached = None
        if n_new < 2:
            self._mesh = None
            self._shard = None
            self._sharded_step = None
            self._mesh_make_step = None
            self._chain_leg_bits = None
            self._full_leg_bits = 0
            self._active_leg_bits = 0
            state = orig
            if old_info is not None:
                old_info.n_devices = 1
                old_info.exchange_mode = "single"
            digest_after = self._state_digest(state)
        else:
            self._setup_sharding(n_new)
            # the new schedule's leg numbering shares nothing with the old
            # mask bookkeeping: run the always-correct full kernel from
            # here on (-1 is the full-kernel sentinel; future activations
            # OR into it harmlessly)
            self._active_leg_bits = -1
            lay = self._shard
            from .mesh.partition import pad_state
            keep, src = lay["keep"], lay["src"]
            ring_o = orig[2]
            ring_p = np.zeros((self.ring_len, len(src)), dtype=ring_o.dtype)
            ring_p[:, keep] = ring_o[:, src[keep]]
            node_src = lay["node_src"]
            valid = node_src >= 0
            tok_p = np.zeros(len(node_src), dtype=np.int64)
            sent_p = np.zeros(len(node_src), dtype=np.int64)
            tok_p[valid] = orig[3][node_src[valid]]
            sent_p[valid] = orig[7][node_src[valid]]
            state = (orig[0], pad_state(lay, orig[1]), ring_p, tok_p,
                     pad_state(lay, orig[4]), pad_state(lay, orig[5]),
                     pad_state(lay, orig[6], fill=-1), sent_p)
            self._state = state
            digest_after = self._state_digest(self._unshard_state(lay))
            # runtime counters survive the re-layout (the schedule-shape
            # fields are the NEW mesh's, by design)
            self._meshinfo.cross_shard_cells += old_info.cross_shard_cells
            self._meshinfo.host_bounces += old_info.host_bounces
        if digest_after != digest_before:
            raise RuntimeError(
                f"device plane re-shard {n_old}->{n_new}: state digest "
                f"changed across the re-layout ({digest_before[:12]} != "
                f"{digest_after[:12]}) — the translation is not the "
                "identity; aborting rather than continuing on corrupt "
                "state")
        if self.mode == "device":
            import jax.numpy as jnp
            state = tuple(jnp.asarray(a) for a in state)
        self._state = state
        engine.supervision.count_reshard(
            n_old, n_new, mttr_ns=_wt.perf_counter_ns() - t0)

    def _read_summaries(self):
        """(delivered, done_tick, node_sent) in the ORIGINAL flow/node
        space, whatever the execution layout.  Final-state reader for
        tests/tooling (e.g. the conservation gate) — the engine hot path
        never calls this; consume() reads the packed flush buffer, and
        materializing full state tensors here would forfeit the pipeline
        if it ever crept into a per-round path."""
        delivered = np.asarray(self._state[4])
        done_tick = np.asarray(self._state[6])
        node_sent = np.asarray(self._state[7])
        if self._shard is None:
            return delivered, done_tick, node_sent
        inv = self._shard["inv"]
        node_src = self._shard["node_src"]
        global_sent = np.zeros(self.n_nodes, dtype=np.int64)
        valid = node_src >= 0
        np.add.at(global_sent, node_src[valid], node_sent[valid])
        return delivered[inv], done_tick[inv], global_sent

    def _flow_args(self):
        """The static flow tables, resident where the kernel runs: committed
        device buffers in device mode (uploaded ONCE — re-sending ~2 MB of
        int64 tables per dispatch at 10k circuits would waste host link
        bandwidth every round), plain numpy for the twin, which takes the
        first ``_TWIN_TABLES``."""
        if self._flow_args_cached is None:
            args = (self.flow_node, self.flow_lat_steps, self.flow_succ,
                    self.seg_start, self.refill_step, self.capacity_step,
                    self.last_flow, self.flow_pred, self.node_seg)
            if self.mode == "device":
                import jax.numpy as jnp
                args = tuple(jnp.asarray(a) for a in args)
            self._flow_args_cached = args
        return self._flow_args_cached

    def _zero_inject(self):
        """A reusable (device-resident in device mode) zero inject vector in
        the execution layout — most dispatches carry no injections, and
        re-uploading two [F] int64 zero vectors per dispatch is exactly the
        per-round transfer chatter the pipeline exists to cut."""
        if self._zero_inject_cached is None:
            f = len(self._shard["src"]) if self._shard is not None \
                else self.n_flows
            z = np.zeros(f, dtype=np.int64)
            if self.mode == "device":
                import jax.numpy as jnp
                z = jnp.asarray(z)
            self._zero_inject_cached = z
        return self._zero_inject_cached

    def _compact_width(self) -> int:
        """The compacted width this dispatch runs: the smallest that holds
        the live flows, or 0 for the full-width program (past the largest
        width, on the numpy twin, a mesh or a fleet lane)."""
        if (self.mode != "device" or self._shard is not None
                or self._lane is not None):
            return 0
        return next((w for w in self._compact_widths
                     if w >= self._live_flows), 0)

    def _live_table(self, width: int, pairs) -> np.ndarray:
        """The compacted program's ``live`` operand: the live chains' flow
        positions, ascending and padded with F to ``width``, with the
        staged ``pairs``' cells and targets at their chains' entry and exit
        flows, and each position's chain.  O(live flows) host work."""
        chains = np.fromiter(self._live, dtype=np.int64, count=len(self._live))
        n = self._chain_len[chains]
        rows = np.repeat(self._chain_base[chains] - np.cumsum(n) + n, n) \
            + np.arange(int(n.sum()))
        pos = np.sort(self._chain_rows[rows])
        live = np.zeros((4, width), dtype=np.int64)
        live[0] = self.n_flows
        live[0, :len(pos)] = pos
        live[3, :len(pos)] = self.flow_circ[pos]
        for circ, cells in pairs:
            live[1, np.searchsorted(pos, self.first_flow[circ])] += cells
            live[2, np.searchsorted(pos, self.last_flow[circ])] += cells
        return live

    # -- app-facing -------------------------------------------------------
    def activate(self, client_name: str, cells: Optional[int] = None) -> int:
        """Called by the client app once its circuit is built: inject both
        directions' cells (download at the server's chain head, upload at
        the client's) on the next dispatch."""
        spec = self._by_client.get(client_name)
        if spec is None:
            raise ValueError(f"{client_name} has no device flow spec")
        if cells is not None and cells < 1:
            # a zero-cell chain's completion (target > 0) can never fire, so
            # the joining client would block until end_time — reject loudly
            raise ValueError(
                f"{client_name}: activate(cells={cells}) — device flows "
                "need at least 1 cell")
        return self._activate_spec(spec, cells)

    def _activate_spec(self, spec, cells: Optional[int] = None) -> int:
        """Inject a spec's cells (shared by name-keyed plugin activation
        and circuit-indexed auto staging — auto flows are not in
        ``_by_client``, a host may carry many of them)."""
        # an explicit cells argument overrides the DOWNLOAD size; the
        # configured upload still runs (completion requires both chains)
        down = spec.cells_down if cells is None else cells
        up = spec.cells_up
        self._inject_buf.append((2 * spec.circuit, down))
        if up:
            self._inject_buf.append((2 * spec.circuit + 1, up))
        if self._chain_leg_bits is not None:
            # quiet-tick fusion bookkeeping: the chains this injection
            # activates may now carry cells over their exchange legs —
            # the active-leg superset only ever GROWS (in-flight cells
            # never migrate legs), which is what keeps every cached
            # masked variant digest-identical to the full kernel
            self._active_leg_bits |= int(self._chain_leg_bits[
                2 * spec.circuit])
            if up:
                self._active_leg_bits |= int(self._chain_leg_bits[
                    2 * spec.circuit + 1])
        self.total_injected_cells += down + up
        return spec.circuit

    def check_route(self, client_name: str, hops: List[str]) -> None:
        """Cross-check the client's RUNTIME route (hop host names in
        client-side order, e.g. [guard, middle, exit] for tor or [server]
        for star bulk) against the spec the flow table was built from.  A
        mismatch means an auto: client's fetched consensus diverged from
        the startup prediction — the flows would silently ride the wrong
        links, so fail loudly instead."""
        spec = self._by_client.get(client_name)
        if spec is None:
            raise ValueError(f"{client_name} has no device flow spec")
        expect = spec.route_up[1:-1] if len(spec.route_up) > 2 \
            else [spec.route_up[-1]]
        if list(hops) != expect:
            raise RuntimeError(
                f"device plane: {client_name}'s runtime route {hops} != "
                f"predicted route {expect} (the consensus diverged from "
                "the startup prediction — e.g. a relay published late)")

    def is_done(self, circuit: int) -> bool:
        return circuit in self._done

    def result(self, circuit: int) -> int:
        return self._done[circuit]

    def register_waiter(self, circuit: int, process, thread) -> None:
        self._waiters[circuit] = (process, thread)

    def warmup(self) -> None:
        """Pre-compile the windowed kernel for this plane's exact shapes,
        and its compacted program at each compacted width, using throwaway
        state (XLA compiles are 20-40s on a real TPU; the bench excludes
        them from timed walls).  No plane state is touched."""
        with self._profiler.tracer.annotate("plane.warmup"):
            self._warmup()

    def _warmup(self) -> None:
        if self.mode != "device":
            return
        if self._lane is not None:
            # fleet lanes share the batched program, compiled once per
            # (shape class, width) at the first launch — a per-lane
            # warmup would compile the UNBATCHED kernel nobody calls
            return
        import jax
        import jax.numpy as jnp
        from ..ops.torcells_device import (RING_DTYPE,
                                           compact_flush_for_backend,
                                           flush_halves,
                                           step_window_flush_for_backend)
        if self._flush_step is None:
            self._flush_step = step_window_flush_for_backend()
        if self._shard is not None:
            lay = self._shard
            fp, hp = len(lay["src"]), len(lay["refill"])
            zp = np.zeros(fp, dtype=np.int64)
            state = (np.int64(0), jnp.zeros(fp, jnp.int64),
                     jnp.zeros((self.ring_len, fp), RING_DTYPE),
                     jnp.asarray(lay["capacity"]),
                     jnp.zeros(fp, jnp.int64), jnp.zeros(fp, jnp.int64),
                     jnp.full(fp, -1, jnp.int64), jnp.zeros(hp, jnp.int64))
            out = self._sharded_step(
                *state, zp, zp, self._pad_targets([1]), np.int64(0),
                lay["flow_node_local"], lay["succ_global"],
                lay["seg_start_local"], lay["refill"], lay["capacity"],
                lay["arr_lat"], lay["shard_base"])
            jax.block_until_ready(out)
            return
        f, h = self.n_flows, self.n_nodes
        z = np.zeros(f, dtype=np.int64)

        def fresh():
            return (np.int64(0), jnp.zeros(f, jnp.int64),
                    jnp.zeros((self.ring_len, f), RING_DTYPE),
                    jnp.asarray(self.capacity_step),
                    jnp.zeros(f, jnp.int64), jnp.zeros(f, jnp.int64),
                    jnp.full(f, -1, jnp.int64), jnp.zeros(h, jnp.int64))

        out = self._flush_step(
            *fresh(), z, z, self._pad_targets([1]), np.int64(0),
            self.flow_node, self.flow_lat_steps, self.flow_succ,
            self.seg_start, self.refill_step, self.capacity_step,
            self.last_flow, self.flow_pred, self.node_seg,
            ring_len=self.ring_len)
        jax.block_until_ready(flush_halves(out[9]))        # its readback
        # every compacted width a dispatch may pick (_compact_width), with
        # a live table of padding alone, and the readback of its flush,
        # whose length is the width's
        self._compact_step = compact_flush_for_backend()
        for width in self._compact_widths:
            live = np.zeros((4, width), dtype=np.int64)
            live[0] = f
            out = self._compact_step(
                *fresh(), live, self._pad_targets([1]), np.int64(0),
                self.flow_node, self.flow_lat_steps, self.flow_succ,
                self.seg_start, self.refill_step, self.capacity_step,
                self.flow_pred, ring_len=self.ring_len)
            jax.block_until_ready((out, flush_halves(out[9])))

    def _pad_targets(self, targets: List[int]) -> np.ndarray:
        """Pad a superwindow's boundary list to the static kernel shape by
        repeating the final boundary (repeats are never reached: the loop
        ends at targets[-1])."""
        pad = self.superwindow_rounds
        out = np.full(pad, int(targets[-1]), dtype=np.int64)
        out[:len(targets)] = np.asarray(targets, dtype=np.int64)
        return out

    # -- engine-facing ----------------------------------------------------
    def negotiate_superwindow(self, nxt: int, lookahead: int, host_next: int,
                              end_time: int, cap_time: Optional[int],
                              max_rounds: int) -> Optional[int]:
        """Replay the K=1 round recurrence forward from the window the
        engine just computed ([nxt, nxt+lookahead)) and merge up to
        ``max_rounds`` consecutive rounds into ONE superwindow, stopping
        before the first round that would contain a host-side event
        (``host_next``: the earliest Python-queue or native-C-heap event) —
        or a checkpoint/resume boundary (``cap_time``).  Returns the merged
        span's end (the engine's new window_end) and stages a _SuperPlan
        for advance(), or None when no extension applies.

        The plan replicates advance()'s own cadence decisions exactly, so
        a K-round launch produces the same dispatch bases/targets — and,
        with the kernel's halt-at-completion rule, the same wake barriers —
        as K separate rounds: digest parity K=1-vs-K is by construction
        (tests/test_superwindow.py pins it).  That construction is why the
        auto-tuner (prof/autotune.py) may deepen K freely from measured
        launch costs: quiet rounds — including the quiet ticks between
        cross-shard exchange activity on a masked mesh variant — merge
        into one span launch with bit-identical results at any depth."""
        if (max_rounds <= 1 or self._state is None or self._inflight
                or self.superwindow_rounds <= 1):
            return None
        if (not self._inject_buf
                and self._cells_delivered_seen >= self._cells_dispatched):
            # empty plane: not driving windows; nothing to merge
            return None
        grid = TICK_NS * self.granule
        q = self.min_dispatch_steps
        synced = self._ticks_synced
        bounds: List[tuple] = []
        targets: List[int] = []
        round_of: List[int] = []
        ws = nxt
        for i in range(min(max_rounds, self.superwindow_rounds)):
            we = min(ws + lookahead, end_time)
            if i > 0 and cap_time is not None \
                    and (ws >= cap_time or we > cap_time):
                # a checkpoint/resume boundary at cap_time: the round
                # containing (or starting at) it must run K=1 so the
                # snapshot digest lands on an exact visited round boundary
                break
            if host_next < we:
                break               # a host event falls inside this round
            t_i = we // grid
            if t_i - synced >= q:   # advance()'s cadence rule, replayed
                targets.append(int(t_i))
                round_of.append(i)
                synced = t_i
            bounds.append((ws, we))
            nxt_dev = (synced + q) * grid
            if nxt_dev >= host_next or nxt_dev >= end_time:
                break               # next round would be host-driven
            ws = nxt_dev
        if len(bounds) < 2 or not targets:
            return None
        self._pending_plan = _SuperPlan(int(self._ticks_synced), targets,
                                        bounds, round_of)
        return bounds[-1][1]

    def advance(self, engine) -> None:
        """LAUNCH: dispatch the window step advancing the plane to the
        current round's barrier — or, when a superwindow was negotiated,
        through the whole merged span in ONE kernel launch.  Called at the
        TOP of the round (right after the engine computes the window), so
        the dispatch computes while the host drains the round's arrivals;
        consume() collects at the next loop iteration, always before the
        next window.  Staged injections (activations from earlier rounds)
        are folded in at the dispatch's base step — the engine has already
        committed the previous dispatch, so the one-deep in-flight slot is
        free here."""
        import time as _wt
        t0 = _wt.perf_counter_ns()
        assert not self._inflight, \
            "device plane: launch with an uncollected dispatch in flight"
        if self._fault_device_lost and self._shard is not None \
                and self._state is not None \
                and engine.rounds_executed + 1 >= self._fault_device_lost:
            # injected device loss: the plane is quiesced here (no dispatch
            # in flight — the assert above IS the boundary condition), so
            # re-partition onto the survivors before this round's launch
            self._fault_device_lost = 0
            self._reshard(engine)
        if self._auto_pos < len(self._auto):
            ws = engine.scheduler.window_start
            if self._state is None and not self._inject_buf \
                    and self.total_injected_cells == 0:
                # nothing has ever dispatched: re-base the step counter to
                # the window so the first dispatch does not grind through
                # the pre-traffic idle gap tick by tick
                self._ticks_synced = max(self._ticks_synced,
                                         ws // (TICK_NS * self.granule))
            self._stage_autos(ws)
        plan, self._pending_plan = self._pending_plan, None
        if plan is None:
            target_ticks = engine.scheduler.window_end // (TICK_NS
                                                           * self.granule)
            n = target_ticks - self._ticks_synced
            if n <= 0 and not self._inject_buf:
                return
            n = max(n, 0)
            if self._state is None:
                if not self._inject_buf and self.total_injected_cells == 0:
                    # nothing has ever activated: don't spin the kernel
                    self._ticks_synced = target_ticks
                    return
                self._init_state()
            elif (not self._inject_buf
                  and self._cells_delivered_seen >= self._cells_dispatched):
                # plane is empty: bank the ticks, skip the dispatch
                self._idle_ticks_banked += n
                self._ticks_synced = target_ticks
                self.idle_rounds_skipped += 1
                return
            if n < self.min_dispatch_steps:
                # cadence batching: let ticks (and injections) accumulate a
                # few rounds before paying a dispatch; next_time() keeps the
                # engine window loop coming back even when the Python plane
                # idles
                return
            targets = [int(target_ticks)]
        else:
            # superwindow: the plan's targets ARE the K=1 dispatch targets;
            # ticks_synced advances at consume, from the flush's t_stop
            # (the kernel may halt at an earlier boundary on a completion)
            targets = plan.targets
            n = targets[-1] - self._ticks_synced
        with self._profiler.tracer.annotate(
                "plane.launch", sim_ns=engine.scheduler.window_start):
            self._launch(engine, plan, targets, n, t0)

    def _launch(self, engine, plan: Optional[_SuperPlan], targets: List[int],
                n: int, t0: int) -> None:
        """advance()'s dispatch: fold the staged injections in at the base
        step and launch the kernel through ``targets`` (``n`` ticks past
        the synced step); ``t0`` is advance()'s entry stamp."""
        import time as _wt
        inject_pairs = list(self._inject_buf)
        self._inject_buf.clear()
        for circ, cells in inject_pairs:
            self._cells_dispatched += cells
            if circ not in self._live:
                self._live[circ] = None
                self._live_flows += int(self._chain_len[circ])
        from ..ops.torcells_device import MAX_CELLS_IN_FLIGHT
        if (self._cells_dispatched - self._cells_delivered_seen
                > MAX_CELLS_IN_FLIGHT):
            # an upper bound on every node's backlog: the kernel's int32
            # segment prefix sums are exact only below it
            raise ValueError(
                "device plane: more than 2**31-1 cells in flight — "
                "the kernel's int32 segment prefix sums would not be "
                "exact (shorten the transfers or split the run)")
        width = self._compact_width()
        if width:
            # the compacted program: the live flows' positions and their
            # injections in one [3, width] upload, nothing of length F
            live = self._live_table(width, inject_pairs)
            self.device_calls += 1              # live table upload
        elif inject_pairs:
            f = self.n_flows
            inject = np.zeros(f, dtype=np.int64)
            inject_target = np.zeros(f, dtype=np.int64)
            for circ, cells in inject_pairs:
                inject[self.first_flow[circ]] += cells
                inject_target[self.last_flow[circ]] += cells
            if self._shard is not None:
                from .mesh.partition import pad_state
                inject = pad_state(self._shard, inject)
                inject_target = pad_state(self._shard, inject_target)
            if self.mode == "device":
                self.device_calls += 1          # inject upload
        else:
            inject = inject_target = self._zero_inject()
        self._launch_width = width or (
            len(self._shard["src"]) if self._shard is not None
            else self.n_flows)
        self._launch_sizes = (width, width) if width \
            else (self.n_chains, self.n_nodes)
        idle = self._idle_ticks_banked
        self._idle_ticks_banked = 0
        # Step continuity: the kernel's carried t equals the last dispatch's
        # end step; _ticks_synced (pre-update here) additionally counts any
        # banked idle steps, so re-basing to it jumps t exactly over the
        # idle gap — legal because idle banking requires an empty ring — and
        # is the identity when nothing was banked.  (Re-basing to anything
        # else desynchronizes the arrival ring's absolute slots: cells would
        # be skipped or re-read — caught by an adversarial review repro and
        # now pinned by test_varying_dispatch_sizes_preserve_arrivals.)
        if self.mode == "device":
            # the log exists solely to recover a FAILED device dispatch;
            # the numpy twin executes synchronously and cannot leave a
            # failed in-flight slot, so logging there (or after demotion)
            # would only accumulate memory it can never use
            self._dispatch_log.append((int(self._ticks_synced),
                                       inject_pairs, list(targets),
                                       int(idle)))
        state = (np.int64(self._ticks_synced), *self._state[1:])
        tvec = self._pad_targets(targets)
        if self._shard is not None:
            lay = self._shard
            out = self._pick_sharded_step()(
                *state, inject, inject_target,
                tvec, np.int64(idle), lay["flow_node_local"],
                lay["succ_global"], lay["seg_start_local"],
                lay["refill"], lay["capacity"], lay["arr_lat"],
                lay["shard_base"])
        elif self.mode == "device" and self._lane is not None:
            # fleet lane (ISSUE 18): the dispatch parks at the shared
            # plane's barrier and returns this lane's row of the vmapped
            # launch — a real-shaped, already-materialized numpy
            # 10-tuple, so consume() runs unchanged (the collect is a
            # no-op np.asarray).  Synchronous by construction: the
            # digest-pinned --device-plane-sync shape.
            out = self._lane.dispatch(state, np.asarray(inject),
                                      np.asarray(inject_target), tvec,
                                      int(idle))
        elif width:
            if self._compact_step is None:
                from ..ops.torcells_device import compact_flush_for_backend
                self._compact_step = compact_flush_for_backend()
            tables = self._flow_args()
            out = self._compact_step(*state, live, tvec, np.int64(idle),
                                     *tables[:6], tables[7],
                                     ring_len=self.ring_len)
            self.compact_dispatches += 1
        elif self.mode == "device":
            if self._flush_step is None:
                from ..ops.torcells_device import (
                    step_window_flush_for_backend)
                self._flush_step = step_window_flush_for_backend()
            out = self._flush_step(*state, inject, inject_target,
                                   tvec, np.int64(idle),
                                   *self._flow_args(),
                                   ring_len=self.ring_len)
        else:
            from ..ops.torcells_device import torcells_step_window_numpy_flush
            out = torcells_step_window_numpy_flush(*state, inject,
                                                   inject_target, tvec, idle,
                                                   *self._flow_args()[
                                                       :_TWIN_TABLES],
                                                   self.ring_len)
        self._state = out[:8]
        self._flush_handle = out[9]
        if plan is None:
            # single-target dispatch: the kernel cannot halt before its one
            # boundary, so the reached step is known without the flush
            self._ticks_synced = targets[-1]
        else:
            self._active_plan = plan
        self._inflight = True
        self.dispatches += 1
        if self.mode == "device":
            self.device_calls += 1              # the dispatch itself
            if self._sync:
                # serial oracle: idle through the kernel instead of
                # overlapping — everything else is identical, so digests
                # must match the pipelined run bit for bit
                import jax
                jax.block_until_ready(self._flush_handle)
        if self._fault_dispatch and self.dispatches == self._fault_dispatch \
                and self.mode == "device":
            # fault harness: this dispatch's collect raises (or hangs) —
            # consume() must recover via the numpy-twin replay (device-only:
            # the twin has no asynchronous slot to poison)
            self._flush_handle = _PoisonedFlush(self._flush_handle,
                                                hang=self._fault_hang)
            self._fault_dispatch = 0
        # per-launch predicted device cost (ISSUE 15): per-tick step
        # kernel + exchange collectives, plus the fixed transfer, from
        # the measured model.  Stored as (per-step, fixed) — a
        # superwindow kernel may HALT at an earlier negotiated boundary
        # on a completion, so consume() scales the per-step half by the
        # steps actually reached (flush t_stop) before judging the
        # band; predicting the full plan span would flag early-halted
        # windows as model-stale on a perfectly calibrated model.
        self._launch_pred = None       # (per_step_us, fixed_us)
        # the kernel's carried t runs from this base to the reached
        # boundary: steps executed = t_stop - base (idle-banked ticks
        # are a re-base jump, not loop iterations, so they don't count)
        self._launch_base = int(targets[-1]) - int(n)
        if self._costmodel is not None and self.mode == "device":
            if self._shard is not None:
                kernel_flows = len(self._shard["src"])
                ex_us = self._meshinfo.predicted_us
            else:
                kernel_flows = self._launch_width
                ex_us = 0.0
            # only predict INSIDE the model's measured range (the
            # two-sided CostModel.covers guard): a table far below the
            # smallest — or above the largest — calibrated flow count
            # would be judged by pure extrapolation and flood
            # prof.model_stale with false positives
            if self._costmodel.covers(kernel_flows):
                self._launch_pred = (
                    self._costmodel.step_us(kernel_flows)
                    + max(ex_us, 0.0),
                    self._costmodel.transfer_us())
        self._launch_wall = _wt.perf_counter_ns()
        self.launch_ns += self._launch_wall - t0
        if self._idle_from is not None:
            self.idle_ns += self._launch_wall - self._idle_from
            self._idle_from = None
        self._profiler.on_dispatch(t0, self._launch_wall, int(n),
                                   len(inject_pairs), self.dispatches,
                                   engine.scheduler.window_end)

    def consume(self, engine) -> None:
        """COLLECT: materialize the in-flight dispatch's packed flush
        buffer (ONE device->host transfer), wake completed flows, and feed
        the per-node byte deltas to the trackers.  Runs before the engine
        computes the next window (same contract as the tpu policy's
        consume_flush).  An exception raised inside the in-flight dispatch
        surfaces HERE, at materialization — nothing is caught."""
        if not self._inflight:
            return
        import time as _wt
        t0 = _wt.perf_counter_ns()
        self.pipeline_overlap_ns += t0 - self._launch_wall
        # the slot is released up front so state stays consistent whether
        # the collect succeeds, raises, or is recovered
        handle, self._flush_handle = self._flush_handle, None
        sizes = self._launch_sizes
        self._inflight = False
        t_read = None
        with self._profiler.tracer.span(
                "device.collect", "device",
                sim_ns=engine.scheduler.window_start,
                args={"dispatch": self.dispatches}):
            try:
                # blocks iff still computing; a failure inside the
                # in-flight dispatch RAISES here (guarded by
                # --device-watchdog-sec), and the dispatch guard recovers
                # it on the numpy twin
                flush, t_read = self._collect_flush(engine, handle)
                if hasattr(handle, "block_until_ready"):
                    self.flush_bytes_read += flush.nbytes
            except Exception as e:  # noqa: BLE001 - any dispatch failure
                flush = self._recover_dispatch(
                    engine, e, injected=isinstance(handle, _PoisonedFlush))
                # the twin's replay packs the full-length flush
                sizes = (self.n_chains, self.n_nodes)
        t1 = _wt.perf_counter_ns()
        # wait up to the readback's start; a recovered dispatch read
        # nothing from the device, so all of it counts as wait
        t_read = t1 if t_read is None else min(max(t_read, t0), t1)
        self.wait_ns += t_read - t0
        self.readback_ns += t1 - t_read
        self._profiler.on_collect(self._launch_wall, t0, t1 - t0,
                                  int(getattr(flush, "nbytes", 0)),
                                  self.dispatches,
                                  engine.scheduler.window_start)
        with self._profiler.tracer.annotate(
                "plane.fold", sim_ns=engine.scheduler.window_start):
            self._fold(engine, flush, sizes, t0, t1)
        self.fold_ns += _wt.perf_counter_ns() - t1
        self._idle_from = t1

    def _fold(self, engine, flush: np.ndarray, sizes: Tuple[int, int],
              t0: int, t1: int) -> None:
        """consume()'s host fold of one collected flush buffer, packed at
        section capacities ``sizes`` (``t0``, ``t1``: the collect's start
        and end stamps): parse it, advance the window bookkeeping, fold
        node byte deltas and wake completed flows."""
        if self.mode == "device":
            self.device_calls += 1              # the flush read
        from ..ops.torcells_device import flush_moved, parse_flush
        (forwards, delivered_sum, t_stop, done_chains, done_steps, node_idx,
         node_delta) = parse_flush(flush, *sizes)
        steps_done = max(int(t_stop) - self._launch_base, 0)
        self.ticks_stepped += steps_done
        self.flow_ticks_moved += flush_moved(flush)
        self.flow_ticks_stepped += steps_done * self._launch_width
        # launch attribution (ISSUE 15): predicted-vs-measured per-launch
        # gauges and the model-stale band check — one call per collect,
        # ~free when no model is loaded and observability is off.  Placed AFTER parse_flush
        # so the prediction covers the steps the kernel actually REACHED
        # (t_stop): a superwindow halting early on a completion is
        # judged on its real span, never flagged stale for not running
        # the merged rounds it skipped.  Device mode only — the numpy
        # twin's host-side walls must not pollute the launch gauges.
        if self.mode == "device":
            pred_us = None
            if self._launch_pred is not None:
                per_step, fixed = self._launch_pred
                pred_us = steps_done * per_step + fixed
            self._profiler.on_window(
                self._launch_wall, t1, t1 - t0, pred_us,
                self._costmodel.band if self._costmodel is not None
                else 0.0)
        if self._meshinfo is not None:
            # mesh flush: ONE trailing slot carries the window's
            # cross-shard cell count (zero extra device reads; a
            # standard-length buffer — the numpy twin after a demotion —
            # contributes 0)
            from .mesh.exchange import mesh_flush_extra
            self._meshinfo.cross_shard_cells += mesh_flush_extra(
                flush, self.n_chains, self.n_nodes)
            if self.mode == "numpy" and forwards > 0 \
                    and self._meshinfo.cross_edges > 0:
                # demoted sharded plane: this window's cross-shard
                # forwards executed HOST-side on the twin — counted so
                # the mesh.host_bounces == 0 steady-state gate is
                # falsifiable, not a tautology (the fault drill pins it
                # going nonzero after a demotion)
                self._meshinfo.host_bounces += 1
        self.total_forwards += forwards
        self._cells_delivered_seen = delivered_sum
        plan, self._active_plan = self._active_plan, None
        if plan is not None:
            # superwindow collect: the kernel reached t_stop — the plan's
            # final boundary, or an earlier one when a completion halted
            # it.  Rewind the engine's bookkeeping to the virtual round
            # that launched the reached span: the window bounds become that
            # round's (so completion wakes clamp to ITS barrier, exactly
            # as K=1 would), and the round counter advances by the merged
            # rounds actually covered (state digests carry it).
            try:
                j = plan.targets.index(t_stop)
            except ValueError:
                raise AssertionError(
                    f"device plane: superwindow stopped at step {t_stop}, "
                    f"not one of its negotiated boundaries {plan.targets}")
            r = plan.round_of[j]
            ws, we = plan.bounds[r]
            engine.scheduler.set_window(ws, we)
            engine.rounds_executed += r
            self._ticks_synced = t_stop
            self.superwindows += 1
            self._rounds_launched += r + 1
        else:
            self._rounds_launched += 1

        # trackers: per-node spent-byte deltas, delta-compacted on device,
        # folded with ONE numpy scatter-add; the per-host split into
        # Tracker counters happens on read (Tracker.pull_device) — the
        # vectorized control-plane cut (ISSUE 7)
        if len(node_idx):
            np.add.at(self._node_pending, node_idx, node_delta)
        # a chain whose completion came back holds no cell: it leaves the
        # live set (a later injection brings it back)
        for chain in done_chains.tolist():
            if chain in self._live:
                del self._live[chain]
                self._live_flows -= int(self._chain_len[chain])

        # wake completed clients: BOTH chains (download 2c, upload 2c+1)
        # must have delivered; wake at the later completion step
        # (deterministic: ticks from the kernel, clamped to the barrier —
        # under a superwindow the halt rule guarantees every completion
        # here belongs to the span whose barrier the window now carries).
        # Only the chains that newly completed THIS dispatch arrive in the
        # flush buffer — O(completions), not O(circuits), per collect.
        # The batched wake fold (ISSUE 10): wake times are computed in one
        # vectorized pass and the events land in the scheduler through ONE
        # push_batch call instead of a per-circuit push chain; the wake
        # event itself then resumes the client directly (the wake IS the
        # continue — _device_wake_task), so a completed flow costs one
        # scheduler round-trip, not two.
        if len(done_chains):
            barrier = engine.scheduler.window_end
            self._chain_done[done_chains] = done_steps
            circs = np.unique(np.asarray(done_chains) >> 1)
            d = self._chain_done[2 * circs]
            u = self._chain_done[2 * circs + 1]
            ready = (d >= 0) & ((u >= 0) | ~self._has_upload[circs])
            steps = np.maximum(d, u)
            wakes = np.maximum((steps + 1) * TICK_NS * self.granule,
                               barrier)
            # ONE fold loop for both delivery sinks, so the done-guard /
            # decline rules can never desync between the planes: under the
            # native plane the wakes land as C-heap continuation events in
            # ONE push_cont_batch extension call (ISSUE 12 — same per-host
            # sequence claims, same wake times, no Python Task/Event per
            # flow); otherwise as Events through one push_batch call
            native = getattr(engine, "native_plane", None)
            make = self._make_wake_item if native is not None \
                else self._make_wake_event
            items = []
            for circ, wake in zip(circs[ready].tolist(),
                                  wakes[ready].tolist()):
                if circ in self._done:
                    continue
                self._done[circ] = wake
                item = make(engine, circ, wake)
                if item is not None:
                    items.append(item)
            if items:
                if native is not None:
                    native.push_device_wakes(items)
                else:
                    engine.counters.count_new("event", len(items))
                    engine.scheduler.policy.push_batch(
                        items, 0, engine.scheduler.window_end)
        # probation clock (ISSUE 17): each clean collect on the demoted
        # twin counts toward re-promotion; the threshold re-attempts the
        # device rung once (permanent-on-repeat preserved via _repromoted)
        if (self.demoted and self.mode == "numpy"
                and self._repromote_after > 0 and not self._repromoted):
            self._probation_clean += 1
            if self._probation_clean >= self._repromote_after:
                self._repromote(engine)

    def _read_flush(self, handle) -> Tuple[np.ndarray, int]:
        """Block until the dispatch behind ``handle`` is done, then copy
        its flush buffer to the host: (buffer, perf_counter_ns stamp
        between the two).  A handle that is already host memory (the numpy
        twin, a fleet lane's row) has nothing to wait for.  A single
        device's full-length flush comes over as its int32 halves
        (flush_halves)."""
        import time as _wt
        tracer = self._profiler.tracer
        block = getattr(handle, "block_until_ready", None)
        if block is not None:
            with tracer.annotate("plane.wait"):
                block()
        t_read = _wt.perf_counter_ns()
        with tracer.annotate("plane.readback"):
            if block is not None and self._shard is None:
                from ..ops.torcells_device import (flush_from_halves,
                                                   flush_halves)
                return flush_from_halves(np.asarray(flush_halves(handle))), \
                    t_read
            return np.asarray(handle), t_read

    def _collect_flush(self, engine, handle) -> Tuple[np.ndarray, int]:
        """Materialize the in-flight dispatch's flush buffer (see
        _read_flush), bounded by ``--device-watchdog-sec`` in device mode:
        the blocking read runs on the collect thread (_CollectThread) so
        a dispatch that never completes (wedged runtime, lost device)
        raises TimeoutError here instead of freezing the round loop
        forever.  Only the guard's bookkeeping (the job's hand-off and the
        wait's return) is charged to supervision overhead — the wait for
        the result is the dispatch's own cost, watchdog or not."""
        if self.mode != "device" or self._watchdog_sec <= 0:
            return self._read_flush(handle)
        import time as _wt
        t_g = _wt.perf_counter_ns()
        # the result box is written by the collect thread and read by the
        # dispatcher: one lock covers both sides (simrace SIM102 — a
        # timed-out wait returning does NOT order the abandoned
        # thread's late write against the dispatcher's read, so the
        # dict-sharing idiom was a real, if narrow, race window)
        box: Dict[str, object] = {}
        box_lock = threading.Lock()
        done = threading.Event()

        def _work() -> None:
            try:
                out = self._read_flush(handle)
            except BaseException as e:  # noqa: BLE001 - forwarded below
                with box_lock:
                    box["err"] = e
            else:
                with box_lock:
                    box["out"] = out
            done.set()

        collector = _CollectThread.get()
        collector.submit(_work)
        engine.supervision.overhead_ns += _wt.perf_counter_ns() - t_g
        if not done.wait(self._watchdog_sec):
            # the collect thread is abandoned with the handle (it cannot
            # be interrupted mid-XLA-call); the numpy replay takes over
            collector.abandon()
            raise TimeoutError(
                f"device dispatch did not complete within "
                f"{self._watchdog_sec:.0f}s (--device-watchdog-sec)")
        t_g = _wt.perf_counter_ns()
        with box_lock:
            err = box.get("err")
            out = box.get("out")
        if err is not None:
            raise err
        engine.supervision.overhead_ns += _wt.perf_counter_ns() - t_g
        return out

    def _pick_sharded_step(self):
        """The sharded kernel variant for this dispatch (quiet-tick
        exchange-leg fusion): when the active chains touch only a subset
        of the schedule's legs, run a variant with the quiet legs
        compiled out — each masked ppermute leg is one collective launch
        saved per tick, and an all-masked span issues zero exchange
        collectives.  The active-leg set only grows, every variant is a
        superset of the cells actually in flight, and a full compile
        cache falls back to the always-correct full kernel."""
        if self._chain_leg_bits is None or self._full_leg_bits == 0:
            return self._sharded_step
        bits = self._active_leg_bits
        full = self._full_leg_bits
        if bits < 0 or full < 0 or bits == full:
            if self._meshinfo is not None:
                self._meshinfo.legs_active = full.bit_length() \
                    if full >= 0 else self._meshinfo.legs
            return self._sharded_step
        step = self._sharded_variants.get(bits)
        if step is None:
            if len(self._sharded_variants) >= 4:
                # compile budget spent: the full kernel is always right
                if self._meshinfo is not None:
                    self._meshinfo.legs_active = full.bit_length()
                return self._sharded_step
            n_legs = full.bit_length()
            mask = tuple(bool(bits >> k & 1) for k in range(n_legs))
            step = self._mesh_make_step(mask)
            self._sharded_variants[bits] = step
            DeviceTrafficPlane.sharded_variants_high_water = max(
                DeviceTrafficPlane.sharded_variants_high_water,
                len(self._sharded_variants))
        if self._meshinfo is not None:
            self._meshinfo.legs_active = bin(bits).count("1")
        return step

    def _recover_dispatch(self, engine, exc: BaseException,
                          injected: bool = False) -> np.ndarray:
        """Graceful device-plane degradation: the in-flight dispatch failed
        (exception or watchdog timeout), so rebuild the plane's state by
        replaying the FULL logged window history on the bit-identical numpy
        twin — the carried device state is donated on accelerators, so
        there is no pre-state buffer to restart from — and PERMANENTLY
        demote the backend to the twin.  Digest parity is preserved (the
        twin is the parity oracle the tests pin); device speed is
        forfeited.  Returns the failed window's flush buffer, which the
        caller consumes exactly as if the device had produced it.  A
        failure the fault harness did not inject (``injected`` False)
        makes the run exit non-zero (SupervisionStats)."""
        get_logger().warning(
            "device-plane",
            f"in-flight dispatch failed ({exc!r}); replaying "
            f"{len(self._dispatch_log)} windows on the numpy twin and "
            "permanently demoting the backend to numpy")
        self.mode = "numpy"
        self.demoted = True
        self.recoveries += 1
        engine.supervision.count_dispatch_recovery(
            f"device dispatch recovered on the numpy twin ({exc!r}); "
            "backend demoted for the rest of the run", injected=injected)
        self._mesh = None
        self._shard = None
        self._sharded_step = None
        self._sharded_variants.clear()
        self._chain_leg_bits = None
        self._flush_step = None
        # predictions are calibrated for the DEVICE kernels; the numpy
        # twin must not be judged (or scheduled) by them
        self._costmodel = None
        self._costmodel_status = "demoted"
        self._launch_pred = None
        self._flow_args_cached = None
        self._zero_inject_cached = None
        from ..ops.torcells_device import (RING_DTYPE,
                                           torcells_step_window_numpy_flush)
        f, h = self.n_flows, self.n_nodes
        if self._replay_base is not None:
            # the window-replay guard armed at re-promotion: this is the
            # re-promoted rung failing AGAIN — replay from the stashed
            # probation-exit state plus the log since, then the demotion
            # is permanent (self._repromoted blocks another probation)
            state = tuple(np.asarray(a).copy() for a in self._replay_base[1])
        else:
            state = (np.int64(0), np.zeros(f, dtype=np.int64),
                     np.zeros((self.ring_len, f), dtype=RING_DTYPE),
                     self.capacity_step.copy(),
                     np.zeros(f, dtype=np.int64), np.zeros(f, dtype=np.int64),
                     np.full(f, -1, dtype=np.int64),
                     np.zeros(h, dtype=np.int64))
        # plain numpy now that mode flipped
        args = self._flow_args()[:_TWIN_TABLES]
        flush = None
        for base, pairs, targets, idle in self._dispatch_log:
            inject = np.zeros(f, dtype=np.int64)
            inject_target = np.zeros(f, dtype=np.int64)
            for circ, cells in pairs:
                inject[self.first_flow[circ]] += cells
                inject_target[self.last_flow[circ]] += cells
            out = torcells_step_window_numpy_flush(
                np.int64(base), *state[1:], inject, inject_target,
                self._pad_targets(targets), np.int64(idle), *args,
                self.ring_len)
            state = out[:8]
            flush = out[9]
        self._state = state
        assert flush is not None, "recovery with an empty dispatch log"
        self._dispatch_log.clear()      # demoted: the log has no future use
        self._replay_base = None
        # arm the probation clock (ISSUE 17): after --repromote-after
        # clean collects on the twin, consume() re-attempts the device
        # rung once.  A rung that already climbed back stays down for good.
        self._probation_clean = 0
        return flush

    def _repromote(self, engine) -> None:
        """Climb back up the recovery ladder (ISSUE 17): the numpy
        demotion served its probation, so re-attempt the device rung ONCE
        with the window-replay guard re-armed — the current twin state is
        stashed as the replay base, so a second dispatch failure rebuilds
        from it (base + log replay) and re-demotes permanently.  Single-
        device rung only: a mesh lost to a real fault re-enters through
        the re-shard path, not here."""
        import jax.numpy as jnp
        self._replay_base = (int(self._ticks_synced),
                             tuple(np.asarray(a).copy()
                                   for a in self._state))
        self._dispatch_log.clear()
        self.mode = "device"
        self.demoted = False
        self._repromoted = True
        self._flush_step = None
        self._flow_args_cached = None
        self._zero_inject_cached = None
        self._state = tuple(jnp.asarray(a) for a in self._state)
        engine.supervision.count_repromotion("device plane backend",
                                             self._probation_clean)

    def _make_wake_event(self, engine, circuit: int,
                         when: int) -> Optional[Event]:
        """Build (not push) one completion-wake event; consume() lands the
        whole collect's wakes in one push_batch call."""
        if when >= engine.end_time:
            return None
        if self.specs[circuit].auto_start_ns is not None:
            # processless flow: no client will ever join — a wake event
            # would only materialize a quiet table row for nothing
            return None
        waiter = self._waiters.pop(circuit, None)
        host = self.engine.host_by_name(self.specs[circuit].client_name)
        task = Task(_device_wake_task, (self, circuit, waiter), None,
                    name="device_flow_done")
        return Event(task, when, host, host, host.next_event_sequence())

    def _make_wake_item(self, engine, circuit: int, when: int):
        """The _make_wake_event twin for the native continuation plane:
        (when, host, plane, circuit, waiter) for push_device_wakes —
        identical decline rules, the sequence claim deferred to the ONE
        push_cont_batch extension call (same per-host counter, same
        order)."""
        if when >= engine.end_time:
            return None
        if self.specs[circuit].auto_start_ns is not None:
            return None
        waiter = self._waiters.pop(circuit, None)
        host = self.engine.host_by_name(self.specs[circuit].client_name)
        return (when, host, self, circuit, waiter)

    def _stage_autos(self, now_ns: int) -> None:
        """Activate every processless flow whose start time has been
        reached (injections enter at the next dispatch base, like an app
        activation staged last round)."""
        while self._auto_pos < len(self._auto) \
                and self._auto[self._auto_pos][0] <= now_ns:
            _t, circ = self._auto[self._auto_pos]
            self._auto_pos += 1
            self._activate_spec(self.specs[circ])

    def busy(self) -> bool:
        """True while the plane still has work the engine must keep
        windows advancing for (undelivered cells, buffered injections, an
        unconsumed dispatch, or un-started processless flows)."""
        return (bool(self._inject_buf) or self._inflight
                or self._cells_delivered_seen < self._cells_dispatched
                or self._auto_pos < len(self._auto))

    def next_time(self) -> int:
        """The next sim time the plane needs a window at — its dispatch
        cadence point, or the next processless flow's start.  Folded into
        the engine's next-window computation so a quiet Python plane
        cannot strand in-flight device traffic (the plane's flows would
        otherwise only progress while unrelated Python events kept the
        round loop alive)."""
        t = stime.SIM_TIME_MAX
        if self._auto_pos < len(self._auto):
            t = self._auto[self._auto_pos][0]
        if (bool(self._inject_buf) or self._inflight
                or self._cells_delivered_seen < self._cells_dispatched):
            t = min(t, (self._ticks_synced + self.min_dispatch_steps)
                    * self.granule * TICK_NS)
        return t

    def take_node_delta(self, i: int) -> Tuple[int, int]:
        """Consume node ``i``'s pending byte delta as (cells, bytes) —
        shared by the Tracker fold below and the host table's column fold
        (scale/hosttable.py), so both account identically."""
        from ..ops.torcells_device import CELL_WIRE_BYTES
        nbytes = int(self._node_pending[i])
        if not nbytes:
            return 0, 0
        self._node_pending[i] = 0
        return nbytes // CELL_WIRE_BYTES, nbytes

    def pull_tracker_nodes(self, tracker, nodes: List[int]) -> None:
        """Fold a host's pending device-plane byte deltas (accumulated by
        consume()'s single scatter-add) into its Tracker counters: an
        egress node's spend is the host's tx, an ingress (delivering hop)
        node's spend is its rx.  Called from Tracker.pull_device at
        observation points (heartbeat, digest, teardown) only — never on
        the round path."""
        for i in nodes:
            ncells, nbytes = self.take_node_delta(i)
            if not nbytes:
                continue
            c = tracker.out_remote if self.node_kind[i] == "tx" \
                else tracker.in_remote
            c.packets_total += ncells
            c.bytes_total += nbytes
            c.packets_data += ncells
            c.bytes_data += nbytes

    def flush_all_trackers(self) -> None:
        """Teardown sweep: fold every pending node delta so post-run
        readers (tests, digests, tools) see final tracker totals.  Table
        rows fold into the table's columns (or through their materialized
        Host's tracker) via the table's own sweep."""
        for host in dict.fromkeys(h for h in self.node_hosts
                                  if h is not None):
            host.tracker.pull_device()
        if self._table is not None:
            self._table.flush_device_nodes(self)

    def stats(self) -> Dict[str, int]:
        # mesh introspection is NOT mirrored here: the mesh.* registry
        # source (mesh/meshplane.py) is the one spelling of those
        # counters — readers scrape the registry like every other source
        import time as _wt
        host_ns = self.launch_ns + self.fold_ns
        device_ns = self.wait_ns + self.readback_ns
        idle_ns = self.idle_ns
        if self._idle_from is not None:
            # the idle interval still open: a scrape inside it (a window
            # opened between a collect and the next launch) reads the
            # idle wall up to now, so differences of scrapes are exact
            idle_ns += _wt.perf_counter_ns() - self._idle_from
        return {
            "circuits": len(self.specs),
            "injected_cells": self.total_injected_cells,
            "forwards": self.total_forwards,
            "completed": len(self._done),
            "dispatches": self.dispatches,
            "idle_rounds_skipped": self.idle_rounds_skipped,
            # superwindow introspection (ISSUE 7): merged multi-round
            # launches, and how many virtual engine rounds each kernel
            # launch covered on average — the dispatch-amortization number
            # the tor10k host wall is attacked with
            "superwindows": self.superwindows,
            "rounds_per_launch": round(
                self._rounds_launched / max(self.dispatches, 1), 2),
            "mode": self.mode,
            # dispatch-guard outcomes: >0 recoveries means a dispatch
            # failed, the window history replayed on the numpy twin, and
            # the backend was demoted for the rest of the run
            "recoveries": self.recoveries,
            "demoted": self.demoted,
            # recovery-ladder introspection (ISSUE 17): whether the rung
            # climbed back after its probation (one shot; a repeat fault
            # re-demotes for good)
            "repromoted": self._repromoted,
            # the plane's own wall split (VERDICT r4 weak #2: this was
            # tracked but never exported, hiding ~half the flagship wall):
            # host_sec = advance() dispatch prep + wake bookkeeping;
            # device_sec = blocking materialization of dispatch summaries
            "plane_host_sec": round(host_ns / 1e9, 3),
            "plane_device_sec": round(device_ns / 1e9, 3),
            # ... and split where the work happens, plus the wall with no
            # dispatch in flight
            "launch_sec": round(self.launch_ns / 1e9, 6),
            "fold_sec": round(self.fold_ns / 1e9, 6),
            "wait_sec": round(self.wait_ns / 1e9, 6),
            "readback_sec": round(self.readback_ns / 1e9, 6),
            "idle_sec": round(idle_ns / 1e9, 6),
            # the kernel's work: ticks executed, and (flow, tick) pairs in
            # which a flow moved a cell — a denominator that does not
            # depend on how the tick is implemented
            "ticks_stepped": self.ticks_stepped,
            "flow_ticks_moved": self.flow_ticks_moved,
            # the flow-ticks the kernel stepped to do that work (width x
            # ticks: the compacted width when a dispatch ran one)
            "flow_ticks_stepped": self.flow_ticks_stepped,
            "compact_dispatches": self.compact_dispatches,
            # bytes of flush buffers copied from the device to the host
            "flush_bytes_read": self.flush_bytes_read,
            # pipeline introspection: host<->device interactions (dispatch +
            # inject upload + flush read; <= 3 per dispatch) and the wall
            # the in-flight dispatch computed behind host round work
            "device_calls": self.device_calls,
            "pipeline_overlap_sec": round(self.pipeline_overlap_ns / 1e9, 3),
            # fraction of device compute hidden behind host round work:
            # overlap / (overlap + blocked collect); 1.0 = the collect
            # never blocked (obs/profiler.py reads the same definition)
            "overlap_efficiency": round(
                self.pipeline_overlap_ns
                / max(self.pipeline_overlap_ns + device_ns, 1), 4),
        }


def _device_wake_task(args, _unused) -> None:
    plane, circuit, waiter = args
    if waiter is None:
        waiter = plane._waiters.pop(circuit, None)
    if waiter is None:
        return                       # client not waiting yet; wait() will
    process, thread = waiter         # see _done and return immediately
    if circuit in plane._woken:
        return
    plane._woken.add(circuit)
    thread.wake_value = plane._done[circuit]
    # the wake IS the continue (the fold _thread_wake_task already uses
    # for sleep wakes): this event executes in the client host's context
    # at the wake time — exactly where the continue event it used to
    # schedule would run — so resuming directly saves one scheduler
    # round-trip per completed flow (ISSUE 10 batched wake path)
    from ..process.process import BLOCKED, RUNNABLE
    if thread.state == BLOCKED:
        thread.state = RUNNABLE
        thread._unblock_cb = None
        # the wake IS the continue: resume directly; any separately
        # scheduled continue event keeps its own (no-op) delivery and
        # clears the coalescing flag itself (ISSUE 12 satellite)
        process.continue_()


def build_plane_from_engine(engine, mode: str = "device"):
    """Scan the engine's processes for device-mode clients (tor circuits
    AND tgen star-bulk flows) plus the host table's processless flow
    configs (scale tier); returns a DeviceTrafficPlane or None if the
    workload has none.  The scan goes through engine.iter_process_specs so
    deferred table rows contribute identical specs to live Hosts."""
    specs = []
    for _hid, host_name, app, args in engine.iter_process_specs():
        spec = None
        if app.endswith("tor"):
            spec = parse_device_client(host_name, args)
        elif app.endswith("tgen"):
            spec = parse_device_tgen(host_name, args)
        if spec is not None:
            specs.append(spec)
    table = getattr(engine, "host_table", None)
    if table is not None and table.flows:
        from ..apps.tor import PAYLOAD_MAX
        for (_row, route_down, route_up, down_bytes, up_bytes,
             start_ns) in table.flows:
            client = route_down[-1]
            s = _FlowSpec(client, list(route_down), list(route_up),
                          max(1, math.ceil(down_bytes / PAYLOAD_MAX)),
                          math.ceil(up_bytes / PAYLOAD_MAX) if up_bytes
                          else 0, dest=route_down[0])
            s.auto_start_ns = int(start_ns)
            specs.append(s)
    if not specs:
        return None
    resolve_auto_routes(engine, specs)
    plane = DeviceTrafficPlane(engine, specs, mode=mode)
    get_logger().message(
        "device-plane",
        f"device traffic plane: {len(specs)} circuits, "
        f"{plane.n_flows} flows, {plane.n_nodes} nodes, "
        f"ring_len={plane.ring_len}, granule={plane.granule} ms, "
        f"mode={mode}")
    return plane
