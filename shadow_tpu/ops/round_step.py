"""The per-round device kernel: all packet hops in a window as one jitted step.

Reference hot path (worker.c:243-304 ``worker_sendPacket``): for EACH packet,
look up path reliability, draw a uniform, maybe drop, look up path latency,
schedule delivery.  That is a per-packet scalar pipeline; on TPU the same
work is one batched step over the round's whole packet set:

    latency  = L[src_row, dst_row]          # int64 ns gather
    rel      = R[src_row, dst_row]          # f32 gather
    u        = threefry(drop_key, uid)      # counter-based, order-independent
    keep     = bootstrap | rel >= 1 | u <= rel
    deliver  = send_time + latency          # int64 ns, exact

Determinism contract: the uniform is keyed by the packet uid, not execution
order, and is the bitwise-identical construction the CPU policies use
(core/rng.py), so the CPU and TPU schedulers drop exactly the same packets
and compute exactly the same delivery times (int64 ns math on device; x64
is enabled by the ops package __init__).

Dynamic per-round packet counts vs XLA static shapes (SURVEY.md §7 hard
part d): batches are padded to power-of-two buckets with a validity mask, so
each bucket size compiles once and is reused.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..core.rng import threefry2x32_jnp

MIN_BUCKET = 256


def bucket_size(n: int) -> int:
    """Smallest power-of-two bucket >= n (min MIN_BUCKET) — bounds the number
    of distinct compiled shapes to log2(max_batch)."""
    b = MIN_BUCKET
    while b < n:
        b <<= 1
    return b


def _uniform_from_uid(key_lo: jnp.ndarray, key_hi: jnp.ndarray,
                      uid_lo: jnp.ndarray, uid_hi: jnp.ndarray) -> jnp.ndarray:
    """f32 uniform in [0,1) from the 64-bit drop key and 64-bit packet uid.
    Same 24-bit-mantissa construction as core.rng.uniform_np, so comparisons
    against f32 reliability values decide identically on CPU and device."""
    x0, _ = threefry2x32_jnp(key_lo, key_hi, uid_lo, uid_hi)
    return (x0 >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


@partial(jax.jit, donate_argnums=())
def packet_hop_step(latency_ns: jnp.ndarray,     # int64 [A, A]
                    reliability: jnp.ndarray,    # f32   [A, A]
                    src_rows: jnp.ndarray,       # int32 [N]
                    dst_rows: jnp.ndarray,       # int32 [N]
                    uid_lo: jnp.ndarray,         # uint32 [N]
                    uid_hi: jnp.ndarray,         # uint32 [N]
                    send_times: jnp.ndarray,     # int64 [N]
                    valid: jnp.ndarray,          # bool  [N]
                    key_lo: jnp.ndarray,         # uint32 scalar
                    key_hi: jnp.ndarray,         # uint32 scalar
                    bootstrap_end: jnp.ndarray,  # int64 scalar
                    barrier: jnp.ndarray,        # int64 scalar (round end clamp)
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One device step for a padded packet batch.

    Returns (deliver_times int64 [N], keep bool [N]).  Invalid (padding) lanes
    come back keep=False.  The barrier clamp mirrors the cross-host push clamp
    (reference scheduler_policy_host_steal.c:225-242) — a safety net that
    never fires when lookahead == min path latency.
    """
    lat = latency_ns[src_rows, dst_rows]
    rel = reliability[src_rows, dst_rows]
    return _finish_hop(lat, rel, uid_lo, uid_hi, send_times, valid,
                       key_lo, key_hi, bootstrap_end, barrier)


def _finish_hop(lat, rel, uid_lo, uid_hi, send_times, valid,
                key_lo, key_hi, bootstrap_end, barrier):
    """Post-gather hop math — ONE definition so every kernel layout
    (single-device, batch-sharded, matrix-sharded) encodes the identical
    CPU/TPU determinism contract."""
    u = _uniform_from_uid(key_lo, key_hi, uid_lo, uid_hi)
    bootstrapping = send_times < bootstrap_end
    keep = (bootstrapping | (rel >= jnp.float32(1.0)) | (u <= rel)) & valid
    deliver = jnp.maximum(send_times + lat, barrier)
    return deliver, keep


@jax.jit
def packet_hop_step_packed(latency_ns: jnp.ndarray,   # int64 [A, A]
                           reliability: jnp.ndarray,  # f32   [A, A]
                           packed: jnp.ndarray,       # int64 [1+B, 3]
                           key_lo: jnp.ndarray, key_hi: jnp.ndarray,
                           bootstrap_end: jnp.ndarray,
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Packed-layout hop step: ONE host->device array per flush instead of
    six, and zero per-call scalar uploads.  Row 0 is a header: (valid row
    count n, round barrier ns, 0).  Data row layout: word0 = (src_row << 32)
    | dst_row, word1 = the packet uid (uint64 bit pattern), word2 = send
    time ns.  The validity mask is derived on-device (iota < n), so padding
    costs no transfer; outputs stay PADDED — callers slice host-side after
    materializing, because a device-side [:n] slice would be a second
    dispatched op per flush (measured ~140us each on the CPU backend).
    Same math as packet_hop_step via _finish_hop — bit-identical decisions."""
    n = packed[0, 0].astype(jnp.int32)
    barrier = packed[0, 1]
    w0 = packed[1:, 0]
    uid = packed[1:, 1]
    send_times = packed[1:, 2]
    src = (w0 >> jnp.int64(32)).astype(jnp.int32)
    dst = (w0 & jnp.int64(0xFFFFFFFF)).astype(jnp.int32)
    # arithmetic >> then mask == logical shift for the uint64 bit pattern
    uid_lo = (uid & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    uid_hi = ((uid >> jnp.int64(32)) & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    valid = jnp.arange(w0.shape[0], dtype=jnp.int32) < n
    lat = latency_ns[src, dst]
    rel = reliability[src, dst]
    return _finish_hop(lat, rel, uid_lo, uid_hi, send_times, valid,
                       key_lo, key_hi, bootstrap_end, barrier)


class PacketHopKernel:
    """Host-side wrapper owning the device-resident topology tensors and the
    drop key; turns a round's (src_row, dst_row, uid, send_time) arrays into
    (deliver_time, keep) numpy arrays with one device call."""

    # >0: batches below this size are computed with the bitwise-identical
    # vectorized numpy path instead of a device call (uniform_np and the jnp
    # threefry are the same cipher — asserted by tests/test_rng.py — so
    # results are indistinguishable).  The default dropped 4096 -> 0 in r4:
    # the packed header-row upload (no per-call scalars), unsliced padded
    # outputs, and the asynchronous launch/consume split cut the measured
    # per-dispatch tax to one ~30us jit call (CPU backend), at which point
    # always-device measured FASTER than any bypass mix on tor200 (5.57s vs
    # 5.69-5.75s).  ``--tpu-device-threshold N`` restores a bypass for
    # environments with pathological dispatch round trips.
    DEVICE_THRESHOLD = 0

    def __init__(self, topology, drop_key: int, bootstrap_end_ns: int,
                 device_threshold: Optional[int] = None):
        lat, rel = topology.device_tensors()
        self.latency = lat
        self.reliability = rel
        # host-side copies for the small-batch path
        self.latency_np = np.asarray(topology.latency_ns)
        self.reliability_np = np.asarray(topology.reliability,
                                         dtype=np.float32)
        kv = int(drop_key) & 0xFFFFFFFFFFFFFFFF
        self.drop_key = kv
        self.key_lo = jnp.uint32(kv & 0xFFFFFFFF)
        self.key_hi = jnp.uint32((kv >> 32) & 0xFFFFFFFF)
        self.bootstrap_end = jnp.int64(bootstrap_end_ns)
        self.bootstrap_end_ns = int(bootstrap_end_ns)
        self.device_calls = 0
        self.host_calls = 0
        if device_threshold is not None:
            self.DEVICE_THRESHOLD = device_threshold
        # distinct padded batch shapes seen = XLA recompile count (the
        # engine heartbeat reports this; SURVEY.md §7 hard part d)
        self.buckets_seen: set = set()

    def _step_numpy(self, src_rows, dst_rows, uids, send_times,
                    barrier_ns: int) -> Tuple[np.ndarray, np.ndarray]:
        """Vectorized host path for small rounds — same math, same cipher,
        same f32 comparison as the device kernel, so the decision per packet
        is identical bit for bit."""
        from ..core.rng import uniform_np
        lat = self.latency_np[src_rows, dst_rows]
        rel = self.reliability_np[src_rows, dst_rows]
        u = uniform_np(self.drop_key, uids.astype(np.uint64))
        send_times = send_times.astype(np.int64, copy=False)
        keep = ((send_times < self.bootstrap_end_ns)
                | (rel >= np.float32(1.0))
                | (u.astype(np.float32) <= rel))
        deliver = np.maximum(send_times + lat, np.int64(barrier_ns))
        self.host_calls += 1
        return deliver, keep

    def _padded_batch(self, src_rows, dst_rows, uids, send_times, b: int):
        """Pad the round's arrays to bucket size b and split 64-bit uids
        into the (lo, hi) u32 pair the threefry kernel consumes."""
        n = len(src_rows)

        def pad(a, fill=0):
            out = np.full(b, fill, dtype=a.dtype)
            out[:n] = a
            return out

        uids = np.asarray(uids, dtype=np.uint64)
        valid = np.zeros(b, dtype=bool)
        valid[:n] = True
        return (pad(np.asarray(src_rows, dtype=np.int32)),
                pad(np.asarray(dst_rows, dtype=np.int32)),
                pad((uids & np.uint64(0xFFFFFFFF)).astype(np.uint32)),
                pad((uids >> np.uint64(32)).astype(np.uint32)),
                pad(np.asarray(send_times, dtype=np.int64)),
                valid)

    def _pack(self, src_rows, dst_rows, uids, send_times, b: int,
              barrier_ns: int) -> np.ndarray:
        """Assemble the [1+b, 3] int64 packed batch (header row 0 carries
        n and the barrier — see packet_hop_step_packed's layout)."""
        n = len(src_rows)
        packed = np.zeros((1 + b, 3), dtype=np.int64)
        packed[0, 0] = n
        packed[0, 1] = barrier_ns
        packed[1:n + 1, 0] = ((np.asarray(src_rows, dtype=np.int64) << 32)
                              | np.asarray(dst_rows, dtype=np.int64))
        packed[1:n + 1, 1] = np.asarray(uids, dtype=np.uint64).view(np.int64)
        packed[1:n + 1, 2] = np.asarray(send_times, dtype=np.int64)
        return packed

    def launch(self, src_rows: np.ndarray, dst_rows: np.ndarray,
               uids: np.ndarray, send_times: np.ndarray,
               barrier_ns: int) -> Tuple[np.ndarray, np.ndarray]:
        """Dispatch one chunk WITHOUT materializing the result: returns
        (deliver, keep) that may be unfinished PADDED device arrays (length
        >= N; callers slice to their row count after np.asarray).  The
        caller converts with np.asarray when it actually needs the values
        (the engine does so at the next round boundary), so device compute
        overlaps host-side work.  The numpy bypass path (DEVICE_THRESHOLD)
        returns finished exact-length host arrays with the same interface."""
        n = len(src_rows)
        if n == 0:
            return (np.empty(0, dtype=np.int64), np.empty(0, dtype=bool))
        if n < self.DEVICE_THRESHOLD:
            return self._step_numpy(np.asarray(src_rows), np.asarray(dst_rows),
                                    np.asarray(uids), np.asarray(send_times),
                                    barrier_ns)
        b = bucket_size(n)
        self.buckets_seen.add(b)
        packed = self._pack(src_rows, dst_rows, uids, send_times, b,
                            barrier_ns)
        deliver, keep = packet_hop_step_packed(
            self.latency, self.reliability, packed,
            self.key_lo, self.key_hi, self.bootstrap_end)
        self.device_calls += 1
        return deliver, keep

    def step(self, src_rows: np.ndarray, dst_rows: np.ndarray,
             uids: np.ndarray, send_times: np.ndarray,
             barrier_ns: int) -> Tuple[np.ndarray, np.ndarray]:
        """Synchronous variant of launch (materialized, exact-length)."""
        n = len(src_rows)
        deliver, keep = self.launch(src_rows, dst_rows, uids, send_times,
                                    barrier_ns)
        return np.asarray(deliver)[:n], np.asarray(keep)[:n]


# ---------------------------------------------------------------------------
# Multi-chip round step: the packet batch is sharded across the mesh (the
# simulator's data-parallel axis); the path matrices are replicated (attached
# vertex counts are small even for 10k-host graphs — SURVEY.md §3.5) or, for
# huge graphs, row-sharded with an all-gather.  ShardedPacketHopKernel is
# the ONE sharding entry point for packet hops (mesh construction comes
# from parallel/mesh.device_mesh, shared with the traffic plane); the
# step builders below are its internals.  (The standalone
# make_sharded_hop_step / make_2d_sharded_hop_step demo builders were
# test-only and retired with the mesh plane — the driver dryrun and
# tests/test_scaleout.py now exercise the kernel class and the mesh
# plane's own collectives instead.)
# ---------------------------------------------------------------------------

def _make_matrix_sharded_hop_step(mesh, axis: str = "pkt"):
    """Row-sharded variant for graphs whose [A, A] path matrices exceed one
    chip's HBM (SURVEY.md §7 stage 10): each device holds A/D rows of the
    latency/reliability matrices; the packet batch is replicated; every
    device gathers the entries whose src row it owns and a psum over the
    mesh assembles the full result (one ICI collective per round, the
    device-side analog of the scheduler's cross-thread barrier merge).

    The mesh size must divide the row count; callers pad the matrices up to
    a multiple first (ShardedPacketHopKernel does this when constructed
    with shard_matrix=True — padded rows are never indexed because src rows
    always reference real attached vertices).
    """
    from jax.sharding import PartitionSpec as P

    def step(latency_ns, reliability, src_rows, dst_rows,
             uid_lo, uid_hi, send_times, valid,
             key_lo, key_hi, bootstrap_end, barrier):

        def shard_body(lat_shard, rel_shard, src, dst):
            rows_per = lat_shard.shape[0]
            shard = jax.lax.axis_index(axis)
            local = src - shard * rows_per
            mine = (local >= 0) & (local < rows_per)
            idx = jnp.clip(local, 0, rows_per - 1)
            lat = jnp.where(mine, lat_shard[idx, dst], jnp.int64(0))
            rel = jnp.where(mine, rel_shard[idx, dst], jnp.float32(0.0))
            # each packet's row lives on exactly one shard -> psum assembles
            return (jax.lax.psum(lat, axis), jax.lax.psum(rel, axis))

        lat, rel = jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(P(axis, None), P(axis, None), P(), P()),
            out_specs=(P(), P()))(latency_ns, reliability,
                                  src_rows, dst_rows)
        return _finish_hop(lat, rel, uid_lo, uid_hi, send_times, valid,
                           key_lo, key_hi, bootstrap_end, barrier)

    return jax.jit(step)


class ShardedPacketHopKernel(PacketHopKernel):
    """Multi-device kernel: same .step API as PacketHopKernel, over a 1-D
    device mesh (``--tpu-devices N``).

    Two layouts:
    * default — the padded batch is sharded over the mesh, path matrices
      replicated on every chip (cheapest when the matrices fit in HBM);
    * ``shard_matrix=True`` (``--tpu-shard-matrix``) — the matrices are
      row-sharded across the mesh (each chip holds A/D rows, padded up to a
      multiple of D) and the batch is replicated; per-packet entries are
      assembled with a psum.  This is the HBM scale-out path for graphs
      whose [A, A] tensors exceed one chip.
    """

    def __init__(self, topology, drop_key: int, bootstrap_end_ns: int,
                 n_devices: int, shard_matrix: bool = False):
        super().__init__(topology, drop_key, bootstrap_end_ns)
        from jax.sharding import NamedSharding, PartitionSpec as P
        # mesh construction (pool selection incl. the virtual-CPU-mesh
        # fallback) has ONE definition, shared with the traffic plane
        from ..parallel.mesh import device_mesh
        self.mesh = device_mesh(n_devices, axis_names=("pkt",))
        self.n_devices = n_devices
        self.shard_matrix = shard_matrix
        self._batch_sharding = NamedSharding(self.mesh, P("pkt"))
        self._replicated = NamedSharding(self.mesh, P())
        if shard_matrix:
            lat = np.asarray(self.latency)
            rel = np.asarray(self.reliability)
            rows = lat.shape[0]
            padded = -(-rows // n_devices) * n_devices
            if padded != rows:
                # padded rows are never indexed: src rows always reference
                # real attached vertices
                lat = np.pad(lat, ((0, padded - rows), (0, 0)))
                rel = np.pad(rel, ((0, padded - rows), (0, 0)))
            row_sharding = NamedSharding(self.mesh, P("pkt", None))
            self.latency = jax.device_put(lat, row_sharding)
            self.reliability = jax.device_put(rel, row_sharding)
            self._step = _make_matrix_sharded_hop_step(self.mesh,
                                                        axis="pkt")
            self._batch_placement = self._replicated
        else:
            self.latency = jax.device_put(self.latency, self._replicated)
            self.reliability = jax.device_put(self.reliability,
                                              self._replicated)
            self._step = _make_batch_sharded_2out(self.mesh, "pkt")
            self._batch_placement = self._batch_sharding

    def launch(self, src_rows, dst_rows, uids, send_times, barrier_ns):
        # the mesh layouts keep their explicit-sharding step; deliveries are
        # still returned unmaterialized (jax arrays, PADDED — callers slice
        # host-side after np.asarray, same contract as the packed kernel),
        # so consume-side overlap applies here too
        return self.step_sharded(src_rows, dst_rows, uids, send_times,
                                 barrier_ns)

    def step(self, src_rows, dst_rows, uids, send_times, barrier_ns):
        n = len(src_rows)
        deliver, keep = self.step_sharded(src_rows, dst_rows, uids,
                                          send_times, barrier_ns)
        return np.asarray(deliver)[:n], np.asarray(keep)[:n]

    def step_sharded(self, src_rows, dst_rows, uids, send_times, barrier_ns):
        n = len(src_rows)
        if n == 0:
            return (np.empty(0, dtype=np.int64), np.empty(0, dtype=bool))
        if n < self.DEVICE_THRESHOLD:
            # same numpy bypass contract as the single-device kernel
            # (--tpu-device-threshold applies to every layout)
            return self._step_numpy(np.asarray(src_rows), np.asarray(dst_rows),
                                    np.asarray(uids), np.asarray(send_times),
                                    barrier_ns)
        # bucket must also be divisible by the mesh axis
        b = max(bucket_size(n), self.n_devices * MIN_BUCKET)
        if b % self.n_devices:
            b = -(-b // self.n_devices) * self.n_devices
        self.buckets_seen.add(b)
        batch = self._padded_batch(src_rows, dst_rows, uids, send_times, b)
        put = partial(jax.device_put, device=self._batch_placement)
        deliver, keep = self._step(
            self.latency, self.reliability,
            *(put(a) for a in batch),
            self.key_lo, self.key_hi, self.bootstrap_end,
            jnp.int64(barrier_ns))
        self.device_calls += 1
        return deliver, keep


def _make_batch_sharded_2out(mesh, axis: str):
    """Batch-sharded step WITHOUT the global-min collective: the engine's
    next-window time comes from the host-side event queues, so paying an
    ICI reduction per round for an unused value would be waste.  (The
    engine's window times come from the host event queues.)"""
    from jax.sharding import NamedSharding, PartitionSpec as P
    batch = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())
    return jax.jit(packet_hop_step,
                   in_shardings=(repl, repl, batch, batch, batch, batch,
                                 batch, batch, repl, repl, repl, repl),
                   out_shardings=(batch, batch))
