"""meshplane: the multi-chip sharded traffic plane (ROADMAP item 1).

Three cooperating modules turn the device-resident traffic plane
(parallel/device_plane.py) from a one-chip program into a D-chip one:

* :mod:`partition` — a deterministic chain/flow partitioner assigning
  whole node segments to shards while keeping each circuit's consecutive
  hops co-located (minimizing cross-shard forwards), plus the padded
  layout builder every sharded consumer goes through — the ONE definition
  of the shard placement contract;
* :mod:`exchange` — the precomputed cross-shard forward schedule: the
  static shard-to-shard cell-edge matrix decomposed BvN-style into <= D-1
  rotation permutation legs (FAST, arxiv 2505.09764; hierarchical BvN,
  arxiv 2602.22756), executed as on-device ``ppermute`` collectives inside
  the shard_map tick loop — cross-shard cells never transit the host;
* :mod:`meshplane` — the DeviceTrafficPlane attachment: builds the mesh,
  partition, and exchange, installs the sharded superwindow kernel, and
  publishes the ``mesh.*`` metrics (host_bounces, cross_shard_cells,
  exchange_legs, per-device occupancy).

This module also owns :func:`device_mesh`, the single definition of
device-pool selection shared by every sharded consumer (the traffic
plane here and ops/round_step.py's ShardedPacketHopKernel).
"""

from __future__ import annotations

import numpy as np


def device_mesh(n_devices: int, axis_names=("flows",), shape=None):
    """Build a 1-D (or, with ``shape``, reshaped) jax Mesh over the first
    ``n_devices`` devices of the default pool.  Raises RuntimeError when
    the pool has fewer — it never switches pools, so a mesh asked of the
    chips is never quietly built on the host CPU.  The ONE definition of
    pool selection for every sharded consumer."""
    import jax
    from jax.sharding import Mesh

    pool = jax.devices()
    if len(pool) < n_devices:
        raise RuntimeError(
            f"--tpu-devices={n_devices} but only {len(pool)} "
            f"{pool[0].platform} device(s) present")
    arr = np.array(pool[:n_devices])
    if shape is not None:
        arr = arr.reshape(shape)
    return Mesh(arr, axis_names=axis_names)
