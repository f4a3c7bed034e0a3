"""AOT compiles for a described TPU v5e (no chip attached): the programs on
the simulator's main path, at real widths, through the chip's own
compiler.  What the compiler refuses here (VMEM, tiling, partitioning)
would otherwise surface at the first dispatch on the chip — as the
donating span-flush's int64 prefix sum did at C = 200, 5 000 and 10 000
(ISSUE 21).  Nothing runs: these pass shapes, never arrays.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and every xdist worker
imports this file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from shadow_tpu.ops import round_step
from shadow_tpu.ops import torcells_device as td


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _flush_shapes(one_chip, n_chains, ring_len=66, rounds=8):
    """The span-flush argument shapes of a plane with ``n_chains`` 5-hop
    chains (tor circuits) and two nodes per chain plus 500 servers,
    through the gather tables (flow_pred, node_seg)."""
    f, h = 5 * n_chains, 2 * n_chains + 500

    def s(shape, dtype=jnp.int64):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return (s(()), s((f,)), s((ring_len, f), td.RING_DTYPE), s((h,)),
            s((f,)), s((f,)), s((f,)), s((h,)),          # carried state
            s((f,)), s((f,)), s((rounds,)), s(()),       # inject, targets
            s((f,)), s((f,)), s((f,)), s((f,)), s((h,)), s((h,)),
            s((n_chains,)), s((f,)), s((2, h)))


@pytest.mark.parametrize("n_chains", [200, 2_000, 5_000, 10_000])
def test_donating_span_flush_compiles_for_v5e(one_chip, n_chains):
    compiled = td.torcells_step_window_flush.lower(
        *_flush_shapes(one_chip, n_chains), ring_len=66).compile()
    assert compiled.memory_analysis().alias_size_in_bytes > 0  # donated


@pytest.mark.parametrize("width", [4096, 65536])
def test_donating_compact_span_flush_compiles_for_v5e(one_chip, width):
    """The compacted span-flush at both widths of a 20,000-chain plane
    (100,000 flows): its tick loop is the loop the v5e compiler once
    refused for VMEM at full width."""
    shapes = _flush_shapes(one_chip, 20_000)
    live = jax.ShapeDtypeStruct((4, width), jnp.int64, sharding=one_chip)
    compiled = td.torcells_step_compact_flush.lower(
        *shapes[:8], live, *shapes[10:18], shapes[19],
        ring_len=66).compile()
    assert compiled.memory_analysis().alias_size_in_bytes > 0  # donated


def test_vmapped_fleet_flush_compiles_for_v5e(one_chip):
    """The fleet plane's vmapped span-flush: 8 lanes of 200 chains."""
    shapes = [jax.ShapeDtypeStruct((8, *a.shape), a.dtype,
                                   sharding=one_chip)
              for a in _flush_shapes(one_chip, 200)]
    td.torcells_step_span_flush_batched.lower(*shapes,
                                              ring_len=66).compile()


@pytest.mark.parametrize("packed", [False, True])
def test_hop_kernel_compiles_at_largest_warmup_bucket(one_chip, packed):
    """The tpu policy's hop step at the largest bucket its warm-up
    compiles (tpu_policy.warmup's default max_batch)."""
    a = 4096
    b = round_step.bucket_size(8192)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    mats = (s((a, a), jnp.int64), s((a, a), jnp.float32))
    keys = (s((), jnp.uint32), s((), jnp.uint32), s((), jnp.int64))
    if packed:
        round_step.packet_hop_step_packed.lower(
            *mats, s((1 + b, 3), jnp.int64), *keys).compile()
    else:
        round_step.packet_hop_step.lower(
            *mats, s((b,), jnp.int32), s((b,), jnp.int32),
            s((b,), jnp.uint32), s((b,), jnp.uint32), s((b,), jnp.int64),
            s((b,), jnp.bool_), *keys, s((), jnp.int64)).compile()


def test_mesh_span_flush_compiles_over_four_described_chips(topo):
    """The meshplane's shard_map superwindow kernel with its cross-shard
    exchange, partitioned over the 2x2 mesh's four chips."""
    from jax.sharding import Mesh
    from shadow_tpu.parallel.mesh.exchange import make_mesh_span_flush
    from shadow_tpu.parallel.mesh.partition import build_mesh_layout

    rng = np.random.default_rng(5)
    n_circ, n_nodes = 2_000, 600
    route = np.stack([rng.choice(n_nodes, size=5, replace=False)
                      for _ in range(n_circ)]).astype(np.int64)
    lat = rng.integers(1, 60, size=(n_nodes, n_nodes)).astype(np.int64)
    flows = td.build_flows(route, lat)
    refill = np.full(n_nodes, 100_000, dtype=np.int64)
    lay = build_mesh_layout(flows["flow_node"], flows["flow_lat"],
                            flows["flow_succ"], flows["seg_start"],
                            refill, refill * 2, 4)
    last = np.array([np.flatnonzero((flows["flow_circ"] == c)
                                    & (flows["flow_stage"] == 4))[0]
                     for c in range(n_circ)], dtype=np.int64)
    ring_len = int(flows["flow_lat"].max()) + 2
    mesh = Mesh(np.array(topo.devices[:4]), ("flows",))
    step = make_mesh_span_flush(mesh, "flows", ring_len, lay,
                                lay["inv"][last], lay["node_src"], n_nodes)
    fp, hp = len(lay["src"]), len(lay["refill"])
    shard, repl = NamedSharding(mesh, P("flows")), NamedSharding(mesh, P())

    def s(shape, sharding, dtype=jnp.int64):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    cols = [s((fp,), shard) for _ in range(4)]
    args = (s((), repl), cols[0],
            s((ring_len, fp), NamedSharding(mesh, P(None, "flows")),
              td.RING_DTYPE),
            s((hp,), shard), cols[1], cols[2], cols[3], s((hp,), shard),
            s((fp,), shard), s((fp,), shard), s((8,), repl), s((), repl),
            *(s(lay[k].shape, shard) for k in (
                "flow_node_local", "succ_global", "seg_start_local",
                "refill", "capacity", "arr_lat", "shard_base")))
    compiled = step.lower(*args).compile()
    text = compiled.as_text()
    assert "all-to-all" in text or "collective-permute" in text
