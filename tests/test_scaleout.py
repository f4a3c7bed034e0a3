"""Multi-chip scale-out (SURVEY.md §7 stage 10): the round batch and the
path matrices sharded over a device mesh, with bitwise parity against the
single-device kernel and the serial CPU schedule.  Runs on the 8-virtual-
device CPU mesh (tests/conftest.py).

ShardedPacketHopKernel is the ONE sharding entry point for packet hops
(mesh construction shared with the traffic plane via
parallel/mesh.device_mesh); the standalone make_sharded_hop_step /
make_2d_sharded_hop_step demo builders were retired with the mesh plane —
the traffic-plane collectives' parity suite is tests/test_meshplane.py.
"""

import textwrap

import numpy as np
import pytest

import jax

from shadow_tpu.core import configuration
from shadow_tpu.core.checkpoint import state_digest
from shadow_tpu.core.controller import Controller
from shadow_tpu.core.options import Options


def _mesh(n, axis="pkt"):
    from shadow_tpu.parallel.mesh import device_mesh
    try:
        return device_mesh(n, axis_names=(axis,))
    except RuntimeError:
        pytest.skip(f"need {n} devices")


def _example(n_rows=16, n_pkts=2048):
    rng = np.random.default_rng(3)
    lat = rng.integers(1_000_000, 90_000_000, size=(n_rows, n_rows),
                       dtype=np.int64)
    rel = rng.uniform(0.85, 1.0, size=(n_rows, n_rows)).astype(np.float32)
    src = rng.integers(0, n_rows, size=n_pkts, dtype=np.int32)
    dst = rng.integers(0, n_rows, size=n_pkts, dtype=np.int32)
    uids = np.arange(n_pkts, dtype=np.uint64)
    st = rng.integers(0, 5_000_000_000, size=n_pkts, dtype=np.int64)
    valid = np.ones(n_pkts, dtype=bool)
    import jax.numpy as jnp
    return (lat, rel, src, dst,
            (uids & np.uint64(0xFFFFFFFF)).astype(np.uint32),
            (uids >> np.uint64(32)).astype(np.uint32),
            st, valid, jnp.uint32(0xABCD), jnp.uint32(0x1234),
            jnp.int64(1_000_000_000), jnp.int64(0))


def test_device_mesh_is_the_one_pool_definition():
    """parallel/mesh.device_mesh: the shared pool-selection rule — honors
    the virtual CPU mesh, raises past the pool size, reshapes on demand."""
    from shadow_tpu.parallel.mesh import device_mesh
    mesh = device_mesh(8, axis_names=("pkt",))
    assert mesh.devices.shape == (8,)
    mesh2 = device_mesh(8, axis_names=("a", "b"), shape=(4, 2))
    assert mesh2.devices.shape == (4, 2)
    with pytest.raises(RuntimeError, match="only 8 cpu device"):
        device_mesh(10_000)


def test_device_mesh_never_switches_pools(monkeypatch):
    """A default pool with too few devices (one chip, say) raises: the mesh
    is never rebuilt on another pool such as the host CPU's."""
    import jax
    from shadow_tpu.parallel.mesh import device_mesh
    cpu = jax.devices()
    asked = []

    def devices(backend=None):
        asked.append(backend)
        return cpu if backend is not None else cpu[:1]

    monkeypatch.setattr(jax, "devices", devices)
    with pytest.raises(RuntimeError, match="only 1 cpu device"):
        device_mesh(4)
    assert asked == [None]


def test_batch_sharded_matches_single_device():
    """The production batch-sharded layout (ShardedPacketHopKernel's
    default step) is bitwise-identical to the single-device kernel."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from shadow_tpu.ops.round_step import (_make_batch_sharded_2out,
                                           packet_hop_step)
    mesh = _mesh(8)
    args = _example()
    batch = NamedSharding(mesh, P("pkt"))
    repl = NamedSharding(mesh, P())
    placements = (repl, repl, batch, batch, batch, batch, batch, batch,
                  repl, repl, repl, repl)
    placed = tuple(jax.device_put(a, s) for a, s in zip(args, placements))
    deliver, keep = _make_batch_sharded_2out(mesh, "pkt")(*placed)
    ref_deliver, ref_keep = packet_hop_step(*args)
    np.testing.assert_array_equal(np.asarray(deliver), np.asarray(ref_deliver))
    np.testing.assert_array_equal(np.asarray(keep), np.asarray(ref_keep))


def test_matrix_sharded_matches_single_device():
    """The row-sharded HBM scale-out layout (--tpu-shard-matrix) is
    bitwise-identical to the single-device kernel."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from shadow_tpu.ops.round_step import (_make_matrix_sharded_hop_step,
                                           packet_hop_step)
    mesh = _mesh(8)
    args = _example(n_rows=32)  # 32 rows / 8 devices = 4 rows per shard
    row_sharded = NamedSharding(mesh, P("pkt", None))
    repl = NamedSharding(mesh, P())
    placed = [jax.device_put(args[0], row_sharded),
              jax.device_put(args[1], row_sharded)]
    placed += [jax.device_put(a, repl) for a in args[2:]]
    deliver, keep = _make_matrix_sharded_hop_step(mesh)(*placed)
    ref_deliver, ref_keep = packet_hop_step(*args)
    np.testing.assert_array_equal(np.asarray(deliver), np.asarray(ref_deliver))
    np.testing.assert_array_equal(np.asarray(keep), np.asarray(ref_keep))


SIM_XML = textwrap.dedent("""\
    <shadow stoptime="60">
      <plugin id="echo" path="python:echo" />
      <host id="server"><process plugin="echo" starttime="1" arguments="udp server 8000" /></host>
      <host id="c" quantity="6">
        <process plugin="echo" starttime="2" arguments="udp client server 8000 6 512" />
      </host>
    </shadow>
""")


def _run(policy, tpu_devices=0, shard_matrix=False):
    cfg = configuration.parse_xml(SIM_XML)
    cfg.stop_time_sec = 60
    opts = Options(scheduler_policy=policy, workers=0, stop_time_sec=60,
                   tpu_devices=tpu_devices, tpu_shard_matrix=shard_matrix)
    ctrl = Controller(opts, cfg)
    assert ctrl.run() == 0
    return ctrl


def test_sharded_tpu_policy_full_sim_parity():
    """A full simulation under --scheduler-policy=tpu --tpu-devices=8 ends
    in the identical state digest as the serial CPU schedule — in both the
    batch-sharded and matrix-row-sharded (--tpu-shard-matrix) layouts."""
    d_serial = state_digest(_run("global").engine)
    d_sharded = state_digest(_run("tpu", tpu_devices=8).engine)
    assert d_serial == d_sharded
    d_matrix = state_digest(_run("tpu", tpu_devices=8,
                                 shard_matrix=True).engine)
    assert d_serial == d_matrix


def test_dryrun_multichip_entrypoint():
    """The driver's dryrun entry must pass on the virtual CPU mesh."""
    import __graft_entry__ as ge
    ge.dryrun_multichip(8)
