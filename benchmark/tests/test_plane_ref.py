"""The plain reference (lib/plane_ref.py) on cases small enough to work
out by hand."""

from benchmark.lib import plane_ref

CELL = 578
ROUTE = ("dest1", "relay1", "relay2", "relay3", "torclient1")


def _bw(rates):
    return lambda host: rates[host.rstrip("0123456789")]


FAST = _bw({"dest": (1 << 20,) * 2, "relay": (1 << 20,) * 2,
            "torclient": (1 << 20,) * 2})


def test_bucket_is_shadows_interface_bucket():
    # 100 MiB/s: 104,857 bytes per ms; one 10 ms tick refills ten of them
    assert plane_ref.bucket(102400, 10) == (1048570, 1048570)
    # a slow host keeps one millisecond's refill plus one MTU of burst
    assert plane_ref.bucket(100, 1) == (102, 1602)


def test_uncontended_chain_takes_one_latency_per_hop():
    flows = [("torclient1", ROUTE, 98, 5, 2_000_000_000)]
    ref = plane_ref.Chains(flows, FAST, 20.0, 10, CELL).run(300)
    start = 200
    for d, cells in ((0, 98), (1, 5)):
        last = ref["stages"][(0, d, 4)]
        assert last["done"] == start + 4 * 2
        assert last["delivered"] == last["target"] == cells
    # four sending hops and one receiving hop per chain
    assert ref["nodes"][("relay2", "tx")][1] == (98 + 5) * CELL
    assert ref["nodes"][("torclient1", "rx")][1] == 98 * CELL
    assert ref["nodes"][("dest1", "rx")][1] == 5 * CELL


def test_state_mid_chain_has_cells_in_flight():
    flows = [("torclient1", ROUTE, 98, 0, 2_000_000_000)]
    ref = plane_ref.Chains(flows, FAST, 20.0, 10, CELL).run(201)
    # sent by the server at tick 200, due at the exit at tick 202
    assert ref["stages"][(0, 0, 1)]["inflight"] == 98
    assert ref["stages"][(0, 0, 1)]["queued"] == 0
    assert ref["stages"][(0, 0, 4)]["target"] == 98
    assert ref["stages"][(0, 0, 4)]["done"] == -1


def test_shared_bottleneck_serves_lower_circuit_first():
    # the exit relay refills 1,150 bytes a tick up to 1,615: about two
    # cells a tick; two circuits start together and share it
    slow = _bw({"dest": (1 << 20,) * 2, "relay": (1 << 20,) * 2,
                "torclient": (1 << 20,) * 2, "exit": (113, 113)})
    route = ("dest1", "exit1", "relay2", "relay3", "torclient1")
    route2 = ("dest1", "exit1", "relay2", "relay3", "torclient2")
    flows = [("torclient1", route, 3, 0, 0), ("torclient2", route2, 3, 0, 0)]
    ref = plane_ref.Chains(flows, slow, 20.0, 10, CELL).run(100)
    # both circuits' cells reach the exit at tick 2; it sends two cells
    # at ticks 2, 3 and 4 (1,615 bytes, then 459 + 1,150 and 453 + 1,150):
    # the first circuit's three, then the second's; three hops of two
    # ticks each after the exit
    assert ref["stages"][(0, 0, 4)]["done"] == 3 + 6
    assert ref["stages"][(1, 0, 4)]["done"] == 4 + 6
    assert ref["nodes"][("exit1", "tx")] == (1615, 6 * CELL)
    mid = plane_ref.Chains(flows, slow, 20.0, 10, CELL).run(5)
    assert mid["nodes"][("exit1", "tx")] == (453 + 1150 - 2 * CELL,
                                             6 * CELL)
