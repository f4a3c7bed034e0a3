"""The benchmark's own copy of the genscen Tor chain generator.

Copied from ``shadow_tpu/scale/genscen.tor`` and ``expand_flows`` (PR 21)
so that a later PR that changes the program's generator cannot change
the yardstick.  The scenario is the program's own ``Configuration``
(process-less clients, each a seeded 3-hop circuit run as two 5-hop
device chains) and goes through ``tools/mkscenario.scenario_options``
like any generated scale scenario.  The traffic file sets the waves:
client ``q`` starts at ``start_s + (q % waves) * step_s``, so every wave
carries the same number of new chains whatever the seed; the seed draws
the circuits and destinations.  The topology is stated, not left to the
program's default: one vertex with one self-loop of ``edge_latency_ms``.
Shadow routes two hosts on one vertex over its cheapest incident edge
twice (topology.c), so every hop takes twice that.
"""

from __future__ import annotations

import math

import numpy as np

CELL_PAYLOAD = 505       # apps/tor.py: 512-byte cells, 7-byte header

GRAPHML = """<?xml version="1.0" encoding="utf-8"?>
<graphml xmlns="http://graphml.graphdrawing.org/xmlns">
<key attr.name="latency" attr.type="double" for="edge" id="latency" />
<key attr.name="packetloss" attr.type="double" for="edge" id="loss" />
<key attr.name="bandwidthdown" attr.type="int" for="node" id="bwdown" />
<key attr.name="bandwidthup" attr.type="int" for="node" id="bwup" />
<graph edgedefault="undirected">
<node id="poi-1"><data key="bwdown">{bw}</data><data key="bwup">{bw}</data>
</node>
<edge source="poi-1" target="poi-1"><data key="latency">{lat}</data>
<data key="loss">0.0</data></edge>
</graph>
</graphml>
"""


def _distinct3(rng, n: int, upper: int):
    a = rng.integers(0, upper, n)
    b = rng.integers(0, upper - 1, n)
    b = b + (b >= a)
    c = rng.integers(0, upper - 2, n)
    lo = np.minimum(a, b)
    hi = np.maximum(a, b)
    c = c + (c >= lo)
    c = c + (c >= hi)
    return a, b, c


def build(sizes: dict, traffic: dict, seed: int) -> dict:
    from shadow_tpu.core.configuration import (Configuration, FlowConfig,
                                               HostConfig)
    n_hosts = int(sizes["n_hosts"])
    n_relays = max(3, n_hosts // 10)
    n_servers = max(1, n_hosts // 100)
    n_clients = max(1, n_hosts - n_relays - n_servers)
    down, up = int(traffic["down_bytes"]), int(traffic["up_bytes"])
    start, waves = float(traffic["start_s"]), int(traffic["waves"])
    step = float(traffic["step_s"])
    path_seed = seed % (1 << 63)
    cfg = Configuration(stop_time_sec=float(sizes["stoptime_s"]))
    cfg.topology_text = GRAPHML.format(bw=int(sizes["relay_bw_kibps"]),
                                       lat=float(sizes["edge_latency_ms"]))
    cfg.hosts.append(HostConfig(
        id="relay", quantity=n_relays,
        bandwidth_down_kibps=int(sizes["relay_bw_kibps"]),
        bandwidth_up_kibps=int(sizes["relay_bw_kibps"])))
    cfg.hosts.append(HostConfig(
        id="dest", quantity=n_servers,
        bandwidth_down_kibps=int(sizes["server_bw_kibps"]),
        bandwidth_up_kibps=int(sizes["server_bw_kibps"])))
    cfg.hosts.append(HostConfig(
        id="torclient", quantity=n_clients,
        bandwidth_down_kibps=int(sizes["client_bw_down_kibps"]),
        bandwidth_up_kibps=int(sizes["client_bw_up_kibps"]),
        flows=[FlowConfig(dest="", start_time_sec=start,
                          down_bytes=down, up_bytes=up,
                          stagger_waves=waves, stagger_step_sec=step,
                          tor_path_seed=path_seed, tor_relays=n_relays,
                          tor_relay_prefix="relay", tor_servers=n_servers,
                          tor_server_prefix="dest")]))
    # the offered flows, stated independently of the program's expansion
    rng = np.random.default_rng(path_seed)
    g, m, e = _distinct3(rng, n_clients, n_relays)
    dests = rng.integers(0, n_servers, n_clients)
    cells_down = max(1, math.ceil(down / CELL_PAYLOAD))
    cells_up = math.ceil(up / CELL_PAYLOAD) if up else 0
    flows = []
    for q in range(n_clients):
        client = f"torclient{q + 1}"
        dest = "dest" if n_servers == 1 else f"dest{int(dests[q]) + 1}"
        start_ns = round((start + (q % waves) * step) * 1e9)
        flows.append((client, (dest, f"relay{int(e[q]) + 1}",
                               f"relay{int(m[q]) + 1}",
                               f"relay{int(g[q]) + 1}", client),
                      cells_down, cells_up, start_ns))
    rates = {"relay": (int(sizes["relay_bw_kibps"]),) * 2,
             "dest": (int(sizes["server_bw_kibps"]),) * 2,
             "torclient": (int(sizes["client_bw_up_kibps"]),
                           int(sizes["client_bw_down_kibps"]))}

    def bandwidth(host: str):
        """(up, down) KiB/s of a host, from its name's group."""
        return rates[host.rstrip("0123456789")]
    return {"kind": "config", "config": cfg, "flows": flows,
            "offered": (len(flows), "circuits"),
            "bandwidth": bandwidth, "processes": False,
            "hop_latency_ms": 2 * float(sizes["edge_latency_ms"]),
            "stop_s": float(sizes["stoptime_s"]),
            "last_arrival_s": max(f[4] for f in flows) / 1e9}
