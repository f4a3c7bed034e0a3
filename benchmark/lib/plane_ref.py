"""The plain reference for process-less Tor chains: what the device
traffic plane has to hold at a given tick, computed from the flows the
benchmark's generator offered and the configuration's stated bandwidths,
hop latency and granule.  It imports nothing of the program.

The semantics, one tick (``granule_ms`` of simulated time) at a time:

* a circuit is two chains, the download along its route (server, exit,
  middle, guard, client) and the upload back along it; each chain has one
  stage per hop, and stage ``k`` of a chain is paced by the sending host's
  upstream bucket, the last stage by the receiving host's downstream one;
* a circuit's cells enter the first stage of each chain at the first tick
  at or after its start time;
* cells a stage sends at tick ``t`` reach the next stage at tick
  ``t + ceil(hop_latency_ms / granule_ms)`` (at least one tick);
* every tick each bucket refills by ``rate x 1024 // 1000`` bytes per
  millisecond, up to its capacity (one millisecond's refill plus one MTU,
  or one tick's refill where that is more: Shadow's interface token
  bucket, refilled every millisecond), and then sends whole cells of
  ``cell_wire_bytes`` to its queued stages in the order (circuit, chain,
  stage), each stage as many as it holds while the bucket lasts;
* a chain is done at the tick its last stage has delivered every cell.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List, Tuple

MTU = 1500                    # bytes: Shadow's CONFIG_MTU
REFILL_PER_S = 1000           # Shadow refills interface buckets every 1 ms

Node = Tuple[str, str]        # (host, "tx" | "rx")


def bucket(kibps: int, granule_ms: int) -> Tuple[int, int]:
    """(refill per tick, capacity) of one host's bucket, in bytes."""
    per_ms = int(kibps) * 1024 // REFILL_PER_S
    refill = per_ms * granule_ms
    return refill, max(per_ms + MTU, refill)


def chain_nodes(route: Tuple[str, ...], direction: int) -> List[Node]:
    """The node pacing each stage of a circuit's download (0) or upload
    (1) chain; ``route`` runs from the server to the client."""
    hops = list(route) if direction == 0 else list(reversed(route))
    return [(h, "tx") for h in hops[:-1]] + [(hops[-1], "rx")]


class Chains:
    """The reference's state after ticks ``0 .. T - 1``.

    ``flows``: (client, route, cells_down, cells_up, start_ns) per
    circuit, in circuit order; ``bandwidth(host)``: (up, down) KiB/s."""

    def __init__(self, flows: List[tuple],
                 bandwidth: Callable[[str], Tuple[int, int]],
                 hop_latency_ms: float, granule_ms: int,
                 cell_wire_bytes: int):
        self.flows = flows
        self.bandwidth = bandwidth
        self.granule_ms = int(granule_ms)
        self.lat = max(1, math.ceil(hop_latency_ms / granule_ms))
        self.cell = int(cell_wire_bytes)
        g_ns = self.granule_ms * 1_000_000
        self.start_tick = [-(-int(f[4]) // g_ns) for f in flows]

    def bucket(self, node: Node) -> Tuple[int, int]:
        up, down = self.bandwidth(node[0])
        return bucket(up if node[1] == "tx" else down, self.granule_ms)

    def run(self, t_end: int) -> dict:
        """Step to tick ``t_end``: per (circuit, direction, stage) the
        cells queued, in flight towards it, delivered, the target and the
        done tick; per touched node its tokens and bytes sent; untouched
        nodes are full and have sent nothing."""
        order = sorted(range(len(self.flows)), key=lambda q: self.start_tick[q])
        started = [q for q in order if self.start_tick[q] < t_end]
        stages: Dict[tuple, dict] = {}
        arriving: Dict[int, Dict[tuple, int]] = {}   # tick -> stage -> cells
        by_node: Dict[Node, List[tuple]] = {}
        for q in started:
            _client, route, down, up, _s = self.flows[q]
            for d, cells in ((0, down), (1, up)):
                nodes = chain_nodes(route, d)
                for k, node in enumerate(nodes):
                    key = (q, d, k)
                    stages[key] = {"node": node, "queued": 0,
                                   "last": k == len(nodes) - 1,
                                   "delivered": 0, "target": 0, "done": -1,
                                   "cells": cells}
                    by_node.setdefault(node, []).append(key)
        for keys in by_node.values():
            keys.sort()
        tokens: Dict[Node, List[int]] = {}          # node -> [tokens, tick]
        sent: Dict[Node, int] = {n: 0 for n in by_node}
        pending = list(started)                     # by start tick
        live = set()
        t = self.start_tick[pending[0]] if pending else t_end
        while t < t_end:
            for key, cells in arriving.pop(t, {}).items():
                stages[key]["queued"] += cells
                live.add(key)
            while pending and self.start_tick[pending[0]] <= t:
                q = pending.pop(0)
                route = self.flows[q][1]
                for d in (0, 1):
                    first = stages[(q, d, 0)]
                    if first["cells"]:
                        first["queued"] += first["cells"]
                        live.add((q, d, 0))
                        last = len(chain_nodes(route, d)) - 1
                        stages[(q, d, last)]["target"] += first["cells"]
            for node in sorted({stages[k]["node"] for k in live}):
                refill, cap = self.bucket(node)
                tok, last_tick = tokens.get(node, [cap, t - 1])
                tok = min(cap, tok + refill * (t - last_tick))
                room = tok // self.cell
                spent = 0
                for key in by_node[node]:
                    st = stages[key]
                    if not st["queued"] or room <= 0:
                        continue
                    n = min(st["queued"], room)
                    room -= n
                    spent += n
                    st["queued"] -= n
                    if st["last"]:
                        st["delivered"] += n
                        if st["done"] < 0 and st["delivered"] >= st["target"]:
                            st["done"] = t
                    else:
                        q, d, k = key
                        arr = arriving.setdefault(t + self.lat, {})
                        arr[(q, d, k + 1)] = arr.get((q, d, k + 1), 0) + n
                    if not st["queued"]:
                        live.discard(key)
                tokens[node] = [tok - spent * self.cell, t]
                sent[node] += spent * self.cell
            if not live and not arriving:
                # nothing queued or in flight: jump to the next start
                t = self.start_tick[pending[0]] if pending else t_end
                t = min(t, t_end)
                continue
            t += 1
        for st in stages.values():
            st["inflight"] = 0
        for arr in arriving.values():
            for key, cells in arr.items():
                stages[key]["inflight"] += cells
        node_state = {}
        for node in by_node:
            refill, cap = self.bucket(node)
            tok, last_tick = tokens.get(node, [cap, t_end - 1])
            node_state[node] = (min(cap, tok + refill * (t_end - 1
                                                         - last_tick)),
                                sent[node])
        return {"stages": stages, "nodes": node_state, "started": started}
