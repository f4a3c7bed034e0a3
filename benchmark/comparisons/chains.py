"""How ``correct`` is decided for process-less chain configurations
(``"comparison": "chains"``): the device traffic plane's state at the
tick it had reached when the window closed, against the plain reference
(``lib/plane_ref.py``), which computes that state from the flows the
benchmark's own generator offered and the configuration's stated
bandwidths, hop latency, granule and cell size (``config["plane"]``).

Every number compared is a count of differences, held to 0, except the
plane's lag behind the closing boundary:

* ``plane_lag_ticks``: the closing boundary's tick less the tick the
  plane's state stands at, in the configuration's granule: a plane that
  stopped advancing, or steps at another granule, lags;
* ``flows_differing``: rows of the flow table (per circuit, chain and
  stage: the pacing node, cells queued, cells in flight towards it,
  delivered, target and done tick) that differ from the reference; rows
  of circuits not started by then have to be empty;
* ``nodes_differing``: token buckets and bytes sent, for every node;
* ``completions_differing``: circuits the plane reports done that the
  reference does not, and the other way round;
* ``flows_not_offered``: the plane's circuits against the generator's
  (client, route, cells);
* ``plane_not_on_device``, ``hop_host_calls``, ``recoveries``: the timed
  path ran on the device and recovered nothing.

The program is read for its state and for the layout that names each
row (chain, stage, node); the layout is checked against the reference's
own node for every row the reference has touched.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmark.lib import plane_ref


def snapshot(engine, scenario: dict, config: dict) -> dict:
    """What the comparison reads from the timed run, once it is over."""
    plane = engine.device_plane
    scrape = engine.metrics.scrape()
    snap = {"hop_host_calls": int(scrape.get("policy.host_calls", 0)),
            "recoveries": int(engine.supervision.recoveries),
            "plane": None}
    if plane is None or plane._state is None:
        return snap
    if plane._shard is not None:
        arrays = plane._unshard_state(plane._shard)
    else:
        arrays = tuple(np.asarray(a) for a in plane._state)
    t, queued, ring, tokens, delivered, target, done_tick, sent = arrays
    snap["plane"] = {
        "t": int(t), "queued": queued, "ring": ring, "tokens": tokens,
        "delivered": delivered, "target": target, "done": done_tick,
        "sent": sent,
        "chain": np.asarray(plane.flow_circ),
        "stage": np.asarray(plane.flow_stage),
        "node": np.asarray(plane.flow_node),
        "node_names": list(plane.node_names),
        "clients": [s.client_name for s in plane.specs],
        "specs": [(s.client_name, tuple(s.route_down), int(s.cells_down),
                   int(s.cells_up)) for s in plane.specs],
        "completed": sorted(plane._done),
        "on_device": (plane.mode == "device" and not plane.demoted
                      and plane.recoveries == 0),
    }
    return snap


def compare(snap: dict, scenario: dict, boundary_ns: int, config: dict):
    """(name -> {"value", "limit"}, circuits started by the closing
    boundary, circuits whose state differs)."""
    granule_ms = int(config["plane"]["granule_ms"])
    cell_wire_bytes = int(config["plane"]["cell_wire_bytes"])
    checks = {"hop_host_calls": snap["hop_host_calls"],
              "recoveries": snap["recoveries"]}
    p = snap["plane"]
    flows = scenario["flows"]
    ref_model = plane_ref.Chains(flows, scenario["bandwidth"],
                                 scenario["hop_latency_ms"], granule_ms,
                                 cell_wire_bytes)
    boundary = boundary_ns // (granule_ms * 10**6)
    started = [q for q, t in enumerate(ref_model.start_tick) if t < boundary]
    if p is None:
        checks.update(plane_lag_ticks=boundary, flows_differing=2 * len(flows),
                      plane_not_on_device=1)
        bad = set(started)
    else:
        ref = ref_model.run(p["t"])
        checks["plane_lag_ticks"] = abs(boundary - p["t"])
        checks["plane_not_on_device"] = int(not p["on_device"])
        checks["flows_not_offered"] = _not_offered(p["specs"], flows)
        bad, checks["flows_differing"] = _flows(p, ref, flows, ref_model.lat)
        checks["nodes_differing"] = _nodes(p, ref, ref_model)
        done = _completions(p, ref, flows)
        checks["completions_differing"] = len(done)
        bad |= done
    out = {k: {"value": int(v), "limit": 0} for k, v in checks.items()}
    out["plane_lag_ticks"]["limit"] = LAG_LIMIT_TICKS
    return out, len(started), len(bad)


# from the readings in PERF.md: 0 on every sound run, 170 for the control
LAG_LIMIT_TICKS = 32


def _client_index(flows: List[tuple]) -> Dict[str, int]:
    return {f[0]: q for q, f in enumerate(flows)}


def _flows(p: dict, ref: dict, flows: List[tuple], lat: int):
    """(circuits with a differing row, rows differing)."""
    by_client = _client_index(flows)
    spec_q = np.array([by_client.get(c, -1) for c in p["clients"]],
                      dtype=np.int64)
    row_q = spec_q[p["chain"] >> 1]
    ring, t = p["ring"], p["t"]
    inflight = np.zeros(len(p["queued"]), dtype=np.int64)
    for j in range(1, lat + 1):
        inflight += ring[(t - j) % ring.shape[0]].astype(np.int64)
    # the first stage receives nothing through the ring
    inflight = np.where(p["stage"] > 0, inflight, 0)
    started = np.zeros(len(flows), dtype=bool)
    started[ref["started"]] = True
    touched = (row_q >= 0) & started[np.maximum(row_q, 0)]
    busy = ((p["queued"] != 0) | (inflight != 0) | (p["delivered"] != 0)
            | (p["target"] != 0) | (p["done"] != -1))
    quiet_bad = ~touched & busy
    bad = set(int(q) for q in np.unique(row_q[quiet_bad & (row_q >= 0)]))
    n_bad = int(quiet_bad.sum())
    seen = set()
    names = p["node_names"]
    for row in np.flatnonzero(touched):
        q = int(row_q[row])
        key = (q, int(p["chain"][row]) & 1, int(p["stage"][row]))
        st = ref["stages"].get(key)
        seen.add(key)
        got = (tuple(names[int(p["node"][row])]), int(p["queued"][row]),
               int(inflight[row]), int(p["delivered"][row]),
               int(p["target"][row]), int(p["done"][row]))
        want = None if st is None else (
            st["node"], st["queued"], st["inflight"], st["delivered"],
            st["target"], st["done"])
        if got != want:
            n_bad += 1
            bad.add(q)
    missing = set(ref["stages"]) - seen
    n_bad += len(missing)
    bad |= {k[0] for k in missing}
    return bad, n_bad


def _nodes(p: dict, ref: dict, model: plane_ref.Chains) -> int:
    names = p["node_names"]
    want_tok = np.empty(len(names), dtype=np.int64)
    want_sent = np.zeros(len(names), dtype=np.int64)
    for i, node in enumerate(names):
        node = tuple(node)
        if node in ref["nodes"]:
            want_tok[i], want_sent[i] = ref["nodes"][node]
        else:
            want_tok[i] = model.bucket(node)[1]
    return int(((p["tokens"] != want_tok) | (p["sent"] != want_sent)).sum())


def _completions(p: dict, ref: dict, flows: List[tuple]) -> set:
    """Circuits whose completion differs: done in one and not the other."""
    by_client = _client_index(flows)
    got = {by_client.get(p["clients"][c], -1) for c in p["completed"]}
    want = set()
    for q in ref["started"]:
        route, ups = flows[q][1], flows[q][3]
        last = len(route) - 1
        down = ref["stages"][(q, 0, last)]["done"]
        up = ref["stages"][(q, 1, last)]["done"]
        if down >= 0 and (up >= 0 or not ups):
            want.add(q)
    return got ^ want


def _not_offered(specs: List[tuple], offered: List[tuple]) -> int:
    want = sorted((c, tuple(r), d, u) for c, r, d, u, _s in offered)
    have = sorted(specs)
    return abs(len(want) - len(have)) + sum(
        1 for a, b in zip(want, have) if a != b)
