"""A test-only comparison, the shape a configuration whose hosts run
processes brings: it reads host-side state (``process.app_state``)
rather than the device plane.  One count: clients that started long
enough before the closing boundary to have fetched the consensus, yet
hold no client state."""

from __future__ import annotations

SETTLE_S = 5.0      # a consensus fetch in this overlay takes well under 1 s


def snapshot(engine, scenario: dict, config: dict) -> dict:
    clients = {}
    for host in engine.hosts.values():
        if host.name in scenario["starts"]:
            states = [p.app_state for p in host.processes]
            clients[host.name] = next(
                (s.streams_ok for s in states if s is not None), None)
    return {"clients": clients}


def compare(snap: dict, scenario: dict, boundary_ns: int, config: dict):
    due = [c for c, t in scenario["starts"].items()
           if t + SETTLE_S <= boundary_ns / 1e9]
    missing = [c for c in due if snap["clients"].get(c) is None]
    return ({"clients_without_state": {"value": len(missing), "limit": 0}},
            len(due), len(missing))
