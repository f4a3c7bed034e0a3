"""A test-only generator: a small Tor overlay whose hosts run
``python:tor`` processes, shaped like ``examples/tor_bootstrap.xml``.

A directory authority; relays that publish bandwidth-weighted
descriptors (weights drawn from the seed); one server; and clients that
fetch the consensus, build a 3-hop circuit over TCP with CREATE/EXTEND
and hand the download to the device traffic plane (``device``).  Client
``q`` starts at ``start_s + q * step_s``.
"""

from __future__ import annotations

import numpy as np


def build(sizes: dict, traffic: dict, seed: int) -> dict:
    rng = np.random.default_rng(seed % (1 << 63))
    n_relays, n_clients = int(sizes["n_relays"]), int(sizes["n_clients"])
    start, step = float(traffic["start_s"]), float(traffic["step_s"])
    spec = f"{int(traffic['up_bytes'])}:{int(traffic['down_bytes'])}"
    lines = [f'<shadow stoptime="{int(sizes["stoptime_s"])}">',
             '  <plugin id="tor" path="python:tor" />',
             '  <host id="dirauth" bandwidthdown="1048576" '
             'bandwidthup="1048576">',
             '    <process plugin="tor" starttime="1" '
             'arguments="dirauth 9030" />',
             '  </host>',
             '  <host id="dest">',
             '    <process plugin="tor" starttime="1" arguments="server 80" />',
             '  </host>']
    for i, bw in enumerate(rng.integers(50, 1000, n_relays)):
        lines.append(f'  <host id="relay{i + 1}"><process plugin="tor" '
                     f'starttime="2" arguments="relay 9001 dirauth:9030 '
                     f'{int(bw)}" /></host>')
    starts = {}
    for q in range(n_clients):
        name = f"client{q + 1}"
        starts[name] = start + q * step
        lines.append(
            f'  <host id="{name}" bandwidthdown="51200" bandwidthup="10240">'
            f'<process plugin="tor" starttime="{starts[name]}" '
            f'arguments="client 9050 auto:dirauth dest 80 1 {spec} device" />'
            '</host>')
    lines.append("</shadow>")
    return {"kind": "xml", "xml": "\n".join(lines) + "\n",
            "offered": (n_clients, "clients"), "starts": starts,
            "last_arrival_s": max(starts.values())}
