"""The benchmark's own tests run on the host CPU, at small sizes, with the
harness's look for a chip skipped (``run.find_devices``)."""

import importlib.util
import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# small sizes of each configuration, for the CPU: 890 circuits, one a
# wave, so the traffic outlasts a CPU-speed window
SMALL = {"tor-chains-100k": {"n_hosts": 1000}}
SMALL_TRAFFIC = {}


@pytest.fixture
def bench(monkeypatch):
    """benchmark/run.py as a module, with the chip check skipped and every
    configuration cut to its small size.  ``bench.sizes`` and
    ``bench.traffic`` override further entries per test."""
    spec = importlib.util.spec_from_file_location(
        "bench_run_under_test", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    import jax
    monkeypatch.setattr(mod, "find_devices", lambda chips: jax.devices())
    mod.sizes, mod.traffic = {}, {}
    base = mod.Cell.__init__

    def init(self, name):
        base(self, name)
        self.config["sizes"].update(SMALL[self.spec["config"]])
        self.config["sizes"].update(mod.sizes)
        self.traffic.update(SMALL_TRAFFIC.get(self.spec["config"], {}))
        self.traffic.update(mod.traffic)
    monkeypatch.setattr(mod.Cell, "__init__", init)
    return mod


def result_of(out: str):
    """The result line, or None where the run printed none."""
    lines = out.strip().splitlines()
    if not lines:
        return None
    try:
        return json.loads(lines[-1])
    except ValueError:
        return None
