"""The benchmark's own tests run on the host CPU, at small sizes, with the
harness's look for a chip skipped (``run.find_devices``)."""

import importlib.util
import json
import os
import shutil
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
        self.config["sizes"].update(SMALL.get(self.spec["config"], {}))
        self.config["sizes"].update(mod.sizes)
        self.traffic.update(SMALL_TRAFFIC.get(self.spec["config"], {}))
        self.traffic.update(mod.traffic)
    monkeypatch.setattr(mod.Cell, "__init__", init)
    return mod


CELLS = os.path.join(HERE, "cells")
FOUND_BY_NAME = ("configs", "traffic", "generators", "comparisons", "metrics")


@pytest.fixture
def inject_cell(bench, monkeypatch, tmp_path):
    """``inject_cell(cell)`` adds a test-only cell to what the harness
    reads, never to ``BENCHMARK.json``: the harness's directories are
    copied under ``tmp_path`` with ``benchmark/tests/cells`` laid over
    them, and the cell joins the workloads it reads."""
    for d in FOUND_BY_NAME:
        shutil.copytree(os.path.join(BENCH, d), tmp_path / d)
        extra = os.path.join(CELLS, d)
        if os.path.isdir(extra):
            shutil.copytree(extra, tmp_path / d, dirs_exist_ok=True)
    monkeypatch.setattr(bench, "HERE", str(tmp_path))
    added = []
    real_load_json = bench.load_json

    def load_json(path):
        out = real_load_json(path)
        if os.path.basename(path) == "BENCHMARK.json":
            out["workloads"] = out["workloads"] + added
        return out
    monkeypatch.setattr(bench, "load_json", load_json)

    def inject(cell: dict) -> str:
        added.append(cell)
        return cell["name"]
    return inject


def result_of(out: str):
    """The result line, or None where the run printed none."""
    lines = out.strip().splitlines()
    if not lines:
        return None
    try:
        return json.loads(lines[-1])
    except ValueError:
        return None
