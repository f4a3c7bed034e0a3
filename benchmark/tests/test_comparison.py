"""The comparison that decides ``correct`` is the configuration's own,
found by the name under its ``"comparison"`` key
(``benchmark/comparisons/<name>.py``), as the generator is found: a
configuration without one, or naming a module that does not exist, has
no result; and a cell the harness has never seen, whose hosts run
processes, runs through the harness with its own comparison."""

import os

import pytest

from conftest import result_of

CHAINS = "tor-chains-100k.waves"


def _args(cell, seed=2**31 + 11, seconds=1.0):
    return ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", "0"]


@pytest.mark.parametrize("comparison, said", [
    (None, 'names no "comparison"'),
    ("no_such_comparison",
     os.path.join("benchmark", "comparisons", "no_such_comparison.py")
     + " does not exist")], ids=["key_missing", "module_missing"])
def test_configuration_without_its_comparison_fails(bench, monkeypatch,
                                                    capsys, comparison,
                                                    said):
    real_load_json = bench.load_json

    def load_json(path):
        out = real_load_json(path)
        if path.endswith(os.path.join("configs", "tor-chains-100k.json")):
            out.pop("comparison")
            if comparison is not None:
                out["comparison"] = comparison
        return out
    monkeypatch.setattr(bench, "load_json", load_json)
    assert bench.main(_args(CHAINS)) != 0
    captured = capsys.readouterr()
    assert result_of(captured.out) is None
    assert said in captured.err


def test_chains_comparison_is_found_by_name(bench):
    cell = bench.Cell(CHAINS)
    assert cell.config["comparison"] == "chains"
    assert cell.comparison.__file__ == os.path.join(
        bench.HERE, "comparisons", "chains.py")


def test_injected_process_cell_runs_with_its_own_comparison(
        bench, inject_cell, monkeypatch, capsys):
    """A test-only cell whose hosts run ``python:tor`` processes in
    ``device`` mode, with a stub comparison of host state: the harness
    warms the hop kernel, opens and closes the window, and prints that
    comparison's checks, with no edit to any harness file."""
    from shadow_tpu.parallel import tpu_policy
    warmed = []
    real_warmup = tpu_policy._TPUBatchMixin.warmup

    def warmup(self, engine, max_batch=8192):
        warmed.append(max_batch)
        return real_warmup(self, engine, max_batch=max_batch)
    monkeypatch.setattr(tpu_policy._TPUBatchMixin, "warmup", warmup)
    cell = inject_cell({"name": "tor-procs-test.circuits",
                        "config": "tor-procs-test", "traffic": "procs-test",
                        "chips": 1, "why": "test only"})
    assert bench.main(_args(cell)) == 0
    captured = capsys.readouterr()
    res = result_of(captured.out)
    assert warmed == [bench.HOP_WARM_BATCH]
    assert "400 clients offered" in captured.err
    assert res is not None and res["correct"] is True
    assert list(res["checks"]) == ["clients_without_state"]
    assert res["checks"]["clients_without_state"] == {"value": 0,
                                                      "limit": 0}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["sim_s_per_wall_s"]["value"] > 0
    assert "check clients_without_state: 0 (limit 0)" in captured.err
