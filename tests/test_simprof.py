"""simprof — the device cost observatory (ISSUE 15).

1. Cost-model mechanics: build/save/load roundtrip, the digest stamp, and
   the REFUSAL contract (foreign fingerprint, tampered payload), plus the
   ``simprof check`` drill and the checked-in COSTMODEL.json's validity.
2. The data-driven exchange decision: choose_exchange_mode picks from
   measured numbers, honors the --exchange-mode override, and falls back
   to the PR-9 heuristic without a model.
3. Digest parity with the scheduler decision FORCED each way (the
   satellite gate): auto/fused/ppermute at K=1 and K=8, sharded-vs-serial
   (--device-plane-sync) and vs the numpy twin — the decision may only
   ever change WHICH identical-result kernel runs.
4. Live attribution: per-launch predicted-vs-measured gauges land in the
   prof.* scrape, an absurd model raises prof.model_stale, out-of-range
   tables are NOT judged (no extrapolation false-positives).
5. Histogram percentile schema (p50/p95/p99) + trace_report --metrics.
6. The trend ledger: append/load, trace_report --trend rendering with
   regression flags, and the --trend CLI.
"""

import copy
import json
import os

import pytest

from shadow_tpu.core import configuration
from shadow_tpu.core.checkpoint import state_digest
from shadow_tpu.core.controller import Controller
from shadow_tpu.core.options import Options
from shadow_tpu.prof import model as prof_model
from shadow_tpu.tools import workloads

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# small sharded star: big enough that cross-shard legs carry traffic,
# small enough that one run is ~a second at the 4 ms granule (parity
# claims are size-independent past engagement; soak depth stays low to
# hold the tier-1 wall — the PR-13 precedent)
STAR_XML = workloads.star_bulk(6, stoptime=120, bulk_bytes=16 * 1024 * 1024,
                               device_data=True)


def _measurements(step_points=None, ppermute_us=300.0, a2a_us=320.0,
                  psum_us=50.0, transfer=60.0):
    return {
        "collectives": {
            "ppermute": {"2x24": ppermute_us, "8x24": ppermute_us,
                         "8x960": ppermute_us},
            "all_to_all": {"2x24": a2a_us, "8x24": a2a_us,
                           "8x960": a2a_us},
            "psum": {"2x24": psum_us, "8x24": psum_us},
        },
        "step_kernel": {"points": step_points if step_points is not None
                        else [{"flows": 1, "us_per_step": 5.0},
                              {"flows": 1000, "us_per_step": 50.0}]},
        "transfer": {"dispatch_us": transfer, "flush_us": transfer},
    }


def _write_model(tmp_path, name="cm.json", **kw):
    data = prof_model.build_model(_measurements(**kw))
    p = str(tmp_path / name)
    prof_model.save_model(p, data)
    return p


def _run(xml, exchange_mode="auto", k=8, n_dev=8, mode="device",
         sync=False, cost_model="/nonexistent-no-model", stop=120,
         **opt_kw):
    cfg = configuration.parse_xml(xml)
    cfg.stop_time_sec = stop
    ctrl = Controller(
        Options(scheduler_policy="global", workers=0, seed=3,
                stop_time_sec=stop, log_level="warning",
                device_plane=mode, device_plane_sync=sync,
                superwindow_rounds=k, tpu_devices=n_dev,
                device_plane_granule_ms=4, exchange_mode=exchange_mode,
                cost_model=cost_model, **opt_kw), cfg)
    assert ctrl.run() == 0
    return ctrl


# deterministic repeat configurations shared across gates (the
# test_meshplane cache pattern — keeps the tier-1 wall share down)
_CACHE: dict = {}


def _star(exchange_mode="auto", k=8, **kw):
    key = (exchange_mode, k, tuple(sorted(kw.items())))
    if key not in _CACHE:
        _CACHE[key] = _run(STAR_XML, exchange_mode=exchange_mode, k=k,
                           **kw)
    return _CACHE[key]


# -- 1. model mechanics -----------------------------------------------------

def test_model_roundtrip_and_query_surface(tmp_path):
    p = _write_model(tmp_path)
    m = prof_model.load_model(p)
    assert m.band == prof_model.DEFAULT_BAND
    # linear fit through (1, 5) and (1000, 50): interpolates + clamps >= 0
    assert 5.0 <= m.step_us(500) <= 50.0
    assert m.transfer_us() == 120.0
    # collective lookup: exact key, then width interpolation within D
    assert m.collective_us("ppermute", 8, 24) == 300.0
    mid = m.collective_us("all_to_all", 8, 500)
    assert 0 < mid <= 320.0
    # per-tick exchange cost composition: fused = a2a + psum, ppermute =
    # legs * ppermute + psum
    fused = m.exchange_tick_us(8, "fused", 3, [4, 4, 4])
    pperm = m.exchange_tick_us(8, "ppermute", 3, [4, 4, 4])
    assert fused == pytest.approx(320.0 + 50.0)
    assert pperm == pytest.approx(3 * 300.0 + 50.0)
    assert m.predict_window_us(10, 1000, 100.0) == pytest.approx(
        10 * (50.0 + 100.0) + 120.0)


def test_model_refuses_foreign_fingerprint_and_tamper(tmp_path):
    p = _write_model(tmp_path)
    data = json.load(open(p))
    # foreign box: digest re-stamped (valid file), fingerprint differs
    foreign = copy.deepcopy(data)
    foreign["fingerprint"]["node"] = str(
        foreign["fingerprint"]["node"]) + "-elsewhere"
    foreign["digest"] = prof_model.payload_digest(foreign)
    p2 = str(tmp_path / "foreign.json")
    prof_model.save_model(p2, foreign)
    with pytest.raises(prof_model.CostModelError, match="fingerprint"):
        prof_model.load_model(p2)
    # tampered measurement: digest left stale
    tampered = copy.deepcopy(data)
    tampered["transfer"]["flush_us"] = 1.0
    p3 = str(tmp_path / "tampered.json")
    with open(p3, "w") as f:
        json.dump(tampered, f)
    with pytest.raises(prof_model.CostModelError, match="digest"):
        prof_model.load_model(p3)
    # schema problem: not silently "loaded"
    with open(p3, "w") as f:
        json.dump({"version": 1}, f)
    with pytest.raises(prof_model.CostModelError, match="schema"):
        prof_model.load_model(p3)


def test_load_for_engine_degrades_never_raises(tmp_path):
    opts = Options(cost_model=str(tmp_path / "missing.json"))
    m, status = prof_model.load_for_engine(opts)
    assert m is None and status == "absent"
    # a refused model degrades to (None, "refused"), not an exception
    p = _write_model(tmp_path)
    data = json.load(open(p))
    data["fingerprint"]["cpus"] = -1
    data["digest"] = prof_model.payload_digest(data)
    prof_model.save_model(p, data)
    m, status = prof_model.load_for_engine(Options(cost_model=p))
    assert m is None and status == "refused"


def test_simprof_check_drills_and_checked_in_model(tmp_path):
    from shadow_tpu.prof.cli import check_model
    chk = check_model(_write_model(tmp_path))
    assert chk["ok"], chk["problems"]
    assert chk["stale_fingerprint_refused"]
    assert chk["tampered_digest_refused"]
    # the checked-in per-box model must stay schema-valid and
    # digest-current on every box (loading it is only legal on the box
    # that calibrated it — loads_on_this_box records which)
    checked_in = os.path.join(REPO, "COSTMODEL.json")
    assert os.path.exists(checked_in), \
        "COSTMODEL.json missing: run simprof calibrate"
    chk = check_model(checked_in)
    assert chk["ok"], chk["problems"]
    # a corrupt file is rc-1 material, never ok
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert not check_model(str(bad))["ok"]


# -- 2. the data-driven decision -------------------------------------------

def _toy_schedule(legs, d=8, pair_width=3, width=4):
    import numpy as np

    from shadow_tpu.parallel.mesh.exchange import ExchangeSchedule
    z = np.zeros(d * width, dtype=np.int64)
    return ExchangeSchedule(d, list(range(1, legs + 1)), [width] * legs,
                            [z] * legs, [z] * legs, legs * width,
                            np.zeros((d, d), dtype=np.int64), pair_width,
                            np.zeros(d * d * pair_width, dtype=np.int64),
                            np.zeros(d * d * pair_width, dtype=np.int64))


def test_choose_exchange_mode_model_heuristic_forced(tmp_path):
    from shadow_tpu.parallel.mesh.exchange import choose_exchange_mode
    # heuristic (no model): the PR-9 rule, predicted 0
    assert choose_exchange_mode(_toy_schedule(3)) == ("fused", 0.0,
                                                      "heuristic")
    assert choose_exchange_mode(_toy_schedule(1)) == ("ppermute", 0.0,
                                                      "heuristic")
    assert choose_exchange_mode(_toy_schedule(0))[0] == "none"
    # model: cheapest measured per-tick cost wins — BOTH ways
    a2a_cheap = prof_model.load_model(_write_model(
        tmp_path, "a.json", ppermute_us=500.0, a2a_us=100.0))
    mode, pred, src = choose_exchange_mode(_toy_schedule(3), a2a_cheap)
    assert (mode, src) == ("fused", "model") and pred > 0
    pp_cheap = prof_model.load_model(_write_model(
        tmp_path, "b.json", ppermute_us=10.0, a2a_us=900.0))
    mode, pred, src = choose_exchange_mode(_toy_schedule(3), pp_cheap)
    assert (mode, src) == ("ppermute", "model")
    # ... even a single leg can go fused when the lone ppermute measures
    # slower (the heuristic could never make this choice)
    mode, _, src = choose_exchange_mode(_toy_schedule(1), a2a_cheap)
    assert (mode, src) == ("fused", "model")
    # forced override beats the model
    mode, _, src = choose_exchange_mode(_toy_schedule(3), pp_cheap,
                                        "fused")
    assert (mode, src) == ("fused", "forced")
    # no cross edges: nothing to schedule, whatever was asked
    assert choose_exchange_mode(_toy_schedule(0), pp_cheap,
                                "fused")[0] == "none"


# -- 3. digest parity with the decision forced each way --------------------

def test_exchange_mode_digest_parity_k1_k8_and_serial():
    """The satellite gate: the scheduler may only ever change WHICH
    identical-result kernel runs.  auto/fused/ppermute at K=8, both
    forced modes at K=1, the --device-plane-sync serial oracle, and the
    numpy twin all land one digest."""
    d0 = state_digest(_star("auto", k=8).engine)
    info = _star("auto", k=8).engine.device_plane._meshinfo
    assert info.legs >= 2, "star must produce a multi-leg schedule"
    for ex in ("fused", "ppermute"):
        for k in (1, 8):
            ctrl = _star(ex, k=k)
            scrape = ctrl.engine.metrics.scrape()
            assert scrape["mesh.exchange_mode"] == ex
            assert scrape["mesh.exchange_source"] == "forced"
            assert scrape["mesh.cross_shard_cells"] > 0
            assert scrape["mesh.host_bounces"] == 0
            assert state_digest(ctrl.engine) == d0, (ex, k)
    serial = _run(STAR_XML, exchange_mode="ppermute", k=8, sync=True)
    assert state_digest(serial.engine) == d0
    twin = _star("auto", k=8, mode="numpy")
    assert state_digest(twin.engine) == d0


def test_model_driven_decision_reaches_the_engine(tmp_path):
    """An engine run with a loaded model records source=model and the
    predicted per-tick cost in the mesh scrape; forcing the other mode
    still lands the same digest (re-pinning parity across the actual
    model decision, not just the forced axes)."""
    pp_cheap = _write_model(tmp_path, "pp.json", ppermute_us=1.0,
                            a2a_us=9000.0)
    ctrl = _run(STAR_XML, cost_model=pp_cheap)
    scrape = ctrl.engine.metrics.scrape()
    assert scrape["mesh.cost_model"] == "loaded"
    assert scrape["mesh.exchange_source"] == "model"
    assert scrape["mesh.exchange_mode"] == "ppermute"
    assert scrape["mesh.predicted_us"] > 0
    assert state_digest(ctrl.engine) == state_digest(
        _star("auto", k=8).engine)


# -- 4. live attribution ---------------------------------------------------

def test_attribution_gauges_and_stale_counter(tmp_path):
    """With an in-range model the per-launch gauges fill and every
    launch is checked; with an absurdly overpredicting model the loud
    prof.model_stale counter fires; a model whose calibrated flow range
    is far above the table skips judgment entirely (no extrapolation
    false-positives)."""
    sane = _write_model(tmp_path, "sane.json")
    ctrl = _run(STAR_XML, cost_model=sane)
    scrape = ctrl.engine.metrics.scrape()
    checked = scrape["prof.launches_checked"]
    assert checked > 0
    assert scrape["prof.launch_predicted_us"]["count"] == checked
    assert scrape["prof.launch_measured_us"]["count"] >= checked
    for key in ("p50", "p95", "p99"):
        assert key in scrape["prof.launch_predicted_us"]
    # absurd model: calibrated IN range (the 8-device pad puts 48 kernel
    # flows on the wire) but predicts ~seconds per tick -> every launch
    # violates the band -> the counter is LOUD.  (An out-of-range absurd
    # model must NOT fire — that is the two-sided no-extrapolation guard
    # pinned below.)
    absurd = _write_model(
        tmp_path, "absurd.json",
        step_points=[{"flows": 48, "us_per_step": 5e6}], transfer=5e6)
    ctrl = _run(STAR_XML, cost_model=absurd)
    scrape = ctrl.engine.metrics.scrape()
    assert scrape["prof.model_stale"] > 0
    # out-of-range model (calibrated at >= 1M flows): the toy table is
    # never judged — zero checked launches, zero stale flags
    far = _write_model(
        tmp_path, "far.json",
        step_points=[{"flows": 1_000_000, "us_per_step": 5e6}])
    ctrl = _run(STAR_XML, cost_model=far)
    scrape = ctrl.engine.metrics.scrape()
    assert scrape["prof.launches_checked"] == 0
    assert scrape["prof.model_stale"] == 0


# -- 5. percentile schema --------------------------------------------------

def test_histogram_percentiles_schema_and_report(tmp_path):
    from shadow_tpu.obs.metrics import (Histogram, MetricsRegistry,
                                        MetricsWriter, read_metrics_file)
    h = Histogram("x")
    for v in range(1, 101):
        h.observe(v)
    s = h.snapshot()
    for key in ("count", "sum", "min", "max", "mean", "p50", "p95",
                "p99", "buckets"):
        assert key in s, f"snapshot lost {key}"
    assert s["min"] <= s["p50"] <= s["p95"] <= s["p99"] <= s["max"]
    # p50 of 1..100 must land in the covering power-of-two bucket
    assert 32 <= s["p50"] <= 64
    assert s["p99"] >= 64
    # empty histogram: schema stays minimal (no fake percentiles)
    assert Histogram("y").snapshot() == {"count": 0}
    # ... and the percentiles ride trace_report --metrics via the final
    # summary scrape (the histograms digest table)
    reg = MetricsRegistry(enabled=True)
    hh = reg.histogram("device.probe_us")
    for v in (10, 20, 400):
        hh.observe(v)
    mpath = str(tmp_path / "m.jsonl")
    w = MetricsWriter(mpath, every_rounds=1)
    w.write_summary(reg, rounds_done=1, sim_time_ns=0)
    from shadow_tpu.tools.trace_report import summarize_metrics
    rep = summarize_metrics(read_metrics_file(mpath))
    assert rep["final"]["device.probe_us"]["p95"] >= \
        rep["final"]["device.probe_us"]["p50"]
    assert rep["histograms"]["device.probe_us"]["count"] == 3
    assert "p99" in rep["histograms"]["device.probe_us"]


# -- 6. the trend ledger ---------------------------------------------------

def test_ledger_append_load_and_trend(tmp_path, capsys):
    from shadow_tpu.prof.ledger import append_row, load_history
    from shadow_tpu.tools.trace_report import main as tr_main
    from shadow_tpu.tools.trace_report import summarize_trend
    lp = str(tmp_path / "hist.jsonl")
    append_row(lp, "flagship", {"wall_sec": 10.0,
                                "sim_sec_per_wall_sec": 2.0,
                                "plane": {"dispatches": 40},
                                "scenario": "standin"})
    append_row(lp, "flagship", {"wall_sec": 9.0,
                                "sim_sec_per_wall_sec": 2.4})
    append_row(lp, "flagship", {"wall_sec": 14.0,
                                "sim_sec_per_wall_sec": 1.5})
    append_row(lp, "multichip", {"host_bounces": 0})
    recs = load_history(lp)
    assert len(recs) == 4
    assert all(r["box"] and r["sha"] and r["ts"] for r in recs)
    # nested dicts flatten one level, strings survive, and the record is
    # keyed by row family
    assert recs[0]["cols"]["plane.dispatches"] == 40
    assert recs[0]["cols"]["scenario"] == "standin"
    rep = summarize_trend(recs)
    cols = rep["rows"]["flagship"]["columns"]
    # wall regressed (lower-better, latest 14 vs best 9) and the rate
    # regressed (higher-better, latest 1.5 vs best 2.4): both flagged
    assert cols["wall_sec"]["regressed"] is True
    assert cols["wall_sec"]["direction"] == "lower"
    assert cols["sim_sec_per_wall_sec"]["regressed"] is True
    assert len(cols["wall_sec"]["spark"]) == 3
    assert "flagship:wall_sec" in rep["regressions"]
    # single-row families render without a verdict
    assert rep["rows"]["multichip"]["columns"]["host_bounces"][
        "regressed"] is None
    # the CLI path: one JSON document, rc 0; empty ledger is rc 1
    assert tr_main(["--trend", lp]) == 0
    out = capsys.readouterr().out
    assert json.loads(out)["regressions"]
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert tr_main(["--trend", str(empty)]) == 1


def test_checked_in_history_renders():
    """The committed BENCH_HISTORY.jsonl must always render — the
    acceptance artifact (>= 1 appended row) and the guarantee that the
    trajectory file never rots."""
    from shadow_tpu.prof.ledger import load_history
    from shadow_tpu.tools.trace_report import summarize_trend
    path = os.path.join(REPO, "BENCH_HISTORY.jsonl")
    assert os.path.exists(path), \
        "BENCH_HISTORY.jsonl missing: run bench.py / --multichip"
    rep = summarize_trend(load_history(path))
    assert rep["records"] >= 1
    assert rep["row_families"]
