"""COSTMODEL-driven dispatch auto-tuner.

1. Decision table (pure plan_dispatch units): synthetic models force each
   regime — launch-bound => deep K, compute-bound => hand default,
   uncalibrated or out-of-range => hand defaults,
   ``--device-autotune off`` => untouched, an explicitly-set knob is
   always honored, cadence/granule stay at contract values.
2. Flush layout (ops level): the device pack is bit-identical to the
   numpy twin, and parse_flush recovers every section.
3. Engine integration: digest parity tuned-vs-hand-defaults,
   device-vs-numpy, explicit-K=1-vs-deep-K, and sharded-vs-serial under
   the tuner; the tuned depth engaged in the scrape; the
   prof.model_stale alarm fires when the TUNED prediction misses the
   band (the tuner's audit trail is live, not just recorded).

Runs are shared through a module cache (the test_meshplane pattern) so
the new gates displace soak depth instead of growing the tier-1 wall.
"""

import os
import tempfile

import numpy as np
import pytest

from shadow_tpu.core import configuration
from shadow_tpu.core.checkpoint import state_digest
from shadow_tpu.core.controller import Controller
from shadow_tpu.core.options import Options
from shadow_tpu.prof import autotune, model as prof_model
from shadow_tpu.tools import workloads

# single-device star with 48 chains; still ~seconds at the 4 ms granule
STAR24_XML = workloads.star_bulk(24, stoptime=120,
                                 bulk_bytes=16 * 1024 * 1024,
                                 device_data=True)
# small sharded star for the mesh-path parity legs (test_simprof's size)
STAR6_XML = workloads.star_bulk(6, stoptime=120,
                                bulk_bytes=16 * 1024 * 1024,
                                device_data=True)

# models shared across tests/cached runs need a module-stable path
# (pytest tmp_path would fork the run-cache key per test)
_TD = tempfile.mkdtemp(prefix="autotune-models-")


def _measurements(step_points, dispatch_us=400.0, flush_us=1600.0):
    return {
        "collectives": {
            "ppermute": {"2x24": 300.0, "8x24": 300.0},
            "all_to_all": {"2x24": 320.0, "8x24": 320.0},
            "psum": {"2x24": 50.0, "8x24": 50.0},
        },
        "step_kernel": {"points": step_points},
        "transfer": {"dispatch_us": dispatch_us, "flush_us": flush_us,
                     "flush_us_per_mb": 0.0},
    }


def _model(step_points, **kw):
    return prof_model.CostModel(
        prof_model.build_model(_measurements(step_points, **kw)))


def _model_file(name, step_points, **kw):
    p = os.path.join(_TD, name)
    if not os.path.exists(p):
        prof_model.save_model(
            p, prof_model.build_model(_measurements(step_points, **kw)))
    return p


# a covering launch-bound model: flat cheap step cost, large fixed
# per-launch transfer — forces deep K
def _launch_bound_file():
    return _model_file("launch-bound.json",
                       [{"flows": 1, "us_per_step": 30.0},
                        {"flows": 1_000_000, "us_per_step": 30.0}])


class _Opts:
    def __init__(self, k=8, cadence=8, autotune="on"):
        self.superwindow_rounds = k
        self.device_plane_batch_steps = cadence
        self.device_autotune = autotune


# -- 1. decision table ------------------------------------------------------

def test_plan_off_restores_hand_defaults():
    m = _model([{"flows": 1, "us_per_step": 30.0},
                {"flows": 1000, "us_per_step": 30.0}])
    plan = autotune.plan_dispatch(m, "loaded", _Opts(autotune="off"), 500)
    assert plan.source == "off"
    assert plan.superwindow_rounds == autotune.DEFAULT_K


def test_plan_uncalibrated_falls_back_to_defaults():
    # no model on this box / model refused
    for model, status in ((None, "absent"), (None, "refused")):
        plan = autotune.plan_dispatch(model, status, _Opts(), 500)
        assert plan.source == "defaults"
        assert plan.superwindow_rounds == autotune.DEFAULT_K
    # loaded but the flow table sits outside the calibrated range: the
    # no-extrapolation guard refuses to tune from it
    m = _model([{"flows": 100_000, "us_per_step": 30.0},
                {"flows": 1_000_000, "us_per_step": 30.0}])
    assert not m.covers(500)
    plan = autotune.plan_dispatch(m, "loaded", _Opts(), 500)
    assert plan.source == "defaults"


def test_plan_launch_bound_deepens_k():
    # fixed transfer 2000us vs 30us/step at cadence 8: the fixed half
    # dominates -> K deepens to the MAX_K ceiling; cadence and granule
    # stay at their digest-bearing contract values
    m = _model([{"flows": 1, "us_per_step": 30.0},
                {"flows": 1_000_000, "us_per_step": 30.0}])
    plan = autotune.plan_dispatch(m, "loaded", _Opts(), 500)
    assert plan.source == "model"
    assert plan.superwindow_rounds == autotune.MAX_K
    assert plan.min_dispatch_steps == autotune.DEFAULT_CADENCE
    assert plan.granule_source == "contract"
    # a compute-bound box (expensive steps, same fixed cost) keeps the
    # hand default — no gratuitous deepening
    m2 = _model([{"flows": 1, "us_per_step": 5000.0},
                 {"flows": 1_000_000, "us_per_step": 5000.0}])
    plan2 = autotune.plan_dispatch(m2, "loaded", _Opts(), 500)
    assert plan2.source == "model"
    assert plan2.superwindow_rounds == autotune.DEFAULT_K


def test_plan_honors_explicit_user_knob():
    m = _model([{"flows": 1, "us_per_step": 30.0},
                {"flows": 1_000_000, "us_per_step": 30.0}])
    plan = autotune.plan_dispatch(m, "loaded", _Opts(k=1), 500)
    assert plan.source == "model"
    assert plan.superwindow_rounds == 1   # the user's knob, not ours


def test_plan_metrics_audit_trail():
    m = _model([{"flows": 1, "us_per_step": 30.0},
                {"flows": 1_000_000, "us_per_step": 30.0}])
    got = autotune.plan_dispatch(m, "loaded", _Opts(), 500).metrics()
    for key in ("prof.autotune_source", "prof.autotune_k",
                "prof.autotune_cadence", "prof.autotune_granule",
                "prof.autotune_predicted_us"):
        assert key in got, f"audit trail lost {key}"
    assert got["prof.autotune_source"] == "model"
    assert got["prof.autotune_granule"] == "contract"
    assert got["prof.autotune_predicted_us"] > 0


# -- 2. flush layout ---------------------------------------------------------

def test_capped_pack_parse_and_overflow_detection():
    """The full-layout round trip: the device pack equals the numpy twin
    bit for bit, and parse_flush recovers every section from it."""
    from shadow_tpu.ops.torcells_device import (
        _pack_flush_jnp, flush_len, pack_flush_np, parse_flush)
    import jax.numpy as jnp

    C, H = 10, 12
    newly = np.zeros(C, bool)
    newly[[1, 4, 5, 9]] = True
    done_last = np.arange(C, dtype=np.int64) * 7
    sent_delta = np.zeros(H, np.int64)
    sent_delta[[0, 2, 3, 7, 8, 11]] = np.int64([5, -2, 9, 1, 4, 6])
    full = np.asarray(_pack_flush_jnp(
        np.int64(123), np.int64(456), np.int64(789), jnp.asarray(newly),
        jnp.asarray(done_last), jnp.asarray(sent_delta), moved=31))
    assert len(full) == flush_len(C, H)
    np.testing.assert_array_equal(
        full, pack_flush_np(np.int64(123), np.int64(456), np.int64(789),
                            newly, done_last, sent_delta, moved=31))
    (forwards, delivered_sum, t_stop, chains, steps, nodes,
     deltas) = parse_flush(full, C, H)
    assert (forwards, delivered_sum, t_stop) == (123, 456, 789)
    np.testing.assert_array_equal(chains, [1, 4, 5, 9])
    np.testing.assert_array_equal(steps, done_last[[1, 4, 5, 9]])
    np.testing.assert_array_equal(nodes, [0, 2, 3, 7, 8, 11])
    np.testing.assert_array_equal(deltas, [5, -2, 9, 1, 4, 6])


# -- 3. engine integration --------------------------------------------------

def _run(xml, n_dev=1, mode="device", k=8, sync=False,
         cost_model="/nonexistent-no-model", autotune_opt="on"):
    cfg = configuration.parse_xml(xml)
    cfg.stop_time_sec = 120
    ctrl = Controller(
        Options(scheduler_policy="global", workers=0, seed=3,
                stop_time_sec=120, log_level="warning",
                device_plane=mode, device_plane_sync=sync,
                superwindow_rounds=k, tpu_devices=n_dev,
                device_plane_granule_ms=4, cost_model=cost_model,
                device_autotune=autotune_opt), cfg)
    assert ctrl.run() == 0
    return ctrl


_CACHE: dict = {}


def _cached(xml_key, **kw):
    key = (xml_key, tuple(sorted(kw.items())))
    if key not in _CACHE:
        xml = STAR24_XML if xml_key == "star24" else STAR6_XML
        _CACHE[key] = _run(xml, **kw)
    return _CACHE[key]


def test_tuned_run_engages_and_accounts_savings():
    ctrl = _cached("star24", cost_model=_launch_bound_file())
    scrape = ctrl.engine.metrics.scrape()
    assert scrape["prof.autotune_source"] == "model"
    assert scrape["prof.autotune_k"] == autotune.MAX_K
    # deep K engaged: launches amortize above the hand-default floor
    st = ctrl.engine.device_plane.stats()
    assert st["rounds_per_launch"] > 1


def test_digest_parity_tuned_vs_hand_defaults_and_numpy():
    tuned = _cached("star24", cost_model=_launch_bound_file())
    base = _cached("star24", cost_model=_launch_bound_file(),
                   autotune_opt="off")
    assert state_digest(base.engine) == state_digest(tuned.engine)
    assert base.engine.events_executed == tuned.engine.events_executed
    # the off side really ran the hand defaults
    assert base.engine.metrics.scrape()["prof.autotune_source"] == "off"
    twin = _cached("star24", cost_model=_launch_bound_file(), mode="numpy")
    assert state_digest(twin.engine) == state_digest(tuned.engine)


def test_digest_parity_explicit_k1_vs_deep_k():
    # --superwindow-rounds 1 is the user's knob: honored (K=1) even with
    # the launch-bound model, and bit-identical to the tuned deep-K run
    tuned = _cached("star24", cost_model=_launch_bound_file())
    k1 = _cached("star24", cost_model=_launch_bound_file(), k=1)
    assert k1.engine.metrics.scrape()["prof.autotune_k"] == 1
    assert state_digest(k1.engine) == state_digest(tuned.engine)


def test_digest_parity_sharded_tuned_vs_off_and_serial():
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("mesh parity needs the virtual device mesh")
    tuned = _cached("star6", n_dev=8, cost_model=_launch_bound_file())
    off = _cached("star6", n_dev=8, cost_model=_launch_bound_file(),
                  autotune_opt="off")
    serial = _cached("star6", n_dev=8, cost_model=_launch_bound_file(),
                     sync=True)
    assert state_digest(off.engine) == state_digest(tuned.engine)
    assert state_digest(serial.engine) == state_digest(tuned.engine)
    scrape = tuned.engine.metrics.scrape()
    assert scrape["prof.autotune_source"] == "model"
    # quiet-tick fusion bookkeeping: the masked variants never claim more
    # active legs than the schedule has
    assert 0 <= scrape["mesh.legs_active"] <= scrape["mesh.exchange_legs"]


def test_model_stale_fires_on_tuned_misprediction():
    # an absurd covering model engages the tuner (source=model) AND its
    # prediction misses the band on every launch — the audit loop is
    # live on tuned runs, not only on hand-default ones
    absurd = _model_file("absurd.json",
                         [{"flows": 1, "us_per_step": 5e6},
                          {"flows": 1_000_000, "us_per_step": 5e6}],
                         dispatch_us=5e6, flush_us=5e6)
    ctrl = _cached("star6", cost_model=absurd)
    scrape = ctrl.engine.metrics.scrape()
    assert scrape["prof.autotune_source"] == "model"
    assert scrape["prof.model_stale"] > 0
