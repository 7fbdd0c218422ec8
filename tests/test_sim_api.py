"""The public simulation API (``repro.sim.api``): registry-based
build/step/collect dispatch, the typed ``ScenarioSpec`` request surface and
the versioned ``RunReport`` — plus the ``driver`` compatibility shim."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hermite
from repro.core.evaluate import make_evaluator
from repro.obs import metrics as obs_metrics
from repro.sim import api, driver, scenarios, telemetry
from repro.sim.scenarios import ScenarioError, ScenarioSpec
from repro.sim.telemetry import REPORT_SCHEMA_VERSION, RunReport


# --------------------------------------------------------------------------
# registry dispatch
# --------------------------------------------------------------------------
def _cfg(**kw):
    base = dict(scenario="plummer", n=16, t_end=0.02, dt=1.0 / 256,
                diag_every=4, validate_ic=False)
    base.update(kw)
    return api.SimConfig(**base)


@pytest.mark.parametrize("cfg,kind", [
    (_cfg(), "single"),
    (_cfg(ensemble=2), "ensemble"),
    (_cfg(stepper="block", dt=None, n_levels=2, impl="xla"), "ensemble"),
    (_cfg(stepper="block", dt=None, n_levels=2, impl="xla",
          strategy="mesh_sharded"), "block_strategy"),
    (_cfg(mix=(("plummer", 16), ("two_body", 2)), scenario="mixed"),
     "mixed"),
])
def test_resolve_kind_dispatch(cfg, kind):
    assert api.resolve_kind(cfg) == kind


def test_get_runner_unknown_kind():
    with pytest.raises(ValueError, match="unknown runner kind"):
        api.get_runner("warp_drive")


def test_resolve_kind_validates_first():
    with pytest.raises(ValueError):
        api.resolve_kind(_cfg(ensemble=0))


def test_driver_shim_is_the_api():
    """The legacy ``driver`` module re-exports the api surface unchanged."""
    assert driver.run is api.run
    assert driver.SimConfig is api.SimConfig
    assert driver.RUNNERS is api.RUNNERS


# --------------------------------------------------------------------------
# build/step/collect == run()
# --------------------------------------------------------------------------
#: physics-deterministic report fields (wall-clock fields excluded)
_DETERMINISTIC = ("scenario", "n_bodies", "ensemble", "steps", "e0", "e1",
                  "de_rel", "t_final", "force_evals_total")


def _deterministic(report):
    return {k: report[k] for k in _DETERMINISTIC if k in report}


@pytest.mark.parametrize("cfg", [
    _cfg(),
    _cfg(ensemble=2, stepper="adaptive", dt=None, t_end=0.01),
    _cfg(mix=(("plummer", 16), ("two_body", 2)), scenario="mixed"),
])
def test_build_step_collect_matches_run(cfg):
    """Driving the triple by hand reproduces ``run()``'s physics exactly."""
    monolithic = api.run(cfg)
    runner = api.get_runner(api.resolve_kind(cfg))
    h = runner.build(cfg)
    while not runner.step(h):
        pass
    composed = runner.collect(h)
    assert isinstance(composed, RunReport)
    assert _deterministic(composed) == _deterministic(monolithic)


def test_run_twice_is_deterministic():
    cfg = _cfg()
    a, b = api.run(cfg), api.run(cfg)
    assert _deterministic(a) == _deterministic(b)


# --------------------------------------------------------------------------
# the lockstep runner's step: one compiled program
# --------------------------------------------------------------------------
_STATE_FIELDS = ("pos", "vel", "acc", "jerk", "snap", "crackle", "pot",
                 "time")


def _lockstep_cfg(stepper, **kw):
    dt = 1.0 / 256 if stepper == "fixed" else None
    return _cfg(**{"n": 256, "impl": "xla", "stepper": stepper, "dt": dt,
                   "dt_max": 1.0 / 64, **kw})


@pytest.mark.parametrize("stepper", ["fixed", "adaptive"])
@pytest.mark.parametrize("order", [4, 6])
def test_single_runner_matches_eager_hermite(order, stepper):
    """The compiled step reproduces an eager loop of ``hermite.step`` with
    the same evaluator, dt choice and clamp, step by step."""
    cfg = _lockstep_cfg(stepper, order=order, t_end=6.5 / 256)
    runner = api.get_runner("single")
    h = runner.build(cfg)
    ev = make_evaluator(order=order, eps=cfg.eps, impl="xla",
                        dtype=cfg.dtype)
    state, t, h_prev = h.state, float(h.state.time), None
    while not h.finished:
        runner.step(h)
        if stepper == "fixed":
            dt = cfg.dt
        else:
            dt = float(hermite.aarseth_dt(state, eta=cfg.eta,
                                          dt_max=cfg.dt_max))
            if h_prev is not None:
                dt = min(max(dt, 0.5 * h_prev), 2.0 * h_prev)
            h_prev = dt
        dt = min(dt, cfg.t_end - t)
        state = hermite.step(state, jnp.asarray(dt, state.dtype), ev,
                             order=order)
        t = float(state.time)
        for k in _STATE_FIELDS:
            ref = np.asarray(getattr(state, k))
            got = np.asarray(getattr(h.state, k))
            if k == "crackle":
                # the crackle is a difference of accelerations over h**3:
                # compare it as it enters the next prediction, in units
                # of the acceleration (c h**3 / max|a|), where float64
                # rounding of the cancelling difference shows at ~1e-16
                unit = dt ** 3 / float(np.max(np.abs(state.acc)))
                ref, got = ref * unit, got * unit
            np.testing.assert_allclose(
                got, ref, rtol=0, atol=1e-12,
                err_msg=f"{k} at step {h.steps}")
    assert h.steps >= 2


@pytest.mark.parametrize("stepper", ["fixed", "adaptive"])
def test_single_runner_builds_its_step_once(stepper):
    """Ten steps, the last clamped to ``t_end``: one engine build and one
    compiled program each for the step and the shared dt."""
    # below every Aarseth step of this cluster, so adaptive steps dt_max too
    dt = 1.0 / 8192
    cfg = _lockstep_cfg(stepper, t_end=9.5 * dt, dt_max=dt,
                        dt=dt if stepper == "fixed" else None)
    runner = api.get_runner("single")
    with obs_metrics.use() as reg:
        h = runner.build(cfg)
        while not runner.step(h):
            pass
        counters = reg.snapshot()["counters"]
    assert h.steps == 10
    assert float(h.state.time) == cfg.t_end
    assert counters["engine.cache_miss.single"]["value"] == 1
    assert h.advance._cache_size() == 1
    assert h.shared_dt._cache_size() == (stepper == "adaptive")


@pytest.mark.parametrize("stepper", ["fixed", "adaptive"])
def test_single_runner_keeps_held_state(stepper):
    """The step donates nothing: a state held from before a step reads
    back unchanged after it."""
    runner = api.get_runner("single")
    h = runner.build(_lockstep_cfg(stepper))
    held = h.state
    before = {k: np.array(getattr(held, k)) for k in _STATE_FIELDS}
    runner.step(h)
    runner.step(h)
    assert h.state is not held
    for k in _STATE_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(held, k)),
                                      before[k])


_STRATEGY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
from repro.core import hermite
from repro.core.strategies import make_strategy_evaluator
from repro.obs import metrics as obs_metrics
from repro.sim import api

for strategy in ("replicated", "mesh_sharded", "ring", "two_level"):
    cfg = api.SimConfig(scenario="plummer", n=256, t_end=5 / 256,
                        dt=1 / 256, strategy=strategy, devices=2,
                        impl="xla", validate_ic=False)
    runner = api.get_runner("single")
    with obs_metrics.use() as reg:
        h = runner.build(cfg)
        ev = make_strategy_evaluator(strategy, devices=jax.devices(),
                                     impl="xla")
        state = h.state
        while not h.finished:
            runner.step(h)
            state = hermite.step(state, jnp.asarray(cfg.dt), ev)
            for k in ("pos", "vel", "acc", "jerk", "snap", "pot"):
                np.testing.assert_allclose(
                    np.asarray(getattr(h.state, k)),
                    np.asarray(getattr(state, k)), rtol=0, atol=1e-12,
                    err_msg=f"{strategy} {k}")
        builds = reg.snapshot()["counters"]["engine.cache_miss.single"]
    assert h.steps == 5, (strategy, h.steps)
    assert builds["value"] == 1, (strategy, builds)
    assert h.advance._cache_size() == 1, (strategy, h.advance._cache_size())
    print(f"{strategy}: OK")
"""


def test_single_runner_strategies_compile_once_2dev():
    """Under every distribution strategy on two devices the compiled step
    matches an eager loop of ``hermite.step`` with the strategy's
    evaluator, and keeps its layout: one program for all five steps."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    res = subprocess.run([sys.executable, "-c", _STRATEGY_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    for strategy in ("replicated", "mesh_sharded", "ring", "two_level"):
        assert f"{strategy}: OK" in res.stdout


# --------------------------------------------------------------------------
# ScenarioSpec: the typed name[:N] request
# --------------------------------------------------------------------------
def test_scenariospec_parse_format_roundtrip():
    for token in ("plummer:24", "two_body:2", "king:32"):
        spec = ScenarioSpec.parse(token)
        assert spec.format() == token
        assert ScenarioSpec.parse(spec.format()) == spec
    bare = ScenarioSpec.parse("plummer")
    assert bare.n is None and bare.format() == "plummer"


def test_scenariospec_parse_bad_int_names_field():
    with pytest.raises(ScenarioError, match="ScenarioSpec.n"):
        ScenarioSpec.parse("plummer:abc")


def test_scenariospec_unknown_name_names_field():
    with pytest.raises(ScenarioError, match="ScenarioSpec.name"):
        ScenarioSpec.parse("warp_core:16")


def test_scenariospec_negative_seed_names_field():
    with pytest.raises(ScenarioError, match="ScenarioSpec.seed"):
        ScenarioSpec(name="plummer", n=16, seed=-1).validate()


def test_scenariospec_unknown_param_names_field():
    with pytest.raises(ScenarioError, match="ScenarioSpec.params"):
        ScenarioSpec(name="plummer", n=16,
                     params={"warp_factor": 9}).validate()


def test_scenariospec_with_n_and_build():
    spec = ScenarioSpec.parse("plummer").with_n(24)
    assert spec.n == 24
    state = spec.build()
    assert state.pos.shape == (24, 3)
    with pytest.raises(ScenarioError, match="ScenarioSpec.n"):
        ScenarioSpec.parse("plummer").scenario()


def test_parse_mix_token_delegates_to_spec():
    assert scenarios.parse_mix_token("king:128") == ("king", 128)
    assert scenarios.parse_mix_token("king") == ("king", None)
    with pytest.raises(ScenarioError):
        scenarios.parse_mix_token("king:x")


# --------------------------------------------------------------------------
# RunReport: versioned, typed, round-trippable
# --------------------------------------------------------------------------
def test_finalize_returns_versioned_runreport():
    rec = telemetry.TelemetryRecorder({"scenario": "x"})
    rec.record_step(4, 0.1, 0.5)
    report = rec.finalize(n_bodies=8)
    assert isinstance(report, RunReport)
    assert isinstance(report, dict)          # legacy consumers keep working
    assert report.schema_version == REPORT_SCHEMA_VERSION
    assert report["schema_version"] == REPORT_SCHEMA_VERSION
    assert report.steps == 4 and report.wall_s == 0.5


def test_runreport_json_roundtrip():
    rec = telemetry.TelemetryRecorder({"scenario": "x"})
    rec.record_step(2, 0.05, 0.25)
    report = rec.finalize(n_bodies=8, n_active=[6])
    back = RunReport.from_json(report.to_json())
    assert back == json.loads(report.to_json())
    assert back.schema_version == report.schema_version
    assert back["n_active"] == [6]


def test_runreport_from_json_rejects_wrong_version():
    bad = json.dumps({"schema_version": REPORT_SCHEMA_VERSION + 1})
    with pytest.raises(ValueError, match="schema_version"):
        RunReport.from_json(bad)
    with pytest.raises(ValueError, match="JSON object"):
        RunReport.from_json("[1, 2]")


def test_runreport_as_dict_deprecated():
    report = RunReport({"wall_s": 1.0})
    with pytest.deprecated_call():
        plain = report.as_dict
    assert plain == dict(report) and type(plain) is dict
