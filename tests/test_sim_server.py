"""The continuous-batching ``SimServer`` (``repro.serve.sim_engine``):
admission-policy tile accounting, batch-mate bit-identity across
retire/backfill, the zero-recompile steady state, retirement reports and
dtype-strict suspend/resume."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.kernels import ops
from repro.serve import (ServerConfig, SimRequest, SimServer,
                         fifo_event_tiles, packed_event_tiles)
from repro.sim.scenarios import ScenarioError, ScenarioSpec
from repro.sim.telemetry import RunReport


def _cfg(**kw):
    base = dict(slots_per_pod=2, n_max=64, chunk_events=4, impl="xla",
                dt_max=0.0625, n_levels=4, block_i=32, block_j=32,
                devices=1)
    base.update(kw)
    return ServerConfig(**base)


def _req(token, stepper="adaptive", t_end=0.02, seed=0):
    return SimRequest(spec=ScenarioSpec.parse(token, seed=seed),
                      stepper=stepper, t_end=t_end)


# --------------------------------------------------------------------------
# admission policy: packing by bucket never launches more tiles than FIFO
# --------------------------------------------------------------------------
def test_packed_tiles_never_exceed_fifo_exhaustive():
    """Every admissible n, every plan shape we serve (pure host math)."""
    for n_max, bi, bj in ((64, 32, 32), (128, 32, 32), (256, 64, 64)):
        plan = ops.CapacityPlan(n_max, n_max, bi, bj)
        for n in range(1, n_max + 1):
            packed = packed_event_tiles(plan, n)
            fifo = fifo_event_tiles(plan, n)
            assert packed <= fifo, (n_max, bi, bj, n, packed, fifo)


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # optional dependency; the exhaustive test still runs
    given = None

if given is not None:
    @settings(deadline=None, max_examples=200)
    @given(case=st.sampled_from([(1024, 32, 32), (1024, 64, 64),
                                 (512, 32, 64)]).flatmap(
        lambda shape: st.tuples(st.just(shape),
                                st.integers(min_value=1,
                                            max_value=shape[0]))))
    def test_packed_tiles_never_exceed_fifo_property(case):
        (n_max, bi, bj), n = case
        plan = ops.CapacityPlan(n_max, n_max, bi, bj)
        assert packed_event_tiles(plan, n) <= fifo_event_tiles(plan, n)


# --------------------------------------------------------------------------
# batch-mate bit-identity across retire + backfill
# --------------------------------------------------------------------------
def _member_rows(pod, slot):
    return jax.tree_util.tree_map(lambda a: np.asarray(a)[slot],
                                  pod.batched)


@pytest.mark.parametrize("stepper", ["adaptive", "block"])
def test_batch_mate_bit_identical_across_backfill(stepper):
    """A neighbour retiring and a new member backfilling its slot must not
    perturb the surviving member's trajectory by a single bit."""
    short, long_ = 0.01, 0.08
    treatment = SimServer(_cfg())
    treatment.submit(_req("plummer:24", stepper, short), now=0.0)
    treatment.submit(_req("two_body:2", stepper, long_), now=0.0)
    treatment.submit(_req("king:20", stepper, short, seed=5), now=0.0)

    control = SimServer(_cfg())
    control.submit(_req("plummer:24", stepper, short), now=0.0)
    control.submit(_req("two_body:2", stepper, long_), now=0.0)

    # pod is full (2 slots), so the third request queues until the first
    # retires; its backfill must leave the second member's rows untouched.
    ticks = 0
    while treatment.busy() or control.busy():
        treatment.step(now=float(ticks))
        control.step(now=float(ticks))
        ticks += 1
        assert ticks < 1000
        (t_pod,), (c_pod,) = treatment.pods.values(), control.pods.values()
        if t_pod.slots[1] is not None and c_pod.slots[1] is not None:
            t_rows = _member_rows(t_pod, 1)
            c_rows = _member_rows(c_pod, 1)
            for t_leaf, c_leaf in zip(jax.tree_util.tree_leaves(t_rows),
                                      jax.tree_util.tree_leaves(c_rows)):
                np.testing.assert_array_equal(t_leaf, c_leaf)

    by_rid = {r["request_id"]: r for r in treatment.reports}
    assert len(by_rid) == 3
    survivor_t = by_rid[1]
    survivor_c = {r["request_id"]: r for r in control.reports}[1]
    for key in ("steps", "t_final", "e1"):
        assert survivor_t[key] == survivor_c[key]


# --------------------------------------------------------------------------
# zero recompiles in steady state
# --------------------------------------------------------------------------
def test_zero_cache_miss_after_warmup():
    server = SimServer(_cfg())
    server.warmup([_req("plummer:24", "adaptive"),
                   _req("plummer:40", "block")])
    baseline = server.cache_misses()
    assert baseline > 0  # warmup itself lowered the engines
    for seed in range(4):
        server.submit(_req("plummer:24", "adaptive", 0.02, seed=seed))
        server.submit(_req("king:40", "block", 0.02, seed=seed))
    reports = server.run_until_drained()
    assert len(reports) == 8
    assert server.cache_misses() == baseline


def test_zero_xla_compiles_after_warmup():
    """Warm-up also covers the eager ops of admission, backfill into a
    running pod's carry, and retirement: serving compiles nothing."""
    compiles = []
    counting = False

    def listen(event, secs, **_):
        if counting and event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    server = SimServer(_cfg())
    server.warmup([_req("plummer:24", "adaptive"),
                   _req("plummer:40", "block")])
    counting = True
    try:
        for seed in range(3):
            server.submit(_req("plummer:24", "adaptive", 0.02, seed=seed))
            server.submit(_req("plummer:40", "block", 0.02, seed=seed))
        assert len(server.run_until_drained()) == 6
    finally:
        counting = False
    assert compiles == []


# --------------------------------------------------------------------------
# retirement reports
# --------------------------------------------------------------------------
def test_retire_report_contents():
    server = SimServer(_cfg())
    rid = server.submit(_req("plummer:24", "block", t_end=0.02, seed=7),
                        now=0.0)
    (report,) = server.run_until_drained()
    assert isinstance(report, RunReport)
    assert report["scenario"] == "plummer:24"
    assert report["n_active"] == [24]
    assert report["n_bodies"] == server.pod_for(
        _req("plummer:24", "block")).cap
    assert report["steps"] >= 1
    assert report["request_id"] == rid
    assert report["t_final"] >= 0.02
    assert report["turnaround_s"] >= report["admission_latency_s"] >= 0.0
    assert np.isfinite(report["de_rel"])
    assert report["grid_tiles"][0] > 0  # block pods count launched tiles
    snap = server.metrics_snapshot()
    assert {"serve.requests_admitted",
            "serve.requests_retired"} <= set(snap["counters"])
    assert "serve.queue_depth" in snap["gauges"]
    assert "serve.turnaround_s" in snap["histograms"]


def test_bucket_packing_separates_pods_and_fifo_per_bucket():
    server = SimServer(_cfg())
    server.submit(_req("plummer:24", "adaptive"))   # cap 32 pod
    server.submit(_req("plummer:40", "adaptive"))   # cap 64 pod
    server.submit(_req("plummer:20", "block"))      # block cap 32 pod
    server.step(now=0.0)
    assert set(server.pods) == {("adaptive", 32), ("adaptive", 64),
                                ("block", 32)}
    assert not server.queue  # distinct buckets never block one another


# --------------------------------------------------------------------------
# suspend / resume
# --------------------------------------------------------------------------
@pytest.mark.parametrize("stepper", ["adaptive", "block"])
def test_suspend_resume_bit_identical(tmp_path, stepper):
    def build():
        s = SimServer(_cfg())
        s.submit(_req("plummer:24", stepper, 0.04), now=0.0)
        s.submit(_req("two_body:2", stepper, 0.04), now=0.0)
        s.submit(_req("king:20", stepper, 0.04, seed=3), now=0.0)
        return s

    straight = build()
    straight.run_until_drained()

    paused = build()
    paused.step(now=0.0)
    paused.step(now=1.0)
    paused.suspend(str(tmp_path / "ckpt"))
    resumed = SimServer.resume(str(tmp_path / "ckpt"))
    assert resumed.cfg == paused.cfg
    resumed.reports = list(paused.reports)
    resumed.run_until_drained()

    def key(reports):
        return sorted((r["request_id"], r["steps"], r["e1"], r["t_final"])
                      for r in reports)

    assert key(resumed.reports) == key(straight.reports)


# --------------------------------------------------------------------------
# admission-boundary validation
# --------------------------------------------------------------------------
def test_submit_rejects_unsized_spec():
    with pytest.raises(ScenarioError, match="SimRequest.spec.n"):
        SimServer(_cfg()).submit(SimRequest(spec=ScenarioSpec.parse(
            "plummer")))


def test_submit_rejects_oversized_request():
    with pytest.raises(ValueError, match="n_max=64"):
        SimServer(_cfg()).submit(_req("plummer:100"))


def test_submit_rejects_fixed_stepper():
    with pytest.raises(ValueError, match="not servable"):
        SimServer(_cfg()).submit(_req("plummer:24", stepper="fixed"))


def test_submit_rejects_nonpositive_deadline():
    with pytest.raises(ValueError, match="SimRequest.t_end"):
        SimServer(_cfg()).submit(_req("plummer:24", t_end=0.0))


def test_config_rejects_unaligned_n_max():
    with pytest.raises(ValueError, match="block_i-aligned"):
        SimServer(dataclasses.replace(_cfg(), n_max=65))
