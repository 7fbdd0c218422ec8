"""Driver/CLI observability plumbing + the tentpole acceptance criteria:

* a traced ``block``+``gather`` run exports Perfetto-loadable Chrome-trace
  JSON with one ``sim.step`` span per chunk holding ``sim.engine``,
  ``sim.readback`` and ``sim.diagnostics``, for every runner kind;
* the telemetry ``metrics`` payload reports launched tiles within the
  analytic ``hermite.block_level_occupancy`` bound;
* ``--trace`` / ``--metrics-interval`` thread from the CLI through
  ``SimConfig`` into the report.
"""

import json

import pytest

from repro.obs import metrics as obs_metrics
from repro.sim import driver

#: small-but-real block+gather config: block_i=8 gives the 32-particle grid
#: four i-tiles (several capacity buckets), so compaction has tiles to drop
#: and the occupancy bound is a non-trivial ceiling
BLOCK_KW = dict(scenario="plummer", n=32, ensemble=2, t_end=0.0625,
                stepper="block", dt_max=0.0625, n_levels=3,
                compaction="gather", block_i=8, block_j=32,
                impl="xla", diag_every=4, validate_ic=False)
#: the children of every ``sim.step`` span, in the order a step runs them
STEP_CHILDREN = ("sim.engine", "sim.readback", "sim.diagnostics")


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    out = tmp_path_factory.mktemp("obs")
    trace_path = str(out / "trace.json")
    cfg = driver.SimConfig(trace=trace_path, metrics_interval=1, **BLOCK_KW)
    report = driver.run(cfg)
    return report, json.load(open(trace_path))


def test_trace_exported_and_loadable(traced_run):
    report, doc = traced_run
    assert report["trace_path"].endswith("trace.json")
    assert doc["otherData"]["producer"] == "repro.obs.trace"
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]


def _by_name(doc):
    by = {}
    for ev in doc["traceEvents"]:
        by.setdefault(ev["name"], []).append(ev)
    return by


def _inside(child, parent, tol=1.0):
    return (parent["ts"] <= child["ts"] + tol and
            child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + tol)


def _check_step_spans(doc, children=STEP_CHILDREN):
    """Every step child nests in a ``sim.step``, and each step holds
    exactly ``children``, once each and in that order; every span is
    measured."""
    by = _by_name(doc)
    steps = by.get("sim.step", [])
    assert steps
    for name in children:
        assert by.get(name), f"no {name} span"
        for child in by[name]:
            assert any(_inside(child, st) for st in steps), \
                f"orphan {name} span at ts={child['ts']}"
    for st in steps:
        kids = sorted((e for n in children for e in by[n]
                       if _inside(e, st)), key=lambda e: e["ts"])
        assert [e["name"] for e in kids] == list(children)
    assert not {"macro-step", "event", "kernel-launch"} & set(by)
    assert not any((e.get("args") or {}).get("synthetic")
                   for e in doc["traceEvents"])


def test_trace_has_nested_span_taxonomy(traced_run):
    _, doc = traced_run
    _check_step_spans(doc)


def test_macro_step_args_carry_measured_aggregates(traced_run):
    """The measured chunk aggregates ride on the ``sim.step`` spans."""
    report, doc = traced_run
    steps = _by_name(doc)["sim.step"]
    assert [e["args"]["chunk"] for e in steps] == \
        list(range(1, len(steps) + 1))
    assert sum(e["args"]["events"] for e in steps) == \
        sum(r["steps"] for r in report["runs"])
    assert sum(e["args"]["tiles"] for e in steps) == pytest.approx(
        report["grid_tiles_total"])


#: one small run of each runner kind; the fixed-dt ensemble reads nothing
#: back between its engine call and its energy snapshot
RUNNER_KINDS = {
    "single": (dict(scenario="plummer", n=32, t_end=0.0625, dt=1 / 64,
                    impl="xla", diag_every=2, validate_ic=False),
               STEP_CHILDREN),
    "single_adaptive": (dict(scenario="plummer", n=32, t_end=0.03125,
                             impl="xla", diag_every=2, validate_ic=False),
                        STEP_CHILDREN),
    "block_strategy": (dict(BLOCK_KW, ensemble=1, strategy="replicated",
                            devices=1), STEP_CHILDREN),
    "ensemble_fixed": (dict(scenario="plummer", n=32, ensemble=2,
                            t_end=0.0625, dt=1 / 64, impl="xla",
                            diag_every=2, validate_ic=False),
                       ("sim.engine", "sim.diagnostics")),
    "ensemble_adaptive": (dict(scenario="plummer", n=32, ensemble=2,
                               t_end=0.03125, impl="xla", diag_every=2,
                               validate_ic=False), STEP_CHILDREN),
    "mixed_block": (dict(mix=(("plummer", 16), ("plummer", 32)),
                         t_end=0.0625, stepper="block", dt_max=0.0625,
                         n_levels=2, impl="xla", diag_every=4,
                         validate_ic=False), STEP_CHILDREN),
}


@pytest.mark.parametrize("kind", sorted(RUNNER_KINDS))
def test_step_spans_nest_for_every_runner_kind(kind, tmp_path):
    kw, children = RUNNER_KINDS[kind]
    path = str(tmp_path / "trace.json")
    driver.run(driver.SimConfig(trace=path, **kw))
    _check_step_spans(json.load(open(path)), children)


def test_metrics_payload_in_report(traced_run):
    report, _ = traced_run
    m = report["metrics"]
    obs_metrics.validate_snapshot(m)
    c = m["counters"]
    assert c["sim.events"]["value"] == sum(r["steps"] for r in report["runs"])
    assert c["sim.tiles_launched"]["value"] == pytest.approx(
        report["grid_tiles_total"])
    # the lru-cached engine constructor ran (at least init + block engines)
    assert c["engine.cache_miss"]["value"] >= 1
    assert c["engine.cache_miss.block"]["value"] >= 1
    assert c["engine.bucket_branches"]["value"] >= 1
    assert m["histograms"]["sim.active_fraction"]["count"] > 0
    assert 0.0 < m["histograms"]["sim.active_fraction"]["mean"] <= 1.0


def test_tiles_within_occupancy_bound(traced_run):
    """Acceptance: launched tiles never exceed the analytic a-priori bound
    from ``hermite.block_level_occupancy`` (its entry 0 — every real
    particle — is the largest active set any tick can see)."""
    report, _ = traced_run
    m = report["metrics"]
    launched = m["counters"]["sim.tiles_launched"]["value"]
    bound = m["gauges"]["sim.tiles_occupancy_bound"]["value"]
    dense = m["counters"]["sim.tiles_dense_baseline"]["value"]
    assert 0 < launched <= bound <= dense


def test_bucket_hits_distribution(traced_run):
    report, _ = traced_run
    hits = report["metrics"]["gauges"]["sim.bucket_hits"]["value"]
    assert len(hits) >= 2  # block_i=8 at N=32: a real bucket schedule
    # every productive member-event dispatched exactly one bucket
    assert sum(hits) == sum(r["steps"] for r in report["runs"])


def test_metrics_interval_attaches_series(traced_run):
    report, _ = traced_run
    tagged = [s for s in report["snapshots"] if "metrics" in s]
    assert tagged, "metrics_interval=1 must tag every chunk snapshot"
    for snap in tagged:
        obs_metrics.validate_snapshot(snap["metrics"])
    # the series is monotone in the events counter (counters never decrease)
    vals = [s["metrics"]["counters"]["sim.events"]["value"] for s in tagged]
    assert vals == sorted(vals)


def test_untraced_run_has_metrics_but_no_trace():
    report = driver.run(driver.SimConfig(**BLOCK_KW))
    assert "trace_path" not in report
    obs_metrics.validate_snapshot(report["metrics"])


def test_metrics_interval_validation():
    with pytest.raises(ValueError, match="metrics_interval"):
        driver.run(driver.SimConfig(metrics_interval=-1, **BLOCK_KW))


def test_cli_threads_trace_and_metrics_interval(tmp_path, capsys):
    from repro.launch import sim_run
    trace_path = str(tmp_path / "cli_trace.json")
    out_path = str(tmp_path / "cli_report.json")
    rc = sim_run.main([
        "--scenario", "plummer", "--n", "32", "--t-end", "0.0625",
        "--stepper", "block", "--compaction", "gather",
        "--block-i", "8", "--block-j", "32", "--impl", "xla",
        "--diag-every", "4", "--no-validate",
        "--trace", trace_path, "--metrics-interval", "1",
        "--out", out_path])
    assert rc == 0
    stdout = capsys.readouterr().out
    assert trace_path in stdout and "sim.events" in stdout
    report = json.load(open(out_path))
    assert report["trace_path"] == trace_path
    obs_metrics.validate_snapshot(report["metrics"])
    doc = json.load(open(trace_path))
    names = {e["name"] for e in doc["traceEvents"]}
    assert set(STEP_CHILDREN) | {"sim.step"} <= names


def test_mixed_run_reports_pad_waste():
    report = driver.run(driver.SimConfig(
        mix=(("plummer", 16), ("plummer", 32)), t_end=0.0625,
        stepper="block", dt_max=0.0625, n_levels=2, impl="xla",
        diag_every=4, validate_ic=False))
    waste = report["metrics"]["gauges"]["sim.pad_waste"]["value"]
    assert waste == pytest.approx(1.0 - (16 + 32) / (2 * 32))
