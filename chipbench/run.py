"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's simulation from the seed through the simulator's
runner API and steps it through one whole step (which compiles every
program the window uses); the window then steps it for ``--seconds`` and
on to the next whole-step edge.  With ``--trace 0`` the result carries
the cell's end-to-end metrics, with ``--trace 1`` the window runs under
the profiler and the result carries its per-layer metrics.  Either way the events that
end the chunks of the window's last whole step are checked against the
float64 reference (``check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``checks``, each compared number beside its limit.  The same
numbers end standard error.  Without a TPU, or with fewer chips than the
cell asks for, it exits 3 and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

#: the persistent compilation cache: a fixed directory inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

NO_CHIP = 3
#: set-up gives up waiting for a whole-step edge after this many steps
WARM_LIMIT = 8


class NoChip(Exception):
    pass


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def check_devices(jax, chips: int, platform: str = "tpu"):
    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoChip(f"JAX found no {platform.upper()} (platform "
                     f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def main(argv=None, *, platform: str = "tpu", overrides=None,
         t_process: float = None, peaks=None,
         cache_dir: str = CACHE_DIR) -> dict:
    """One run; returns the result object (``NoChip`` without the chips).

    ``platform``, ``overrides``, ``peaks`` and ``cache_dir`` exist for the
    tests, which drive a small run on the CPU; the benchmark itself always
    asks for a TPU, reads its peaks from ``peaks.json`` and keeps its
    compilation cache in the checkout.
    """
    args = parse(argv)
    t_process = T_PROCESS if t_process is None else t_process
    from chipbench import spec as spec_mod

    cell = spec_mod.load_cell(args.workload)
    os.makedirs(cache_dir, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    import jax

    jax.config.update("jax_enable_x64", True)
    devices = check_devices(jax, cell.chips, platform)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: an environment that sets a size limit turns on JAX's
    # access-time bookkeeping, whose missing files made every write fail
    jax.config.update("jax_compilation_cache_max_size", -1)

    import jax.numpy as jnp
    import numpy as np

    from chipbench import check, harness, reference, tracing
    from chipbench.compiles import Compiles
    from repro.launch import compile_cache
    from repro.obs import metrics as obs_metrics
    from repro.sim import api

    compile_cache.enable()
    compiles = Compiles(jax)
    dev = devices[0]
    kind = dev.device_kind
    peaks = peaks or spec_mod.peaks(kind)

    overrides = dict(overrides or {})
    chunks = overrides.pop("chunk_events", cell.traffic["chunk_events"])
    cfg = harness.sim_config(api, cell, args.seed,
                             {**overrides, "diag_every": chunks[0]})
    # a whole step is n_sub ticks: a macro step, or one lockstep step
    if cfg.stepper == "block":
        dt_step, n_sub = cfg.dt_max, 2 ** (cfg.n_levels - 1)
    else:
        dt_step, n_sub = cfg.dt, 1
    registry = contextlib.ExitStack()       # the run's metrics registry
    registry.enter_context(obs_metrics.use())
    runner = api.get_runner(api.resolve_kind(cfg))
    handle = harness.Handle(runner, runner.build(cfg), chunks)
    # one whole step compiles every program of a one-chip cell, one for
    # each chunk length; a sharded engine's second call meets its own
    # output's layout and compiles once more, so its traffic asks for two.
    # Set-up ends on a whole-step edge.
    warm, edge = 0, False
    while warm < cell.traffic.get("warm_steps", 1) or not edge:
        edge = handle.step(jnp)
        warm += 1
        if warm >= WARM_LIMIT:
            break
    setup = compiles.since((0, 0.0, 0, 0))
    setup_s = time.perf_counter() - t_process
    # a cold set-up compiled what the next run in this checkout loads
    harness.say("setup", setup_s=setup_s,
                cold=bool(setup["xla_compiles"] or setup["cache_misses"]),
                warm_steps=warm,
                xla_compiles=setup["xla_compiles"],
                compile_s=setup["compile_s"],
                cache_hits=setup["cache_hits"],
                cache_misses=setup["cache_misses"], n=cfg.n,
                stepper=cfg.stepper, strategy=cfg.strategy,
                devices=len(devices), device_kind=kind)

    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host runtime events, no Python
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    win = harness.run_window(handle, jax, jnp, args.seconds, compiles,
                             dt_step, trace_ctx=bool(args.trace))
    if args.trace:
        jax.profiler.stop_trace()
    mem_peak = harness.memory_peak(devices)
    harness.say(
        "window", wall_s=win.wall_s, sim_t=win.sim_t, steps=win.steps,
        edge_steps=win.edge_pairs, step_wall_s_min=min(win.step_walls),
        step_wall_s_max=max(win.step_walls), events=win.events,
        pairs_per_pass=win.pairs, tiles=win.tiles,
        xla_compiles=win.compiles["xla_compiles"],
        cache_lookups=win.compiles["cache_hits"]
        + win.compiles["cache_misses"], memory_peak_bytes=mem_peak)

    # the program's state leaves the device before the reference runs
    states = [harness.to_host(*r) for r in (win.last or [])]
    start = harness.to_host(*win.start)
    del win.last, win.start, handle
    registry.close()
    gc.collect()

    numbers, limits = {}, cell.limits["limits"]
    if states:
        t_ref = time.perf_counter()
        numbers = check.compare(
            reference.Device64(cfg.eps), states, n_sub, dt_step / n_sub,
            args.seed, cell.chips, cell.limits["sample_targets"])
        if cfg.stepper == "block":
            lv = np.bincount(states[0]["levels"], minlength=cfg.n_levels)
            dt_i = harness.aarseth_dt(states[0], cfg.eta)
            harness.say("levels", per_level=",".join(map(str, lv)),
                        finer_than_under_1_16=int(np.sum(dt_i >= 1 / 32)),
                        chunk_ticks=",".join(
                            str(int(s["t_last"].max())) for s in states))
        harness.say("reference", seconds=time.perf_counter() - t_ref,
                    sample=cell.limits["sample_targets"],
                    energy_drift=check.energy_drift(start, states[-1]))
    numbers["window_compiles"] = float(
        win.compiles["xla_compiles"] + win.compiles["cache_hits"]
        + win.compiles["cache_misses"])
    numbers["edge_steps"] = float(win.edge_pairs)
    checks = {}
    for name, limit in limits.items():
        value = numbers.get(name)
        ok = value is not None and (value >= limit if name == "edge_steps"
                                    else value <= limit)
        checks[name] = {"value": value, "limit": limit, "ok": bool(ok)}
    correct = all(c["ok"] for c in checks.values())

    result = {"correct": correct, "attempted": len(checks),
              "failed": sum(not c["ok"] for c in checks.values())}
    device = {"platform": dev.platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": mem_peak}
    if args.trace:
        from chipbench import metrics_context

        path = tracing.find_xplane(trace_dir)
        reduced = tracing.reduce_xplane(path)
        ctx = metrics_context.Context(
            trace=reduced, window=win, cell=cell, cfg=cfg, peaks=peaks,
            chips=len(devices))
        metrics = {}
        for m in cell.per_layer:
            value = spec_mod.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = ctx.busy_s()
        device["window_s"] = reduced.window_ns / 1e9
        result["breakdown"] = {"device_ops": tracing.top_ops(reduced),
                               "idle_gaps": tracing.top_gaps(reduced)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        rate = win.wall_s / win.sim_t if win.sim_t > 0 else float("inf")
        metrics = {"wall_s_per_nbody_t": {"value": rate, "unit": "s/t_nbody"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: [c["value"], c["limit"]] for k, c in checks.items()}
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr, flush=True)
    return result


def finite(x):
    """``x`` with every non-finite float replaced by None (valid JSON)."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


if __name__ == "__main__":
    try:
        out = main()
    except NoChip as e:
        print(f"chipbench: {e}; no result", file=sys.stderr, flush=True)
        sys.exit(NO_CHIP)
    print(json.dumps(finite(out)), flush=True)
