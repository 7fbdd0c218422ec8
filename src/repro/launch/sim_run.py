"""Scenario/ensemble simulation launcher — the ``repro.sim`` front door.

    PYTHONPATH=src python -m repro.launch.sim_run \
        --scenario king --w0 6 --n 256 --t-end 0.1
    PYTHONPATH=src python -m repro.launch.sim_run \
        --scenario merger --ensemble 8 --devices 2 --strategy replicated
    PYTHONPATH=src python -m repro.launch.sim_run \
        --scenario king:256 merger:512 plummer:128 --pad auto --kernel pallas

``--scenario`` takes either one registry name (homogeneous runs; ``name:N``
is shorthand for ``--n N``) or several ``name:N`` tokens — a *mixed*
ensemble, packed into one rectangular batch with zero-mass padding up to
``--pad`` (``auto`` = largest member).  ``--kernel`` routes force evaluation through the reference
all-pairs op (``ref``) or the tiled Pallas kernel (``pallas``; interpreted
on CPU).  Mixed-run telemetry counts interactions with each run's
``n_active``, never the padded N.

``--stepper {fixed,adaptive,block}`` selects the timestep mode:
``fixed`` (``--dt``), ``adaptive`` (shared Aarseth lockstep, capped at
``--dt-max``), or ``block`` (hierarchical per-particle power-of-two levels,
``--dt-max`` x ``--levels``; ``--levels auto`` sizes the hierarchy from the
initial Aarseth dt distribution; see docs/ensembles.md).  Telemetry reports
the *measured* per-run force-evaluation counts in every mode — in block mode
only the active targets of each event are evaluated, so the count is far
below ``steps * N**2`` on scenarios with a wide timestep dynamic range.
``--compaction gather`` additionally gathers each event's active targets
into a dense block-aligned buffer so the kernel grid *shrinks* to the live
block instead of masking it — telemetry then shows ``grid_tiles`` falling
with the active set (``--block-i/--block-j`` tune the tile shape); a
mixed batch's capacity buckets are dispatched per member group.
``--strategy X --devices k --stepper block`` shards a single run's domain
so every device compacts its *local* active targets (the report then
carries ``grid_tiles_per_shard``).

Each invocation emits a one-line summary plus a JSON telemetry report
(wall time, steps/s, interactions/s, modeled energy/EDP, per-run energy
conservation) under ``experiments/sim/`` (override with ``--out``).

``--devices k`` takes the first k visible devices and fails when fewer are
visible.  On the CPU (``JAX_PLATFORMS=cpu``) the launcher forces k host
devices through XLA_FLAGS before importing jax; on a TPU host ``--devices 4``
is the four chips.  The persistent compilation cache lives where
``repro.launch.compile_cache`` says.
"""

from __future__ import annotations

import argparse
import os
import sys


def force_host_devices(n: int) -> None:
    """Give the CPU backend ``n`` devices, only when the user asked for the
    CPU (``JAX_PLATFORMS=cpu``) and set no XLA_FLAGS of their own; must run
    before jax is imported.  On an accelerator the real devices are used."""
    if (n > 1 and os.environ.get("JAX_PLATFORMS") == "cpu"
            and "XLA_FLAGS" not in os.environ):
        os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"


def _parse_params(pairs):
    """--param k=v (repeatable) -> dict with int/float coercion."""
    out = {}
    for pair in pairs or ():
        if "=" not in pair:
            raise SystemExit(f"--param expects k=v, got {pair!r}")
        k, v = pair.split("=", 1)
        try:
            out[k] = int(v)
        except ValueError:
            try:
                out[k] = float(v)
            except ValueError:
                out[k] = v
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario", nargs="+", default=["plummer"],
                    help="one registry name, or several name:N tokens for a "
                         "mixed padded ensemble (e.g. king:256 merger:512)")
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--pad", default=None,
                    help="mixed-ensemble padded size: 'auto' (largest member)"
                         " or an integer N_max")
    ap.add_argument("--kernel", default=None, choices=(None, "ref", "pallas"),
                    help="force kernel: 'ref' (all-pairs XLA op) or 'pallas' "
                         "(tiled kernel; interpret mode on the CPU)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ensemble", type=int, default=1,
                    help="batch B independent runs (seeds seed..seed+B-1)")
    ap.add_argument("--t-end", type=float, default=1.0)
    ap.add_argument("--dt", type=float, default=None,
                    help="fixed step (single-run default: shared adaptive)")
    ap.add_argument("--stepper", default=None,
                    choices=(None, "fixed", "adaptive", "block"),
                    help="timestep mode: fixed dt, shared-adaptive (Aarseth) "
                         "lockstep, or hierarchical per-particle block "
                         "timesteps (default: fixed when --dt is given, "
                         "else adaptive)")
    ap.add_argument("--dt-max", type=float, default=0.0625,
                    help="coarsest timestep (adaptive cap / block level 0)")
    ap.add_argument("--levels", default="8",
                    help="block-timestep hierarchy depth (finest step is "
                         "dt_max / 2**(levels-1)), or 'auto' to size each "
                         "member from its initial Aarseth dt distribution "
                         "(clamped to [1, 8])")
    ap.add_argument("--compaction", default="none",
                    choices=("none", "gather"),
                    help="block stepper only: gather each event's active "
                         "targets into a dense block-aligned buffer and "
                         "launch the kernels on the shrunk grid (bit-for-bit "
                         "the masked result, far fewer tiles enqueued); "
                         "with --strategy X --devices k every shard gathers "
                         "its own LOCAL active targets")
    ap.add_argument("--block-i", type=int, default=None,
                    help="kernel target-tile rows (block stepper; default: "
                         "kernel's own — small N wants a smaller tile so "
                         "compaction has tiles to drop)")
    ap.add_argument("--block-j", type=int, default=None,
                    help="kernel source-tile columns (block stepper)")
    ap.add_argument("--sources", default="full",
                    choices=("full", "neighbor"),
                    help="block stepper force sources: 'full' (all-pairs, "
                         "bit-identical to the historical path) or "
                         "'neighbor' (Ahmad-Cohen split: near force from "
                         "gathered per-block neighbor windows every event, "
                         "far field Taylor-predicted between refreshes)")
    ap.add_argument("--neighbor-radius", type=float, default=0.25,
                    help="neighbor window radius in simulation length units "
                         "(--sources neighbor; larger = more exact near "
                         "force, wider gathers)")
    ap.add_argument("--refresh-levels", type=int, default=2,
                    help="far-field refresh cadence: rebuild windows every "
                         "n_sub >> K ticks of the block hierarchy "
                         "(--sources neighbor; 0 = once per macro step)")
    ap.add_argument("--eta", type=float, default=0.02)
    ap.add_argument("--order", type=int, default=6, choices=(4, 6))
    ap.add_argument("--strategy", default="single",
                    choices=("single", "replicated", "two_level",
                             "mesh_sharded", "ring"))
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--impl", default=None,
                    choices=(None, "pallas", "pallas_interpret", "xla",
                             "fp64"))
    ap.add_argument("--dtype", default="fp32",
                    choices=("fp64", "fp32", "mixed"),
                    help="precision axis: 'fp64' (pure-jnp golden oracle), "
                         "'fp32' (paper device precision), or 'mixed' "
                         "(bfloat16 per-pair arithmetic with compensated "
                         "fp32 accumulation — the Tensix unpack-fp32/"
                         "compute-reduced/pack-fp32 pattern)")
    ap.add_argument("--diag-every", type=int, default=16)
    ap.add_argument("--w0", type=float, default=None,
                    help="King concentration (sugar for --param w0=...)")
    ap.add_argument("--param", action="append", metavar="K=V",
                    help="scenario parameter, repeatable")
    ap.add_argument("--out", default=None, help="JSON report path")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write a Chrome-trace/Perfetto JSON of the run "
                         "(one sim.step span per chunk around sim.engine, "
                         "sim.readback and sim.diagnostics; load at "
                         "https://ui.perfetto.dev)")
    ap.add_argument("--metrics-interval", type=int, default=0,
                    help="attach a metrics-registry snapshot to every K-th "
                         "diagnostics record (0 = final snapshot only; the "
                         "report always carries the final one under "
                         "'metrics')")
    ap.add_argument("--no-validate", dest="validate", action="store_false",
                    help="skip construction-time scenario diagnostics")
    ap.add_argument("--list-scenarios", action="store_true")
    args = ap.parse_args(argv)

    force_host_devices(args.devices)

    import jax

    jax.config.update("jax_enable_x64", True)

    from repro.launch import compile_cache
    from repro.sim import api, scenarios, telemetry

    compile_cache.enable()

    if args.list_scenarios:
        for name in scenarios.available():
            spec = scenarios.get_spec(name)
            print(f"{name:16s} {spec.description}  defaults={dict(spec.defaults)}")
        return 0

    params = _parse_params(args.param)
    if args.w0 is not None:
        params["w0"] = args.w0

    if args.levels == "auto":
        n_levels = None
    else:
        try:
            n_levels = int(args.levels)
        except ValueError:
            raise SystemExit(
                f"--levels expects an integer or 'auto', got {args.levels!r}"
            ) from None

    # one token => homogeneous path (name:N is shorthand for --n N, so the
    # report keeps the real scenario label); several tokens => mixed padded
    # ensemble, bare names inheriting --n.  ScenarioSpec.parse validates at
    # the flag boundary (registry name, minimum N) with errors naming the
    # bad field — the same typed requests the serving layer admits.
    try:
        specs = [scenarios.ScenarioSpec.parse(t, seed=args.seed)
                 for t in args.scenario]
    except scenarios.ScenarioError as e:
        raise SystemExit(f"--scenario: {e}") from None
    mixed = len(specs) > 1
    if mixed:
        mix = tuple((s.name, s.with_n(args.n).n) for s in specs)
        scenario_name, n_arg = "mixed", max(n for _, n in mix)
    else:
        mix = None
        scenario_name = specs[0].name
        n_arg = specs[0].with_n(args.n).n
    pad = None
    if args.pad is not None:
        if not mixed:
            raise SystemExit("--pad only applies to mixed name:N ensembles")
        if args.pad != "auto":
            try:
                pad = int(args.pad)
            except ValueError:
                raise SystemExit(
                    f"--pad expects 'auto' or an integer, got {args.pad!r}") \
                    from None

    cfg = api.SimConfig(
        scenario=scenario_name, n=n_arg, seed=args.seed,
        ensemble=args.ensemble, t_end=args.t_end, dt=args.dt,
        stepper=args.stepper, dt_max=args.dt_max, n_levels=n_levels,
        compaction=args.compaction, block_i=args.block_i,
        block_j=args.block_j, sources=args.sources,
        neighbor_radius=args.neighbor_radius,
        refresh_levels=args.refresh_levels, eta=args.eta,
        order=args.order, strategy=args.strategy, devices=args.devices,
        impl=args.impl, kernel=args.kernel, dtype=args.dtype,
        mix=mix, pad=pad,
        diag_every=args.diag_every, scenario_params=params,
        validate_ic=args.validate,
        trace=args.trace, metrics_interval=args.metrics_interval,
        out=args.out or telemetry.default_report_path(
            {"scenario": scenario_name, "n": n_arg,
             "ensemble": args.ensemble if not mixed
             else len(mix) * args.ensemble,
             "strategy": args.strategy}),
    )
    report = api.run(cfg)

    desc = " ".join(f"{nm}:{n}" for nm, n in mix) if mixed \
        else f"{scenario_name} n={n_arg}"
    print(f"[sim] scenario={desc} "
          f"ensemble={report['ensemble']} strategy={args.strategy} "
          f"devices={args.devices} order={args.order} "
          f"stepper={report.get('stepper', 'fixed')} "
          f"dtype={args.dtype}"
          + (f" sources={args.sources}" if args.sources != "full" else "")
          + (f" kernel={args.kernel}" if args.kernel else ""))
    if mixed:
        print(f"[sim] padded N_max={report['n_bodies']} "
              f"n_active={report['n_active']}")
    print(f"[sim] t={report['t_final']:.4f} steps={report['steps']} "
          f"wall={report['wall_s']:.2f}s "
          f"steps/s={report['steps_per_s']:.1f} "
          f"pairs/s={report['interactions_per_s']:.3e}"
          + (f" force_evals={report['force_evals_total']:.3e}"
             if "force_evals_total" in report else "")
          + (f" grid_tiles={report['grid_tiles_total']:.3e}"
             if "grid_tiles_total" in report else ""))
    if "grid_tiles_per_shard" in report:
        shards = " ".join(f"{t:.0f}" for t in report["grid_tiles_per_shard"])
        print(f"[sim] grid_tiles_per_shard=[{shards}]")
    print(f"[sim] |dE/E|={report['de_rel']:.3e} "
          f"E_model={report['modeled']['energy_J']:.1f}J "
          f"EDP={report['modeled']['edp_Js']:.1f}Js")
    metrics = report.get("metrics") or {}
    counters = metrics.get("counters") or {}
    if counters:
        bits = " ".join(f"{k}={v['value']:g}"
                        for k, v in sorted(counters.items()))
        print(f"[sim] metrics: {bits}")
    if "trace_path" in report:
        print(f"[sim] trace -> {report['trace_path']}")
    print(f"[sim] report -> {report.get('report_path', '(not written)')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
