"""N-body simulation launcher — the paper's workload end-to-end.

Runs a Plummer-sphere direct N-body simulation with the 6th-order Hermite
integrator, the FP32 force evaluation offloaded to the (Pallas/XLA) kernel,
under any of the paper's three scaling strategies (+ the beyond-paper ring):

  PYTHONPATH=src python -m repro.launch.nbody_run --n 4096 --t-end 1.0 \
      --strategy replicated --devices 4

``--devices k`` takes the first k visible devices and fails when fewer are
visible; on the CPU (``JAX_PLATFORMS=cpu``) the launcher forces k host
devices before importing jax.
"""

from __future__ import annotations

import argparse
import sys
import time

from repro.launch.sim_run import force_host_devices


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--t-end", type=float, default=1.0)
    ap.add_argument("--dt", type=float, default=None,
                    help="fixed step (default: shared adaptive Aarseth)")
    ap.add_argument("--eta", type=float, default=0.02)
    ap.add_argument("--order", type=int, default=6, choices=(4, 6))
    ap.add_argument("--strategy", default="single",
                    choices=("single", "replicated", "two_level",
                             "mesh_sharded", "ring"))
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--impl", default=None,
                    choices=(None, "pallas", "pallas_interpret", "xla"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--x64", action="store_true", default=True)
    args = ap.parse_args()

    force_host_devices(args.devices)

    import jax

    if args.x64:
        jax.config.update("jax_enable_x64", True)
    if args.devices > len(jax.devices()):
        raise SystemExit(f"--devices {args.devices}: only "
                         f"{len(jax.devices())} visible")

    from repro.core import hermite, nbody
    from repro.core.evaluate import make_evaluator
    from repro.core.strategies import make_strategy_evaluator

    state = nbody.plummer(args.n, seed=args.seed)
    impl = args.impl or ("xla" if args.strategy != "single" else None)
    if args.strategy == "single":
        ev = make_evaluator(order=args.order, impl=impl)
    else:
        ev = make_strategy_evaluator(
            args.strategy, devices=jax.devices()[: args.devices],
            order=args.order, impl=impl or "xla")

    e0_state = hermite.initialize(state, ev)
    e0 = float(nbody.total_energy(e0_state))
    t0 = time.perf_counter()
    out = hermite.evolve(state, ev, t_end=args.t_end, dt=args.dt,
                         eta=args.eta, order=args.order)
    jax.block_until_ready(out.pos)
    wall = time.perf_counter() - t0
    e1 = float(nbody.total_energy(out))
    print(f"[nbody] N={args.n} strategy={args.strategy} "
          f"devices={args.devices} order={args.order}")
    print(f"[nbody] t={float(out.time):.4f} wall={wall:.2f}s "
          f"E0={e0:.6f} E1={e1:.6f} |dE/E0|={abs((e1 - e0) / e0):.3e}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
