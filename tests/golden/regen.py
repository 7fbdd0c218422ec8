"""Regenerate the committed golden-trajectory reference files.

Run from the repo root after an *intentional* physics change:

    PYTHONPATH=src python tests/golden/regen.py

Each golden file records the final (pos, vel) of a short fixed-dt Hermite-6
integration computed with the FP64 golden evaluator (pure-jnp oracle at host
precision — no device kernel involved), plus the exact run recipe.  The
regression test (``tests/test_golden_trajectories.py``) replays the recipe
through every kernel/strategy combination and asserts agreement, so a silent
physics change in any kernel refactor fails loudly.  Commit the regenerated
JSON together with the change that motivated it.

Cases whose meta carries ``devices: k`` need a forced k-device host mesh,
which must be configured BEFORE jax initializes — ``main()`` re-executes
itself per such case in a subprocess with the right ``XLA_FLAGS``, so the
multi-device fixtures of ``tests/test_strategy_compaction.py`` regenerate
from the same single command as everything else.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import hermite  # noqa: E402
from repro.core.evaluate import make_evaluator  # noqa: E402
from repro.sim import ensemble as ens  # noqa: E402
from repro.sim import scenarios  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))

#: The committed golden cases: (filename, scenario recipe).  ``mode="block"``
#: cases run the hierarchical block-timestep engine (B=1 batch) instead of
#: the fixed-dt scan; their recipe pins (dt_max, n_levels, eta, t_end) and
#: the recorded ``n_events`` fingerprints the level schedule itself — a
#: kernel whose timestep quantization drifts fails on the event count before
#: it fails on positions.
CASES = {
    "two_body.json": dict(scenario="two_body", n=2, seed=0,
                          dt=1.0 / 256, n_steps=32, order=6, eps=1e-7),
    "plummer16.json": dict(scenario="plummer", n=16, seed=42,
                           dt=1.0 / 256, n_steps=32, order=6, eps=1e-7),
    "binary_plummer_block.json": dict(
        scenario="binary_plummer", n=24, seed=1, mode="block",
        dt_max=1.0 / 64, n_levels=4, t_end=0.0625, eta=0.02, order=6,
        eps=1e-7),
    # forced-multi-device fixture: the same block recipe, its domain sharded
    # over a 2-device host mesh with shard-local compaction (mode
    # "block_strategy" runs FP32 strategy evaluation — no fp64 oracle exists
    # for the distributed layer, so the differential suite compares this
    # golden at FP32 tolerance and leans on gather==none being bit-for-bit).
    # Needs more devices than a default process has: main() re-executes
    # itself in a subprocess with XLA_FLAGS set before jax initializes.
    "binary_plummer_block_2dev.json": dict(
        scenario="binary_plummer", n=24, seed=1, mode="block_strategy",
        strategy="mesh_sharded", impl="xla", devices=2,
        compaction="gather", block_i=8, block_j=128,
        dt_max=1.0 / 64, n_levels=4, t_end=0.0625, eta=0.02, order=6,
        eps=1e-7),
    # Ahmad-Cohen neighbor split (sources="neighbor"): near force from
    # gathered per-block windows, far field NM08-predicted between level
    # refreshes.  The fp64 oracle pins the split itself (window build, far
    # capture, prediction blend) — the recorded positions are in the
    # engine's ORB-sorted row order, pos0 in build order.  The radius is
    # chosen so windows are real subsets (some blocks see all sources,
    # some few): both gather paths and the fallback-free steady state get
    # exercised.
    "binary_plummer_neighbor.json": dict(
        scenario="binary_plummer", n=64, seed=1, mode="block",
        sources="neighbor", neighbor_radius=0.5, refresh_levels=2,
        block_i=16, block_j=16,
        dt_max=1.0 / 64, n_levels=4, t_end=0.0625, eta=0.02, order=6,
        eps=1e-7),
}


def integrate(meta: dict):
    state = scenarios.make(meta["scenario"], meta["n"], seed=meta["seed"])
    if meta.get("mode") == "block_strategy":
        out, carry = ens.evolve_strategy_block(
            state, t_end=meta["t_end"], dt_max=meta["dt_max"],
            n_levels=meta["n_levels"], eta=meta["eta"], order=meta["order"],
            eps=meta["eps"], impl=meta["impl"], strategy=meta["strategy"],
            compaction=meta["compaction"], block_i=meta["block_i"],
            block_j=meta["block_j"], devices=meta["devices"])
        return state, out, int(carry.n_events)
    if meta.get("mode") == "block":
        kw = {k: meta[k] for k in ("sources", "neighbor_radius",
                                   "refresh_levels", "block_i", "block_j")
              if k in meta}
        batched, carry = ens.evolve_ensemble_block(
            [state], t_end=meta["t_end"], dt_max=meta["dt_max"],
            n_levels=meta["n_levels"], eta=meta["eta"], order=meta["order"],
            eps=meta["eps"], impl="fp64", **kw)
        out = jax.tree_util.tree_map(lambda x: x[0], batched)
        return state, out, int(carry.n_events[0])
    ev = make_evaluator(precision="fp64", order=meta["order"],
                        eps=meta["eps"])
    out = hermite.evolve_scan(state, ev, n_steps=meta["n_steps"],
                              dt=meta["dt"], order=meta["order"])
    return state, out, None


def _respawn(fname: str, devices: int) -> None:
    """Regenerate one case in a subprocess that forces ``devices``
    host-platform devices BEFORE jax initializes (the same constraint the
    multi-device tests work around; this keeps every committed golden —
    single- and multi-device — reproducible from one command)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(HERE, "..", "..", "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    res = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--only", fname],
        env=env, capture_output=True, text=True, timeout=1200)
    if res.returncode != 0:
        raise RuntimeError(
            f"multi-device regen of {fname} failed:\n{res.stderr[-2000:]}")
    print(res.stdout, end="")


def main(only: str | None = None):
    for fname, meta in CASES.items():
        if only is not None and fname != only:
            continue
        devices = int(meta.get("devices", 1))
        if devices > jax.device_count():
            _respawn(fname, devices)
            continue
        state, out, n_events = integrate(meta)
        if meta.get("mode") == "block_strategy":
            evaluator = (f"fp32 {meta['strategy']} strategy x "
                         f"{meta['devices']} devices")
        else:
            evaluator = "fp64 golden (kernels.ref at x64)"
        doc = {
            "meta": {**meta, "generator": "tests/golden/regen.py",
                     "evaluator": evaluator},
            "pos0": np.asarray(state.pos, np.float64).tolist(),
            "vel0": np.asarray(state.vel, np.float64).tolist(),
            "mass": np.asarray(state.mass, np.float64).tolist(),
            "pos": np.asarray(out.pos, np.float64).tolist(),
            "vel": np.asarray(out.vel, np.float64).tolist(),
            "energy": float(jnp.sum(
                0.5 * out.mass * jnp.sum(out.vel**2, axis=-1)
                + 0.5 * out.mass * out.pot)),
        }
        if n_events is not None:
            doc["n_events"] = n_events
        path = os.path.join(HERE, fname)
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
        t_end = meta["t_end"] if "t_end" in meta \
            else meta["dt"] * meta["n_steps"]
        print(f"wrote {path} (t_end={t_end:.6f})")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None, metavar="FNAME",
                    help="regenerate a single case (used by the "
                         "multi-device subprocess respawn)")
    main(only=ap.parse_args().only)
