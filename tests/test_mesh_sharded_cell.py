"""The benchmark's four-chip cell at a size the CPU holds: a Plummer run
domain-decomposed over four host devices with ``strategy="mesh_sharded"``
block steps (gather compaction per shard, macro steps in chunks of 1, 14
and 1 events), through the public runner API.

One subprocess (the host device count is fixed before JAX starts) runs
N = 2048 with 128 x 128 tiles and five levels for three macro steps, as
configured and with the bfloat16 per-pair control (``dtype="mixed"``),
and the same run on one device.  The tests read its JSON:

* the event schedule is the one-device run's, bitwise, and the positions
  agree within float32 rounding;
* the first event after a macro boundary (only the finest level active,
  gathered into a small bucket per shard) and the boundary itself agree
  with the float64 reference (``chipbench/reference.py``) within the
  tolerances below, and the control exceeds at least one of them;
* once the first macro step has compiled every chunk length, the second
  compiles nothing;
* the per-shard counters read sensibly.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: float32 per-pair sums over 2048 sources against float64: the stored
#: derivatives differ by a few float32 roundings of the largest terms, so
#: each tolerance sits two to three orders above float32's 6e-8 and below
#: the bfloat16 control's readings (which start near 1e-3)
TOLERANCES = {"acc_err": 3e-5, "jerk_err": 3e-5, "pot_err": 3e-6,
              "snap_err": 3e-5, "step_err": 3e-5}
#: positions after three macro steps against one device: each row's force
#: sums run over the same source blocks in the same order on either
#: layout (bitwise equal on the CPU); the tolerance admits float32
#: rounding of the forces, carried through three corrector steps, where a
#: backend fuses a shard's sums otherwise
POS_RTOL = 1e-6

_SCRIPT = r"""
import dataclasses, json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
root = sys.argv[1]
sys.path[:0] = [root, os.path.join(root, "src")]
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
from chipbench import check, harness, reference
from chipbench.compiles import Compiles
from repro.obs import metrics as obs_metrics
from repro.sim import api

assert len(jax.devices()) == 4
compiles = Compiles(jax)
SEED, N_LEVELS = 3000000041, 5
base = api.SimConfig(
    scenario="plummer", n=2048, seed=SEED, t_end=1.0e6, eta=0.02, order=6,
    eps=1e-7, impl="xla", stepper="block", dt_max=1 / 64,
    n_levels=N_LEVELS, compaction="gather", block_i=128, block_j=128,
    strategy="mesh_sharded", devices=4, validate_ic=False, diag_every=1)
n_sub = 2 ** (N_LEVELS - 1)


def run(cfg):
    with obs_metrics.use() as reg:
        runner = api.get_runner(api.resolve_kind(cfg))
        hd = harness.Handle(runner, runner.build(cfg), [1, 14, 1])
        out = {"kind": runner.kind}
        states = []
        for macro in range(3):
            mark = compiles.mark()
            states = [harness.to_host(*hd.refs())]
            while True:
                edge = hd.step(jnp)
                states.append(harness.to_host(*hd.refs()))
                if edge:
                    break
            out[f"compiles_{macro}"] = compiles.since(mark)["xla_compiles"]
        c = hd.h.carry
        out["n_events"] = int(np.max(np.asarray(c.n_events)))
        out["n_pairs"] = float(np.sum(np.asarray(c.n_pairs)))
        out["pos"] = states[-1]["pos"].tolist()
        out["time"] = states[-1]["time"]
        out["numbers"] = check.compare(
            reference.Device64(cfg.eps), states, n_sub,
            cfg.dt_max / n_sub, SEED, 4, 512)
        if runner.kind == "block_strategy":
            out["gauges"] = reg.snapshot()["gauges"]
            out["tiles_per_shard"] = \
                runner.collect(hd.h)["grid_tiles_per_shard"]
    return out


print(json.dumps({
    "mesh": run(base),
    "one": run(dataclasses.replace(base, strategy="single", devices=1)),
    "mixed": run(dataclasses.replace(base, dtype="mixed")),
}))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", _SCRIPT, ROOT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _failed(numbers):
    return sorted(k for k, v in numbers.items()
                  if v > TOLERANCES[k.split(".")[0]])


def test_runs_through_the_strategy_runner(runs):
    assert runs["mesh"]["kind"] == "block_strategy"
    assert runs["mesh"]["time"] == runs["one"]["time"] == 3 / 64


def test_event_schedule_is_the_one_device_schedule(runs):
    mesh, one = runs["mesh"], runs["one"]
    assert mesh["n_events"] == one["n_events"]
    assert mesh["n_pairs"] == one["n_pairs"]


def test_positions_match_one_device_within_float32(runs):
    a, b = (np.asarray(runs[k]["pos"]) for k in ("mesh", "one"))
    assert np.abs(a - b).max() <= POS_RTOL * np.abs(b).max()


def test_partial_event_and_boundary_match_the_float64_reference(runs):
    numbers = runs["mesh"]["numbers"]
    # both checked events of the last macro step were checkable
    assert set(numbers) == {k + s for k in TOLERANCES
                            for s in ("", ".partial")}, sorted(numbers)
    assert not _failed(numbers), numbers


def test_the_bfloat16_control_fails_a_tolerance(runs):
    assert _failed(runs["mixed"]["numbers"]), runs["mixed"]["numbers"]


def test_second_macro_step_compiles_nothing(runs):
    assert runs["mesh"]["compiles_0"] > 0
    assert runs["mesh"]["compiles_1"] == 0
    assert runs["mesh"]["compiles_2"] == 0


def test_shard_counters(runs):
    tiles = runs["mesh"]["tiles_per_shard"]
    assert len(tiles) == 4 and min(tiles) > 0
    imbalance = runs["mesh"]["gauges"]["sim.shard_imbalance"]["value"]
    assert imbalance >= 1.0
    assert imbalance == pytest.approx(max(tiles) / (sum(tiles) / 4))
