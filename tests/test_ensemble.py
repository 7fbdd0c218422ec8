"""Ensemble engine: batching round-trips, ensemble-vs-sequential numerical
equivalence, strategy-label equivalence, and the driver's telemetry report."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hermite
from repro.core.evaluate import make_evaluator
from repro.core.strategies import STRATEGIES
from repro.sim import driver, ensemble as ens, scenarios


def _states(b=3, n=32):
    return [scenarios.make("plummer", n, seed=s) for s in range(b)]


def test_stack_unstack_roundtrip():
    states = _states()
    batched = ens.stack_states(states)
    assert batched.pos.shape == (3, 32, 3)
    for orig, back in zip(states, ens.unstack_states(batched)):
        np.testing.assert_array_equal(np.asarray(orig.pos),
                                      np.asarray(back.pos))
        np.testing.assert_array_equal(np.asarray(orig.mass),
                                      np.asarray(back.mass))


def test_stack_rejects_mismatched_n():
    with pytest.raises(ValueError):
        ens.stack_states([scenarios.make("plummer", 32),
                          scenarios.make("plummer", 48)])


def test_ensemble_matches_sequential_fixed_dt():
    """The batched vmapped loop reproduces per-run evolve_scan exactly."""
    states = _states()
    out_b = ens.evolve_ensemble(ens.stack_states(states), n_steps=4, dt=1e-2)
    ev = make_evaluator(impl="xla")
    for i, s in enumerate(states):
        ref = hermite.evolve_scan(s, ev, n_steps=4, dt=1e-2)
        np.testing.assert_allclose(np.asarray(out_b.pos[i]),
                                   np.asarray(ref.pos),
                                   rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(np.asarray(out_b.vel[i]),
                                   np.asarray(ref.vel),
                                   rtol=1e-12, atol=1e-12)


def test_ensemble_strategy_labels_equivalent():
    """Independent runs have no cross-run comms: every strategy label yields
    the same one-step result (single-device mesh here; the multi-device
    batch sharding is exercised in the slow subprocess test)."""
    batched = ens.stack_states(_states())
    outs = [ens.evolve_ensemble(batched, n_steps=1, dt=1e-2, strategy=s)
            for s in ("single",) + STRATEGIES]
    for out in outs[1:]:
        np.testing.assert_array_equal(np.asarray(outs[0].pos),
                                      np.asarray(out.pos))
    with pytest.raises(ValueError):
        ens.evolve_ensemble(batched, n_steps=1, dt=1e-2, strategy="bogus")


def test_adaptive_ensemble_reaches_t_end_and_conserves():
    batched = ens.stack_states(_states(b=2, n=48))
    batched = ens.ensemble_initialize(batched)
    e0 = np.asarray(ens.batched_total_energy(batched))
    h = cnt = None
    for _ in range(64):
        batched, h, cnt = ens.ensemble_run_adaptive(
            batched, t_end=0.125, n_steps=16, h_prev=h, n_taken=cnt)
        if float(np.min(np.asarray(batched.time))) >= 0.125:
            break
    times = np.asarray(batched.time)
    np.testing.assert_allclose(times, 0.125, rtol=0, atol=1e-12)
    e1 = np.asarray(ens.batched_total_energy(batched))
    assert np.abs((e1 - e0) / e0).max() < 1e-3
    # per-run productive step counts are positive and can differ
    cnt = np.asarray(cnt)
    assert (cnt > 0).all()


def test_ensemble_rejects_unknown_impl():
    """pallas/pallas_interpret are vmap-safe since the padded-ensemble PR;
    only genuinely unknown impls are rejected."""
    with pytest.raises(ValueError):
        ens.evolve_ensemble(ens.stack_states(_states(b=2)), n_steps=1,
                            dt=1e-2, impl="bogus")
    with pytest.raises(ValueError):
        ens.evolve_ensemble(ens.stack_states(_states(b=2)), n_steps=1,
                            dt=1e-2, impl="pallas_marked")


def test_driver_single_run_report(tmp_path):
    out = str(tmp_path / "report.json")
    report = driver.run(driver.SimConfig(
        scenario="king", n=48, t_end=0.05, dt=1.0 / 256, impl="xla",
        diag_every=4, out=out))
    assert report["de_rel"] < 1e-3
    assert report["steps"] > 0 and report["wall_s"] > 0
    assert report["modeled"]["energy_J"] > 0
    assert report["modeled"]["edp_Js"] > 0
    assert report["snapshots"][0]["step"] == 0
    import json
    on_disk = json.load(open(out))
    assert on_disk["scenario"] == "king" and on_disk["de_rel"] < 1e-3


def test_driver_ensemble_report():
    report = driver.run(driver.SimConfig(
        scenario="merger", n=32, ensemble=3, t_end=0.05, diag_every=8,
        impl="xla"))
    assert report["ensemble"] == 3 and len(report["runs"]) == 3
    assert report["de_rel"] < 1e-3
    assert {r["seed"] for r in report["runs"]} == {0, 1, 2}
    assert report["t_final"] >= 0.05 - 1e-12


@pytest.mark.slow
def test_ensemble_batch_sharding_2dev_subprocess():
    """Multi-device batch sharding matches the single-device result (needs
    placeholder devices before jax init, hence the subprocess)."""
    import os
    import subprocess
    import sys

    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.sim import scenarios, ensemble as ens

states = [scenarios.make("plummer", 32, seed=s) for s in range(3)]  # 3 % 2 != 0
b = ens.stack_states(states)
one = ens.evolve_ensemble(b, n_steps=3, dt=1e-2)
two = ens.evolve_ensemble(b, n_steps=3, dt=1e-2, devices=jax.devices())
err = float(np.abs(np.asarray(one.pos) - np.asarray(two.pos)).max())
assert err < 1e-12, err
print("SHARDED-ENSEMBLE: OK")
"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    assert "SHARDED-ENSEMBLE: OK" in res.stdout


def test_block_engine_batch_mesh_4dev_bitwise_subprocess():
    """The block engine's kernel calls run per batch shard on a 4-device
    mesh (a Pallas kernel cannot be partitioned by sharding constraints):
    bucket groups of 1 and 2 members, padded to the mesh inside each call,
    and the neighbor scheme's windowed calls reproduce one device bitwise."""
    import os
    import subprocess
    import sys

    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.sim import scenarios, ensemble as ens

batched = ens.stack_states(
    [scenarios.make("plummer", 256, seed=s) for s in range(3)])
na = np.array([256, 128, 256], np.int32)
for impl, compaction, sources in [("pallas_interpret", "gather", "full"),
                                  ("xla", "none", "neighbor")]:
    b = batched if sources == "full" else \
        ens.spatial_sort_batched(batched, na, leaf=64)
    outs = []
    for devs in (None, jax.devices()):
        b0 = ens.ensemble_initialize(b, n_active=na, impl=impl, devices=devs)
        out, carry = ens.ensemble_run_block(
            b0, t_end=0.0625, n_events=8, n_active=na, n_levels=4,
            impl=impl, compaction=compaction, sources=sources, block_i=64,
            block_j=64, devices=devs)
        outs.append([np.asarray(x) for x in (out.pos, out.vel,
                                             carry.n_events)])
    for one, four in zip(*outs):
        assert np.array_equal(one, four), (impl, sources)
print("BLOCK-MESH: OK")
"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    assert "BLOCK-MESH: OK" in res.stdout
