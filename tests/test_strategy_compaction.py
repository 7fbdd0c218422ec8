"""Shard-local compaction across the distribution strategies + per-member
capacity buckets.

Locks the distributed-compaction contracts:

* **Differential suite** (forced 2-device host mesh, subprocess): for every
  strategy x kernel combination, ``compaction="gather"`` reproduces
  ``compaction="none"`` **bit-for-bit** on the committed block golden
  recipe — same event schedule, same measured pairs, strictly fewer local
  grid tiles — and tracks both the FP64 block golden (FP32 tolerance) and
  the committed 2-device strategy golden (``tests/golden/regen.py``
  regenerates it through its multi-device subprocess respawn).
* **Hypothesis properties**: per-shard and per-member bucket selection never
  underestimates the active count, and shard-local scatter∘gather is the
  identity under arbitrary activity masks and uneven shard occupancy.
* **Heterogeneous buckets**: a deliberately lopsided mixed batch (deep
  binary-rich member + quiescent two-body member) launches strictly fewer
  ``grid_tiles_total`` under per-member bucket groups than the block engine
  run with the whole batch as one bucket group, with bit-for-bit identical
  physics (energy drift unchanged).
* **Plumbing**: ``CapacityPlan`` shard/restrict units, driver routing of
  strategy block runs (``grid_tiles_per_shard`` telemetry).
"""

import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.sim import driver, ensemble as ens, scenarios

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
GOLDEN = os.path.join(GOLDEN_DIR, "binary_plummer_block.json")
GOLDEN_2DEV = os.path.join(GOLDEN_DIR, "binary_plummer_block_2dev.json")


# --------------------------------------------------------------------------
# differential suite: every strategy x kernel on a forced 2-device mesh
# --------------------------------------------------------------------------
# XLA's host-platform device count must be set before jax initializes, so
# the sweep runs in one subprocess (mirroring tests/test_strategies.py).
_DIFF_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json, sys
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.core.strategies import STRATEGIES
from repro.sim import ensemble as ens, scenarios

assert len(jax.devices()) == 2
with open(sys.argv[1]) as f:
    doc = json.load(f)            # the FP64 single-device block golden
with open(sys.argv[2]) as f:
    doc2 = json.load(f)           # the committed 2-device strategy golden
m = doc["meta"]
state = scenarios.make(m["scenario"], m["n"], seed=m["seed"])
kw = dict(t_end=m["t_end"], dt_max=m["dt_max"], n_levels=m["n_levels"],
          eta=m["eta"], order=m["order"], eps=m["eps"],
          block_i=8, block_j=128, devices=2)

for strategy in STRATEGIES:
    for impl in sys.argv[3].split(","):
        dense, c0 = ens.evolve_strategy_block(
            state, strategy=strategy, impl=impl, compaction="none", **kw)
        packed, c1 = ens.evolve_strategy_block(
            state, strategy=strategy, impl=impl, compaction="gather", **kw)
        tag = (strategy, impl)
        # identical event schedule and measured pairwise work ...
        assert int(c1.n_events) == int(c0.n_events) == doc["n_events"], tag
        assert float(c1.n_pairs) == float(c0.n_pairs), tag
        # ... bit-for-bit identical trajectory ...
        assert np.array_equal(np.asarray(packed.pos),
                              np.asarray(dense.pos)), tag
        assert np.array_equal(np.asarray(packed.vel),
                              np.asarray(dense.vel)), tag
        # ... strictly fewer tiles enqueued on EVERY shard
        tn, tg = np.asarray(c0.n_tiles), np.asarray(c1.n_tiles)
        assert tn.shape == tg.shape == (2,), tag
        assert (tg < tn).all(), (tag, tn, tg)
        # FP32 distributed evaluation tracks the FP64 block golden (the
        # binary-rich case compounds FP32 noise; cf. BLOCK_TOL in
        # tests/test_golden_trajectories.py)
        np.testing.assert_allclose(np.asarray(packed.pos),
                                   np.asarray(doc["pos"]),
                                   rtol=0, atol=1e-6)
        np.testing.assert_allclose(np.asarray(packed.vel),
                                   np.asarray(doc["vel"]),
                                   rtol=0, atol=1e-5)
        print(f"{strategy}/{impl}: OK tiles {tn.sum():.0f} -> {tg.sum():.0f}")

# --sources neighbor rides the vmapped batch engine: the same member
# duplicated across the 2-device batch axis must match its 1-device
# evolution bit-for-bit (sharding the batch never touches per-member
# math), march in lockstep, rebuild windows on the same schedule, and
# stay within the far-field prediction tier of the all-pairs trajectory
nkw = dict(t_end=m["t_end"], dt_max=m["dt_max"], n_levels=m["n_levels"],
           eta=m["eta"], order=m["order"], eps=m["eps"],
           block_i=8, block_j=8)
srt = ens.spatial_sort_state(state, leaf=8)
for impl in sys.argv[3].split(","):
    two, cn2 = ens.evolve_ensemble_block(
        [state, state], impl=impl, sources="neighbor",
        neighbor_radius=0.5, devices=jax.devices()[:2], **nkw)
    one, cn1 = ens.evolve_ensemble_block(
        [state, state], impl=impl, sources="neighbor",
        neighbor_radius=0.5, devices=jax.devices()[:1], **nkw)
    for leaf in ("pos", "vel"):
        assert np.array_equal(np.asarray(getattr(two, leaf)),
                              np.asarray(getattr(one, leaf))), (impl, leaf)
    assert np.array_equal(np.asarray(two.pos[0]), np.asarray(two.pos[1]))
    assert np.asarray(cn2.nbr.n_refresh).tolist() \
        == np.asarray(cn1.nbr.n_refresh).tolist()
    assert int(cn2.nbr.n_refresh[0]) > 0
    full, _ = ens.evolve_ensemble_block([srt], impl=impl, **nkw)
    np.testing.assert_allclose(np.asarray(two.pos[0]),
                               np.asarray(full.pos[0]), rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(two.vel[0]),
                               np.asarray(full.vel[0]), rtol=0, atol=1e-4)
    print(f"neighbor/{impl}: OK refreshes {int(cn2.nbr.n_refresh[0])}")

# the committed 2-device fixture replays exactly (same code path + version)
m2 = doc2["meta"]
state2 = scenarios.make(m2["scenario"], m2["n"], seed=m2["seed"])
out2, c2 = ens.evolve_strategy_block(
    state2, strategy=m2["strategy"], impl=m2["impl"],
    compaction=m2["compaction"], t_end=m2["t_end"], dt_max=m2["dt_max"],
    n_levels=m2["n_levels"], eta=m2["eta"], order=m2["order"],
    eps=m2["eps"], block_i=m2["block_i"], block_j=m2["block_j"],
    devices=m2["devices"])
assert int(c2.n_events) == doc2["n_events"]
np.testing.assert_allclose(np.asarray(out2.pos), np.asarray(doc2["pos"]),
                           rtol=0, atol=1e-9)
np.testing.assert_allclose(np.asarray(out2.vel), np.asarray(doc2["vel"]),
                           rtol=0, atol=1e-9)
print("GOLDEN-2DEV: OK")
print("DIFFERENTIAL: OK")
"""


def _run_differential(impls: str) -> str:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    res = subprocess.run(
        [sys.executable, "-c", _DIFF_SCRIPT, GOLDEN, GOLDEN_2DEV, impls],
        env=env, capture_output=True, text=True, timeout=1200)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    return res.stdout


@pytest.mark.slow
def test_strategy_compaction_differential_2dev_xla():
    out = _run_differential("xla")
    for strategy in ("replicated", "two_level", "mesh_sharded", "ring"):
        assert f"{strategy}/xla: OK" in out
    assert "neighbor/xla: OK" in out
    assert "GOLDEN-2DEV: OK" in out
    assert "DIFFERENTIAL: OK" in out


@pytest.mark.slow
def test_strategy_compaction_differential_2dev_pallas():
    out = _run_differential("pallas_interpret")
    for strategy in ("replicated", "two_level", "mesh_sharded", "ring"):
        assert f"{strategy}/pallas_interpret: OK" in out
    assert "neighbor/pallas_interpret: OK" in out
    assert "DIFFERENTIAL: OK" in out


# --------------------------------------------------------------------------
# hypothesis properties: shard-local buckets and gather/scatter
# --------------------------------------------------------------------------
def _shard_split(x, p):
    n_local = x.shape[0] // p
    return [x[i * n_local:(i + 1) * n_local] for i in range(p)]


def test_shard_bucket_never_underestimates_property():
    """For any activity mask and any (even wildly uneven) shard occupancy,
    every shard's selected local bucket holds its local active count."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 4),
           st.integers(1, 16), st.integers(1, 16),
           st.floats(0.0, 1.0))
    def run(seed, p, n_local_blocks, block_i, frac):
        rng = np.random.default_rng(seed)
        n = p * n_local_blocks * block_i
        # uneven occupancy: a random contiguous span of actives, so some
        # shards can be full while others are empty
        start = int(rng.integers(0, n))
        width = int(frac * n)
        mask = np.zeros(n, bool)
        mask[start:min(start + width, n)] = True
        plan = ops.CapacityPlan(n, n, block_i, 128).shard(p)
        assert plan.n_targets == n // p
        # host-side shard() agrees with what in-shard code builds from its
        # own local extent (strategies._shard_plan)
        assert plan.caps == ops.capacity_buckets(n // p, block_i)
        for mask_l in _shard_split(mask, p):
            n_act = int(mask_l.sum())
            cap = plan.caps[int(plan.bucket(n_act))]
            assert cap >= n_act
            assert cap % block_i == 0

    run()


def test_shard_local_scatter_gather_identity_property():
    """Shard-local scatter∘gather == identity on each shard's active rows,
    zero elsewhere — reassembled over shards it equals the global masked
    array, whatever the mask and however uneven the shard occupancy."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 4),
           st.integers(2, 12), st.integers(1, 8))
    def run(seed, p, n_local, block_i):
        rng = np.random.default_rng(seed)
        n = p * n_local
        x = rng.standard_normal((n, 3))
        mask = rng.uniform(size=n) < rng.uniform()
        back = []
        for x_l, m_l in zip(_shard_split(x, p), _shard_split(mask, p)):
            plan = ops.CapacityPlan(n_local, n, block_i, 128)
            cap = plan.caps[int(plan.bucket(m_l.sum()))]
            perm = jnp.argsort(~jnp.asarray(m_l), stable=True)
            x_c, m_c = ops.compact_targets(perm, cap, jnp.asarray(x_l),
                                           jnp.asarray(m_l))
            (b,) = ops.scatter_outputs(perm, cap, n_local,
                                       x_c * m_c[:, None])
            back.append(np.asarray(b))
        back = np.concatenate(back)
        np.testing.assert_array_equal(back[mask], x[mask])
        assert not back[~mask].any()

    run()


def test_member_bucket_never_underestimates_property():
    """Per-member dispatch: each bucket group's shared cap bounds every
    group member's per-event active count, for any n_active profile and any
    active counts below the per-member ceilings."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 6),
           st.integers(2, 64), st.integers(1, 16))
    def run(seed, b, n, block_i):
        rng = np.random.default_rng(seed)
        n_active = rng.integers(1, n + 1, size=b)
        groups = ens._bucket_groups(n, n_active, block_i, 128, "gather")
        # groups partition the batch
        members = sorted(m for ms, _ in groups for m in ms)
        assert members == list(range(b))
        caps = ops.capacity_buckets(n, block_i)
        for ms, n_caps in groups:
            gcaps = caps[:n_caps]
            counts = np.asarray([rng.integers(0, n_active[m] + 1)
                                 for m in ms])
            # the ceiling bucket covers every member of the group ...
            assert all(gcaps[-1] >= n_active[m] for m in ms)
            # ... and the group's shared per-event bucket covers them all
            cap = gcaps[int(ops.bucket_index(counts.max(), gcaps))]
            assert (cap >= counts).all()

    run()


def test_bucket_groups_modes():
    """Under gather a homogeneous batch collapses to one full-schedule
    group and a mixed batch splits by its members' ceiling buckets; without
    gather the whole batch is one group over the full schedule."""
    caps = ops.capacity_buckets(256, 32)
    homo = ens._bucket_groups(256, [256, 256, 256], 32, 256, "gather")
    assert homo == (((0, 1, 2), len(caps)),)
    small = len(ops.CapacityPlan(256, 256, 32, 256).restrict(64).caps)
    assert small < len(caps)
    assert ens._bucket_groups(256, [64, 256], 32, 256, "gather") \
        == (((0,), small), ((1,), len(caps)))
    assert ens._bucket_groups(256, [64, 256], 32, 256, "none") \
        == (((0, 1), len(caps)),)


def test_capacity_plan_shard_restrict_units():
    plan = ops.CapacityPlan(256, 256, 32, 128)
    assert plan.caps == (32, 64, 128, 256)
    assert plan.tiles_by_cap == (4, 8, 16, 32)        # 2 j-tiles x 2 passes
    assert plan.dense_tiles == 32
    local = plan.shard(2)
    assert local.n_targets == 128 and local.caps == (32, 64, 128)
    assert local.n_sources == 256                     # sources stay full
    small = plan.restrict(64)
    assert small.caps == (32, 64)
    # exact bucket boundaries select their own bucket as the ceiling
    assert plan.restrict(256).caps == plan.caps
    assert plan.restrict(32).caps == (32,)
    assert plan.restrict(33).caps == (32, 64)
    # a ceiling above the top bucket is a caller error, not a request for
    # the full schedule: that member could exceed every launchable bucket
    with pytest.raises(ValueError, match="capacity range"):
        plan.restrict(1000)
    with pytest.raises(ValueError, match="capacity range"):
        plan.restrict(0)
    with pytest.raises(ValueError, match="shards"):
        plan.shard(3)
    # ring-style plan: per-pass launch per streamed shard
    ring = ops.CapacityPlan(128, 128, 32, 128, n_passes=4)
    assert ring.tiles_by_cap == (4, 8, 16)


# --------------------------------------------------------------------------
# heterogeneous buckets: lopsided mixed batch
# --------------------------------------------------------------------------
def _one_group_block(batched, n_active, *, t_end, dt_max, n_levels, impl,
                     block_i, block_j, n_events=256):
    """Run the block engine with the whole batch as ONE bucket group over
    the full capacity schedule (the batch-shared dispatch), chunk by chunk
    to ``t_end`` as :func:`ens.evolve_ensemble_block` does; returns the
    initialized batch too, so callers can measure energy drift from it."""
    b, n = ens.batch_size(batched), batched.pos.shape[1]
    groups = ((tuple(range(b)),
               len(ops.CapacityPlan(n, n, block_i, block_j).caps)),)
    init, run = ens._block_engine(6, 1e-7, impl, None, 0.02, dt_max,
                                  n_levels, "gather", block_i, block_j,
                                  groups, "fp32")
    na = jnp.asarray(n_active, jnp.int32)
    t = ens._as_t_end(batched, t_end)
    start = ens.ensemble_initialize(batched, n_active=na, impl=impl)
    out, carry = start, init(start, na, t)
    for _ in range(1000):
        out, carry = run(out, carry, na, t, n_events)
        if float(jnp.min(out.time)) >= t_end:
            break
    return start, out, carry


def test_lopsided_mixed_batch_member_buckets_beat_shared():
    """One deep-hierarchy member (binary-rich Plummer) + one quiescent
    member (two-body, n_active=2 inside a 24-row pad): per-member bucket
    groups launch strictly fewer total tiles than the one-group
    batch-shared schedule, at bit-for-bit identical physics (same
    trajectory, same measured pairs, same energy drift)."""
    specs = [scenarios.Scenario(name="binary_plummer", n=24, seed=1),
             scenarios.Scenario(name="two_body", n=2, seed=0)]
    batched, n_active = scenarios.build_padded(specs, n_max=24)
    kw = dict(t_end=0.0625, dt_max=1 / 64, n_levels=4, impl="xla",
              block_i=8, block_j=128)
    _, shared, cs = _one_group_block(batched, n_active, **kw)
    member, cm = ens.evolve_ensemble_block(batched, compaction="gather",
                                           n_active=n_active, **kw)
    # launch economics: strictly fewer tiles, and the quiescent member is
    # the one that got cheaper
    assert float(np.sum(np.asarray(cm.n_tiles))) \
        < float(np.sum(np.asarray(cs.n_tiles)))
    assert float(cm.n_tiles[1]) < float(cs.n_tiles[1])
    # physics: bit-for-bit unchanged
    np.testing.assert_array_equal(np.asarray(member.pos),
                                  np.asarray(shared.pos))
    np.testing.assert_array_equal(np.asarray(member.vel),
                                  np.asarray(shared.vel))
    np.testing.assert_array_equal(np.asarray(cm.n_pairs),
                                  np.asarray(cs.n_pairs))
    np.testing.assert_array_equal(np.asarray(cm.n_events),
                                  np.asarray(cs.n_events))
    e_m = np.asarray(ens.batched_total_energy(member))
    e_s = np.asarray(ens.batched_total_energy(shared))
    np.testing.assert_array_equal(e_m, e_s)


def test_lopsided_mixed_driver_reports_fewer_tiles(tmp_path):
    """The same lopsided comparison end-to-end through the driver: its
    telemetry ``grid_tiles_total`` is below the one-group engine run's,
    while the measured pairs and each member's energy drift are equal."""
    mix = (("binary_plummer", 24), ("two_body", 2))
    kw = dict(t_end=0.03125, dt_max=1 / 64, n_levels=4, block_i=8,
              block_j=128, impl="xla")
    report = driver.run(driver.SimConfig(
        mix=mix, seed=0, stepper="block", compaction="gather", diag_every=16,
        out=str(tmp_path / "m.json"), **kw))
    batched, n_active = scenarios.build_padded(
        scenarios.make_mix(mix, seed=0))
    start, shared, cs = _one_group_block(batched, n_active, n_events=16,
                                         **kw)
    assert report["grid_tiles_total"] < float(np.sum(np.asarray(cs.n_tiles)))
    assert report["force_evals_total"] \
        == sum(float(p) for p in np.asarray(cs.n_pairs))
    e0 = np.asarray(ens.batched_total_energy(start), np.float64)
    e1 = np.asarray(ens.batched_total_energy(shared), np.float64)
    assert [r["de_rel"] for r in report["runs"]] \
        == [float(d) for d in np.abs((e1 - e0) / e0)]


# --------------------------------------------------------------------------
# plumbing: driver strategy routing + validation
# --------------------------------------------------------------------------
def test_driver_block_strategy_reports_per_shard_tiles(tmp_path):
    """strategy + block routes through the sharded engine (here on the
    1-device mesh every local path still sees) and reports per-shard
    grid_tiles."""
    cfg = driver.SimConfig(scenario="binary_plummer", n=24, seed=1,
                           t_end=0.03125, stepper="block", dt_max=1 / 64,
                           n_levels=4, compaction="gather", block_i=8,
                           block_j=128, strategy="mesh_sharded", devices=1,
                           impl="xla", diag_every=16,
                           out=str(tmp_path / "r.json"))
    report = driver.run(cfg)
    assert report["strategy"] == "mesh_sharded"
    assert len(report["grid_tiles_per_shard"]) == 1
    assert report["grid_tiles_total"] == sum(report["grid_tiles_per_shard"])
    # compaction engaged: fewer than the dense per-shard schedule
    plan = ops.CapacityPlan(24, 24, 8, 128)
    assert report["grid_tiles_total"] < plan.dense_tiles * report["steps"]
    assert report["steps"] > 0 and report["force_evals_total"] > 0
