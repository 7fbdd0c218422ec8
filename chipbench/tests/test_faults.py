"""Each fault the cells can have, planted under a small run on the CPU,
turns ``correct`` false; the unbroken run stays correct.

The harness's look for a chip is skipped (``platform="cpu"``); everything
else is a run as the benchmark makes it, at a small size.
"""

import dataclasses

import pytest

from conftest import small_run

BLOCK = "plummer64k_1chip.block"
LOCK = "plummer64k_1chip.lockstep"
SEED = 3000000021


@pytest.fixture(autouse=True)
def fresh_engines():
    """Engines are cached per process; a planted fault needs new ones."""
    from repro.sim import ensemble as ens

    def clear():
        ens._block_engine.cache_clear()
        ens._strategy_block_engine.cache_clear()

    clear()
    yield
    clear()


def _failed(out):
    return sorted(k for k, (v, lim) in out["checks"].items()
                  if v is None or (v < lim if k == "edge_steps" else v > lim))


@pytest.mark.parametrize("cell", [BLOCK, LOCK])
def test_unbroken_run_is_correct(cell, tmp_path):
    out = small_run(cell, SEED, tmp_path)
    assert out["correct"], out["checks"]


def _frozen(monkeypatch):
    """A step that returns its state unchanged, time included."""
    from repro.core import hermite
    from repro.sim import ensemble as ens

    real_block = ens.ensemble_run_block

    def block(batched, **kw):
        _, carry = real_block(batched, **kw)
        return batched, carry

    monkeypatch.setattr(ens, "ensemble_run_block", block)
    monkeypatch.setattr(hermite, "step", lambda state, dt, ev, order=6: state)


def _frozen_but_time(monkeypatch):
    """A step that moves nothing but the clock."""
    from repro.core import hermite
    from repro.sim import ensemble as ens

    real_block = ens.ensemble_run_block
    real_step = hermite.step

    def block(batched, **kw):
        out, carry = real_block(batched, **kw)
        return dataclasses.replace(batched, time=out.time), carry

    def step(state, dt, ev, order=6):
        return dataclasses.replace(state,
                                   time=real_step(state, dt, ev,
                                                     order=order).time)

    monkeypatch.setattr(ens, "ensemble_run_block", block)
    monkeypatch.setattr(hermite, "step", step)


def _half_sources(monkeypatch):
    """Half the sources left out, the other half's mass doubled (the mean
    taken over the rest)."""
    from repro.kernels import ops

    p1, p2 = ops.acc_jerk_pot_rect, ops.snap_rect

    def rect1(pt, vt, ps, vs, m, **kw):
        return p1(pt, vt, ps[::2], vs[::2], 2 * m[::2], **kw)

    def rect2(pt, vt, at, ps, vs, as_, m, **kw):
        return p2(pt, vt, at, ps[::2], vs[::2], as_[::2], 2 * m[::2], **kw)

    monkeypatch.setattr(ops, "acc_jerk_pot_rect", rect1)
    monkeypatch.setattr(ops, "snap_rect", rect2)


def _altered(monkeypatch):
    """Every force answer altered by one part in a thousand where the
    kernel produces it."""
    from repro.kernels import ops

    p1 = ops.acc_jerk_pot_rect

    def rect1(*a, **kw):
        acc, jerk, pot = p1(*a, **kw)
        return acc * 1.001, jerk, pot

    monkeypatch.setattr(ops, "acc_jerk_pot_rect", rect1)


def _wrong_rows(monkeypatch):
    """The gathered targets' outputs written back to the first rows, not
    to the rows they were gathered from: the identity on a macro boundary,
    where every row is active, and wrong rows at every partial tick."""
    import jax.numpy as jnp

    from repro.kernels import ops

    real = ops.scatter_outputs

    def scatter(perm, cap, n, *outs):
        return real(jnp.arange(perm.shape[0], dtype=perm.dtype), cap, n,
                    *outs)

    monkeypatch.setattr(ops, "scatter_outputs", scatter)


FAULTS = {"frozen": _frozen, "frozen_but_time": _frozen_but_time,
          "half_sources": _half_sources, "altered": _altered}
#: faults of the gather and scatter, which only the block cell has
BLOCK_FAULTS = {"wrong_rows": _wrong_rows}


@pytest.mark.parametrize(
    "cell,fault", [(c, f) for c in (BLOCK, LOCK) for f in sorted(FAULTS)]
    + [(BLOCK, f) for f in sorted(BLOCK_FAULTS)])
def test_fault_is_not_correct(cell, fault, tmp_path, monkeypatch):
    {**FAULTS, **BLOCK_FAULTS}[fault](monkeypatch)
    out = small_run(cell, SEED, tmp_path)
    assert not out["correct"], out["checks"]
    assert _failed(out), out["checks"]


def test_wrong_rows_fail_the_partial_tick(tmp_path, monkeypatch):
    """A wrong-row scatter fails the partial tick's own numbers, where the
    gathered targets' forces are compared on their rows."""
    _wrong_rows(monkeypatch)
    failed = _failed(small_run(BLOCK, SEED, tmp_path))
    assert {n + ".partial" for n in ("acc_err", "jerk_err", "pot_err")} \
        <= set(failed), failed
