"""Pallas N-body kernel vs the pure-jnp oracle (paper §4.1 validation).

The kernel is TPU-targeted; on CPU it executes under ``interpret=True``
(Mosaic-free Python interpretation of the same kernel body), swept over
shapes, block sizes and target/source splits and compared against ``ref.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import nbody_force, ops, ref

F32 = jnp.float32


def _cloud(n, seed=0, dtype=F32):
    rng = np.random.default_rng(seed)
    pos = jnp.asarray(rng.standard_normal((n, 3)), dtype)
    vel = jnp.asarray(rng.standard_normal((n, 3)) * 0.1, dtype)
    mass = jnp.asarray(rng.uniform(0.5, 1.5, n) / n, dtype)
    return pos, vel, mass


def _members(n, block_i, layout):
    """Clouds and target masks of a kernel-vs-oracle case.

    ``plain`` is one all-active cloud (no mask); ``masked`` leaves the
    first i-block wholly inactive and the second partly active; ``vmap``
    batches an all-active member with a wholly inactive one."""
    if layout == "vmap":
        return ([_cloud(n), _cloud(n, seed=5)],
                [np.ones(n, bool), np.zeros(n, bool)])
    if layout == "plain":
        return [_cloud(n)], [None]
    mask = np.ones(n, bool)
    mask[:block_i] = False
    mask[block_i + 1 : 2 * block_i : 3] = False
    return [_cloud(n)], [mask]


def _evaluate(fn, clouds, masks):
    """``fn(pos, vel, mass, mask)`` per member; one ``jax.vmap``-batched
    call when the case has more than one member."""
    if len(clouds) == 1:
        return [fn(*clouds[0], masks[0])]
    stacked = [jnp.stack(xs) for xs in zip(*clouds)] + [jnp.stack(masks)]
    out = jax.vmap(fn)(*stacked)
    return [jax.tree_util.tree_map(lambda x: x[b], out)
            for b in range(len(clouds))]


def _assert_rows(got, want, mask, tols):
    """Active rows match the oracle; inactive rows are exact zeros."""
    for g, w, tol in zip(got, want, tols):
        g, w = np.asarray(g), np.asarray(w)
        if mask is not None:
            assert not g[~mask].any()
            g, w = g[mask], w[mask]
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol)


# n, block_i, block_j, layout: the source sweep as one block of one or two
# 128-lane chunks and as 8 or more blocks, block_j below and above 128,
# and target activity per i-block and per batch member
KERNEL_CASES = [
    (256, 128, 128, "plain"),
    (512, 256, 512, "plain"),
    (300, 128, 256, "plain"),     # non-multiple of block => padding path
    (1024, 256, 512, "plain"),
    (128, 8, 128, "plain"),       # minimal sublane/lane-aligned blocks
    (256, 64, 256, "plain"),      # one source block of two lane chunks
    (1024, 64, 32, "plain"),      # 32 source blocks of 32 lanes
    (1024, 128, 128, "masked"),   # 8 source blocks, i-block 0 inactive
    (512, 64, 256, "vmap"),       # 2 source blocks, member 1 inactive
]
SNAP_CASES = [KERNEL_CASES[i] for i in (0, 2, 1, 5, 6, 7, 8)]


def _case_id(case):
    n, block_i, block_j, layout = case
    tail = "" if layout == "plain" else f"-{layout}"
    return f"{n}-{block_i}-{block_j}{tail}"


@pytest.mark.parametrize("n,block_i,block_j,layout", KERNEL_CASES,
                         ids=[_case_id(c) for c in KERNEL_CASES])
def test_acc_jerk_pot_matches_ref(n, block_i, block_j, layout):
    clouds, masks = _members(n, block_i, layout)

    def kernel(pos, vel, mass, mask):
        return ops.acc_jerk_pot_rect(
            pos, vel, pos, vel, mass, mask_t=mask, impl="pallas_interpret",
            block_i=block_i, block_j=block_j)

    for (pos, vel, mass), mask, got in zip(
            clouds, masks, _evaluate(kernel, clouds, masks)):
        want = ref.acc_jerk_pot_rect(pos, vel, pos, vel, mass)
        _assert_rows(got, want, mask, (2e-5, 2e-4, 2e-5))


@pytest.mark.parametrize("n_t,n_s", [(128, 512), (512, 128), (256, 256)])
def test_rectangular_contract(n_t, n_s):
    """Targets != sources (the multi-device strategies' local view)."""
    pt, vt, _ = _cloud(n_t, seed=1)
    ps, vs, ms = _cloud(n_s, seed=2)
    a_k, j_k, p_k = ops.acc_jerk_pot_rect(
        pt, vt, ps, vs, ms, impl="pallas_interpret")
    a_r, j_r, p_r = ref.acc_jerk_pot_rect(pt, vt, ps, vs, ms)
    np.testing.assert_allclose(a_k, a_r, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(j_k, j_r, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n,block_i,block_j,layout", SNAP_CASES,
                         ids=[_case_id(c) for c in SNAP_CASES])
def test_snap_matches_ref(n, block_i, block_j, layout):
    clouds, masks = _members(n, block_i, layout)

    def kernel(pos, vel, mass, mask):
        acc, _, _ = ref.acc_jerk_pot_rect(pos, vel, pos, vel, mass)
        return (ops.snap_rect(pos, vel, acc, pos, vel, acc, mass,
                              mask_t=mask, impl="pallas_interpret",
                              block_i=block_i, block_j=block_j),)

    for (pos, vel, mass), mask, got in zip(
            clouds, masks, _evaluate(kernel, clouds, masks)):
        acc, _, _ = ref.acc_jerk_pot_rect(pos, vel, pos, vel, mass)
        want = ref.snap_rect(pos, vel, acc, pos, vel, acc, mass)
        _assert_rows(got, (want,), mask, (5e-4,))


def test_row_chunked_rect_matches_dense(monkeypatch):
    """Above ``DENSE_PAIR_LIMIT`` the oracle streams target-row chunks
    through ``lax.map`` instead of fusing one (N_t, N_s) rectangle (the
    memory wall a 65536-body sweep hits at >100 GiB).  Row chunking never
    reorders a row-local source reduction, so the chunked results must
    match the dense path to reduction-vectorization rounding — and be
    shape-exact through padding (n_t not a multiple of the chunk rows)."""
    pt, vt, _ = _cloud(100, seed=1)
    at = jnp.asarray(np.random.default_rng(3).standard_normal((100, 3)), F32)
    ps, vs, ms = _cloud(64, seed=2)
    dense = ref.acc_jerk_pot_rect(pt, vt, ps, vs, ms)
    dense_s = ref.snap_rect(pt, vt, at, ps, vs, at[:64], ms)
    monkeypatch.setattr(ref, "DENSE_PAIR_LIMIT", 1 << 9)  # 8-row chunks
    chunked = ref.acc_jerk_pot_rect(pt, vt, ps, vs, ms)
    chunked_s = ref.snap_rect(pt, vt, at, ps, vs, at[:64], ms)
    for d, c in zip(dense + (dense_s,), chunked + (chunked_s,)):
        assert d.shape == c.shape
        np.testing.assert_allclose(np.asarray(c), np.asarray(d),
                                   rtol=1e-6, atol=1e-6)
    # under vmap (the batched ensemble engines) chunking lowers via scan
    bat = jax.vmap(lambda p, v: ref.acc_jerk_pot_rect(p, v, ps, vs, ms))
    a_b, _, _ = bat(jnp.stack([pt, pt]), jnp.stack([vt, vt]))
    np.testing.assert_allclose(np.asarray(a_b[0]), np.asarray(chunked[0]),
                               rtol=0, atol=0)


def test_zero_mass_padding_is_exact():
    """Padding particles carry m=0 => exactly zero contribution."""
    pos, vel, mass = _cloud(200)
    a1, j1, p1 = ops.acc_jerk_pot_rect(pos, vel, pos, vel, mass, impl="xla")
    # embed the same cloud among zero-mass strangers
    rng = np.random.default_rng(9)
    extra = jnp.asarray(rng.standard_normal((56, 3)), F32)
    pos_p = jnp.concatenate([pos, extra])
    vel_p = jnp.concatenate([vel, jnp.zeros_like(extra)])
    mass_p = jnp.concatenate([mass, jnp.zeros((56,), F32)])
    a2, j2, p2 = ops.acc_jerk_pot_rect(pos, vel, pos_p, vel_p, mass_p,
                                       impl="xla")
    np.testing.assert_allclose(a1, a2, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(j1, j2, rtol=1e-6, atol=1e-7)


def test_paper_accuracy_bands_fp32_vs_fp64_golden():
    """Paper §4.1: FP32 vs FP64 golden — acc <= 0.05%, jerk <= 0.2%."""
    n = 1024
    rng = np.random.default_rng(3)
    pos64 = jnp.asarray(rng.standard_normal((n, 3)), jnp.float64)
    vel64 = jnp.asarray(rng.standard_normal((n, 3)) * 0.1, jnp.float64)
    mass64 = jnp.asarray(np.full(n, 1.0 / n), jnp.float64)

    a64, j64, _ = ref.acc_jerk_pot_rect(pos64, vel64, pos64, vel64, mass64)
    a32, j32, _ = ops.acc_jerk_pot_rect(
        pos64.astype(F32), vel64.astype(F32), pos64.astype(F32),
        vel64.astype(F32), mass64.astype(F32), impl="pallas_interpret")

    def rel(x, y):
        scale = jnp.maximum(jnp.abs(y), jnp.abs(y).mean())
        return float(jnp.max(jnp.abs(x.astype(jnp.float64) - y) / scale))

    assert rel(a32, a64) < 5e-4, rel(a32, a64)   # 0.05 %
    assert rel(j32, j64) < 2e-3, rel(j32, j64)   # 0.2 %


def test_packing_layout():
    pos, vel, mass = _cloud(130)
    tgt = ops.pack_targets(pos, vel, 256)
    src = ops.pack_sources(pos, vel, mass, 256)
    assert tgt.shape == (256, 8) and src.shape == (8, 256)
    np.testing.assert_array_equal(tgt[:130, 0], pos[:, 0])
    np.testing.assert_array_equal(src[3, :130], mass)
    assert float(jnp.abs(tgt[130:]).sum()) == 0.0
    assert float(jnp.abs(src[:, 130:]).sum()) == 0.0
