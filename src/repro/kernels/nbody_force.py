"""Pallas TPU kernel for the all-pairs N-body force evaluation.

TPU adaptation of the paper's Tensix read/compute/write pipeline (DESIGN.md §2):

* The paper stages particle tiles through circular buffers between dedicated
  data-movement and compute RISC-V cores.  Here the same producer/consumer
  overlap is expressed by the Pallas grid pipeline: ``BlockSpec`` index maps
  describe which (i-block, j-block) of particle data each grid step consumes,
  and Mosaic double-buffers the HBM->VMEM DMAs against the VPU compute.
* The paper replicates every scalar 1024x so the Tensix tile engine can act on
  it.  TPUs broadcast natively, so we store each particle ONCE in a packed
  struct-of-arrays layout and broadcast inside the kernel (DESIGN.md §2.1):

      tgt  : (N, 8)  rows = target particles,  cols = [x y z act vx vy vz pad]
      src  : (8, N)  rows = [x y z m vx vy vz pad], cols = source particles
      out  : (N, 8)  cols = [ax ay az jx jy jz pot pad]

  Column 3 of the target block is the **activity mask** (1.0 = evaluate this
  target; ``ops.pack_targets`` writes all-ones when no mask is given, 0.0 on
  its alignment padding).  The block-timestep engine uses it to evaluate
  forces only *on* the currently active block of targets while sources stay
  full: each output row is scaled by its activity flag, and an i-block whose
  targets are all inactive skips its compute entirely via ``pl.when`` — the
  Tensix analogue would be the host simply not enqueueing that tile.

  A ``(BI, 8)`` target block meets an ``(8, BJ)`` source block and the whole
  (BI, BJ) interaction tile lives in VMEM registers/vregs.
* Accumulation runs along the source (j) grid axis, which is the innermost
  grid dimension, so the output block stays resident in VMEM across the sweep
  — the same "accumulate along the row direction" schedule as the paper's
  Fig. 2, without the dst-register acquire/release dance (VMEM is the staging
  buffer and Mosaic schedules the reuse).

The snap kernel is the second evaluation pass of the 6th-order Hermite scheme
and additionally consumes the pass-1 accelerations of both partners:

      tgt_acc : (N, 8) cols = [ax ay az pad...]
      src_acc : (8, N) rows = [ax ay az pad...]
      out     : (N, 8) cols = [sx sy sz pad...]

All math is FP32 (the paper's SFPU precision); padding particles carry m = 0
so they contribute exactly zero: every output term (acc, jerk, snap, pot) is
a sum over source columns of ``m_j * f(...)`` with ``f`` finite under the
zero-distance guard, so an m = 0 column is exactly annihilated.  This is the
mask contract that lets ``core.strategies`` pad to block multiples and
``sim.scenarios.build_padded`` pack ragged-N ensembles (tested by
``tests/test_padding_invariance.py``).

The kernel is also ``jax.vmap``-safe — batching a ``pallas_call`` prepends
grid dimensions (and the interpreter follows the same rule), which is how
``repro.sim.ensemble`` evaluates B stacked runs in one call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Default interaction-block shape.  VMEM working set is ~12 live (BI, BJ)
# fp32 tensors: 12 * 256 * 512 * 4 B ~= 6.3 MB, comfortably inside the 16 MB
# VMEM of a v5e core with room for the double-buffered input blocks.
# BJ is lane-aligned (multiple of 128), BI sublane-aligned (multiple of 8).
DEFAULT_BLOCK_I = 256
DEFAULT_BLOCK_J = 512

_X, _Y, _Z, _M, _VX, _VY, _VZ = 0, 1, 2, 3, 4, 5, 6
_ACT = _M  # target blocks carry the activity mask in the (unused) mass slot


def _geometry(tgt, src, eps):
    """Pairwise displacement + softened inverse-distance for one block pair."""
    f32 = jnp.float32
    xi, yi, zi = (tgt[:, k : k + 1] for k in (_X, _Y, _Z))    # (BI, 1)
    xj, yj, zj = (src[k : k + 1, :] for k in (_X, _Y, _Z))    # (1, BJ)
    dx = xj - xi
    dy = yj - yi
    dz = zj - zi
    r2 = dx * dx + dy * dy + dz * dz
    d2 = r2 + f32(eps) ** 2
    # self-pairs (r2 == 0) must contribute exactly zero, incl. the potential
    safe = r2 > 0.0
    inv_r = jnp.where(safe, jax.lax.rsqrt(jnp.where(safe, d2, 1.0)), 0.0)
    d2s = jnp.where(safe, d2, 1.0)
    return dx, dy, dz, d2s, inv_r


def _dv(tgt, src):
    dvx = src[_VX : _VX + 1, :] - tgt[:, _VX : _VX + 1]
    dvy = src[_VY : _VY + 1, :] - tgt[:, _VY : _VY + 1]
    dvz = src[_VZ : _VZ + 1, :] - tgt[:, _VZ : _VZ + 1]
    return dvx, dvy, dvz


def _round(x, compute_dtype):
    """Round a per-pair term through the reduced compute dtype (fp32 I/O).

    Models the Tensix unpack-fp32 / compute-reduced / pack-fp32 datapath:
    the (BI, BJ) contribution tile is what the FPU emits at reduced
    precision; the accumulation that follows stays fp32.  ``None`` is the
    identity, keeping the full-precision path bit-identical.
    """
    if compute_dtype is None:
        return x
    return x.astype(compute_dtype).astype(jnp.float32)


def _accumulate(out_ref, comp_ref, contrib):
    """Accumulate ``contrib`` into ``out_ref`` across the j-sweep.

    With a compensation ref, each j-block add is an exact two-sum: the
    rounding error of ``out += contrib`` is recovered and carried in
    ``comp_ref``, so the j-loop accumulator error stays O(1 ulp) instead of
    growing with the number of source blocks (the fp32-accumulate half of
    the mixed-precision pattern).  Without one, this is the historical
    in-place add.
    """
    if comp_ref is None:
        out_ref[...] += contrib
    else:
        a = out_ref[...]
        s = a + contrib
        bb = s - a
        err = (a - (s - bb)) + (contrib - bb)
        out_ref[...] = s
        comp_ref[...] += err


def _fold_compensation(out_ref, comp_ref, j_step):
    """Fold the carried compensation into the output at the last j-block.

    Deliberately OUTSIDE the activity gate: an i-block whose final j-steps
    are predicated away must still fold the error term accumulated on its
    earlier active steps.
    """
    if comp_ref is None:
        return

    @pl.when(j_step == pl.num_programs(1) - 1)
    def _fold():
        out_ref[...] = out_ref[...] + comp_ref[...]


def _acc_jerk_kernel(tgt_ref, src_ref, out_ref, comp_ref=None, *,
                     eps: float, compute_dtype=None):
    """One (i-block, j-block) step of the acc/jerk/potential sweep."""
    j_step = pl.program_id(1)

    @pl.when(j_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        if comp_ref is not None:
            comp_ref[...] = jnp.zeros_like(comp_ref)

    tgt = tgt_ref[...]
    act = tgt[:, _ACT : _ACT + 1]                       # target activity mask

    # an i-block with no active target contributes nothing: skip its compute
    # (the grid still visits the step, but the VPU work is predicated away)
    @pl.when(jnp.sum(act) > 0.0)
    def _compute():
        src = src_ref[...]
        dx, dy, dz, d2, inv_r = _geometry(tgt, src, eps)
        inv_r3 = inv_r * inv_r * inv_r
        mj = src[_M : _M + 1, :]
        t = mj * inv_r3                                 # t_j  (paper Alg. 3)

        dvx, dvy, dvz = _dv(tgt, src)
        rv = dx * dvx + dy * dvy + dz * dvz             # v_r
        q = (-3.0 * rv) / d2                            # A_ij * v_r

        ax = jnp.sum(_round(t * dx, compute_dtype), axis=1)
        ay = jnp.sum(_round(t * dy, compute_dtype), axis=1)
        az = jnp.sum(_round(t * dz, compute_dtype), axis=1)
        jx = jnp.sum(_round(t * (dvx + q * dx), compute_dtype), axis=1)
        jy = jnp.sum(_round(t * (dvy + q * dy), compute_dtype), axis=1)
        jz = jnp.sum(_round(t * (dvz + q * dz), compute_dtype), axis=1)
        pot = -jnp.sum(_round(mj * inv_r, compute_dtype), axis=1)
        zero = jnp.zeros_like(ax)

        partial = jnp.stack([ax, ay, az, jx, jy, jz, pot, zero], axis=1)
        _accumulate(out_ref, comp_ref, act * partial)

    _fold_compensation(out_ref, comp_ref, j_step)


def _snap_kernel(tgt_ref, src_ref, tacc_ref, sacc_ref, out_ref,
                 comp_ref=None, *, eps: float, compute_dtype=None):
    """Second Hermite pass: snap from positions, velocities and pass-1 accs."""
    j_step = pl.program_id(1)

    @pl.when(j_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        if comp_ref is not None:
            comp_ref[...] = jnp.zeros_like(comp_ref)

    tgt = tgt_ref[...]
    act = tgt[:, _ACT : _ACT + 1]                       # target activity mask

    @pl.when(jnp.sum(act) > 0.0)
    def _compute():
        src = src_ref[...]
        dx, dy, dz, d2, inv_r = _geometry(tgt, src, eps)
        inv_r3 = inv_r * inv_r * inv_r
        mj = src[_M : _M + 1, :]
        t = mj * inv_r3

        dvx, dvy, dvz = _dv(tgt, src)
        dax = sacc_ref[0:1, :] - tacc_ref[:, 0:1]
        day = sacc_ref[1:2, :] - tacc_ref[:, 1:2]
        daz = sacc_ref[2:3, :] - tacc_ref[:, 2:3]

        alpha = (dx * dvx + dy * dvy + dz * dvz) / d2
        beta = (dvx * dvx + dvy * dvy + dvz * dvz
                + dx * dax + dy * day + dz * daz) / d2 + alpha * alpha

        # A0 / A1 / A2 chains, per component (paper Alg. 3 extended to snap).
        a3, b3 = -3.0 * alpha, -3.0 * beta
        px, py, pz = t * dx, t * dy, t * dz                   # A0
        jx_, jy_, jz_ = t * dvx + a3 * px, t * dvy + a3 * py, t * dvz + a3 * pz
        sx = jnp.sum(_round(t * dax - 6.0 * alpha * jx_ + b3 * px,
                            compute_dtype), axis=1)
        sy = jnp.sum(_round(t * day - 6.0 * alpha * jy_ + b3 * py,
                            compute_dtype), axis=1)
        sz = jnp.sum(_round(t * daz - 6.0 * alpha * jz_ + b3 * pz,
                            compute_dtype), axis=1)
        zero = jnp.zeros_like(sx)

        partial = jnp.stack([sx, sy, sz, zero, zero, zero, zero, zero],
                            axis=1)
        _accumulate(out_ref, comp_ref, act * partial)

    _fold_compensation(out_ref, comp_ref, j_step)


def grid_tiles(n_t: int, n_s: int, block_i: int, block_j: int) -> int:
    """Number of (i-block, j-block) grid tiles one kernel launch enqueues.

    This is the unit the compaction layer shrinks: a launch over ``n_t``
    targets costs ``ceil(n_t/BI) * ceil(n_s/BJ)`` tiles whether or not
    ``pl.when`` predicates some of them away — the Tensix analogue is the
    host enqueueing a tile descriptor per (i, j) pair.  Gathering the active
    targets into a dense ``cap``-row buffer replaces ``n_t = N`` with
    ``n_t = cap`` so the tiles are *not enqueued at all* (telemetry reports
    this count per run as ``grid_tiles``).
    """
    return -(-n_t // block_i) * -(-n_s // block_j)


# Block index maps.  The constant block index is an explicit int32: under
# x64 a bare ``0`` lowers as an i64 literal next to the i32 grid index, and
# Mosaic refuses the mixed ``(i32, i64)`` return.
def _row_block(i, j):
    return i, jnp.int32(0)


def _col_block(i, j):
    return jnp.int32(0), j


def _grid_specs(n_t: int, n_s: int, block_i: int, block_j: int):
    # n_t is independent of n_s (rectangular contract): the compaction layer
    # exploits exactly this by shrinking the target extent to the gathered
    # active block while sources stay full.
    grid = (n_t // block_i, n_s // block_j)
    tgt_spec = pl.BlockSpec((block_i, 8), _row_block)
    src_spec = pl.BlockSpec((8, block_j), _col_block)
    out_spec = pl.BlockSpec((block_i, 8), _row_block)
    return grid, tgt_spec, src_spec, out_spec


def _out_wiring(n_t: int, out_spec, compute_dtype):
    """(out_specs, out_shape, unpack) for a launch.

    The full-precision path keeps its historical single output.  A reduced
    compute dtype adds a second (N_t, 8) output carrying the two-sum
    compensation term across the j-sweep; the kernel folds it into the
    primary output at the last j-step and the wrapper discards it.
    """
    shape = jax.ShapeDtypeStruct((n_t, 8), jnp.float32)
    if compute_dtype is None:
        return out_spec, shape, lambda out: out
    return [out_spec, out_spec], [shape, shape], lambda outs: outs[0]


@functools.partial(
    jax.jit,
    static_argnames=("eps", "block_i", "block_j", "interpret",
                     "compute_dtype"),
)
def acc_jerk_pot_packed(
    tgt,
    src,
    *,
    eps: float = 1e-7,
    block_i: int = DEFAULT_BLOCK_I,
    block_j: int = DEFAULT_BLOCK_J,
    interpret: bool = False,
    compute_dtype: str | None = None,
):
    """Pallas all-pairs acceleration+jerk+potential on packed operands.

    ``tgt``: (N_t, 8) float32, ``src``: (8, N_s) float32, with N_t divisible
    by ``block_i`` and N_s by ``block_j`` (``ops.py`` handles padding).
    Returns packed (N_t, 8) output.  N_t and N_s may differ — the rectangular
    contract used by the multi-device strategies (local targets x streamed
    sources).  ``compute_dtype`` (e.g. ``"bfloat16"``) rounds per-pair terms
    through the reduced dtype and compensates the j-loop accumulation.
    """
    n_t, n_s = tgt.shape[0], src.shape[1]
    grid, tgt_spec, src_spec, out_spec = _grid_specs(n_t, n_s, block_i, block_j)
    out_specs, out_shape, unpack = _out_wiring(n_t, out_spec, compute_dtype)
    return unpack(pl.pallas_call(
        functools.partial(_acc_jerk_kernel, eps=eps,
                          compute_dtype=compute_dtype),
        grid=grid,
        in_specs=[tgt_spec, src_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(tgt, src))


@functools.partial(
    jax.jit,
    static_argnames=("eps", "block_i", "block_j", "interpret",
                     "compute_dtype"),
)
def snap_packed(
    tgt,
    src,
    tgt_acc,
    src_acc,
    *,
    eps: float = 1e-7,
    block_i: int = DEFAULT_BLOCK_I,
    block_j: int = DEFAULT_BLOCK_J,
    interpret: bool = False,
    compute_dtype: str | None = None,
):
    """Pallas all-pairs snap pass on packed operands (see module docstring)."""
    n_t, n_s = tgt.shape[0], src.shape[1]
    grid, tgt_spec, src_spec, out_spec = _grid_specs(n_t, n_s, block_i, block_j)
    acc_t_spec = pl.BlockSpec((block_i, 8), _row_block)
    acc_s_spec = pl.BlockSpec((8, block_j), _col_block)
    out_specs, out_shape, unpack = _out_wiring(n_t, out_spec, compute_dtype)
    return unpack(pl.pallas_call(
        functools.partial(_snap_kernel, eps=eps,
                          compute_dtype=compute_dtype),
        grid=grid,
        in_specs=[tgt_spec, src_spec, acc_t_spec, acc_s_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(tgt, src, tgt_acc, src_acc))
