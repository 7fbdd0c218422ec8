"""Pallas TPU kernels for the all-pairs N-body force evaluation.

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

The source sweep.  A ``(BI, 8)`` target block meets the ``(8, BJ)`` source
blocks in turn along the source (j) grid axis, the innermost grid dimension,
so the i-block's state stays resident in VMEM across the sweep — the same
"accumulate along the row direction" schedule as the paper's Fig. 2, without
the dst-register acquire/release dance.  Work that does not depend on the
source block is done once per i-block, not once per grid step:

* **first j-step, once per i-block**: each target column the pair terms read
  (x y z vx vy vz, and ax ay az in the snap pass) is lane-broadcast into a
  lane-dense ``(BI, L)`` VMEM scratch tile, with ``L = gcd(BJ, 128)``; the
  i-block's activity (any target active) is kept as a scalar in SMEM; the
  accumulators are zeroed.
* **every j-step** of an active i-block: a loop over groups of
  ``ROW_GROUP`` target rows sweeps the source block in ``BJ / L`` lane
  chunks against the broadcast columns; a group's chunk terms are summed
  and added lane by lane into one ``(BI, L)`` VMEM accumulator per output
  (7 in pass 1, 3 in pass 2).  The step does pair arithmetic and lane-dense
  adds only: no lane broadcast, no cross-lane reduction, no
  vector-to-scalar move.  A group's pair terms fit in vregs; whole
  ``(BI, L)`` tiles of intermediates would be spilled to VMEM.
* **last j-step, once per i-block**, outside the activity gate (an inactive
  i-block writes zeros): each accumulator is reduced across its ``L`` lanes,
  scaled by the activity column and written into the packed ``(BI, 8)``
  output block, which the pipeline then writes back.

Per pair the float32 arithmetic is the reference's; only the order of the
float32 sums differs from a plain row sum (chunks within a step, then steps
per lane, then lanes).

The snap kernel is the second evaluation pass of the 6th-order Hermite scheme
and additionally consumes the pass-1 accelerations of both partners:

      tgt_acc : (N, 8) cols = [ax ay az pad...]
      src_acc : (8, N) rows = [ax ay az pad...]
      out     : (N, 8) cols = [sx sy sz pad...]

All math is FP32 (the paper's SFPU precision).  With a reduced
``compute_dtype`` each per-pair term is rounded through it, and each j-step's
add into the accumulators is an exact two-sum whose rounding error is
carried per lane in a compensation scratch and folded in at the last j-step.

Padding particles carry m = 0 so they contribute exactly zero: every output
term (acc, jerk, snap, pot) is a sum over source columns of ``m_j * f(...)``
with ``f`` finite under the zero-distance guard, so an m = 0 column is
exactly annihilated.  This is the mask contract that lets
``core.strategies`` pad to block multiples and ``sim.scenarios.build_padded``
pack ragged-N ensembles (tested by ``tests/test_padding_invariance.py``).

The kernels are also ``jax.vmap``-safe — batching a ``pallas_call`` prepends
grid dimensions (and the interpreter follows the same rule; the scratch is
refilled at each i-block's first j-step), which is how ``repro.sim.ensemble``
evaluates B stacked runs in one call.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Default interaction-block shape.  BJ is lane-aligned (multiple of 128), BI
# sublane-aligned (multiple of 8).  VMEM per launch at 256 x 512: the sweep's
# scratch is (BI, 128) fp32 tiles of 128 KiB — pass 1 holds 6 broadcast
# target columns and 7 accumulators (13 tiles, 1.70 MB), pass 2 holds 9 and
# 3 (12 tiles, 1.57 MB); a reduced compute dtype adds one compensation tile
# per accumulator (0.92 / 0.39 MB).  The double-buffered blocks add 0.56 MB
# (pass 1) and 0.85 MB (pass 2): a (BI, 8) block occupies (BI, 128) lanes.
# The pair terms of a row group live in vregs.  All of it sits well inside
# the 16 MB VMEM of a v5e core.
DEFAULT_BLOCK_I = 256
DEFAULT_BLOCK_J = 512

_X, _Y, _Z, _M, _VX, _VY, _VZ = 0, 1, 2, 3, 4, 5, 6
_ACT = _M  # target blocks carry the activity mask in the (unused) mass slot
#: target columns both passes read per pair, lane-broadcast once per i-block
_POS_VEL = (_X, _Y, _Z, _VX, _VY, _VZ)
#: lanes of a vreg: the width of the sweep's chunks and accumulators
LANES = 128
#: target rows a j-step takes at a time (two vregs per value): one trip of
#: the step's loop, small enough that its pair terms stay in registers
ROW_GROUP = 16


def _geometry(xi, yi, zi, src, eps):
    """Pairwise displacement + softened inverse-distance for one lane chunk.

    ``xi, yi, zi`` are lane-broadcast target columns (BI, L), ``src`` the
    (8, L) source chunk; every result is a (BI, L) tile of pairs."""
    f32 = jnp.float32
    dx = src[_X : _X + 1, :] - xi
    dy = src[_Y : _Y + 1, :] - yi
    dz = src[_Z : _Z + 1, :] - zi
    r2 = dx * dx + dy * dy + dz * dz
    d2 = r2 + f32(eps) ** 2
    # self-pairs (r2 == 0) must contribute exactly zero, incl. the potential
    safe = r2 > 0.0
    inv_r = jnp.where(safe, jax.lax.rsqrt(jnp.where(safe, d2, 1.0)), 0.0)
    d2s = jnp.where(safe, d2, 1.0)
    return dx, dy, dz, d2s, inv_r


def _dv(vxi, vyi, vzi, src):
    dvx = src[_VX : _VX + 1, :] - vxi
    dvy = src[_VY : _VY + 1, :] - vyi
    dvz = src[_VZ : _VZ + 1, :] - vzi
    return dvx, dvy, dvz


def _round(x, compute_dtype):
    """Round a per-pair term through the reduced compute dtype (fp32 I/O).

    Models the Tensix unpack-fp32 / compute-reduced / pack-fp32 datapath:
    the contribution tile is what the FPU emits at reduced precision; the
    accumulation that follows stays fp32.  ``None`` is the identity,
    keeping the full-precision path bit-identical.
    """
    if compute_dtype is None:
        return x
    return x.astype(compute_dtype).astype(jnp.float32)


def _acc_jerk_terms(ti, src, *, eps, compute_dtype):
    """Per-pair acc (3), jerk (3) and potential (1) terms of one chunk.

    The potential term is ``m_j / r``; the sweep negates its sum."""
    xi, yi, zi, vxi, vyi, vzi = ti
    dx, dy, dz, d2, inv_r = _geometry(xi, yi, zi, src, eps)
    inv_r3 = inv_r * inv_r * inv_r
    mj = src[_M : _M + 1, :]
    t = mj * inv_r3                                     # t_j  (paper Alg. 3)

    dvx, dvy, dvz = _dv(vxi, vyi, vzi, src)
    rv = dx * dvx + dy * dvy + dz * dvz                 # v_r
    q = (-3.0 * rv) / d2                                # A_ij * v_r

    terms = (t * dx, t * dy, t * dz,
             t * (dvx + q * dx), t * (dvy + q * dy), t * (dvz + q * dz),
             mj * inv_r)
    return [_round(x, compute_dtype) for x in terms]


def _snap_terms(ti, src, sacc, *, eps, compute_dtype):
    """Per-pair snap terms (3) of one chunk, from the pass-1 accelerations."""
    xi, yi, zi, vxi, vyi, vzi, axi, ayi, azi = ti
    dx, dy, dz, d2, inv_r = _geometry(xi, yi, zi, src, eps)
    inv_r3 = inv_r * inv_r * inv_r
    mj = src[_M : _M + 1, :]
    t = mj * inv_r3

    dvx, dvy, dvz = _dv(vxi, vyi, vzi, src)
    dax = sacc[0:1, :] - axi
    day = sacc[1:2, :] - ayi
    daz = sacc[2:3, :] - azi

    alpha = (dx * dvx + dy * dvy + dz * dvz) / d2
    beta = (dvx * dvx + dvy * dvy + dvz * dvz
            + dx * dax + dy * day + dz * daz) / d2 + alpha * alpha

    # A0 / A1 / A2 chains, per component (paper Alg. 3 extended to snap).
    a3, b3 = -3.0 * alpha, -3.0 * beta
    px, py, pz = t * dx, t * dy, t * dz                   # A0
    jx_, jy_, jz_ = t * dvx + a3 * px, t * dvy + a3 * py, t * dvz + a3 * pz
    terms = (t * dax - 6.0 * alpha * jx_ + b3 * px,
             t * day - 6.0 * alpha * jy_ + b3 * py,
             t * daz - 6.0 * alpha * jz_ + b3 * pz)
    return [_round(x, compute_dtype) for x in terms]


def _accumulate(acc_ref, comp_ref, idx, contrib):
    """Add one j-step's lane-dense ``contrib`` into ``acc_ref[idx]``.

    With a compensation ref, the add is an exact two-sum, lane by lane: its
    rounding error is recovered and carried in ``comp_ref``, so the
    accumulator error stays O(1 ulp) instead of growing with the number of
    source blocks (the fp32-accumulate half of the mixed-precision
    pattern).  Without one, this is a plain add.
    """
    if comp_ref is None:
        acc_ref[idx] += contrib
    else:
        a = acc_ref[idx]
        s = a + contrib
        bb = s - a
        err = (a - (s - bb)) + (contrib - bb)
        acc_ref[idx] = s
        comp_ref[idx] += err


def _sweep(tgt_ref, out_ref, tcol_ref, live_ref, acc_ref, comp_ref, *,
           tgt_cols, src_refs, pair_terms, signs):
    """One (i-block, j-block) grid step of a pass's source sweep.

    ``tgt_cols`` lists the (ref, column) target columns ``pair_terms``
    reads, in its order; ``src_refs`` the (8, BJ) source blocks it reads;
    ``signs`` the sign each output's sum is written with.  The first
    j-step fills the scratch, every j-step of an active i-block adds its
    pair terms lane by lane, and the last one reduces across lanes and
    writes the packed block (module docstring).
    """
    j_step = pl.program_id(1)
    lanes = tcol_ref.shape[-1]

    @pl.when(j_step == 0)
    def _begin():
        for k, (ref, c) in enumerate(tgt_cols):
            tcol_ref[k] = jnp.broadcast_to(ref[:, c : c + 1],
                                           tcol_ref.shape[1:])
        act = tgt_ref[:, _ACT : _ACT + 1]
        live_ref[0] = (jnp.sum(act) > 0.0).astype(jnp.int32)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if comp_ref is not None:
            comp_ref[...] = jnp.zeros_like(comp_ref)

    # an i-block with no active target contributes nothing: skip its compute
    # (the grid still visits the step, but the VPU work is predicated away)
    @pl.when(live_ref[0] > 0)
    def _step():
        group = math.gcd(tcol_ref.shape[1], ROW_GROUP)
        i32 = jnp.int32  # Mosaic refuses the i64 index math of x64

        def row_group(g, _):
            rows = pl.ds(pl.multiple_of(g * i32(group), group), group)
            ti = [tcol_ref[k, rows, :] for k in range(len(tgt_cols))]
            sums = None
            for c in range(src_refs[0].shape[1] // lanes):
                chunk = slice(c * lanes, (c + 1) * lanes)
                terms = pair_terms(ti, *(r[:, chunk] for r in src_refs))
                sums = terms if sums is None else [
                    s + t for s, t in zip(sums, terms)]
            for k, s in enumerate(sums):
                _accumulate(acc_ref, comp_ref, (k, rows), s)

        jax.lax.fori_loop(i32(0), i32(tcol_ref.shape[1] // group), row_group,
                          None)

    # outside the activity gate: an inactive i-block writes zeros, and an
    # active one whose last j-steps are skipped still writes its sums
    @pl.when(j_step == pl.num_programs(1) - 1)
    def _finish():
        def lane_sum(k):
            total = acc_ref[k]
            if comp_ref is not None:
                total = total + comp_ref[k]
            return signs[k] * jnp.sum(total, axis=1)

        cols = [lane_sum(k) for k in range(len(signs))]
        zero = jnp.zeros_like(cols[0])
        cols += [zero] * (out_ref.shape[1] - len(cols))
        act = tgt_ref[:, _ACT : _ACT + 1]
        out_ref[...] = act * jnp.stack(cols, axis=1)


def _acc_jerk_kernel(tgt_ref, src_ref, out_ref, tcol_ref, live_ref, acc_ref,
                     comp_ref=None, *, eps: float, compute_dtype=None):
    """Pass 1: acceleration, jerk and potential, ``[ax ay az jx jy jz pot]``."""
    _sweep(tgt_ref, out_ref, tcol_ref, live_ref, acc_ref, comp_ref,
           tgt_cols=[(tgt_ref, c) for c in _POS_VEL],
           src_refs=(src_ref,),
           pair_terms=functools.partial(_acc_jerk_terms, eps=eps,
                                        compute_dtype=compute_dtype),
           signs=(1.0,) * 6 + (-1.0,))


def _snap_kernel(tgt_ref, src_ref, tacc_ref, sacc_ref, out_ref, tcol_ref,
                 live_ref, acc_ref, comp_ref=None, *, eps: float,
                 compute_dtype=None):
    """Pass 2: snap from positions, velocities and pass-1 accs, ``[sx sy sz]``."""
    _sweep(tgt_ref, out_ref, tcol_ref, live_ref, acc_ref, comp_ref,
           tgt_cols=([(tgt_ref, c) for c in _POS_VEL]
                     + [(tacc_ref, c) for c in range(3)]),
           src_refs=(src_ref, sacc_ref),
           pair_terms=functools.partial(_snap_terms, eps=eps,
                                        compute_dtype=compute_dtype),
           signs=(1.0,) * 3)


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


def _scratch(block_i: int, block_j: int, n_cols: int, n_out: int,
             compute_dtype):
    """Scratch of one launch, in the kernel's argument order: the
    lane-broadcast target columns, the i-block's activity flag, the
    accumulators and, with a reduced compute dtype, their compensation."""
    tile = (block_i, math.gcd(block_j, LANES))
    f32 = jnp.float32
    shapes = [pltpu.VMEM((n_cols,) + tile, f32),
              pltpu.SMEM((1,), jnp.int32),
              pltpu.VMEM((n_out,) + tile, f32)]
    if compute_dtype is not None:
        shapes.append(pltpu.VMEM((n_out,) + tile, f32))
    return shapes


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
    return pl.pallas_call(
        functools.partial(_acc_jerk_kernel, eps=eps,
                          compute_dtype=compute_dtype),
        grid=grid,
        in_specs=[tgt_spec, src_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((n_t, 8), jnp.float32),
        scratch_shapes=_scratch(block_i, block_j, len(_POS_VEL), 7,
                                compute_dtype),
        interpret=interpret,
    )(tgt, src)


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
    return pl.pallas_call(
        functools.partial(_snap_kernel, eps=eps,
                          compute_dtype=compute_dtype),
        grid=grid,
        in_specs=[tgt_spec, src_spec, acc_t_spec, acc_s_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((n_t, 8), jnp.float32),
        scratch_shapes=_scratch(block_i, block_j, len(_POS_VEL) + 3, 3,
                                compute_dtype),
        interpret=interpret,
    )(tgt, src, tgt_acc, src_acc)
