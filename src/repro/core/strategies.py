"""Multi-device distribution strategies for the N-body force evaluation.

These are the paper's three scaling configurations (§3, Fig. 3) mapped onto
JAX collectives, plus one beyond-paper strategy (DESIGN.md §3):

* ``replicated``   — paper's Multi-Host Single-Chip: targets sharded over all
  devices, the full source set all-gathered onto every device once per
  evaluation (each chip holds a full replicated copy).
* ``two_level``    — paper's Multi-Host Multi-Chip: identical math, but the
  source gather is staged hierarchically over a (card, chip) view of the
  devices — all-gather across the chips of a card first, then across cards —
  modelling the explicit per-card partitioning of the paper.
* ``mesh_sharded`` — paper's Mesh-Based configuration: no explicit
  collectives; targets bind with a sharded spec and sources with a
  replicated one, and the runtime (XLA SPMD here, TT-NN there) inserts the
  communication.  "Sharded buffers for domain-decomposed data, replicated
  buffers for globally shared particle data."
* ``ring``         — beyond-paper: systolic ``ppermute`` ring; every device
  keeps only N/P sources resident and overlaps each (N/P)^2 interaction block
  with the shift of the next source shard.  O(N/P) memory instead of O(N).

All strategies implement the same ``Evaluator`` contract and are numerically
equivalent to the single-device evaluation (tested property), because
all-pairs summation is order-invariant in the source index.

Each strategy additionally has a **compaction-aware block evaluator**
(:func:`make_strategy_block_evaluator`) for the hierarchical block-timestep
scheme: an active-target mask rides with the sharded targets, and with
``compaction="gather"`` every shard gathers its *local* active targets into
a dense block-aligned buffer of one of a few static capacities before
launching the kernels — the distributed analogue of
``core.evaluate.make_block_evaluator``, with per-shard launched-tile
accounting for telemetry.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.hermite import Evaluation, Evaluator
from repro.kernels import nbody_force, ops
from repro.obs import metrics as obs_metrics

STRATEGIES = ("replicated", "two_level", "mesh_sharded", "ring")
#: compaction modes of the strategy block evaluators (mirrors core.evaluate)
COMPACTIONS = ("none", "gather")
#: ring source-shift schedules: "overlap" is the double-buffered default
#: (prefetch the next source window before the local kernels, exactly p-1
#: ppermute rounds per pass); "sync" is the pre-overlap baseline the bench
#: measures against (shift after compute inside a fori_loop, p rounds per
#: pass — the p-th round's result is discarded, the dead collective the
#: overlap schedule eliminates)
RING_MODES = ("overlap", "sync")
#: the named scope of the collectives mesh_sharded's specs imply
IMPLIED_SCOPE = "collective.implied"


def make_batch_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    *,
    axis_name: str = "batch",
) -> Mesh:
    """1-D mesh over ``devices`` for batch-axis (ensemble) data parallelism.

    This is the same flat device view the 1-D strategies build internally;
    ``repro.sim.ensemble`` shards the leading axis of stacked runs over it.
    """
    devs = np.asarray(list(devices) if devices is not None else jax.devices())
    return Mesh(devs.reshape(devs.size), (axis_name,))


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _pad_particles(pos, vel, mass, n_pad: int):
    n = pos.shape[0]
    return (
        jnp.pad(pos, ((0, n_pad - n), (0, 0))),
        jnp.pad(vel, ((0, n_pad - n), (0, 0))),
        jnp.pad(mass, ((0, n_pad - n),)),  # zero mass => zero contribution
    )


def _force_kw(impl, block_i, block_j, eps, dtype="fp32"):
    # the kw dict is passed straight into the ops rect wrappers, so the
    # precision axis rides with the tile shape and softening everywhere a
    # strategy launches a kernel
    return dict(eps=eps, impl=impl, block_i=block_i, block_j=block_j,
                dtype=dtype)


def make_strategy_evaluator(
    strategy: str,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    chips_per_card: int = 2,
    eps: float = 1e-7,
    order: int = 6,
    impl: str = "xla",
    block_i: int = nbody_force.DEFAULT_BLOCK_I,
    block_j: int = nbody_force.DEFAULT_BLOCK_J,
    dtype: str = "fp32",
    ring_mode: str = "overlap",
) -> Evaluator:
    """Build an ``Evaluator`` that distributes the evaluation over devices.

    The strategy meshes are *internal views* over the given devices: a 1D
    ``('dev',)`` mesh for replicated/mesh_sharded/ring, a 2D
    ``('card', 'chip')`` view for two_level (paper: 2 chips per n300 card).

    ``dtype`` is the kernel precision axis (``"fp32"`` or ``"mixed"``);
    the strategies keep fp32 state and collectives either way — only the
    per-pair arithmetic inside each shard's launches narrows.

    ``ring_mode`` selects the ring strategy's source-shift schedule
    (:data:`RING_MODES`): the double-buffered ``"overlap"`` default issues
    exactly ``p - 1`` prefetch-first ``ppermute`` rounds per pass, the
    ``"sync"`` baseline keeps the legacy shift-after-compute loop with its
    dead ``p``-th round.  Both are bit-for-bit identical in output.
    """
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown strategy {strategy!r}; one of {STRATEGIES}")
    if ring_mode not in RING_MODES:
        raise ValueError(
            f"ring_mode must be one of {RING_MODES}; got {ring_mode!r}")
    devs = np.asarray(devices if devices is not None else jax.devices())
    p = devs.size
    kw = _force_kw(impl, block_i, block_j, eps, dtype)

    if strategy == "two_level":
        if p % chips_per_card:
            raise ValueError(f"{p} devices not divisible by {chips_per_card=}")
        mesh = Mesh(devs.reshape(p // chips_per_card, chips_per_card),
                    ("card", "chip"))
        return _two_level(mesh, order, kw)
    mesh = Mesh(devs.reshape(p), ("dev",))
    if strategy == "replicated":
        return _replicated(mesh, order, kw)
    if strategy == "mesh_sharded":
        return _mesh_sharded(mesh, order, kw)
    return _ring(mesh, order, kw, ring_mode)


def _wrap(mesh, p, order, eval_padded):
    """Pad N to a multiple of the device count, evaluate, slice back."""

    def evaluate(pos, vel, mass) -> Evaluation:
        n = pos.shape[0]
        f32 = jnp.float32
        pos32 = jnp.asarray(pos, f32)
        vel32 = jnp.asarray(vel, f32)
        mass32 = jnp.asarray(mass, f32)
        n_pad = _round_up(n, p)
        pp, vp, mp = _pad_particles(pos32, vel32, mass32, n_pad)
        acc, jerk, snp, pot = eval_padded(pp, vp, mp)
        return Evaluation(acc[:n], jerk[:n], snp[:n], pot[:n])

    return evaluate


# --------------------------------------------------------------------------
# Strategy 1 — replicated (Multi-Host Single-Chip analogue)
# --------------------------------------------------------------------------
def _replicated(mesh: Mesh, order: int, kw) -> Evaluator:
    axes = mesh.axis_names

    @jax.jit
    @jax.shard_map(mesh=mesh, in_specs=(P(axes),) * 3,
                   out_specs=(P(axes),) * 4, check_vma=False)
    def eval_padded(pos, vel, mass):
        # each device: local targets x full (gathered) source set
        with jax.named_scope("collective.all_gather"):
            gp = jax.lax.all_gather(pos, axes, axis=0, tiled=True)
            gv = jax.lax.all_gather(vel, axes, axis=0, tiled=True)
            gm = jax.lax.all_gather(mass, axes, axis=0, tiled=True)
        acc, jerk, pot = ops.acc_jerk_pot_rect(pos, vel, gp, gv, gm, **kw)
        if order >= 6:
            with jax.named_scope("collective.all_gather"):
                ga = jax.lax.all_gather(acc, axes, axis=0, tiled=True)
            snp = ops.snap_rect(pos, vel, acc, gp, gv, ga, gm, **kw)
        else:
            snp = jnp.zeros_like(acc)
        return acc, jerk, snp, pot

    return _wrap(mesh, mesh.size, order, eval_padded)


# --------------------------------------------------------------------------
# Strategy 2 — two_level (Multi-Host Multi-Chip analogue)
# --------------------------------------------------------------------------
def _two_level(mesh: Mesh, order: int, kw) -> Evaluator:
    axes = mesh.axis_names  # ("card", "chip")

    def gather2(x):
        # stage 1: within the card (the paper's explicit chip partitioning),
        # stage 2: across cards (the MPI level).  Source order differs from
        # the 1D gather but all-pairs summation is order-invariant.
        with jax.named_scope("collective.all_gather2"):
            x = jax.lax.all_gather(x, "chip", axis=0, tiled=True)
            return jax.lax.all_gather(x, "card", axis=0, tiled=True)

    @jax.jit
    @jax.shard_map(mesh=mesh, in_specs=(P(axes),) * 3,
                   out_specs=(P(axes),) * 4, check_vma=False)
    def eval_padded(pos, vel, mass):
        gp, gv, gm = gather2(pos), gather2(vel), gather2(mass)
        acc, jerk, pot = ops.acc_jerk_pot_rect(pos, vel, gp, gv, gm, **kw)
        if order >= 6:
            ga = gather2(acc)
            snp = ops.snap_rect(pos, vel, acc, gp, gv, ga, gm, **kw)
        else:
            snp = jnp.zeros_like(acc)
        return acc, jerk, snp, pot

    return _wrap(mesh, mesh.size, order, eval_padded)


# --------------------------------------------------------------------------
# Strategy 3 — mesh_sharded (Mesh-Based analogue; runtime-managed comms)
# --------------------------------------------------------------------------
def _mesh_sharded(mesh: Mesh, order: int, kw) -> Evaluator:
    """Sharded buffers for the targets, replicated buffers for the globally
    shared source data: the kernel regions are shard_mapped with replicated
    in_specs for the sources, so the runtime inserts the all-gathers (cf.
    TT-NN MeshDevice).  A Pallas kernel cannot be partitioned automatically,
    so the regions are shard_mapped rather than left to sharding
    constraints."""
    sh, rep = P(mesh.axis_names), P()

    @jax.shard_map(mesh=mesh, in_specs=(sh, sh, rep, rep, rep),
                   out_specs=(sh,) * 3, check_vma=False)
    def pass1(pt, vt, ps, vs, ms):
        return ops.acc_jerk_pot_rect(pt, vt, ps, vs, ms, **kw)

    @jax.shard_map(mesh=mesh, in_specs=(sh, sh, sh) + (rep,) * 4,
                   out_specs=sh, check_vma=False)
    def pass2(pt, vt, at, ps, vs, as_, ms):
        return ops.snap_rect(pt, vt, at, ps, vs, as_, ms, **kw)

    @jax.jit
    def eval_padded(pos, vel, mass):
        # the same arrays bound twice with different specs
        acc, jerk, pot = pass1(pos, vel, pos, vel, mass)
        if order >= 6:
            snp = pass2(pos, vel, acc, pos, vel, acc, mass)
        else:
            snp = jnp.zeros_like(acc)
        return acc, jerk, snp, pot

    return _wrap(mesh, mesh.size, order, eval_padded)


# --------------------------------------------------------------------------
# Strategy 4 — ring (beyond-paper systolic pipeline)
# --------------------------------------------------------------------------
def _ring_shift(axis_name: str, ring):
    """One systolic shift *round*: every source-window array hops one
    device along the ring.  Counted into the ``ring.shifts_issued`` metric
    at trace time (``rounds`` carries a fori_loop body's trip count, so the
    counter always reflects the rounds the traced program executes) — the
    collective-count assertion of the overlap tests pins the schedule
    through this counter."""

    def shift(arrays, rounds: int = 1):
        obs_metrics.registry().counter(
            "ring.shifts_issued", unit="rounds",
            help="source-shift ppermute rounds per traced ring pass",
        ).inc(rounds)
        with jax.named_scope("collective.ppermute"):
            return tuple(jax.lax.ppermute(a, axis_name, ring)
                         for a in arrays)

    return shift


def _ring_sweep(p, shift, ring_mode, init, src, compute):
    """Accumulate ``compute(src_k)`` over the ``p`` ring positions of the
    source window ``src`` (a tuple of arrays that hops one device per
    round); returns the accumulated output tuple.

    ``overlap`` (default): Python-unrolled double buffer — round ``k+1``'s
    source window is put in flight *before* round ``k``'s local kernels, so
    on hardware with async collectives the hop hides behind the local
    interaction block, and the final round issues no shift at all: exactly
    ``p - 1`` rounds per pass.  The accumulation order is untouched, so the
    result is bit-for-bit the synchronous schedule's.

    ``sync``: the pre-overlap baseline — a ``fori_loop`` whose body shifts
    after computing, every one of ``p`` iterations, so the last round's
    shifted window is computed and discarded (the dead collective round the
    overlap schedule eliminates).  Kept only as the measured baseline of
    the ``ring_overlap`` bench.
    """
    if ring_mode == "sync":

        def body(_, carry):
            acc, win = carry
            out = compute(win)
            acc = tuple(x + o for x, o in zip(acc, out))
            # body traces once but runs p rounds — count the trip count
            return (acc, shift(win, rounds=p))

        acc, _ = jax.lax.fori_loop(0, p, body, (init, src))
        return acc

    acc, win = init, src
    for k in range(p):
        # prefetch: next window in flight before this round's kernels
        nxt = shift(win) if k + 1 < p else None
        out = compute(win)
        acc = tuple(x + o for x, o in zip(acc, out))
        if nxt is not None:
            win = nxt
    return acc


def _ring(mesh: Mesh, order: int, kw, ring_mode: str = "overlap") -> Evaluator:
    axes = mesh.axis_names
    p = mesh.size
    ring = [(i, (i + 1) % p) for i in range(p)]
    shift = _ring_shift(axes[0], ring)

    @jax.jit
    @jax.shard_map(mesh=mesh, in_specs=(P(axes),) * 3,
                   out_specs=(P(axes),) * 4, check_vma=False)
    def eval_padded(pos, vel, mass):
        zeros3 = jnp.zeros_like(pos)
        zeros1 = jnp.zeros_like(mass)

        def aj(win):
            sp, sv, sm = win
            return ops.acc_jerk_pot_rect(pos, vel, sp, sv, sm, **kw)

        acc, jerk, pot = _ring_sweep(p, shift, ring_mode,
                                     (zeros3, zeros3, zeros1),
                                     (pos, vel, mass), aj)
        if order >= 6:

            def sn(win):
                sp, sv, sa, sm = win
                return (ops.snap_rect(pos, vel, acc, sp, sv, sa, sm, **kw),)

            (snp,) = _ring_sweep(p, shift, ring_mode, (zeros3,),
                                 (pos, vel, acc, mass), sn)
        else:
            snp = zeros3
        return acc, jerk, snp, pot

    return _wrap(mesh, p, order, eval_padded)


# --------------------------------------------------------------------------
# compaction-aware block evaluators (shard-local active-target gathering)
# --------------------------------------------------------------------------
# Distributed analogue of ``core.evaluate.make_block_evaluator``: every shard
# holds N/P target rows and an activity mask over them; with
# ``compaction="gather"`` each shard gathers its *local* active targets into
# a dense block-aligned buffer of one of a few static capacities
# (``ops.CapacityPlan`` at the local extent) and launches
# ``ceil(cap_local/BI) x N/BJ`` tiles instead of ``(N/P)/BI x N/BJ``.
#
# The bucket is selected per shard by a ``lax.switch`` on the shard-local
# active count.  Under SPMD every device traces the same program, but the
# switch operand is a runtime value, so shards genuinely diverge — one chip
# can take its smallest bucket while another syncs its whole domain.  That
# divergence is only sound because every branch is COLLECTIVE-FREE: the
# source gathers (explicit ``all_gather``/``ppermute`` or the runtime-
# inserted replication of mesh_sharded) are hoisted outside the switch, so
# all shards always execute the same collective sequence.
#
# The gather/scatter themselves are hoisted out of the switch too: the
# window of the LARGEST local capacity is gathered once, each branch runs
# the kernels on a static *prefix* of it (``window[:cap]``, zero-padding its
# output back to the window), and the one scatter happens after the switch.
# Semantically identical (rows past the chosen cap are inactive whenever the
# bucket bounds the active count, so their scattered output is exactly zero
# either way), it keeps the branch bodies to pure kernel launches — which
# both matches the Tensix picture (the host resizes the tile *grid*, not the
# data movement plan) and avoids exercising data-dependent gather/scatter
# under jit-of-shard_map branches, where jax 0.4.x CPU lowering was observed
# to miscompile (tests/test_strategy_compaction.py would catch it: the
# differential suite is bit-exact).


def _shard_plan(n_local: int, n_sources: int, kw, n_passes: int):
    """The local plan a shard builds from its own static shapes.

    Identical to ``global_plan.shard(P)`` of the host-side plan (the
    property suite asserts the equivalence) — in-shard code sees only the
    local extent, so it constructs the local plan directly.
    """
    return ops.CapacityPlan(n_local, n_sources, kw["block_i"], kw["block_j"],
                            n_passes=n_passes,
                            dtype=kw.get("dtype", "fp32"))


def _window_switch(cap_idx, caps, launch, window, extra=()):
    """``lax.switch`` over the capacity buckets: each branch runs
    ``launch`` on a static *prefix* of the pre-gathered target ``window``
    and zero-pads the output(s) back to the window extent.

    This is the one place the prefix-launch-and-pad invariant lives: rows
    past the chosen cap are inactive whenever the bucket bounds the active
    count, so their padded (and later scattered) output is exactly the
    masked result.  ``window`` and ``extra`` arrays ride as explicit switch
    operands, keeping every branch a pure function of its operands (see
    the module note on the jit-of-shard_map miscompile).
    """
    w = window[0].shape[0]
    n_win = len(window)

    def make_branch(cap: int):
        c = min(cap, w)

        def branch(*args):
            outs = launch(tuple(x[:c] for x in args[:n_win]), *args[n_win:])
            if not isinstance(outs, tuple):
                outs = (outs,)
            padded = tuple(
                jnp.pad(o, ((0, w - c),) + ((0, 0),) * (o.ndim - 1))
                for o in outs)
            return padded if len(padded) > 1 else padded[0]

        return branch

    return jax.lax.switch(cap_idx, [make_branch(c) for c in caps],
                          *window, *extra)


def _shard_pass1(pos, vel, ap, mask, perm, cap_idx, plan, kw, src, order):
    """Pass 1 on the compacted local targets: ``lax.switch`` over the local
    capacity buckets, each branch a pure kernel launch on a static window
    prefix.  Returns the scattered (acc, jerk, pot) plus the blended snap
    source operand (fresh acc on active rows, predicted elsewhere — the
    source-side compaction of the snap operand: the blend touches only the
    gathered window, never a dense intermediate)."""
    n_local = pos.shape[0]
    cap_max = plan.caps[-1]
    window = ops.compact_targets(perm, cap_max, pos, vel, mask)
    m_w = window[2]

    def launch(win, gp, gv, gm):
        p_c, v_c, m_c = win
        return ops.acc_jerk_pot_rect(p_c, v_c, gp, gv, gm, mask_t=m_c, **kw)

    a_w, j_w, pt_w = _window_switch(cap_idx, plan.caps, launch, window, src)
    acc, jerk, pot = ops.scatter_outputs(perm, cap_max, n_local,
                                         a_w, j_w, pt_w)
    acc_s = ops.scatter_sources(perm, cap_max, ap, a_w, m_w) \
        if order >= 6 else ap
    return acc, jerk, pot, acc_s


def _shard_pass2(pos, vel, acc, mask, perm, cap_idx, plan, kw, src, ga):
    """Snap pass on the compacted local targets (same bucket as pass 1);
    ``ga`` is the already-gathered blended source acceleration."""
    gp, gv, gm = src
    n_local = pos.shape[0]
    cap_max = plan.caps[-1]
    window = ops.compact_targets(perm, cap_max, pos, vel, acc, mask)

    def launch(win, gp, gv, ga, gm):
        p_c, v_c, a_c, m_c = win
        return ops.snap_rect(p_c, v_c, a_c, gp, gv, ga, gm,
                             mask_t=m_c, **kw)

    s_w = _window_switch(cap_idx, plan.caps, launch, window,
                         (gp, gv, ga, gm))
    (snp,) = ops.scatter_outputs(perm, cap_max, n_local, s_w)
    return snp


def _dense_pass1(pos, vel, ap, mask, kw, src, order):
    """The ``compaction="none"`` baseline: masked full-local-extent launch
    (inactive i-blocks are ``pl.when``-skipped but still enqueued)."""
    gp, gv, gm = src
    acc, jerk, pot = ops.acc_jerk_pot_rect(pos, vel, gp, gv, gm,
                                           mask_t=mask, **kw)
    acc_s = jnp.where(mask[:, None], acc, ap) if order >= 6 else ap
    return acc, jerk, pot, acc_s


def _shard_bucket(plan, bound):
    """Bucket index from the shard's ``(1,)`` active-count bound (clamped
    to the local extent, so an over-wide analytic bound still lands on the
    full-window bucket instead of out of range)."""
    with jax.named_scope("compaction.bucket_index"):
        return plan.bucket(jnp.minimum(bound[0], plan.caps[-1]))


def _shard_block_body(pos, vel, ap, mask, bound, src, *, kw, order,
                      compaction, n_passes):
    """Shared per-shard two-pass block evaluation against resident sources.

    ``bound`` is the shard's ``(1,)`` active-count bound — the measured
    local count, or the analytic ``hermite.block_level_occupancy`` bound
    the block engine schedules tiles from (host-side sizing: never below
    the true active count, so the bucket never underestimates).

    Returns (acc, jerk, snp, pot, acc_s, tiles) in the local layout; the
    caller supplies the gather of ``acc_s`` between the passes (the only
    collective the snap pass needs) via :func:`_resident_snap`.
    """
    n_local, n_src = pos.shape[0], src[0].shape[0]
    plan = _shard_plan(n_local, n_src, kw, n_passes)
    if compaction == "gather":
        perm = ops.active_first(mask)
        cap_idx = _shard_bucket(plan, bound)
        acc, jerk, pot, acc_s = _shard_pass1(pos, vel, ap, mask, perm,
                                             cap_idx, plan, kw, src, order)
        tiles = jnp.reshape(plan.tiles(cap_idx), (1,))
        return acc, jerk, pot, acc_s, (perm, cap_idx, plan), tiles
    acc, jerk, pot, acc_s = _dense_pass1(pos, vel, ap, mask, kw, src, order)
    tiles = jnp.full((1,), plan.dense_tiles, jnp.int32)
    return acc, jerk, pot, acc_s, None, tiles


def _resident_snap(pos, vel, acc, mask, src, ga, compacted, kw):
    """Dispatch the snap pass for strategies with resident full sources."""
    if compacted is not None:
        perm, cap_idx, plan = compacted
        return _shard_pass2(pos, vel, acc, mask, perm, cap_idx, plan, kw,
                            src, ga)
    return ops.snap_rect(pos, vel, acc, *src[:2], ga, src[2],
                         mask_t=mask, **kw)


def _wrap_block(p, eval_padded):
    """Pad N (and the activity mask/predicted acc) to a device multiple,
    evaluate, slice back.  Padding rows carry mask = False (never gathered
    as targets) and m = 0 (invisible as sources).

    ``n_bound`` (optional ``(P,)`` int32) is a per-shard active-count bound
    for the compaction bucket switch — the block engine passes the analytic
    ``hermite.block_level_occupancy`` bound over each shard's contiguous
    row chunk (host-side tile scheduling).  ``None`` falls back to the
    measured per-shard mask sum, which selects the identical bucket (the
    bound is exact for a schedule-consistent carry) — either way the
    chosen branch, and therefore the physics, is bit-for-bit unchanged."""

    def evaluate(pos, vel, acc_pred, mass, mask_t, n_bound=None):
        n = pos.shape[0]
        f32 = jnp.float32
        with jax.named_scope(ops.PACK):
            pos32 = jnp.asarray(pos, f32)
            vel32 = jnp.asarray(vel, f32)
            ap32 = jnp.asarray(acc_pred, f32)
            mass32 = jnp.asarray(mass, f32)
            mask = jnp.asarray(mask_t, bool)
            n_pad = _round_up(n, p)
            pp, vp, mp = _pad_particles(pos32, vel32, mass32, n_pad)
            app = jnp.pad(ap32, ((0, n_pad - n), (0, 0)))
            mk = jnp.pad(mask, ((0, n_pad - n),))
        if n_bound is None:
            bound = jnp.sum(mk.reshape(p, -1), axis=1).astype(jnp.int32)
        else:
            bound = jnp.asarray(n_bound, jnp.int32).reshape(p)
        acc, jerk, snp, pot, tiles = eval_padded(pp, vp, app, mp, mk, bound)
        with jax.named_scope(ops.PACK):
            return (Evaluation(acc[:n], jerk[:n], snp[:n], pot[:n]), tiles)

    return evaluate


def make_strategy_block_evaluator(
    strategy: str,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    chips_per_card: int = 2,
    eps: float = 1e-7,
    order: int = 6,
    impl: str = "xla",
    block_i: int = nbody_force.DEFAULT_BLOCK_I,
    block_j: int = nbody_force.DEFAULT_BLOCK_J,
    compaction: str = "none",
    dtype: str = "fp32",
    sources: str = "full",
    ring_mode: str = "overlap",
):
    """Distributed active-target evaluator for the block-timestep scheme.

    Signature of the returned callable::

        evaluate(pos, vel, acc_pred, mass, mask_t, n_bound=None) \
            -> (Evaluation, tiles)

    ``mask_t`` is the (N,) target-activity mask; ``acc_pred`` the predicted
    acceleration of every particle (the snap pass's source operand for
    inactive rows).  ``n_bound``, when given, is a ``(P,)`` host-side upper
    bound on each shard's active-target count — ``compaction="gather"``
    sizes its launch bucket from it instead of a runtime mask reduction
    (ROADMAP 5c host-side tile scheduling; the block scheme's analytic
    :func:`repro.core.hermite.block_level_occupancy` bound is *exact*, so
    the selected bucket — and hence the physics and tile count — is
    identical to the measured path).  ``None`` falls back to measuring.
    ``tiles`` is the ``(P,)`` vector of kernel grid tiles each shard
    enqueued for this event (both Hermite passes) — the per-shard launch
    cost telemetry reports, and the count ``compaction="gather"`` shrinks
    by gathering each shard's local active targets before launch.

    With an all-ones mask and ``compaction="none"`` this reduces to the
    lockstep :func:`make_strategy_evaluator` math; with ``"gather"`` the
    result is **bit-for-bit** the masked dense result of the same strategy
    (each target row is a row-local reduction over identical source blocks
    in identical order, whatever i-block it occupies — the same identity the
    single-device compaction rests on, locked by
    ``tests/test_strategy_compaction.py``).
    """
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown strategy {strategy!r}; one of {STRATEGIES}")
    if compaction not in COMPACTIONS:
        raise ValueError(
            f"compaction must be one of {COMPACTIONS}; got {compaction!r}")
    if sources not in ops.SOURCES:
        raise ValueError(f"sources must be one of {ops.SOURCES}; "
                         f"got {sources!r}")
    if sources == "neighbor":
        raise ValueError(
            "sources='neighbor' runs on the vmapped ensemble block engine "
            "(strategy='single'); the sharded strategies evaluate full "
            "sources only")
    if ring_mode not in RING_MODES:
        raise ValueError(
            f"ring_mode must be one of {RING_MODES}; got {ring_mode!r}")
    devs = np.asarray(devices if devices is not None else jax.devices())
    p = devs.size
    kw = _force_kw(impl, block_i, block_j, eps, dtype)
    n_passes = 2 if order >= 6 else 1

    if strategy == "two_level":
        if p % chips_per_card:
            raise ValueError(f"{p} devices not divisible by {chips_per_card=}")
        mesh = Mesh(devs.reshape(p // chips_per_card, chips_per_card),
                    ("card", "chip"))
        return _two_level_block(mesh, order, kw, compaction, n_passes)
    mesh = Mesh(devs.reshape(p), ("dev",))
    if strategy == "replicated":
        return _replicated_block(mesh, order, kw, compaction, n_passes)
    if strategy == "mesh_sharded":
        return _mesh_sharded_block(mesh, order, kw, compaction, n_passes)
    return _ring_block(mesh, order, kw, compaction, n_passes, ring_mode)


def _gathered_block(mesh, order, kw, compaction, n_passes, gather):
    """Shared body of replicated/two_level: explicit source gather(s), then
    the per-shard two-pass compacted evaluation."""
    axes = mesh.axis_names

    @jax.jit
    @jax.shard_map(mesh=mesh, in_specs=(P(axes),) * 6,
                   out_specs=(P(axes),) * 5, check_vma=False)
    def eval_padded(pos, vel, ap, mass, mask, bound):
        src = (gather(pos), gather(vel), gather(mass))
        acc, jerk, pot, acc_s, compacted, tiles = _shard_block_body(
            pos, vel, ap, mask, bound, src, kw=kw, order=order,
            compaction=compaction, n_passes=n_passes)
        if order >= 6:
            ga = gather(acc_s)  # the one collective between the switches
            snp = _resident_snap(pos, vel, acc, mask, src, ga, compacted, kw)
        else:
            snp = jnp.zeros_like(acc)
        return acc, jerk, snp, pot, tiles

    return _wrap_block(mesh.size, eval_padded)


def _replicated_block(mesh, order, kw, compaction, n_passes):
    axes = mesh.axis_names

    def gather(x):
        with jax.named_scope("collective.all_gather"):
            return jax.lax.all_gather(x, axes, axis=0, tiled=True)

    return _gathered_block(mesh, order, kw, compaction, n_passes, gather)


def _two_level_block(mesh, order, kw, compaction, n_passes):
    def gather2(x):
        with jax.named_scope("collective.all_gather2"):
            x = jax.lax.all_gather(x, "chip", axis=0, tiled=True)
            return jax.lax.all_gather(x, "card", axis=0, tiled=True)

    return _gathered_block(mesh, order, kw, compaction, n_passes, gather2)


def _mesh_sharded_block(mesh, order, kw, compaction, n_passes):
    """Runtime-managed comms: the kernel regions are shard_mapped with
    *replicated* in_specs for the source operands — the collective is implied
    by the spec (cf. TT-NN MeshDevice replicated buffers), never written.
    The partitioner places it at the ``shard_map`` boundary, which is
    scoped ``collective.implied``; the kernels and the compaction inside
    keep their own, deeper scopes."""
    axes = mesh.axis_names
    sh, rep = P(axes), P()

    @jax.shard_map(mesh=mesh, in_specs=(sh,) * 5 + (rep,) * 3,
                   out_specs=(sh,) * 5, check_vma=False)
    def pass1(pos, vel, ap, mask, bound, gp, gv, gm):
        acc, jerk, pot, acc_s, _, tiles = _shard_block_body(
            pos, vel, ap, mask, bound, (gp, gv, gm), kw=kw, order=order,
            compaction=compaction, n_passes=n_passes)
        return acc, jerk, pot, acc_s, tiles

    @jax.shard_map(mesh=mesh, in_specs=(sh,) * 5 + (rep,) * 4,
                   out_specs=sh, check_vma=False)
    def pass2(pos, vel, acc, mask, bound, gp, gv, ga, gm):
        src = (gp, gv, gm)
        n_local, n_src = pos.shape[0], gp.shape[0]
        plan = _shard_plan(n_local, n_src, kw, n_passes)
        if compaction == "gather":
            # same bucket as pass 1: the local active set did not change
            perm = ops.active_first(mask)
            cap_idx = _shard_bucket(plan, bound)
            return _shard_pass2(pos, vel, acc, mask, perm, cap_idx, plan,
                                kw, src, ga)
        return ops.snap_rect(pos, vel, acc, gp, gv, ga, gm,
                             mask_t=mask, **kw)

    @jax.jit
    def eval_padded(pos, vel, ap, mass, mask, bound):
        # targets arrive sharded, sources replicated — the same arrays bound
        # twice with different specs; the runtime inserts the all-gathers
        with jax.named_scope(IMPLIED_SCOPE):
            acc, jerk, pot, acc_s, tiles = pass1(pos, vel, ap, mask, bound,
                                                 pos, vel, mass)
        if order >= 6:
            with jax.named_scope(IMPLIED_SCOPE):
                snp = pass2(pos, vel, acc, mask, bound, pos, vel, acc_s,
                            mass)
        else:
            snp = jnp.zeros_like(acc)
        return acc, jerk, snp, pot, tiles

    return _wrap_block(mesh.size, eval_padded)


def _ring_block(mesh, order, kw, compaction, n_passes,
                ring_mode: str = "overlap"):
    """Systolic ring with shard-local compaction: the compacted local target
    block meets every streamed source shard, so the switch sits *inside* the
    loop body (pure local work per branch) while the ``ppermute`` shifts stay
    outside it — every shard runs the same collective schedule whatever
    bucket it took.  The shift schedule itself is :func:`_ring_sweep`'s:
    double-buffered prefetch (``p - 1`` rounds) by default, the legacy
    synchronous loop as the bench baseline."""
    axes = mesh.axis_names
    p = mesh.size
    ring = [(i, (i + 1) % p) for i in range(p)]
    shift = _ring_shift(axes[0], ring)

    @jax.jit
    @jax.shard_map(mesh=mesh, in_specs=(P(axes),) * 6,
                   out_specs=(P(axes),) * 5, check_vma=False)
    def eval_padded(pos, vel, ap, mass, mask, bound):
        n_local = pos.shape[0]
        # each of the n_passes sweeps launches once per streamed shard
        plan = _shard_plan(n_local, n_local, kw, n_passes * p)
        zeros3 = jnp.zeros_like(pos)
        zeros1 = jnp.zeros_like(mass)

        if compaction == "gather":
            # window gathered ONCE, outside the source loop: the systolic
            # stream rotates sources, the compacted target block is fixed,
            # and partial sums accumulate in the window layout (same adds,
            # one scatter at the end)
            perm = ops.active_first(mask)
            cap_idx = _shard_bucket(plan, bound)
            tiles = jnp.reshape(plan.tiles(cap_idx), (1,))
            cap_max = plan.caps[-1]
            window = ops.compact_targets(perm, cap_max, pos, vel, mask)
            m_w = window[2]
            w = window[0].shape[0]

            def launch1(win, sp, sv, sm):
                p_c, v_c, m_c = win
                return ops.acc_jerk_pot_rect(p_c, v_c, sp, sv, sm,
                                             mask_t=m_c, **kw)

            # zeros_like keeps the accumulators varying over the ring axis,
            # the type the loop carries them at
            zw3 = jnp.zeros_like(window[0])
            zw1 = jnp.zeros_like(m_w, jnp.float32)
            a_w, j_w, pt_w = _ring_sweep(
                p, shift, ring_mode, (zw3, zw3, zw1), (pos, vel, mass),
                lambda src: _window_switch(cap_idx, plan.caps, launch1,
                                           window, src))
            acc, jerk, pot = ops.scatter_outputs(perm, cap_max, n_local,
                                                 a_w, j_w, pt_w)

            if order >= 6:
                # blended snap-source operand via the window (source-side
                # compaction); a_w already holds the summed fresh acc
                acc_s = ops.scatter_sources(perm, cap_max, ap, a_w, m_w)
                snap_window = window[:2] + (a_w, m_w)

                def launch2(win, sp, sv, sa, sm):
                    p_c, v_c, a_c, m_c = win
                    return ops.snap_rect(p_c, v_c, a_c, sp, sv, sa, sm,
                                         mask_t=m_c, **kw)

                (s_w,) = _ring_sweep(
                    p, shift, ring_mode, (zw3,), (pos, vel, acc_s, mass),
                    lambda src: (_window_switch(cap_idx, plan.caps, launch2,
                                                snap_window, src),))
                (snp,) = ops.scatter_outputs(perm, cap_max, n_local, s_w)
            else:
                snp = zeros3
            return acc, jerk, snp, pot, tiles

        tiles = jnp.full((1,), plan.dense_tiles, jnp.int32)

        def aj(src):
            sp, sv, sm = src
            return ops.acc_jerk_pot_rect(pos, vel, sp, sv, sm,
                                         mask_t=mask, **kw)

        acc, jerk, pot = _ring_sweep(p, shift, ring_mode,
                                     (zeros3, zeros3, zeros1),
                                     (pos, vel, mass), aj)
        if order >= 6:
            acc_s = jnp.where(mask[:, None], acc, ap)

            def sn(src):
                sp, sv, sa, sm = src
                return (ops.snap_rect(pos, vel, acc, sp, sv, sa, sm,
                                      mask_t=mask, **kw),)

            (snp,) = _ring_sweep(p, shift, ring_mode, (zeros3,),
                                 (pos, vel, acc_s, mass), sn)
        else:
            snp = zeros3
        return acc, jerk, snp, pot, tiles

    return _wrap_block(mesh.size, eval_padded)
