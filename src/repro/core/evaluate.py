"""Evaluator factories: the FP32 force-evaluation stage of the Hermite loop.

``make_block_evaluator`` is the single implementation body: an active-target
evaluator (per-target activity mask, sources stay full) with an optional
**compaction** layer that gathers the active targets into a dense,
block-aligned buffer before launching the kernels.  ``make_evaluator`` — the
lockstep evaluator used by the fixed/adaptive paths and the paper's one-chip
configuration — is the all-ones-mask special case of the same body (pinned
exact by ``test_mask_all_ones_is_identity``).  The multi-device strategies
live in ``repro.core.strategies`` and share the ``Evaluator`` signature.

Compaction (``compaction="gather"``): at each call the active targets are
gathered (via a caller-supplied permutation putting active rows first) into
a buffer of one of a few static capacities (``ops.capacity_buckets``), both
kernels run on a ``ceil(cap/BI) x N/BJ`` grid instead of ``N/BI x N/BJ``,
and the outputs scatter back to particle slots.  The capacity bucket is
picked by a traced index dispatched through ``lax.switch`` over pre-lowered
instances, so XLA only ever sees static shapes; under ``jax.vmap`` the
caller must pass the bucket index *unbatched* (``in_axes=None`` — e.g. the
max active count across the batch) so the switch stays a real branch instead
of degrading to an execute-all-branches select.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.hermite import Evaluation, Evaluator
from repro.kernels import nbody_force, ops

#: compaction modes of the block evaluator
COMPACTIONS = ("none", "gather")


def shared_cap_index(plan: ops.CapacityPlan, bounds) -> jax.Array:
    """Unbatched capacity-bucket index shared by a group of members.

    ``lax.switch`` must see an *unbatched* operand under ``jax.vmap`` to
    stay a real branch (a batched index degrades to an execute-all-branches
    select), so the ensemble engine's bucket groups, which dispatch one
    switch for many members, share the max of the members' active-count
    ``bounds`` (any shape; flattened).  Sound because a shared
    cap bounds every member's own count: gathered window rows past a
    member's active set are mask-zeroed by the kernels, so the scattered
    result is bit-for-bit the per-member bucket's — only the launch grid
    widens.  The bound is clamped to the plan's widest bucket, so an
    over-wide analytic bound (e.g. ``hermite.block_level_occupancy`` over
    rows that include padding) lands on the full-window bucket instead of
    out of range.
    """
    with jax.named_scope("compaction.bucket_index"):
        bound = jnp.max(jnp.asarray(bounds, jnp.int32).reshape(-1))
        return plan.bucket(jnp.minimum(bound, plan.caps[-1]))


def _rect_passes(*, eps, impl, block_i, block_j, precision, dtype):
    """The two Hermite passes in rectangular (targets x sources) form with
    the activity mask applied — the only layer that differs between the
    FP32 kernels and the FP64 oracle.  Shared by the full-source block
    evaluator and the Ahmad-Cohen neighbor-window evaluator; returns
    ``(cast, rect1, rect2)``."""
    if dtype is None:
        dtype = "fp64" if precision == "fp64" else "fp32"
    if dtype not in ops.DTYPES:
        raise ValueError(f"dtype must be one of {ops.DTYPES}; got {dtype!r}")
    if dtype == "fp64" or precision == "fp64":
        from repro.kernels import ref

        def cast(x):
            return jnp.asarray(x)

        def rect1(pt, vt, ps, vs, m, mask_c):
            acc, jerk, pot = ref.acc_jerk_pot_rect(pt, vt, ps, vs, m, eps=eps)
            m3 = mask_c[:, None]
            return (jnp.where(m3, acc, 0.0), jnp.where(m3, jerk, 0.0),
                    jnp.where(mask_c, pot, 0.0))

        def rect2(pt, vt, at, ps, vs, as_, m, mask_c):
            snp = ref.snap_rect(pt, vt, at, ps, vs, as_, m, eps=eps)
            return jnp.where(mask_c[:, None], snp, 0.0)
    else:
        impl_ = impl or ops.default_impl()
        kw = dict(eps=eps, block_i=block_i, block_j=block_j, impl=impl_,
                  dtype=dtype)

        def cast(x):
            return jnp.asarray(x, jnp.float32)

        def rect1(pt, vt, ps, vs, m, mask_c):
            return ops.acc_jerk_pot_rect(pt, vt, ps, vs, m, mask_t=mask_c,
                                         **kw)

        def rect2(pt, vt, at, ps, vs, as_, m, mask_c):
            return ops.snap_rect(pt, vt, at, ps, vs, as_, m, mask_t=mask_c,
                                 **kw)

    return cast, rect1, rect2


def make_block_evaluator(
    *,
    eps: float = 1e-7,
    order: int = 6,
    impl: Optional[str] = None,
    block_i: int = nbody_force.DEFAULT_BLOCK_I,
    block_j: int = nbody_force.DEFAULT_BLOCK_J,
    precision: str = "fp32",  # "fp32" (paper device precision) | "fp64" golden
    compaction: str = "none",
    n_caps: Optional[int] = None,
    dtype: Optional[str] = None,
):
    """Active-target evaluator for the hierarchical block-timestep scheme.

    Pass 1 computes acc/jerk/potential *on the active targets only* (sources
    stay full).  The 6th-order snap pass needs the acceleration of every
    source at the current time; inactive sources were not evaluated, so
    their Taylor-predicted acceleration ``acc_pred`` (Nitadori & Makino 2008
    j-particle predictor) substitutes — active sources use the fresh pass-1
    value.  With an all-ones mask this reduces exactly to the lockstep
    evaluator (evaluated accelerations are used everywhere).

    Signatures by ``compaction``:

    * ``"none"`` — ``evaluate(pos, vel, acc_pred, mass, mask_t)``: the dense
      masked launch (inactive i-blocks are ``pl.when``-skipped but their
      tiles are still enqueued).
    * ``"gather"`` — ``evaluate(pos, vel, acc_pred, mass, mask_t, perm,
      cap_idx)``: ``perm`` orders active targets first
      (``ops.active_first``), ``cap_idx`` selects the static capacity bucket
      (``ops.capacity_buckets(n, block_i)``) — it must bound the true active
      count, and must be unbatched under ``vmap``.  Output is bit-for-bit
      the ``"none"`` result: each target row is a row-local reduction over
      identical source blocks in identical order, whatever i-block it
      occupies.

    ``n_caps`` (gather mode only) truncates the capacity schedule to its
    first ``n_caps`` buckets — the *bucket group* of callers whose active
    count provably never exceeds ``caps[n_caps-1]`` (a mixed batch groups
    members by their static ``n_active`` ceiling; see
    ``ops.CapacityPlan.restrict``).  ``cap_idx`` then indexes the truncated
    schedule, and only those buckets are ever lowered.

    ``precision="fp64"`` is the golden-reference mode (pure-jnp oracle at
    host precision, no kernel) used for validation and convergence tests;
    it supports both compaction modes through the same gather/scatter path.

    ``dtype`` is the full precision axis (``ops.DTYPES``): ``"fp64"`` is a
    synonym for ``precision="fp64"``, ``"fp32"`` the historical kernel
    path, and ``"mixed"`` the Tensix-fidelity reduced-precision mode
    (bfloat16 per-pair arithmetic, compensated fp32 accumulation) in both
    kernel implementations.  ``dtype=None`` defers to ``precision`` so
    existing callers are untouched.
    """
    if compaction not in COMPACTIONS:
        raise ValueError(
            f"compaction must be one of {COMPACTIONS}; got {compaction!r}")
    cast, rect1, rect2 = _rect_passes(eps=eps, impl=impl, block_i=block_i,
                                      block_j=block_j, precision=precision,
                                      dtype=dtype)

    if compaction == "none":

        def evaluate(pos, vel, acc_pred, mass, mask_t) -> Evaluation:
            with jax.named_scope(ops.PACK):
                p, v, m = cast(pos), cast(vel), cast(mass)
            acc, jerk, pot = rect1(p, v, p, v, m, mask_t)
            if order >= 6:
                with jax.named_scope("compaction.scatter_sources"):
                    acc_s = jnp.where(mask_t[:, None], acc, cast(acc_pred))
                snp = rect2(p, v, acc, p, v, acc_s, m, mask_t)
            else:
                snp = jnp.zeros_like(acc)
            return Evaluation(acc=acc, jerk=jerk, snap=snp, pot=pot)

        return evaluate

    def evaluate_gather(pos, vel, acc_pred, mass, mask_t, perm,
                        cap_idx) -> Evaluation:
        n = pos.shape[0]
        caps = ops.capacity_buckets(n, block_i)
        if n_caps is not None:
            caps = caps[: min(n_caps, len(caps))]
        with jax.named_scope(ops.PACK):
            p, v, m, ap = cast(pos), cast(vel), cast(mass), cast(acc_pred)

        def make_branch(cap: int):
            def branch(p, v, ap, m, mask_t, perm) -> Evaluation:
                p_c, v_c, mask_c = ops.compact_targets(perm, cap,
                                                       p, v, mask_t)
                acc_c, jerk_c, pot_c = rect1(p_c, v_c, p, v, m, mask_c)
                acc, jerk, pot = ops.scatter_outputs(perm, cap, n,
                                                     acc_c, jerk_c, pot_c)
                if order >= 6:
                    # source-side compaction: the compacted fresh rows are
                    # scattered straight into the predicted-acc operand —
                    # bit-for-bit where(mask, acc, ap) without the dense
                    # intermediate blend
                    acc_s = ops.scatter_sources(perm, cap, ap, acc_c, mask_c)
                    snp_c = rect2(p_c, v_c, acc_c, p, v, acc_s, m, mask_c)
                    (snp,) = ops.scatter_outputs(perm, cap, n, snp_c)
                else:
                    snp = jnp.zeros_like(acc)
                return Evaluation(acc=acc, jerk=jerk, snap=snp, pot=pot)

            return branch

        # the switch's index clamp belongs to the bucket choice
        with jax.named_scope("compaction.bucket_index"):
            return jax.lax.switch(cap_idx, [make_branch(c) for c in caps],
                                  p, v, ap, m, mask_t, perm)

    return evaluate_gather


def make_neighbor_block_evaluator(
    *,
    n: int,
    eps: float = 1e-7,
    impl: Optional[str] = None,
    block_i: int = nbody_force.DEFAULT_BLOCK_I,
    block_j: int = nbody_force.DEFAULT_BLOCK_J,
    precision: str = "fp32",
    dtype: Optional[str] = None,
):
    """Near-window (regular-force) evaluator of the Ahmad-Cohen split.

    The source-axis dual of :func:`make_block_evaluator`'s compaction: the
    *targets* stay dense (every block launches — the activity mask handles
    inactive rows), but each target block sweeps only its gathered window
    of neighbor source blocks (``kernels.neighbor.build_windows``) instead
    of the full source extent.  The window capacity is one of the plan's
    static ``source_caps`` buckets, dispatched through ``lax.switch`` —
    ``w_idx`` must bound every live window count and, under ``vmap``, must
    be unbatched (``in_axes=None``), exactly like the target-side
    ``cap_idx``.  The last bucket is the full padded source extent, so an
    overflowing window dispatches the exact all-pairs sweep.

    Returns ``(near1, near2)``::

        near1(pos, vel, mass, mask_t, win_idx, win_cnt, w_idx)
            -> (acc, jerk, pot)                     # near-field only
        near2(pos, vel, acc_t, acc_s, mass, mask_t, win_idx, win_cnt, w_idx)
            -> snap                                 # near-field only

    ``acc_t`` is the *total* (near + far) acceleration of the targets and
    ``acc_s`` the total acceleration of every source row — the snap term
    depends on both particles' full accelerations even when only the near
    pairs are summed.  Window slots past ``win_cnt`` gather with their mass
    zeroed, so by the kernels' mask contract they contribute exactly zero:
    growing a shared bucket only appends exact zeros to each row's
    reduction tail.
    """
    cast, rect1, rect2 = _rect_passes(eps=eps, impl=impl, block_i=block_i,
                                      block_j=block_j, precision=precision,
                                      dtype=dtype)
    nbt = -(-n // block_i)
    nsb = -(-n // block_j)
    nt_pad, ns_pad = nbt * block_i, nsb * block_j
    # window capacities in source *blocks* per target block
    w_caps = tuple(c // block_j for c in ops.capacity_buckets(n, block_j))

    def _blocks(x, nb, block, rows):
        pad = ((0, rows - n),) + ((0, 0),) * (x.ndim - 1)
        return jnp.pad(x, pad).reshape((nb, block) + x.shape[1:])

    def _blocks_t(x):
        return _blocks(x, nbt, block_i, nt_pad)

    def _blocks_s(x):
        return _blocks(x, nsb, block_j, ns_pad)

    def _unblock(x):
        return x.reshape((nt_pad,) + x.shape[2:])[:n]

    def _gather(win_idx, win_cnt, w, sm, *blocks):
        """First ``w`` window entries of every target block, flattened to
        (nbt, w*block_j, ...); slots past ``win_cnt`` zero their mass."""
        idx = win_idx[:, :w]
        val = jnp.arange(w)[None, :] < win_cnt[:, None]
        gm = jnp.where(val[..., None], sm[idx], 0.0)
        flat = [gm.reshape(nbt, w * block_j)]
        for b in blocks:
            g = b[idx]
            flat.append(g.reshape((nbt, w * block_j) + g.shape[3:]))
        return flat

    def near1(pos, vel, mass, mask_t, win_idx, win_cnt, w_idx):
        p, v, m = cast(pos), cast(vel), cast(mass)
        tm = _blocks_t(jnp.asarray(mask_t, bool))
        tp, tv = _blocks_t(p), _blocks_t(v)
        sp, sv, sm = _blocks_s(p), _blocks_s(v), _blocks_s(m)

        def make_branch(w: int):
            def branch(tp, tv, tm, sp, sv, sm, win_idx, win_cnt):
                gm, gp, gv = _gather(win_idx, win_cnt, w, sm, sp, sv)
                return jax.vmap(rect1)(tp, tv, gp, gv, gm, tm)

            return branch

        acc, jerk, pot = jax.lax.switch(
            w_idx, [make_branch(w) for w in w_caps],
            tp, tv, tm, sp, sv, sm, win_idx, win_cnt)
        return _unblock(acc), _unblock(jerk), _unblock(pot)

    def near2(pos, vel, acc_t, acc_s, mass, mask_t, win_idx, win_cnt, w_idx):
        p, v, m = cast(pos), cast(vel), cast(mass)
        at, as_ = cast(acc_t), cast(acc_s)
        tm = _blocks_t(jnp.asarray(mask_t, bool))
        tp, tv, ta = _blocks_t(p), _blocks_t(v), _blocks_t(at)
        sp, sv, sa, sm = (_blocks_s(p), _blocks_s(v), _blocks_s(as_),
                          _blocks_s(m))

        def make_branch(w: int):
            def branch(tp, tv, ta, tm, sp, sv, sa, sm, win_idx, win_cnt):
                gm, gp, gv, ga = _gather(win_idx, win_cnt, w, sm, sp, sv, sa)
                return jax.vmap(rect2)(tp, tv, ta, gp, gv, ga, gm, tm)

            return branch

        snp = jax.lax.switch(
            w_idx, [make_branch(w) for w in w_caps],
            tp, tv, ta, tm, sp, sv, sa, sm, win_idx, win_cnt)
        return _unblock(snp)

    return near1, near2


def make_evaluator(
    *,
    eps: float = 1e-7,
    order: int = 6,
    impl: Optional[str] = None,
    block_i: int = nbody_force.DEFAULT_BLOCK_I,
    block_j: int = nbody_force.DEFAULT_BLOCK_J,
    precision: str = "fp32",  # "fp32" (paper device precision) | "fp64" golden
    dtype: Optional[str] = None,
) -> Evaluator:
    """Single-device lockstep evaluator (Pallas kernel or XLA fallback).

    The all-ones-mask specialization of :func:`make_block_evaluator` — the
    identity the block stepper degenerates to in lockstep, pinned exact by
    ``test_mask_all_ones_is_identity`` (the kernel's activity column is 1.0
    either way, so the packed operands are bitwise identical).  The blended
    snap-source acceleration reduces to the fresh pass-1 value everywhere,
    so the zero ``acc_pred`` placeholder is never read.

    ``precision="fp64"`` is the golden-reference mode (pure-jnp oracle at
    host precision, no kernel) used for validation and convergence tests.
    """
    block_eval = make_block_evaluator(
        eps=eps, order=order, impl=impl, block_i=block_i, block_j=block_j,
        precision=precision, dtype=dtype)

    def evaluate(pos, vel, mass) -> Evaluation:
        mask = jnp.ones(pos.shape[0], bool)
        return block_eval(pos, vel, jnp.zeros_like(pos), mass, mask)

    return evaluate
