"""Batched ensemble engine: B independent simulations in one traced loop.

Independent runs are stacked on a leading batch axis of every
``ParticleState`` leaf and advanced in lockstep: the full Hermite
predict-evaluate-correct step is lifted over the batch with ``jax.vmap``,
the step loop is a single ``lax.scan``, and the batch axis carries a
sharding constraint over a 1-D device mesh (built by
``repro.core.strategies.make_batch_mesh``), so many small-N runs fill the
hardware the way one large-N run does.

Because the runs are independent there is *no cross-run communication*: all
of the paper's distribution strategies coincide on the batch axis (the
strategy label is accepted for CLI symmetry and recorded in telemetry).

**Kernels.** Per-run force evaluation routes through either the reference
all-pairs op (``kernel="ref"``, i.e. ``impl="xla"``), the tiled Pallas
kernel (``kernel="pallas"`` — compiled on TPU, ``interpret=True`` elsewhere;
``pallas_call`` is vmap-safe, the batch axis simply prepends a grid
dimension), or the FP64 golden reference (``impl="fp64"``).

**Steppers.** Three timestep modes share the engine: fixed dt
(``ensemble_run``), per-run shared-adaptive Aarseth lockstep
(``ensemble_run_adaptive``), and hierarchical block timesteps
(``ensemble_run_block``) — per-particle power-of-two levels inside each
member, only the active block evaluated per substep, measured per-run
force-evaluation and grid-tile counts returned for telemetry.  The block
stepper's ``compaction="gather"`` mode additionally gathers each event's
active targets into a dense block-aligned buffer (static capacity buckets,
``lax.switch``-dispatched) so the kernel grid *shrinks* to the live block
instead of masking it — bit-for-bit identical physics, far fewer tiles
launched (see ``core.evaluate.make_block_evaluator``).

**Masking (ragged batches).** Heterogeneous mixes are packed by
``repro.sim.scenarios.build_padded`` into a rectangular ``(B, N_max, ...)``
batch plus a per-run ``n_active`` vector.  Rows ``>= n_active[b]`` are
padding: zero mass makes them invisible as force *sources* (a kernel
invariant, property-tested), and the engine's per-member mask zeroes their
evaluated derivatives so they are inert as *targets* — frozen in place, with
no influence on the per-run Aarseth timestep (zero acc/jerk/snap falls into
the ``num > 0`` guard) nor on mass-weighted energy diagnostics.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import hermite, nbody
from repro.core.evaluate import (make_block_evaluator, make_evaluator,
                                 make_neighbor_block_evaluator,
                                 shared_cap_index)
from repro.core.hermite import Evaluation
from repro.core.nbody import ParticleState
from repro.core.strategies import STRATEGIES, make_batch_mesh
from repro.kernels import nbody_force, neighbor, ops
from repro.obs import metrics as obs_metrics

BATCH_AXIS = "ensemble"
#: vmap-safe evaluation paths (the Pallas kernel batches by grid extension)
ENSEMBLE_IMPLS = ("xla", "fp64", "pallas", "pallas_interpret")
#: user-facing force-kernel switch: "ref" (all-pairs XLA op) | "pallas"
KERNELS = ("ref", "pallas")
#: stepper modes of the ensemble engine (see docs/ensembles.md)
STEPPERS = ("fixed", "adaptive", "block")


def resolve_kernel(kernel: Optional[str]) -> str:
    """Map the user-facing ``kernel`` switch to an evaluation ``impl``.

    ``"ref"`` is the blocked all-pairs XLA op; ``"pallas"`` is the tiled
    kernel — compiled where Mosaic can lower (TPU), interpreted elsewhere so
    the same kernel body is validated on CPU.
    """
    if kernel in (None, "ref"):
        return "xla"
    if kernel == "pallas":
        return ops.default_impl()
    raise ValueError(f"unknown kernel {kernel!r}; one of {KERNELS}")


def resolve_eval_impl(impl: Optional[str], kernel: Optional[str], *,
                      default: Optional[str] = "xla") -> Optional[str]:
    """Resolve the (``impl``, ``kernel``) pair to one evaluation impl.

    The user-facing ``kernel`` switch and the low-level ``impl`` are
    mutually exclusive when both are explicit: silently preferring one
    could e.g. turn a requested ``impl="fp64"`` golden-reference run into
    FP32 with no trace in the report.
    """
    if kernel is not None:
        if impl is not None:
            raise ValueError(
                f"pass either impl={impl!r} or kernel={kernel!r}, not both")
        return resolve_kernel(kernel)
    return impl if impl is not None else default


# --------------------------------------------------------------------------
# batch packing
# --------------------------------------------------------------------------
def stack_states(states: Sequence[ParticleState]) -> ParticleState:
    """Pack independent runs (same N) into one leading-batch-axis state."""
    if not states:
        raise ValueError("need at least one state")
    ns = {s.pos.shape[0] for s in states}
    if len(ns) != 1:
        raise ValueError(f"all ensemble members must share N; got {ns}")
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def unstack_states(batched: ParticleState) -> List[ParticleState]:
    b = batch_size(batched)
    return [jax.tree_util.tree_map(lambda x: x[i], batched) for i in range(b)]


def batch_size(batched: ParticleState) -> int:
    return batched.pos.shape[0]


def batched_total_energy(batched: ParticleState) -> jax.Array:
    """(B,) total energy per ensemble member.

    Mass-weighted, so zero-mass padding rows contribute nothing — padded and
    unpadded batches of the same runs report identical energies.
    """
    return jax.vmap(nbody.total_energy)(batched)


def batched_virial_ratio(batched: ParticleState) -> jax.Array:
    """(B,) virial ratio T/|U| per member (mass-weighted: padding-blind)."""
    t = jax.vmap(nbody.kinetic_energy)(batched)
    u = jax.vmap(nbody.potential_energy)(batched)
    tiny = jnp.asarray(jnp.finfo(t.dtype).tiny, t.dtype)  # fp32-safe clamp
    return t / jnp.maximum(jnp.abs(u), tiny)


# --------------------------------------------------------------------------
# engine
# --------------------------------------------------------------------------
def _inner_evaluator(order: int, eps: float, impl: str, dtype: str = "fp32"):
    if impl == "fp64" and dtype == "mixed":
        raise ValueError("impl='fp64' conflicts with dtype='mixed' — the "
                         "oracle path has no reduced-precision mode")
    if impl == "fp64" or dtype == "fp64":
        return make_evaluator(precision="fp64", order=order, eps=eps)
    if impl not in ENSEMBLE_IMPLS:
        raise ValueError(
            f"ensemble impl must be one of {ENSEMBLE_IMPLS} (the vmappable "
            f"evaluation paths); got {impl!r}")
    return make_evaluator(order=order, eps=eps, impl=impl, dtype=dtype)


def _mask_evaluator(ev, n_active):
    """Zero the evaluated derivatives of padding rows (>= ``n_active``).

    Sources with m = 0 already contribute zero force (kernel invariant);
    masking the *outputs* additionally freezes padding rows as targets, so
    they never drift into the active set and never tighten the per-run
    Aarseth timestep.  With ``n_active == N`` the mask is all-ones and the
    multiply is an exact identity.
    """

    def evaluate(pos, vel, mass) -> Evaluation:
        out = ev(pos, vel, mass)
        active = jnp.arange(pos.shape[0]) < n_active
        m3 = active.astype(out.acc.dtype)[:, None]
        return Evaluation(acc=out.acc * m3, jerk=out.jerk * m3,
                          snap=out.snap * m3,
                          pot=out.pot * active.astype(out.pot.dtype))

    return evaluate


def _constrain(tree, mesh):
    """Shard the leading (batch) axis of every leaf over the mesh."""
    if mesh is None:
        return tree

    def one(x):
        axes = [BATCH_AXIS] + [None] * (x.ndim - 1)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*axes)))

    return jax.tree_util.tree_map(one, tree)


def _batch_map(fn, mesh, in_axes=0):
    """``vmap(fn, in_axes)`` over the batch axis; on a mesh, inside a
    ``shard_map`` over that axis, so each device runs its own members.  A
    Pallas kernel cannot be partitioned through sharding constraints.  A
    batch that does not divide over the mesh (one bucket group of the block
    engine) is padded by repeating its first member, and the padding is
    dropped."""
    fn = jax.vmap(fn, in_axes=in_axes)
    if mesh is None:
        return fn
    axes = in_axes if isinstance(in_axes, tuple) else None
    specs = tuple(P(BATCH_AXIS) if a == 0 else P() for a in axes) \
        if axes else P(BATCH_AXIS)
    sharded = jax.shard_map(fn, mesh=mesh, in_specs=specs,
                            out_specs=P(BATCH_AXIS), check_vma=False)
    extent = mesh.shape[BATCH_AXIS]

    def call(*args):
        mapped = [axes is None or axes[i] == 0 for i in range(len(args))]
        b = jax.tree_util.tree_leaves(args[mapped.index(True)])[0].shape[0]
        if b % extent == 0:
            return sharded(*args)
        args = tuple(_pad_batch(a, extent)[0] if m else a
                     for a, m in zip(args, mapped))
        return jax.tree_util.tree_map(lambda x: x[:b], sharded(*args))

    return call


def _count_engine_build(kind: str) -> None:
    """Emit one ``engine.cache_miss`` tick into the current metrics registry.

    Every engine constructor below is ``lru_cache``d, so its body only runs
    when a (config, mesh, groups) key has never been lowered before — the
    counter IS the recompile count the observability layer reports, with no
    tracing-internals spelunking.
    """
    reg = obs_metrics.registry()
    reg.counter("engine.cache_miss", unit="builds",
                help="engine constructions = fresh XLA lowerings").inc()
    reg.counter(f"engine.cache_miss.{kind}", unit="builds").inc()


@functools.lru_cache(maxsize=64)
def _engine(order: int, eps: float, impl: str, mesh, dtype: str):
    _count_engine_build("fixed")
    ev = _inner_evaluator(order, eps, impl, dtype)

    init_all = _batch_map(
        lambda s, na: hermite.initialize(s, _mask_evaluator(ev, na)), mesh)
    step_all = _batch_map(
        lambda m, na, dt: hermite.step(m, dt.astype(m.dtype),
                                       _mask_evaluator(ev, na), order=order),
        mesh, in_axes=(0, 0, None))

    @jax.jit
    def init(batched: ParticleState, n_active) -> ParticleState:
        batched, n_active = _constrain((batched, n_active), mesh)
        return _constrain(init_all(batched, n_active), mesh)

    @functools.partial(jax.jit, static_argnames=("n_steps",))
    def run(batched: ParticleState, n_active, dt, n_steps: int
            ) -> ParticleState:
        batched, n_active = _constrain((batched, n_active), mesh)

        def body(s, _):
            return _constrain(step_all(s, n_active, dt), mesh), None

        out, _ = jax.lax.scan(body, batched, None, length=n_steps)
        return out

    return init, run


def _pad_batch(tree, p: int):
    """Pad B to a multiple of the device count by repeating the first run.

    Works on any pytree whose leaves carry the batch on the leading axis
    (a ParticleState, or a tuple of per-run carries).
    """
    b = jax.tree_util.tree_leaves(tree)[0].shape[0]
    if p <= 1 or b % p == 0:
        return tree, b
    pad = p - b % p
    padded = jax.tree_util.tree_map(
        lambda x: jnp.concatenate([x, jnp.repeat(x[:1], pad, axis=0)]),
        tree)
    return padded, b


def _batch_mesh(devices) -> Optional[object]:
    if devices is None:
        return None
    devices = list(devices)
    if len(devices) <= 1:
        return None
    return make_batch_mesh(devices, axis_name=BATCH_AXIS)


def _as_n_active(batched: ParticleState, n_active) -> jax.Array:
    """Normalize ``n_active`` to a (B,) int32 vector (default: all active)."""
    b, n = batched.pos.shape[0], batched.pos.shape[1]
    if n_active is None:
        return jnp.full((b,), n, jnp.int32)
    n_active = jnp.asarray(n_active, jnp.int32)
    if n_active.shape != (b,):
        raise ValueError(
            f"n_active must have shape ({b},) for a B={b} batch; "
            f"got {n_active.shape}")
    return n_active


def _as_t_end(batched: ParticleState, t_end) -> jax.Array:
    """Normalize ``t_end`` to a (B,) vector in the state dtype.

    A scalar broadcasts to every member (bit-identical to the historical
    shared-deadline behaviour — the per-member subtraction ``t_end - time``
    sees the same value either way); a vector gives each member its own
    deadline, which is how the serving layer freezes retired slots without
    perturbing — or recompiling for — their batch-mates.
    """
    b = batch_size(batched)
    t = jnp.asarray(t_end, batched.pos.dtype)
    if t.ndim == 0:
        return jnp.full((b,), t, batched.pos.dtype)
    if t.shape != (b,):
        raise ValueError(
            f"t_end must be a scalar or shape ({b},) for a B={b} batch; "
            f"got {t.shape}")
    return t


def ensemble_initialize(
    batched: ParticleState,
    *,
    n_active=None,
    order: int = 6,
    eps: float = 1e-7,
    impl: str = "xla",
    dtype: str = "fp32",
    devices: Optional[Sequence[jax.Device]] = None,
) -> ParticleState:
    """Bootstrap derivatives for every ensemble member (batched t=0 pass)."""
    mesh = _batch_mesh(devices)
    init, _ = _engine(order, eps, impl, mesh, dtype)
    n_active = _as_n_active(batched, n_active)
    (padded, na), b = _pad_batch((batched, n_active),
                                 mesh.size if mesh else 1)
    out = init(padded, na)
    return jax.tree_util.tree_map(lambda x: x[:b], out)


def ensemble_run(
    batched: ParticleState,
    *,
    n_steps: int,
    dt: float,
    n_active=None,
    order: int = 6,
    eps: float = 1e-7,
    impl: str = "xla",
    dtype: str = "fp32",
    devices: Optional[Sequence[jax.Device]] = None,
) -> ParticleState:
    """Advance an *initialized* batched state by ``n_steps`` fixed-dt steps."""
    mesh = _batch_mesh(devices)
    _, run = _engine(order, eps, impl, mesh, dtype)
    n_active = _as_n_active(batched, n_active)
    (padded, na), b = _pad_batch((batched, n_active),
                                 mesh.size if mesh else 1)
    out = run(padded, na, jnp.asarray(dt, batched.pos.dtype), n_steps)
    return jax.tree_util.tree_map(lambda x: x[:b], out)


@functools.lru_cache(maxsize=64)
def _adaptive_engine(order: int, eps: float, impl: str, mesh,
                     eta: float, dt_max: float, dtype: str):
    """Per-run shared-adaptive (Aarseth) lockstep engine.

    Each run carries its own timestep: ``aarseth_dt`` is evaluated per
    ensemble member under vmap, rate-limited against the member's previous
    step, and clamped to its remaining time.  Members that have reached
    ``t_end`` keep stepping in lockstep (the batch is rectangular) but their
    state is frozen by a per-run select — wasted flops, never wrong physics.
    """
    _count_engine_build("adaptive")
    ev = _inner_evaluator(order, eps, impl, dtype)

    def one_step(s, hp, na, t_end):
        remaining = t_end - s.time
        active = remaining > 0.0
        # padding rows carry zero derivatives (masked evaluator), so they
        # fall into aarseth_dt's num > 0 guard and never tighten the step
        h = hermite.aarseth_dt(s, eta=eta, dt_max=dt_max)
        # rate-limit dt changes (noise robustness; hp <= 0 marks "first step")
        h = jnp.where(hp > 0.0,
                      jnp.minimum(jnp.maximum(h, 0.5 * hp), 2.0 * hp), h)
        h = jnp.minimum(h, jnp.maximum(remaining, 1e-12))
        h_safe = jnp.where(active, h, jnp.ones_like(h))  # corrector / h^3
        s1 = hermite.step(s, h_safe.astype(s.dtype), _mask_evaluator(ev, na),
                          order=order)
        s1 = jax.tree_util.tree_map(
            lambda new, old: jnp.where(active, new, old), s1, s)
        return s1, jnp.where(active, h, hp), active

    step_all = _batch_map(one_step, mesh)

    @functools.partial(jax.jit, static_argnames=("n_steps",))
    def run(batched, h_prev, n_taken, n_active, t_end, n_steps: int):
        batched, n_active = _constrain((batched, n_active), mesh)

        def body(carry, _):
            s, hp, cnt = carry
            s1, hp1, active = step_all(s, hp, n_active, t_end)
            return (_constrain(s1, mesh), hp1,
                    cnt + active.astype(cnt.dtype)), None

        carry, _ = jax.lax.scan(body, (batched, h_prev, n_taken), None,
                                length=n_steps)
        return carry

    return run


def ensemble_run_adaptive(
    batched: ParticleState,
    *,
    t_end: float,
    n_steps: int,
    h_prev: Optional[jax.Array] = None,
    n_taken: Optional[jax.Array] = None,
    n_active=None,
    eta: float = 0.02,
    dt_max: float = 0.0625,
    order: int = 6,
    eps: float = 1e-7,
    impl: str = "xla",
    dtype: str = "fp32",
    devices: Optional[Sequence[jax.Device]] = None,
):
    """Advance an initialized batch by up to ``n_steps`` adaptive steps each.

    Returns ``(batched, h_prev, n_taken)``; call again with the returned
    carries until ``batched.time.min() >= t_end``.  ``n_taken`` counts the
    *productive* steps per run (frozen lockstep steps excluded).  ``t_end``
    is a shared scalar or a per-member ``(B,)`` vector (see
    :func:`_as_t_end`).
    """
    mesh = _batch_mesh(devices)
    run = _adaptive_engine(order, eps, impl, mesh, eta, dt_max, dtype)
    state_dtype = batched.pos.dtype
    if h_prev is None:
        h_prev = jnp.zeros(batch_size(batched), state_dtype)
    if n_taken is None:
        n_taken = jnp.zeros(batch_size(batched), jnp.int32)
    n_active = _as_n_active(batched, n_active)
    t_end_ = _as_t_end(batched, t_end)
    carry, b = _pad_batch((batched, h_prev, n_taken, n_active, t_end_),
                          mesh.size if mesh else 1)
    out, hp, cnt = run(*carry, n_steps)
    return tuple(jax.tree_util.tree_map(lambda x: x[:b], t)
                 for t in (out, hp, cnt))


# --------------------------------------------------------------------------
# hierarchical block-timestep engine (per-particle power-of-two levels)
# --------------------------------------------------------------------------
def _block_inner_evaluator(order: int, eps: float, impl: str,
                           compaction: str, block_i: int, block_j: int,
                           n_caps: Optional[int] = None,
                           dtype: str = "fp32"):
    kw = dict(order=order, eps=eps, compaction=compaction,
              block_i=block_i, block_j=block_j, n_caps=n_caps)
    if impl == "fp64" and dtype == "mixed":
        raise ValueError("impl='fp64' conflicts with dtype='mixed' — the "
                         "oracle path has no reduced-precision mode")
    if impl == "fp64" or dtype == "fp64":
        return make_block_evaluator(precision="fp64", **kw)
    if impl not in ENSEMBLE_IMPLS:
        raise ValueError(
            f"ensemble impl must be one of {ENSEMBLE_IMPLS} (the vmappable "
            f"evaluation paths); got {impl!r}")
    return make_block_evaluator(impl=impl, dtype=dtype, **kw)


def _neighbor_evaluators(n: int, eps: float, impl: str, block_i: int,
                         block_j: int, dtype: str):
    """Windowed near-pass evaluator pair for ``sources="neighbor"`` (same
    impl/precision routing as :func:`_block_inner_evaluator`)."""
    kw = dict(n=n, eps=eps, block_i=block_i, block_j=block_j)
    if impl == "fp64" and dtype == "mixed":
        raise ValueError("impl='fp64' conflicts with dtype='mixed' — the "
                         "oracle path has no reduced-precision mode")
    if impl == "fp64" or dtype == "fp64":
        return make_neighbor_block_evaluator(precision="fp64", **kw)
    if impl not in ENSEMBLE_IMPLS:
        raise ValueError(
            f"ensemble impl must be one of {ENSEMBLE_IMPLS} (the vmappable "
            f"evaluation paths); got {impl!r}")
    return make_neighbor_block_evaluator(impl=impl, dtype=dtype, **kw)


def _window_pairs(mask, win_cnt, block_i: int, block_j: int, out_dtype):
    """(B,) gathered interaction rows of one neighbor event: each masked
    target sweeps its block's ``win_cnt * block_j`` gathered source rows —
    the measured ``n_pairs`` cost the scheme shrinks from ``active * N``."""
    b, n = mask.shape
    nbt = win_cnt.shape[1]
    pad = nbt * block_i - n
    per_block = jnp.sum(
        jnp.pad(mask, ((0, 0), (0, pad))).reshape(b, nbt, block_i), axis=2)
    return (jnp.sum(per_block * win_cnt, axis=1).astype(out_dtype)
            * block_j)


def spatial_sort_state(state: ParticleState, n_active=None, *,
                       leaf: int = 32) -> ParticleState:
    """Spatial sort of one run's rows (padding rows stay last).

    The neighbor scheme windows *contiguous index blocks*, so spatial
    locality of adjacent rows is what keeps the per-block bounding spheres
    — and with them the gathered windows — tight.  Rows are laid out by
    balanced orthogonal recursive bisection (``kernels.neighbor.kd_perm``;
    ``leaf`` should divide the kernel block sizes), whose aligned blocks
    are compact cells even in a heavy halo.  The physics is
    permutation-invariant; entry points apply this once at build/admission
    time and never mid-run (windows are rebuilt at refreshes, so slowly
    decaying locality degrades only the *cost*, never the result).
    """
    n = state.pos.shape[0]
    valid = jnp.arange(n) < (n if n_active is None else n_active)
    perm = neighbor.kd_perm(state.pos, valid, leaf=leaf)
    return jax.tree_util.tree_map(
        lambda x: x[perm] if getattr(x, "ndim", 0) >= 1 else x, state)


def spatial_sort_batched(batched: ParticleState, n_active=None, *,
                         leaf: int = 32) -> ParticleState:
    """Per-member :func:`spatial_sort_state` over a batched state."""
    na = _as_n_active(batched, n_active)
    return jax.vmap(
        functools.partial(spatial_sort_state, leaf=leaf))(batched, na)


# --- one block event, member view (shared by the vmapped ensemble engine
# --- and the single-run strategy engine; statics bound via functools.partial)
def _macro_levels(s, dt_macro, *, eta, n_levels: int):
    """Fresh levels for a member synchronized at its macro start."""
    dt_i = hermite.aarseth_dt_particles(s, eta=eta, dt_max=dt_macro)
    return hermite.quantize_block_levels(dt_i, dt_max=dt_macro,
                                         n_levels=n_levels)


def _event_init(s, na, t_end, *, eta, dt_max, n_levels: int):
    del na
    dtype = s.pos.dtype
    remaining = t_end - s.time
    dt_macro = jnp.minimum(jnp.asarray(dt_max, dtype),
                           jnp.maximum(remaining, 1e-12))
    levels = _macro_levels(s, dt_macro, eta=eta, n_levels=n_levels)
    t_last = jnp.zeros(s.pos.shape[0], jnp.int32)
    return t_last, levels, dt_macro


def _scope(name: Optional[str]):
    return jax.named_scope(name) if name else contextlib.nullcontext()


# One event is split in three stages so the compaction layer can pick its
# capacity bucket(s) *between* the per-member vmaps (the ensemble engine) or
# inside the per-shard switch (the strategy engine).
def _event_pre(s, t_last, levels, dt_macro, na, t_end, *, n_sub: int,
               reduce_scope: Optional[str] = None):
    """``reduce_scope`` names the reduction over rows (the strategy engine
    shards its rows, so there it is an all-reduce)."""
    dtype = s.pos.dtype
    live = (t_end - s.time) > 0.0
    real = jnp.arange(s.pos.shape[0]) < na
    period = jnp.asarray(n_sub, jnp.int32) >> levels
    cand = t_last + period
    with _scope(reduce_scope):
        t_next = jnp.min(jnp.where(real, cand, n_sub))
    active = real & (cand == t_next)
    dt_fine = dt_macro / n_sub
    h = ((t_next - t_last).astype(dtype) * dt_fine)[:, None]

    xp, vp = hermite.predict(s, h)
    ap = hermite.predict_acc(s, h)
    return live, t_next, active, h, xp, vp, ap


def _event_post(s, ev, live, t_next, active, h, t_last, levels,
                dt_macro, na, t_end, *, n_sub: int, eta, dt_max,
                n_levels: int, order: int,
                reduce_scope: Optional[str] = None):
    dtype = s.pos.dtype
    period = jnp.asarray(n_sub, jnp.int32) >> levels
    # an active particle last corrected exactly its own step ago, so the
    # prediction horizon IS the corrector interval
    x1, v1, crk = hermite.correct(s, ev, h, order=order)
    m3 = active[:, None]
    st1 = ParticleState(
        pos=jnp.where(m3, x1, s.pos),
        vel=jnp.where(m3, v1, s.vel),
        acc=jnp.where(m3, ev.acc.astype(dtype), s.acc),
        jerk=jnp.where(m3, ev.jerk.astype(dtype), s.jerk),
        snap=jnp.where(m3, ev.snap.astype(dtype), s.snap),
        crackle=jnp.where(m3, crk, s.crackle),
        mass=s.mass,
        pot=jnp.where(active, ev.pot.astype(s.mass.dtype), s.pot),
        time=s.time,
    )
    t_last1 = jnp.where(active, t_next, t_last)

    # level update from the freshly corrected derivatives: finer at will
    # (always commensurate), coarser one level at doubled-period ticks
    dt_i = hermite.aarseth_dt_particles(st1, eta=eta, dt_max=dt_macro)
    want = hermite.quantize_block_levels(dt_i, dt_max=dt_macro,
                                         n_levels=n_levels)
    can_coarsen = (t_next % (period << 1)) == 0
    lev1 = jnp.where(active & (want > levels), want,
                     jnp.where(active & (want < levels) & can_coarsen,
                               levels - 1, levels))

    # macro boundary: advance member time, requantize, reset the grid
    sync = t_next == n_sub
    time1 = jnp.where(sync, s.time + dt_macro, s.time)
    st1 = dataclasses.replace(st1, time=time1)
    remaining = t_end - time1
    dt_macro1 = jnp.where(
        sync, jnp.minimum(jnp.asarray(dt_max, dtype),
                          jnp.maximum(remaining, 1e-12)), dt_macro)
    lev1 = jnp.where(sync, _macro_levels(st1, dt_macro1, eta=eta,
                                         n_levels=n_levels), lev1)
    t_last1 = jnp.where(sync, 0, t_last1)

    # members past t_end freeze whole (lockstep batch stays rectangular)
    st1, t_last1, lev1, dt_macro1 = jax.tree_util.tree_map(
        lambda new, old: jnp.where(live, new, old),
        (st1, t_last1, lev1, dt_macro1), (s, t_last, levels, dt_macro))
    # int32 counts: a 64-bit sum over sharded rows becomes an unscoped
    # all-reduce of 32-bit halves on the TPU
    with _scope(reduce_scope):
        n_active = jnp.sum(active, dtype=jnp.int32)
    dp = jnp.where(live, n_active.astype(dtype) * na, 0.0)
    return st1, t_last1, lev1, dt_macro1, dp, live


class NeighborCarry(NamedTuple):
    """Per-batch carry of the Ahmad-Cohen neighbor scheme.

    ``win_idx``/``win_cnt`` are the current neighbor windows (per target
    block, see ``kernels.neighbor.build_windows``); ``acc_far``/``jerk_far``
    /``snap_far``/``pot_far`` the far-field Taylor coefficients captured at
    the last refresh (``far = full - near`` at the refresh anchor, predicted
    between refreshes as ``a_far(h) = A + h J + h^2/2 S``); ``t_ref`` the
    ``(B,)`` refresh anchor tick (``-1`` = never refreshed, forces a refresh
    at the member's next event); ``n_refresh``/``n_overflow`` accumulate
    refresh events and window-overflow fallbacks (a refresh whose widest
    window fit no bucket below the full-extent one) for telemetry.
    """

    win_idx: jax.Array
    win_cnt: jax.Array
    acc_far: jax.Array
    jerk_far: jax.Array
    snap_far: jax.Array
    pot_far: jax.Array
    t_ref: jax.Array
    n_refresh: jax.Array
    n_overflow: jax.Array


class BlockCarry(NamedTuple):
    """Opaque per-batch carry of the block engine (pass back unchanged).

    ``t_last``/``levels`` are ``(B, N)`` integer ticks / block levels,
    ``dt_macro`` the ``(B,)`` current macro length, ``n_pairs`` the ``(B,)``
    accumulated pairwise force evaluations (per Hermite pass), ``n_events``
    the ``(B,)`` productive event count, ``n_tiles`` the ``(B,)`` accumulated
    kernel grid tiles launched (both Hermite passes) — the count compaction
    shrinks while ``n_pairs`` stays the same.

    ``bucket_hits`` is the capacity-bucket switch hit distribution:
    ``(B, n_caps)`` counts of how often each member's event dispatched each
    bucket of the *full* capacity schedule (restricted group schedules are
    prefixes, so indices align).  All zeros without ``compaction="gather"``;
    the strategy engine carries an empty ``(0,)`` vector (its switch lives
    inside the shards — see ``grid_tiles_per_shard`` for the per-chip view).

    ``nbr`` is the Ahmad-Cohen :class:`NeighborCarry` under
    ``sources="neighbor"`` and ``None`` (an empty pytree node — existing
    carries keep their treedef) under the default full-source evaluation.
    """

    t_last: jax.Array
    levels: jax.Array
    dt_macro: jax.Array
    n_pairs: jax.Array
    n_events: jax.Array
    n_tiles: jax.Array
    bucket_hits: jax.Array
    nbr: Optional[NeighborCarry] = None


def _bucket_groups(n: int, n_active, block_i: int, block_j: int,
                   compaction: str) -> tuple:
    """Static pre-lowered bucket groups of a (possibly mixed) batch.

    Members are grouped by the ceiling bucket of their *static* ``n_active``
    — the bucket a member's per-event active count can never exceed.  Each
    group dispatches its own unbatched ``lax.switch`` over a capacity
    schedule truncated at that ceiling (``ops.CapacityPlan.restrict``), so a
    quiescent small member in a mixed batch never launches — nor even
    lowers — the widest member's buckets.  Returns a tuple of
    ``(member_indices, n_caps)`` pairs partitioning ``range(B)``; without
    compaction the whole batch is one group over the full schedule, which a
    homogeneous batch also reduces to (one ceiling => one group).
    """
    na = np.asarray(n_active)
    b = na.shape[0]
    plan = ops.CapacityPlan(n, n, block_i, block_j)
    if compaction != "gather":
        return ((tuple(range(b)), len(plan.caps)),)
    by: dict = {}
    for member, a in enumerate(na):
        by.setdefault(len(plan.restrict(int(a)).caps), []).append(member)
    return tuple(sorted((tuple(ms), n_caps) for n_caps, ms in by.items()))


@functools.lru_cache(maxsize=64)
def _block_engine(order: int, eps: float, impl: str, mesh,
                  eta: float, dt_max: float, n_levels: int,
                  compaction: str, block_i: int, block_j: int,
                  groups: tuple, dtype: str, sources: str = "full",
                  radius: float = 0.25, refresh_levels: int = 2):
    """Hierarchical block-timestep engine (Aarseth dt -> power-of-two levels).

    Time is organized in **macro-steps** of ``dt_macro = min(dt_max,
    remaining)``, subdivided on an integer grid of ``2**(n_levels-1)`` fine
    ticks; a particle at level ``l`` steps every ``2**(n_levels-1-l)`` ticks.
    The engine is **event-driven**: each iteration jumps straight to the next
    *occupied* activation tick (``min_i(t_last_i + period_i)``), so deep
    hierarchies cost wall time proportional to the events that actually
    happen, not to the full substep count — exactly the economics of the
    paper's kernel-bound force phase, where skipping inactive targets is the
    whole point.

    At each event the **active block** (particles whose step completes at
    that tick, composed with the ``n_active`` padding mask) is
    predicted-evaluated-corrected over its own elapsed step; everyone else is
    Taylor-predicted to the event time as force *sources* (including
    predicted accelerations for the snap pass).  After correction a particle
    may move to a finer level immediately (always commensurate) or one level
    coarser when the event tick is a multiple of the doubled period — the
    classic Aarseth promotion rule, which is what lets hardening binaries
    chase their shrinking timestep mid-macro.  The macro boundary is a full
    synchronization point: every particle is active there, levels are
    requantized from scratch, and per-member diagnostics (energy, virial)
    are exact.

    ``sources="neighbor"`` is the **Ahmad-Cohen split** of the same event
    loop: the force on each event's active block is the *near* sum over its
    target blocks' gathered neighbor windows plus a Taylor-*predicted* far
    field.  Far coefficients are captured at **refresh events** — the full
    evaluation minus the near sum over the freshly built windows, both at
    the refresh anchor's predicted positions — and a member refreshes when
    ``refresh_levels`` irregular levels have elapsed since its anchor
    (``t_next - t_ref >= n_sub >> refresh_levels``), at every macro
    synchronization, and at its first event.  Windows come from
    ``kernels.neighbor.build_windows`` (bounding-sphere test with
    ``radius``; no pair within the radius is ever dropped) and dispatch
    over the plan's ``source_caps`` schedule, whose last bucket is the full
    source extent — overflow falls back to the exact full window, never to
    truncation.  Refresh-event members get the full evaluation itself
    (prediction horizon zero), so macro boundaries remain exact
    synchronization points.
    """
    _count_engine_build("block")
    if compaction == "gather":
        # switch branches lowered across the pre-lowered bucket groups: the
        # denominator of the recompile accounting (engine.cache_miss ticks
        # once however many branches one build lowers)
        obs_metrics.registry().counter(
            "engine.bucket_branches", unit="branches",
            help="kernel switch branches lowered across bucket groups"
        ).inc(sum(n_caps for _, n_caps in groups))
    n_sub = 2 ** (n_levels - 1)
    n_passes = 2 if order >= 6 else 1
    member_init = functools.partial(_event_init, eta=eta, dt_max=dt_max,
                                    n_levels=n_levels)
    member_pre = functools.partial(_event_pre, n_sub=n_sub)
    member_post = functools.partial(_event_post, n_sub=n_sub, eta=eta,
                                    dt_max=dt_max, n_levels=n_levels,
                                    order=order)
    if compaction != "gather":
        bev = _block_inner_evaluator(order, eps, impl, compaction,
                                     block_i, block_j, dtype=dtype)

    @functools.partial(jax.jit, static_argnames=("n_events",))
    def run(batched, carry: BlockCarry, n_active, t_end, n_events: int):
        batched, n_active = _constrain((batched, n_active), mesh)
        n = batched.pos.shape[1]
        # counter dtype: host precision when x64 is on (exact integer adds
        # far past float32's 2**24 window), silently float32 otherwise
        count_dtype = jax.dtypes.canonicalize_dtype(jnp.float64)
        if compaction == "gather":
            plan = ops.CapacityPlan(n, n, block_i, block_j,
                                    n_passes=n_passes, dtype=dtype)
            # one evaluator + switch per pre-lowered bucket group: members
            # grouped by their n_active ceiling dispatch over a schedule
            # truncated there (lax.switch needs its operand unbatched under
            # vmap to stay a real branch, so the index is shared *within*
            # each group — the max live active count of the group's members)
            group_data = [
                (np.asarray(members, np.intp),
                 plan.restrict(plan.caps[min(n_caps, len(plan.caps)) - 1]),
                 _block_inner_evaluator(order, eps, impl, compaction,
                                        block_i, block_j, n_caps,
                                        dtype=dtype))
                for members, n_caps in groups]
            inv = np.argsort(np.concatenate([m for m, _, _ in group_data]))
        else:
            # the masked dense launch always enqueues the full grid, however
            # many i-blocks pl.when predicates away
            full_tiles = nbody_force.grid_tiles(n, n, block_i, block_j) \
                * n_passes

        def body(acc, _):
            s, c = acc
            with jax.named_scope("event_loop.pre"):
                live, t_next, active, h, xp, vp, ap = jax.vmap(
                    member_pre, in_axes=(0, 0, 0, 0, 0, 0))(
                        s, c.t_last, c.levels, c.dt_macro, n_active, t_end)
            hits_event = None
            if compaction == "gather":
                perm = jax.vmap(ops.active_first)(active)
                with jax.named_scope("compaction.bucket_index"):
                    n_act = jnp.sum(active, axis=1).astype(jnp.int32)
                n_caps_full = c.bucket_hits.shape[1]
                evs, tiles_parts, hits_parts = [], [], []
                for members, gplan, gbev in group_data:
                    with jax.named_scope("compaction.bucket_index"):
                        cap_idx = shared_cap_index(gplan, jnp.where(
                            live[members], n_act[members], 0))
                    with jax.named_scope("compaction.group"):
                        operands = (xp[members], vp[members], ap[members],
                                    s.mass[members], active[members],
                                    perm[members])
                    evs.append(_batch_map(
                        gbev, mesh, in_axes=(0, 0, 0, 0, 0, 0, None))(
                            *operands, cap_idx))
                    with jax.named_scope("event_loop.carry"):
                        tiles_parts.append(jnp.broadcast_to(
                            gplan.tiles(cap_idx).astype(count_dtype),
                            (len(members),)))
                        hits_parts.append(jnp.broadcast_to(
                            jax.nn.one_hot(cap_idx, n_caps_full,
                                           dtype=count_dtype),
                            (len(members), n_caps_full)))
                with jax.named_scope("compaction.group"):
                    ev = jax.tree_util.tree_map(
                        lambda *xs: jnp.concatenate(xs)[inv], *evs)
                with jax.named_scope("event_loop.carry"):
                    tiles_event = jnp.concatenate(tiles_parts)[inv]
                    hits_event = jnp.concatenate(hits_parts)[inv]
            else:
                ev = _batch_map(bev, mesh)(xp, vp, ap, s.mass, active)
                tiles_event = jnp.asarray(full_tiles, count_dtype)
            with jax.named_scope("event_loop.post"):
                s1, t_last, levels, dt_macro, dp, live = jax.vmap(
                    member_post,
                    in_axes=(0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0))(
                        s, ev, live, t_next, active, h, c.t_last, c.levels,
                        c.dt_macro, n_active, t_end)
            with jax.named_scope("event_loop.carry"):
                c1 = BlockCarry(
                    t_last=t_last, levels=levels, dt_macro=dt_macro,
                    n_pairs=c.n_pairs + dp,
                    n_events=c.n_events + live.astype(jnp.int32),
                    n_tiles=c.n_tiles + jnp.where(live, tiles_event, 0.0),
                    bucket_hits=c.bucket_hits
                    if hits_event is None else c.bucket_hits
                    + jnp.where(live[:, None], hits_event, 0.0))
                return (_constrain(s1, mesh), c1), None

        step_body = body
        if sources == "neighbor":
            near1, near2 = _neighbor_evaluators(n, eps, impl, block_i,
                                                block_j, dtype)
            nplan = ops.CapacityPlan(n, n, block_i, block_j,
                                     n_passes=n_passes, dtype=dtype,
                                     sources="neighbor")
            src_caps = nplan.source_caps
            refresh_period = max(1, n_sub >> refresh_levels)
            state_dtype = batched.pos.dtype

            def neighbor_body(acc, _):
                s, c = acc
                nb = c.nbr
                with jax.named_scope("event_loop.pre"):
                    live, t_next, active, h, xp, vp, ap = jax.vmap(
                        member_pre, in_axes=(0, 0, 0, 0, 0, 0))(
                            s, c.t_last, c.levels, c.dt_macro, n_active,
                            t_end)
                need = live & ((nb.t_ref < 0)
                               | (t_next - nb.t_ref >= refresh_period)
                               | (t_next == n_sub))
                real = jnp.arange(n)[None, :] < n_active[:, None]
                cd = count_dtype
                zero = jnp.zeros((), cd)

                def near_total(mask, win_idx, win_cnt, w_idx):
                    """Near(windows) + NM08-predicted far, every member.

                    The far anchor never moves inside this event (the
                    refresh branch *replaces* it), so the same prediction
                    serves both the acc operands and the returned
                    Evaluation; members whose result the caller discards
                    (refreshing ones) just ride the vmap.
                    """
                    a_n, j_n, p_n = _batch_map(
                        near1, mesh, in_axes=(0, 0, 0, 0, 0, 0, None))(
                            xp, vp, s.mass, mask, win_idx, win_cnt, w_idx)
                    hf = ((t_next - jnp.maximum(nb.t_ref, 0))
                          .astype(state_dtype) * c.dt_macro / n_sub)
                    h1 = hf[:, None, None]
                    a_far = (nb.acc_far + h1 * nb.jerk_far
                             + (0.5 * h1 * h1) * nb.snap_far)
                    acc_t = a_n.astype(state_dtype) + a_far
                    if order >= 6:
                        acc_s = jnp.where(mask[..., None], acc_t, ap)
                        s_n = _batch_map(
                            near2, mesh,
                            in_axes=(0, 0, 0, 0, 0, 0, 0, 0, None))(
                                xp, vp, acc_t, acc_s, s.mass, mask,
                                win_idx, win_cnt, w_idx)
                        snp = s_n.astype(state_dtype) + nb.snap_far
                    else:
                        snp = jnp.zeros_like(acc_t)
                    return Evaluation(
                        acc=acc_t,
                        jerk=(j_n.astype(state_dtype) + nb.jerk_far
                              + h1 * nb.snap_far),
                        snap=snp,
                        pot=p_n.astype(state_dtype) + nb.pot_far)

                zi = jnp.zeros_like(nb.t_ref)
                # the gathered window width of one event is shared by every
                # launched target block, so size it over the blocks that
                # hold *active* targets: the frequently stepping core has
                # tight windows, while a sparse halo block's full-extent
                # window only widens the (rare) events that step it — the
                # Ahmad-Cohen economics at block granularity
                npad_i = nb.win_cnt.shape[1] * block_i - n
                act_blk = jnp.any(jnp.pad(active, ((0, 0), (0, npad_i)))
                                  .reshape(active.shape[0], -1, block_i),
                                  axis=2)

                def no_refresh(_):
                    wmax = jnp.max(jnp.where(live[:, None] & act_blk,
                                             nb.win_cnt, 0))
                    w_idx = nplan.source_bucket(wmax * block_j)
                    ev = near_total(active, nb.win_idx, nb.win_cnt, w_idx)
                    dp = jnp.where(live, _window_pairs(
                        active, nb.win_cnt, block_i, block_j, cd), zero)
                    tiles = jnp.where(
                        live, nplan.window_tiles(w_idx).astype(cd), zero)
                    return (ev, nb.win_idx, nb.win_cnt, nb.acc_far,
                            nb.jerk_far, nb.snap_far, nb.pot_far, nb.t_ref,
                            zi, zi, dp, tiles)

                def do_refresh(_):
                    # members keeping their anchor still need this event's
                    # near force over their OLD windows (bucket sized over
                    # them alone — an all-refresh event launches the
                    # cheapest bucket and discards it)
                    keep = live & ~need
                    wmax_o = jnp.max(jnp.where(keep[:, None] & act_blk,
                                               nb.win_cnt, 0))
                    w_old = nplan.source_bucket(wmax_o * block_j)
                    ev_o = near_total(active, nb.win_idx, nb.win_cnt, w_old)
                    # refresh anchor: full force at the event's predicted
                    # positions; new windows from the same positions; far =
                    # full - near with IDENTICAL acc operands in both
                    with jax.named_scope("event_loop.neighbor_refresh"):
                        ev_f = _batch_map(bev, mesh)(xp, vp, ap, s.mass,
                                                     real)
                    win_idx_n, win_cnt_n = jax.vmap(
                        lambda p_, v_: neighbor.build_windows(
                            p_, v_, block_i=block_i, block_j=block_j,
                            radius=radius))(xp, real)
                    wmax_n = jnp.max(jnp.where(need[:, None],
                                               win_cnt_n, 0))
                    w_new = nplan.source_bucket(wmax_n * block_j)
                    a_nn, j_nn, p_nn = _batch_map(
                        near1, mesh, in_axes=(0, 0, 0, 0, 0, 0, None))(
                            xp, vp, s.mass, real, win_idx_n, win_cnt_n,
                            w_new)
                    af = ev_f.acc.astype(state_dtype)
                    jf = ev_f.jerk.astype(state_dtype)
                    pf = ev_f.pot.astype(state_dtype)
                    if order >= 6:
                        acc_s = jnp.where(real[..., None], af, ap)
                        s_nn = _batch_map(
                            near2, mesh,
                            in_axes=(0, 0, 0, 0, 0, 0, 0, 0, None))(
                                xp, vp, af, acc_s, s.mass, real,
                                win_idx_n, win_cnt_n, w_new)
                        sf = ev_f.snap.astype(state_dtype)
                        snapf_n = sf - s_nn.astype(state_dtype)
                        snap_ev = jnp.where(need[:, None, None], sf,
                                            ev_o.snap)
                    else:
                        snapf_n = jnp.zeros_like(af)
                        snap_ev = jnp.zeros_like(af)
                    sel3, sel2 = need[:, None, None], need[:, None]
                    ev = Evaluation(
                        acc=jnp.where(sel3, af, ev_o.acc),
                        jerk=jnp.where(sel3, jf, ev_o.jerk),
                        snap=snap_ev,
                        pot=jnp.where(sel2, pf, ev_o.pot))
                    tref = jnp.where(
                        need, jnp.where(t_next == n_sub, 0, t_next),
                        nb.t_ref)
                    if len(src_caps) > 1:
                        rows = jnp.max(win_cnt_n, axis=1) * block_j
                        dov = (need & (rows > src_caps[-2])).astype(
                            jnp.int32)
                    else:
                        dov = zi  # one bucket == the full window already
                    na_f = n_active.astype(cd)
                    dp = jnp.where(live, jnp.where(
                        need,
                        na_f * na_f + _window_pairs(real, win_cnt_n,
                                                    block_i, block_j, cd),
                        _window_pairs(active, nb.win_cnt, block_i, block_j,
                                      cd)), zero)
                    tiles = jnp.where(live, jnp.where(
                        need,
                        jnp.asarray(full_tiles, cd)
                        + nplan.window_tiles(w_new).astype(cd),
                        nplan.window_tiles(w_old).astype(cd)), zero)
                    return (
                        ev,
                        jnp.where(sel3, win_idx_n, nb.win_idx),
                        jnp.where(sel2, win_cnt_n, nb.win_cnt),
                        jnp.where(sel3, af - a_nn.astype(state_dtype),
                                  nb.acc_far),
                        jnp.where(sel3, jf - j_nn.astype(state_dtype),
                                  nb.jerk_far),
                        jnp.where(sel3, snapf_n, nb.snap_far),
                        jnp.where(sel2, pf - p_nn.astype(state_dtype),
                                  nb.pot_far),
                        tref, need.astype(jnp.int32), dov, dp, tiles)

                with jax.named_scope("event_loop.neighbor"):
                    (ev, wi, wc, accf, jerkf, snapf, potf, tref, dref,
                     dov, dp, tiles) = jax.lax.cond(
                        jnp.any(need), do_refresh, no_refresh, None)
                with jax.named_scope("event_loop.post"):
                    s1, t_last, levels, dt_macro, _, live = jax.vmap(
                        member_post,
                        in_axes=(0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0))(
                            s, ev, live, t_next, active, h, c.t_last,
                            c.levels, c.dt_macro, n_active, t_end)
                with jax.named_scope("event_loop.carry"):
                    c1 = BlockCarry(
                        t_last=t_last, levels=levels, dt_macro=dt_macro,
                        n_pairs=c.n_pairs + dp,
                        n_events=c.n_events + live.astype(jnp.int32),
                        n_tiles=c.n_tiles + tiles,
                        bucket_hits=c.bucket_hits,
                        nbr=NeighborCarry(
                            win_idx=wi, win_cnt=wc, acc_far=accf,
                            jerk_far=jerkf, snap_far=snapf, pot_far=potf,
                            t_ref=tref, n_refresh=nb.n_refresh + dref,
                            n_overflow=nb.n_overflow + dov))
                    return (_constrain(s1, mesh), c1), None

            step_body = neighbor_body

        # the scan's own loop control, and the copies XLA makes of the
        # carried state, belong to the event loop's carry
        with jax.named_scope("event_loop.carry"):
            (batched, carry), _ = jax.lax.scan(step_body, (batched, carry),
                                               None, length=n_events)
        return batched, carry

    @jax.jit
    def init(batched, n_active, t_end):
        t_last, levels, dt_macro = jax.vmap(
            member_init, in_axes=(0, 0, 0))(batched, n_active, t_end)
        b, n = t_last.shape
        # counters accumulate at host precision (exact integer adds far past
        # float32's 2**24 window; silently float32 when x64 is disabled)
        count_dtype = jax.dtypes.canonicalize_dtype(jnp.float64)
        n_caps = len(ops.CapacityPlan(n, n, block_i, block_j).caps)
        nbr = None
        if sources == "neighbor":
            # t_ref = -1 forces a refresh at every member's first event, so
            # the zeroed windows/coefficients here are never consumed
            sd = batched.pos.dtype
            nbt, nsb = -(-n // block_i), -(-n // block_j)
            nbr = NeighborCarry(
                win_idx=jnp.zeros((b, nbt, nsb), jnp.int32),
                win_cnt=jnp.zeros((b, nbt), jnp.int32),
                acc_far=jnp.zeros((b, n, 3), sd),
                jerk_far=jnp.zeros((b, n, 3), sd),
                snap_far=jnp.zeros((b, n, 3), sd),
                pot_far=jnp.zeros((b, n), sd),
                t_ref=jnp.full((b,), -1, jnp.int32),
                n_refresh=jnp.zeros((b,), jnp.int32),
                n_overflow=jnp.zeros((b,), jnp.int32))
        return BlockCarry(
            t_last=t_last, levels=levels, dt_macro=dt_macro,
            n_pairs=jnp.zeros(b, count_dtype),
            n_events=jnp.zeros(b, jnp.int32),
            n_tiles=jnp.zeros(b, count_dtype),
            bucket_hits=jnp.zeros((b, n_caps), count_dtype),
            nbr=nbr)

    return init, run


def ensemble_run_block(
    batched: ParticleState,
    *,
    t_end: float,
    n_events: int = 64,
    dt_max: float = 0.0625,
    n_levels: int = 8,
    carry: Optional[BlockCarry] = None,
    n_active=None,
    eta: float = 0.02,
    order: int = 6,
    eps: float = 1e-7,
    impl: str = "xla",
    dtype: str = "fp32",
    compaction: str = "none",
    block_i: Optional[int] = None,
    block_j: Optional[int] = None,
    sources: str = "full",
    neighbor_radius: float = 0.25,
    refresh_levels: int = 2,
    devices: Optional[Sequence[jax.Device]] = None,
):
    """Advance an initialized batch by up to ``n_events`` block events each.

    Returns ``(batched, carry)``; call again with the returned carry until
    ``batched.time.min() >= t_end`` (a member's ``time`` advances at its
    macro boundaries).  ``t_end`` is a shared scalar or a per-member ``(B,)``
    vector (see :func:`_as_t_end`) — a member whose deadline has passed
    freezes whole while its batch-mates keep integrating.  ``carry.n_pairs`` accumulates the per-run pairwise
    force evaluations actually performed (per Hermite pass) — the measured
    cost telemetry reports; ``carry.n_events`` counts productive events;
    ``carry.n_tiles`` the kernel grid tiles launched per member (both
    passes).

    ``compaction="gather"`` gathers each event's active targets into a
    dense block-aligned buffer sized from a static capacity schedule and
    launches the kernels on the shrunk ``ceil(cap/BI) x N/BJ`` grid
    (bit-for-bit the masked dense result).  Members are grouped by their
    static ``n_active`` ceiling into pre-lowered bucket groups (see
    :func:`_bucket_groups`), so a mixed batch's quiescent members stop
    paying for its widest member's grid.
    ``block_i``/``block_j`` override the kernel tile shape (default: the
    kernel's own); the compaction win is bounded by ``N / block_i``, so
    small-N runs want a smaller ``block_i`` than the all-pairs default.

    ``sources="neighbor"`` switches the force evaluation to the
    Ahmad-Cohen near/far split (see :func:`_block_engine`):
    ``neighbor_radius`` is the bounding-sphere window radius in simulation
    length units, ``refresh_levels`` how many levels below the macro the
    far-field refresh cadence sits (refresh every ``n_sub >>
    refresh_levels`` ticks).  The batch should be Morton-sorted first
    (:func:`spatial_sort_batched`; the convenience entry points do it) so
    index blocks are spatially tight.  ``sources="full"`` is bit-identical
    to the pre-neighbor engine.
    """
    if n_levels < 1:
        raise ValueError(f"n_levels={n_levels} must be >= 1")
    if sources not in ops.SOURCES:
        raise ValueError(
            f"sources must be one of {ops.SOURCES}; got {sources!r}")
    if sources == "neighbor" and compaction != "none":
        raise ValueError(
            "sources='neighbor' gathers its own per-block source windows; "
            "it composes with compaction='none' only")
    if refresh_levels < 0:
        raise ValueError(f"refresh_levels={refresh_levels} must be >= 0")
    # an unknown compaction mode fails in make_block_evaluator (same
    # ValueError) when the engine is first built — no duplicate check here
    mesh = _batch_mesh(devices)
    bext = mesh.size if mesh else 1
    n_active = _as_n_active(batched, n_active)
    t_end_ = _as_t_end(batched, t_end)
    if carry is None:
        (padded, na, t_end_), b = _pad_batch((batched, n_active, t_end_),
                                             bext)
    else:
        (padded, na, t_end_, carry), b = _pad_batch(
            (batched, n_active, t_end_, carry), bext)
    bi = block_i or nbody_force.DEFAULT_BLOCK_I
    bj = block_j or nbody_force.DEFAULT_BLOCK_J
    # groups come from the *padded* batch (padding repeats the first run,
    # so it lands in that run's group); n_active must be concrete here —
    # these entry points run host-side loops anyway
    groups = _bucket_groups(padded.pos.shape[1], na, bi, bj, compaction)
    init, run = _block_engine(
        order, eps, impl, mesh, eta, dt_max, n_levels, compaction, bi, bj,
        groups, dtype, sources, float(neighbor_radius), refresh_levels)
    if carry is None:
        carry = init(padded, na, t_end_)
    out, carry = run(padded, carry, na, t_end_, n_events)
    return tuple(jax.tree_util.tree_map(lambda x: x[:b], t)
                 for t in (out, carry))


def block_admit_member(carry: BlockCarry, member: ParticleState, slot: int,
                       t_end, *, eta: float = 0.02, dt_max: float = 0.0625,
                       n_levels: int = 8) -> BlockCarry:
    """Splice a freshly admitted member's block carry into ``slot``.

    The serving layer backfills a retired slot by writing the new member's
    *initialized* ``(N,)`` state into the batch and resetting that slot's
    carry: fresh levels/ticks from the member's own Aarseth dt distribution
    (:func:`_event_init`, the same bootstrap ``init`` runs batch-wide) and
    zeroed per-member counters, so the retiring run's telemetry never bleeds
    into its successor's.  Every other slot's carry leaves are untouched —
    batch-mates stay bit-identical (the backfill invariance test pins this).
    ``eta``/``dt_max``/``n_levels`` must match the engine the pod runs.
    """
    t_end_ = jnp.asarray(t_end, member.pos.dtype)
    t_last, levels, dt_macro = _event_init(
        member, member.pos.shape[0], t_end_, eta=eta, dt_max=dt_max,
        n_levels=n_levels)
    nbr = carry.nbr
    if nbr is not None:
        # t_ref = -1 forces the new member to refresh (and rebuild its
        # windows) at its first event; the retiring run's far field and
        # neighbor telemetry never bleed into its successor
        nbr = NeighborCarry(
            win_idx=nbr.win_idx.at[slot].set(0),
            win_cnt=nbr.win_cnt.at[slot].set(0),
            acc_far=nbr.acc_far.at[slot].set(0),
            jerk_far=nbr.jerk_far.at[slot].set(0),
            snap_far=nbr.snap_far.at[slot].set(0),
            pot_far=nbr.pot_far.at[slot].set(0),
            t_ref=nbr.t_ref.at[slot].set(-1),
            n_refresh=nbr.n_refresh.at[slot].set(0),
            n_overflow=nbr.n_overflow.at[slot].set(0))
    return BlockCarry(
        t_last=carry.t_last.at[slot].set(t_last),
        levels=carry.levels.at[slot].set(levels),
        dt_macro=carry.dt_macro.at[slot].set(dt_macro),
        n_pairs=carry.n_pairs.at[slot].set(0),
        n_events=carry.n_events.at[slot].set(0),
        n_tiles=carry.n_tiles.at[slot].set(0),
        bucket_hits=carry.bucket_hits.at[slot].set(0)
        if carry.bucket_hits.ndim == 2 else carry.bucket_hits,
        nbr=nbr)


def evolve_ensemble_block(
    states,
    *,
    t_end: float,
    dt_max: float = 0.0625,
    n_levels: int = 8,
    n_active=None,
    eta: float = 0.02,
    order: int = 6,
    eps: float = 1e-7,
    impl: Optional[str] = None,
    kernel: Optional[str] = None,
    dtype: str = "fp32",
    compaction: str = "none",
    block_i: Optional[int] = None,
    block_j: Optional[int] = None,
    sources: str = "full",
    neighbor_radius: float = 0.25,
    refresh_levels: int = 2,
    devices: Optional[Sequence[jax.Device]] = None,
    n_events: int = 256,
    max_chunks: int = 100_000,
):
    """One-shot block-timestep convenience: stack, initialize, evolve to
    ``t_end``.  Returns ``(batched, carry)`` (see
    :func:`ensemble_run_block`).  ``sources="neighbor"`` ORB-sorts the
    batch (``spatial_sort_batched``) before the bootstrap so the neighbor
    windows see spatially tight index blocks; the returned batch is in
    that sorted order."""
    impl = resolve_eval_impl(impl, kernel)
    batched = states if isinstance(states, ParticleState) else \
        stack_states(list(states))
    if sources == "neighbor":
        bi = block_i or nbody_force.DEFAULT_BLOCK_I
        bj = block_j or nbody_force.DEFAULT_BLOCK_J
        batched = spatial_sort_batched(batched, n_active,
                                       leaf=math.gcd(bi, bj))
    kw = dict(n_active=n_active, order=order, eps=eps, impl=impl,
              dtype=dtype, devices=devices)
    batched = ensemble_initialize(batched, **kw)
    carry = None
    for _ in range(max_chunks):
        batched, carry = ensemble_run_block(
            batched, t_end=t_end, n_events=n_events, dt_max=dt_max,
            n_levels=n_levels, carry=carry, eta=eta, compaction=compaction,
            block_i=block_i, block_j=block_j,
            sources=sources, neighbor_radius=neighbor_radius,
            refresh_levels=refresh_levels, **kw)
        if float(jnp.min(batched.time)) >= t_end:
            break
    return batched, carry


# --------------------------------------------------------------------------
# single-run block stepper under a multi-device distribution strategy
# --------------------------------------------------------------------------
#: the strategy engine's row axis: its run's rows are sharded over it
ROW_AXIS = "dev"
#: the strategy engine's reductions over its sharded rows
ROWS_REDUCE_SCOPE = "collective.all_reduce"


@functools.lru_cache(maxsize=64)
def _strategy_layout(devices: tuple, n: int):
    """``(state, carry)`` shardings in which the strategy block engine keeps
    one run between its calls, on a 1-D mesh over ``devices``.

    Rows are sharded: each device predicts and corrects only its own
    contiguous N/P rows in float64, which are exactly the targets its
    force shard evaluates, and the evaluator's replicated source specs
    gather the predicted rows (the collectives of the paper's strategies).
    Replicated rows would put all N rows' float64 event-loop work on every
    device.  ``mass`` and ``time`` are replicated (the mass is a source
    operand of every pass and never changes), as is every counter but
    ``n_tiles``, whose entry ``k`` is shard ``k``'s.  When N does not
    divide over the devices the rows are replicated too."""
    mesh = make_batch_mesh(devices, axis_name=ROW_AXIS)
    rep = NamedSharding(mesh, P())
    per_device = NamedSharding(mesh, P(ROW_AXIS))
    rows = per_device if n % len(devices) == 0 else rep
    state = ParticleState(pos=rows, vel=rows, acc=rows, jerk=rows,
                          snap=rows, crackle=rows, mass=rep, pot=rows,
                          time=rep)
    carry = BlockCarry(t_last=rows, levels=rows, dt_macro=rep, n_pairs=rep,
                       n_events=rep, n_tiles=per_device, bucket_hits=rep)
    return state, carry


@functools.lru_cache(maxsize=64)
def _strategy_block_engine(strategy: str, devices: tuple,
                           chips_per_card: int, order: int, eps: float,
                           impl: str, eta: float, dt_max: float,
                           n_levels: int, compaction: str,
                           block_i: int, block_j: int, dtype: str,
                           sources: str = "full"):
    """Block-timestep engine whose force evaluation is *distributed* over a
    device mesh instead of vmapped over a batch: one run, its domain sharded
    by one of the paper's strategies, each shard compacting its own local
    active targets (``core.strategies.make_strategy_block_evaluator``).

    Reuses the exact per-event logic of the ensemble engine
    (:func:`_event_pre` / :func:`_event_post`), so the event schedule — and
    with it the committed block golden trajectory — is identical; only the
    evaluator (and the per-*shard* tile accounting in the carry) differs.

    Capacity buckets are sized **host-side** (ROADMAP 5c): each event's
    per-shard launch extent comes from the analytic
    ``block_level_occupancy`` bound at the tick's threshold level — no
    runtime gather of the activity mask feeds the bucket switch.  A
    particle at level ``l`` steps at exactly the multiples of its period
    (promotion is commensurate, demotion lands on doubled-period ticks),
    so the tick's active set IS ``{level >= threshold}`` and the bound
    equals the measured count — identical buckets, tiles, and physics
    (``test_obs_metrics.py`` pins ``launched <= bound-sized <= dense``).

    Both programs return the run in :func:`_strategy_layout`, the layout
    :func:`strategy_run_block` places it in, so each chunk length compiles
    once.
    """
    from repro.core.strategies import make_strategy_block_evaluator

    _count_engine_build("block_strategy")
    n_devices = len(devices)
    bev = make_strategy_block_evaluator(
        strategy, devices=devices, chips_per_card=chips_per_card, eps=eps,
        order=order, impl=impl, block_i=block_i, block_j=block_j,
        compaction=compaction, dtype=dtype, sources=sources)
    n_sub = 2 ** (n_levels - 1)
    event_init = functools.partial(_event_init, eta=eta, dt_max=dt_max,
                                   n_levels=n_levels)
    event_pre = functools.partial(_event_pre, n_sub=n_sub,
                                  reduce_scope=ROWS_REDUCE_SCOPE)
    event_post = functools.partial(_event_post, n_sub=n_sub, eta=eta,
                                   dt_max=dt_max, n_levels=n_levels,
                                   order=order,
                                   reduce_scope=ROWS_REDUCE_SCOPE)

    def placed(state, carry):
        layout = _strategy_layout(devices, state.pos.shape[0])
        return jax.lax.with_sharding_constraint((state, carry), layout)

    @functools.partial(jax.jit, static_argnames=("n_events",))
    def run(state, carry: BlockCarry, t_end, n_events: int):
        n = state.pos.shape[0]
        count_dtype = jax.dtypes.canonicalize_dtype(jnp.float64)

        def body(acc, _):
            s, c = acc
            with jax.named_scope("event_loop.pre"):
                live, t_next, active, h, xp, vp, ap = event_pre(
                    s, c.t_last, c.levels, c.dt_macro, n, t_end)
            # the shard-local permutations live inside the shards
            with jax.named_scope("compaction.bucket_index"):
                # host-side bucket sizing: padded rows carry level -1, so
                # each shard's contiguous chunk counts only real particles
                # at or above the tick's threshold level
                thr = hermite.tick_threshold_level(t_next,
                                                   n_levels=n_levels)
                n_pad = -(-n // n_devices) * n_devices
                lev_pad = jnp.pad(c.levels, (0, n_pad - n),
                                  constant_values=-1)
                bound = jax.vmap(
                    lambda lv: hermite.block_level_occupancy(
                        lv, n_levels=n_levels)[thr]
                )(lev_pad.reshape(n_devices, -1))
            ev, tiles = bev(xp, vp, ap, s.mass, active, bound)
            with jax.named_scope("event_loop.post"):
                s1, t_last, levels, dt_macro, dp, live = event_post(
                    s, ev, live, t_next, active, h, c.t_last, c.levels,
                    c.dt_macro, n, t_end)
            with jax.named_scope("event_loop.carry"):
                c1 = BlockCarry(
                    t_last=t_last, levels=levels, dt_macro=dt_macro,
                    n_pairs=c.n_pairs + dp,
                    n_events=c.n_events + live.astype(jnp.int32),
                    n_tiles=c.n_tiles + jnp.where(
                        live, tiles, 0).astype(count_dtype),
                    bucket_hits=c.bucket_hits)
                return (s1, c1), None

        with jax.named_scope("event_loop.carry"):
            (state, carry), _ = jax.lax.scan(body, (state, carry), None,
                                             length=n_events)
            return placed(state, carry)

    @jax.jit
    def init(state, t_end):
        t_last, levels, dt_macro = event_init(state, state.pos.shape[0],
                                              t_end)
        count_dtype = jax.dtypes.canonicalize_dtype(jnp.float64)
        carry = BlockCarry(
            t_last=t_last, levels=levels, dt_macro=dt_macro,
            n_pairs=jnp.zeros((), count_dtype),
            n_events=jnp.zeros((), jnp.int32),
            n_tiles=jnp.zeros(n_devices, count_dtype),
            # the per-shard switch lives inside the shards; no batch-level
            # bucket distribution to report (see grid_tiles_per_shard)
            bucket_hits=jnp.zeros((0,), count_dtype))
        return placed(state, carry)[1]

    return init, run


def _devices(devices) -> tuple:
    """``devices`` (an int count, a device sequence, or None for all
    visible devices) as a device tuple."""
    if devices is None:
        return tuple(jax.devices())
    if isinstance(devices, int):
        return tuple(jax.devices()[:devices])
    return tuple(devices)


def strategy_run_block(
    state: ParticleState,
    *,
    t_end: float,
    n_events: int = 64,
    dt_max: float = 0.0625,
    n_levels: int = 8,
    carry: Optional[BlockCarry] = None,
    eta: float = 0.02,
    order: int = 6,
    eps: float = 1e-7,
    impl: str = "xla",
    dtype: str = "fp32",
    strategy: str = "replicated",
    chips_per_card: int = 2,
    compaction: str = "none",
    block_i: Optional[int] = None,
    block_j: Optional[int] = None,
    sources: str = "full",
    devices=None,
):
    """Advance ONE initialized run by up to ``n_events`` block events, the
    force evaluation distributed by ``strategy`` over ``devices`` (an int
    count, a device sequence, or None for all visible devices).
    ``sources`` is validated by the strategy evaluator — the sharded
    strategies evaluate full sources only (``"neighbor"`` runs on the
    ensemble engine, strategy ``"single"``).

    Returns ``(state, carry)`` like :func:`ensemble_run_block`, except the
    carry's scalar leaves are unbatched and ``carry.n_tiles`` is the
    ``(P,)`` vector of kernel grid tiles *each shard* enqueued — with
    ``compaction="gather"`` every shard gathers its local active targets
    and launches ``ceil(cap_local/BI) x N/BJ`` tiles, so the vector shows
    which chips' launch schedules the active set actually touched.
    """
    if n_levels < 1:
        raise ValueError(f"n_levels={n_levels} must be >= 1")
    devs = _devices(devices)
    init, run = _strategy_block_engine(
        strategy, devs, chips_per_card, order, eps, impl,
        eta, dt_max, n_levels, compaction,
        block_i or nbody_force.DEFAULT_BLOCK_I,
        block_j or nbody_force.DEFAULT_BLOCK_J, dtype, sources)
    t_end_ = jnp.asarray(t_end, state.pos.dtype)
    # the engine's own layout from the first call on (a no-op after it)
    state_layout, carry_layout = _strategy_layout(devs, state.pos.shape[0])
    state = jax.device_put(state, state_layout)
    carry = (init(state, t_end_) if carry is None
             else jax.device_put(carry, carry_layout))
    return run(state, carry, t_end_, n_events)


def evolve_strategy_block(
    state: ParticleState,
    *,
    t_end: float,
    strategy: str = "replicated",
    dt_max: float = 0.0625,
    n_levels: int = 8,
    eta: float = 0.02,
    order: int = 6,
    eps: float = 1e-7,
    impl: Optional[str] = None,
    kernel: Optional[str] = None,
    dtype: str = "fp32",
    chips_per_card: int = 2,
    compaction: str = "none",
    block_i: Optional[int] = None,
    block_j: Optional[int] = None,
    devices=None,
    n_events: int = 64,
    max_chunks: int = 100_000,
):
    """One-shot strategy-distributed block run: initialize (with the same
    strategy's lockstep evaluator), evolve to ``t_end``.  Returns
    ``(state, carry)`` (see :func:`strategy_run_block`)."""
    from repro.core.strategies import make_strategy_evaluator

    impl = resolve_eval_impl(impl, kernel)
    devs = _devices(devices)
    ev = make_strategy_evaluator(
        strategy, devices=devs,
        chips_per_card=chips_per_card, eps=eps, order=order, impl=impl,
        block_i=block_i or nbody_force.DEFAULT_BLOCK_I,
        block_j=block_j or nbody_force.DEFAULT_BLOCK_J, dtype=dtype)
    state = hermite.initialize(state, ev)
    carry = None
    for _ in range(max_chunks):
        state, carry = strategy_run_block(
            state, t_end=t_end, n_events=n_events, dt_max=dt_max,
            n_levels=n_levels, carry=carry, eta=eta, order=order, eps=eps,
            impl=impl, dtype=dtype, strategy=strategy,
            chips_per_card=chips_per_card,
            compaction=compaction, block_i=block_i, block_j=block_j,
            devices=devs)
        if float(state.time) >= t_end:
            break
    return state, carry


def evolve_ensemble(
    states,
    *,
    n_steps: int,
    dt: float,
    n_active=None,
    order: int = 6,
    eps: float = 1e-7,
    impl: Optional[str] = None,
    kernel: Optional[str] = None,
    dtype: str = "fp32",
    devices: Optional[Sequence[jax.Device]] = None,
    strategy: str = "replicated",
) -> ParticleState:
    """One-shot convenience: stack (if needed), initialize, evolve.

    ``strategy`` is validated against the known strategy names but — the runs
    being independent — only affects telemetry labeling, not the math.
    Pass at most one of ``impl`` (low-level path, default "xla") and
    ``kernel`` ("ref" | "pallas"); an explicit pair conflicts.
    """
    if strategy not in STRATEGIES and strategy != "single":
        raise ValueError(
            f"unknown strategy {strategy!r}; one of {('single',) + STRATEGIES}")
    impl = resolve_eval_impl(impl, kernel)
    batched = states if isinstance(states, ParticleState) else \
        stack_states(list(states))
    kw = dict(n_active=n_active, order=order, eps=eps, impl=impl,
              dtype=dtype, devices=devices)
    batched = ensemble_initialize(batched, **kw)
    return ensemble_run(batched, n_steps=n_steps, dt=dt, **kw)
