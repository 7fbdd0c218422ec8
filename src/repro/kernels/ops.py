"""Public jit'd wrappers around the N-body force kernels.

These functions own the (un)packing between the physics-facing layout
(pos/vel/mass arrays, arbitrary N, any float dtype) and the kernel's packed,
block-padded FP32 layout. They dispatch to

* the Pallas TPU kernel (``nbody_force.py``) — compiled on TPU, interpreted
  (``interpret=True``) on CPU for validation, or
* a pure-XLA blocked fallback (``impl="xla"``) — used inside the multi-device
  strategies and the dry-run, where the CPU backend cannot lower Mosaic.

The primitive contract is *rectangular*: a set of N_t targets against a set
of N_s sources (the paper's "i-particles" x "j-particles"). Symmetric
all-pairs is the special case targets == sources; a target that also appears
in the source set self-cancels via the softened-zero-distance guard.

Mixed precision follows the paper: evaluation in FP32, caller keeps FP64
state.

**Mask contract** (tested by ``tests/test_padding_invariance.py``): a source
row with m = 0 contributes *exactly zero* acceleration, jerk, snap and
potential to every target — so callers may freely pad the source set with
zero-mass particles (block alignment here, device-count alignment in
``core.strategies``, ragged-N batches in ``sim.scenarios.build_padded``)
and the active particles' results stay invariant up to FP32 summation
order.

**Target-activity mask** (block timesteps): the rect wrappers take an
optional ``mask_t`` over targets — inactive rows return exact zeros, sources
stay full, and the Pallas kernel skips fully-inactive i-blocks via
``pl.when``.  ``mask_t=None`` is the all-active identity.

**vmap safety**: every wrapper is a pure shape-polymorphic function of its
array arguments, and ``pallas_call`` batches by prepending a grid dimension,
so ``jax.vmap`` lifts both the XLA fallback and the Pallas kernel (compiled
or interpreted) over a leading batch axis — the ensemble engine's path.

**Layer scopes**: the work around the kernels carries ``compaction.*``
names in the HLO's ``op_name`` metadata — ``compaction.perm``,
``compaction.gather.cap<c>``, ``compaction.scatter.cap<c>``,
``compaction.scatter_sources.cap<c>`` and ``compaction.pack`` for the
``(N, 8)`` / ``(8, N)`` operand repacking — while the kernels themselves
are known by their Pallas names (``acc_jerk_pot_packed``, ``snap_packed``).
"""

from __future__ import annotations

import bisect
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import nbody_force, ref

_PAD_COLS = 8
#: the named scope of the operand repacking around the Pallas kernels
PACK = "compaction.pack"
IMPLS = ("pallas", "pallas_interpret", "xla", "pallas_marked")
# pallas_marked: ref math inside a PALLAS_VMEM_REGION named scope — the
# dry-run cost model for the deployed Pallas kernel (Mosaic cannot lower on
# the CPU dry-run host; hlo_analysis applies VMEM-fusion semantics to the
# marked region, and the kernel itself is interpret-validated in tests).

# The precision axis (--dtype): fp64 = the oracle path in core.evaluate,
# fp32 = the historical kernel path, mixed = fp32 I/O with reduced-precision
# per-pair arithmetic and compensated fp32 accumulation (the Tensix
# unpack-fp32 / compute-reduced / pack-fp32 datapath).
DTYPES = ("fp64", "fp32", "mixed")
_COMPUTE_DTYPE = {"fp32": None, "mixed": "bfloat16"}
_IO_BYTES = {"fp64": 8, "fp32": 4, "mixed": 4}
_COMPUTE_BYTES = {"fp64": 8, "fp32": 4, "mixed": 2}

# The source axis (--sources): "full" sweeps every launch over the complete
# source extent (the historical all-pairs path, bit-identical to before the
# axis existed); "neighbor" is the Ahmad-Cohen split — each target block
# sweeps only its gathered neighbor window of source blocks at every event,
# with the far-field remainder refreshed on a slower level (see
# kernels/neighbor.py and docs/ensembles.md "Neighbor scheme").
SOURCES = ("full", "neighbor")


def compute_dtype_for(dtype: str):
    """Kernel compute dtype for a precision-axis name (None = full fp32).

    ``mixed`` uses bfloat16 rather than fp16: the pairwise ``m_j / d^3``
    term overflows fp16's 65504 max on softened close encounters, while
    bf16 keeps fp32's exponent range — the reduced-*mantissa* half of the
    Tensix pattern is what changes the arithmetic.  ``fp64`` never reaches
    the packed kernels; ``core.evaluate``'s oracle branch owns it.
    """
    try:
        return _COMPUTE_DTYPE[dtype]
    except KeyError:
        raise ValueError(
            f"kernel dtype must be 'fp32' or 'mixed' (fp64 runs the oracle "
            f"path in core.evaluate); got {dtype!r}") from None


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def pack_targets(pos, vel, n_pad: int, mask=None):
    """(N,3)x2 -> (n_pad, 8) target block [x y z act vx vy vz 0].

    Column 3 (the slot sources use for mass) carries the target **activity
    mask**: 1.0 = evaluate this row, 0.0 = skip (the kernel scales the row's
    output by it and skips fully-inactive i-blocks).  ``mask=None`` means all
    targets active; block-alignment padding rows are always inactive.
    """
    n = pos.shape[0]
    f32 = jnp.float32
    act = jnp.ones((n,), f32) if mask is None else jnp.asarray(mask, f32)
    cols = [
        pos[:, 0], pos[:, 1], pos[:, 2], act,
        vel[:, 0], vel[:, 1], vel[:, 2], jnp.zeros((n,), f32),
    ]
    tgt = jnp.stack([jnp.asarray(c, f32) for c in cols], axis=1)
    return jnp.pad(tgt, ((0, n_pad - n), (0, 0)))


def pack_sources(pos, vel, mass, n_pad: int):
    """(N,3)x2 + (N,) -> (8, n_pad) source block [x y z m vx vy vz 0] rows."""
    n = pos.shape[0]
    f32 = jnp.float32
    rows = [
        pos[:, 0], pos[:, 1], pos[:, 2], mass,
        vel[:, 0], vel[:, 1], vel[:, 2], jnp.zeros((n,), f32),
    ]
    src = jnp.stack([jnp.asarray(r, f32) for r in rows], axis=0)
    return jnp.pad(src, ((0, 0), (0, n_pad - n)))


def pack_acc_targets(acc, n_pad: int):
    a = jnp.pad(jnp.asarray(acc, jnp.float32), ((0, n_pad - acc.shape[0]), (0, _PAD_COLS - 3)))
    return a


def pack_acc_sources(acc, n_pad: int):
    a = jnp.pad(
        jnp.asarray(acc, jnp.float32).T, ((0, _PAD_COLS - 3), (0, n_pad - acc.shape[0]))
    )
    return a


def _mask_rows(mask_t, *arrays):
    """Zero the rows of each array where the target mask is inactive."""
    m = jnp.asarray(mask_t, arrays[0].dtype)
    return tuple(a * (m[:, None] if a.ndim == 2 else m) for a in arrays)


# --------------------------------------------------------------------------
# active-target compaction (gather/scatter around the rect kernels)
# --------------------------------------------------------------------------
# The block-timestep engine's activity mask lets the kernels *skip* inactive
# i-blocks, but the grid is still launched at the full N/BI target extent.
# Compaction converts the skipped work into launches that never happen:
# gather the active targets into a dense, block-aligned buffer of one of a
# few static capacities, run the rect kernels on a ceil(cap/BI) x N/BJ grid
# (sources stay full, so physics is unchanged), and scatter the outputs back
# to their particle slots.  Every per-target output row is a row-local sum
# over the same source blocks in the same order, so the compacted result is
# bit-for-bit the masked dense result (tests/test_compaction.py).


def capacity_buckets(n: int, block_i: int) -> tuple:
    """Static capacity schedule for ``n`` targets: block-aligned powers of
    two ``(BI, 2*BI, 4*BI, ..., ceil(n/BI)*BI)``.

    Each event picks the smallest bucket holding its active count (see
    :func:`bucket_index`) and dispatches via ``lax.switch`` over kernels
    pre-lowered at these sizes — XLA only ever sees static target extents.
    """
    n_pad = _round_up(n, block_i)
    caps = []
    c = block_i
    while c < n_pad:
        caps.append(c)
        c *= 2
    caps.append(n_pad)
    return tuple(caps)


def bucket_index(n_active, caps) -> jax.Array:
    """Index of the smallest capacity bucket with ``caps[i] >= n_active``.

    ``n_active`` may be traced; ``caps`` is the static ascending schedule
    from :func:`capacity_buckets` (its last entry is ``>= n``, so the result
    is always in range — buckets can never underestimate the active count).
    """
    return jnp.searchsorted(jnp.asarray(caps, jnp.int32),
                            jnp.asarray(n_active, jnp.int32), side="left")


@dataclasses.dataclass(frozen=True)
class CapacityPlan:
    """Static capacity-bucket plan for one compacted launch extent.

    The original compaction layer kept its schedule as a bare tuple computed
    at each call site; distributing compaction turns the schedule into a
    *plan*: the dense target extent being compacted (the full ``N`` on one
    device, the local ``N/P`` inside a shard), the source extent every launch
    sweeps, the tile shape, and the pass count travel together, so
    evaluators, engines and telemetry all agree on what one bucket costs.

    ``caps`` defaults to :func:`capacity_buckets` over ``n_targets``;
    :meth:`restrict` truncates it for a bucket *group* whose members can
    never exceed a known active-count ceiling (the per-member dispatch of
    mixed batches), and :meth:`shard` rescales the whole plan to the
    per-shard local extent (the distributed strategies).  The plan is
    hashable, so it can key lowering caches and ride as a static argument.

    ``n_passes`` counts the kernel launches one event performs at the chosen
    capacity: 2 for the 6th-order Hermite scheme's acc/jerk + snap sweeps
    over resident sources, ``2 * P`` for the ring strategy, whose every pass
    launches once per streamed source shard.
    """

    n_targets: int
    n_sources: int
    block_i: int
    block_j: int
    n_passes: int = 2
    caps: tuple = ()
    dtype: str = "fp32"
    sources: str = "full"

    def __post_init__(self):
        if self.dtype not in DTYPES:
            raise ValueError(
                f"plan dtype must be one of {DTYPES}, got {self.dtype!r}")
        if self.sources not in SOURCES:
            raise ValueError(
                f"plan sources must be one of {SOURCES}, got {self.sources!r}")
        if not self.caps:
            object.__setattr__(
                self, "caps", capacity_buckets(self.n_targets, self.block_i))

    @property
    def io_bytes_per_element(self) -> int:
        """Bytes per staged element (HBM<->VMEM) at this plan's dtype.

        ``mixed`` stages fp32 — the Tensix pattern unpacks/packs fp32 and
        only the in-register arithmetic narrows."""
        return _IO_BYTES[self.dtype]

    @property
    def compute_bytes_per_element(self) -> int:
        """Bytes per in-flight per-pair element at this plan's dtype."""
        return _COMPUTE_BYTES[self.dtype]

    @property
    def tile_io_bytes(self) -> int:
        """Bytes one (i, j) grid tile stages: the (BI, 8) target block and
        (8, BJ) source block in, the (BI, 8) output block out.

        A ``sources="neighbor"`` plan additionally pays the window gather
        per tile: the (8, BJ) source block is read from its resident slot
        and written into the per-target-block gathered window before the
        kernel streams it — the staging cost the Ahmad-Cohen split trades
        for sweeping far fewer j-tiles per event."""
        base = (2 * self.block_i * 8 + 8 * self.block_j) \
            * self.io_bytes_per_element
        if self.sources == "neighbor":
            base += 2 * 8 * self.block_j * self.io_bytes_per_element
        return base

    @property
    def tile_vmem_bytes(self) -> int:
        """Working-set bytes of one (BI, BJ) interaction tile, an occupancy
        model: ~12 live per-pair intermediates at the compute width plus
        the staged blocks at the I/O width — a ``mixed`` plan's tile fits
        in roughly half the fp32 footprint, which is what lets occupancy
        rise at fixed VMEM."""
        live = 12 * self.block_i * self.block_j * self.compute_bytes_per_element
        return live + self.tile_io_bytes

    def tiles_per_vmem(self, vmem_bytes: int) -> int:
        """How many interaction tiles a ``vmem_bytes`` budget holds — the
        occupancy headroom the narrower compute width buys."""
        return max(1, vmem_bytes // self.tile_vmem_bytes)

    @property
    def tiles_by_cap(self) -> tuple:
        """Grid tiles one event enqueues at each capacity (all passes)."""
        j_tiles = -(-self.n_sources // self.block_j)
        return tuple((c // self.block_i) * j_tiles * self.n_passes
                     for c in self.caps)

    @property
    def dense_tiles(self) -> int:
        """Tiles of the uncompacted (masked full-extent) launch this plan
        shrinks — the ``compaction="none"`` baseline."""
        return (nbody_force.grid_tiles(self.n_targets, self.n_sources,
                                       self.block_i, self.block_j)
                * self.n_passes)

    def bucket(self, n_active) -> jax.Array:
        """Traced index of the smallest bucket holding ``n_active``."""
        return bucket_index(n_active, self.caps)

    def tiles(self, idx) -> jax.Array:
        """Traced lookup: tiles one event enqueues at bucket ``idx``."""
        return jnp.asarray(self.tiles_by_cap, jnp.int32)[idx]

    # -- the source-extent schedule (the Ahmad-Cohen neighbor windows) -----
    @property
    def source_caps(self) -> tuple:
        """Static *source*-extent schedule, in rows: block_j-aligned powers
        of two up to the padded full source extent — the target-side
        ``caps`` idea applied to the source axis.  The last bucket **is**
        the full window, so a neighbor window that outgrows every smaller
        bucket dispatches the exact all-pairs sweep: overflow falls back to
        the full window, never to silent truncation (the same
        never-underestimate semantics as :func:`bucket_index`)."""
        return capacity_buckets(self.n_sources, self.block_j)

    def source_bucket(self, n_src_rows) -> jax.Array:
        """Traced index of the smallest source bucket holding
        ``n_src_rows`` gathered source rows."""
        return bucket_index(n_src_rows, self.source_caps)

    @property
    def window_tiles_by_cap(self) -> tuple:
        """Grid tiles one *neighbor* event enqueues at each source-window
        capacity (all passes): every target block sweeps its gathered
        window of ``cap / BJ`` source blocks instead of the full j-extent."""
        i_tiles = -(-self.n_targets // self.block_i)
        return tuple(i_tiles * (c // self.block_j) * self.n_passes
                     for c in self.source_caps)

    def window_tiles(self, idx) -> jax.Array:
        """Traced lookup: tiles one neighbor event enqueues at source
        bucket ``idx``."""
        return jnp.asarray(self.window_tiles_by_cap, jnp.int32)[idx]

    def shard(self, n_shards: int) -> "CapacityPlan":
        """The per-shard local plan: each shard compacts its own
        ``n_targets / n_shards`` target rows (the strategies pad to a device
        multiple before sharding, so the split is exact)."""
        if self.n_targets % n_shards:
            raise ValueError(
                f"{self.n_targets} targets do not split over "
                f"{n_shards} shards")
        return dataclasses.replace(
            self, n_targets=self.n_targets // n_shards, caps=())

    def restrict(self, ceiling: int) -> "CapacityPlan":
        """Plan truncated to the buckets a member with at most ``ceiling``
        active targets can ever select — its pre-lowered bucket group.

        A mixed batch groups members by this ceiling (their static
        ``n_active``): each group dispatches over its own shorter schedule,
        so a quiescent small member never lowers — let alone launches — the
        widest member's buckets.

        ``ceiling`` must lie in ``(0, caps[-1]]`` — the same range
        :meth:`admission_cap` enforces.  A ceiling above the top bucket is a
        caller error (the member could exceed every bucket this plan can
        launch), not a request for the full schedule.
        """
        ceiling = int(ceiling)
        if not 0 < ceiling <= self.caps[-1]:
            raise ValueError(
                f"ceiling={ceiling} outside this plan's capacity range "
                f"(0, {self.caps[-1]}]")
        idx = bisect.bisect_left(self.caps, ceiling)
        return dataclasses.replace(self, caps=self.caps[: idx + 1])

    def admission_cap(self, n_active: int) -> int:
        """Host-side capacity ceiling for admitting a run with ``n_active``
        bodies: the top bucket of :meth:`restrict`, i.e. the smallest pod
        extent whose launch schedule the member can never exceed.

        The serving layer's bucket-packing admission keys pods by this value
        — every member of a pod shares one ceiling, so its bucket groups
        (and with them the lowered engine) stay invariant under admit,
        retire and backfill.
        """
        n_active = int(n_active)
        if not 0 < n_active <= self.caps[-1]:
            raise ValueError(
                f"n_active={n_active} outside this plan's capacity range "
                f"(0, {self.caps[-1]}]")
        return self.restrict(n_active).caps[-1]


def active_first(mask):
    """The permutation that puts the active rows first (a stable argsort of
    the negated mask; row order inside the gathered buffer is irrelevant to
    the row-local kernel math, the permutation only densifies the launch)."""
    with jax.named_scope("compaction.perm"):
        return jnp.argsort(~mask, stable=True)


def compact_targets(perm, cap: int, *rows):
    """Gather the first ``cap`` permuted rows of each per-target array.

    ``perm`` puts active rows first (:func:`active_first`), so with
    ``cap >= n_active`` the gathered buffer holds every active target
    followed by inactive fill rows (whose outputs the activity mask zeroes).
    ``cap`` is static — each capacity bucket is its own lowered computation.
    """
    with jax.named_scope(f"compaction.gather.cap{cap}"):
        idx = perm[: min(cap, perm.shape[0])]
        return tuple(r[idx] for r in rows)


def scatter_outputs(perm, cap: int, n: int, *outs):
    """Scatter compacted kernel outputs back to their particle slots.

    Rows outside the gathered set stay exactly zero — the same contract as
    the masked dense evaluation (inactive targets return exact zeros), so
    ``scatter_outputs`` after :func:`compact_targets` is the identity on
    active rows and zero elsewhere.
    """
    with jax.named_scope(f"compaction.scatter.cap{cap}"):
        idx = perm[: min(cap, perm.shape[0])]
        return tuple(
            jnp.zeros((n,) + o.shape[1:], o.dtype).at[idx].set(o)
            for o in outs
        )


def scatter_sources(perm, cap: int, base, upd, mask_c):
    """Blend compacted pass-1 outputs into a predicted source operand.

    The snap pass needs the acceleration of *every* source at the event
    time: fresh values for the targets the event just evaluated, the
    Taylor-predicted ``base`` rows for everyone else.  Scattering the
    compacted fresh rows (where their compacted activity mask is set)
    straight into ``base`` produces exactly
    ``where(mask, scatter_outputs(upd), base)`` — bit for bit — without
    materializing the dense scattered intermediate: the source-side
    compaction of the snap operand.  Inactive fill rows inside the gathered
    window write their own ``base`` value back, and rows outside the window
    are untouched (an active row is always inside the window when ``cap``
    bounds the active count).
    """
    with jax.named_scope(f"compaction.scatter_sources.cap{cap}"):
        idx = perm[: min(cap, perm.shape[0])]
        m = mask_c[:, None] if upd.ndim == 2 else mask_c
        rows = jnp.where(m, upd.astype(base.dtype), base[idx])
        return base.at[idx].set(rows)


@partial(jax.jit,
         static_argnames=("eps", "block_i", "block_j", "impl", "dtype"))
def acc_jerk_pot_rect(
    pos_t, vel_t, pos_s, vel_s, mass_s,
    *,
    mask_t=None,
    eps: float = 1e-7,
    block_i: int = nbody_force.DEFAULT_BLOCK_I,
    block_j: int = nbody_force.DEFAULT_BLOCK_J,
    impl: str = "pallas",
    dtype: str = "fp32",
):
    """(acc, jerk, pot) of N_t targets due to N_s sources, FP32 I/O.

    ``mask_t`` (optional ``(N_t,)`` activity mask) restricts evaluation to
    the active *targets* — the block-timestep hot path.  Sources stay full.
    Inactive rows return exact zeros; in the Pallas path a fully-inactive
    i-block skips its compute, in the XLA path the mask zeroes the outputs
    (dense XLA cannot skip, so the saving there is accounting-only).
    ``dtype="mixed"`` narrows the per-pair arithmetic (see
    :func:`compute_dtype_for`) in both the Pallas and XLA paths.
    """
    compute_dtype = compute_dtype_for(dtype)
    if impl in ("xla", "pallas_marked"):
        f32 = jnp.float32
        args = (
            jnp.asarray(pos_t, f32), jnp.asarray(vel_t, f32),
            jnp.asarray(pos_s, f32), jnp.asarray(vel_s, f32),
            jnp.asarray(mass_s, f32),
        )
        if impl == "pallas_marked":
            with jax.named_scope("PALLAS_VMEM_REGION"):
                acc, jerk, pot = ref.acc_jerk_pot_rect(
                    *args, eps=eps, compute_dtype=compute_dtype)
        else:
            acc, jerk, pot = ref.acc_jerk_pot_rect(
                *args, eps=eps, compute_dtype=compute_dtype)
        if mask_t is not None:
            acc, jerk, pot = _mask_rows(mask_t, acc, jerk, pot)
        return acc, jerk, pot
    n_t, n_s = pos_t.shape[0], pos_s.shape[0]
    nt_pad = _round_up(n_t, block_i)
    ns_pad = _round_up(n_s, block_j)
    with jax.named_scope(PACK):
        tgt = pack_targets(pos_t, vel_t, nt_pad, mask_t)
        src = pack_sources(pos_s, vel_s, mass_s, ns_pad)
    out = nbody_force.acc_jerk_pot_packed(
        tgt, src, eps=eps, block_i=block_i, block_j=block_j,
        interpret=(impl == "pallas_interpret"),
        compute_dtype=compute_dtype,
    )
    with jax.named_scope(PACK):
        out = out[:n_t]
        return out[:, 0:3], out[:, 3:6], out[:, 6]


@partial(jax.jit,
         static_argnames=("eps", "block_i", "block_j", "impl", "dtype"))
def snap_rect(
    pos_t, vel_t, acc_t, pos_s, vel_s, acc_s, mass_s,
    *,
    mask_t=None,
    eps: float = 1e-7,
    block_i: int = nbody_force.DEFAULT_BLOCK_I,
    block_j: int = nbody_force.DEFAULT_BLOCK_J,
    impl: str = "pallas",
    dtype: str = "fp32",
):
    """Snap of N_t targets due to N_s sources (second Hermite pass), FP32 I/O.

    ``mask_t`` restricts the pass to active targets (see
    :func:`acc_jerk_pot_rect`); ``acc_s`` must then hold the *predicted*
    acceleration of inactive sources (the caller blends evaluated/predicted).
    """
    compute_dtype = compute_dtype_for(dtype)
    if impl in ("xla", "pallas_marked"):
        f32 = jnp.float32
        args = (
            jnp.asarray(pos_t, f32), jnp.asarray(vel_t, f32),
            jnp.asarray(acc_t, f32),
            jnp.asarray(pos_s, f32), jnp.asarray(vel_s, f32),
            jnp.asarray(acc_s, f32), jnp.asarray(mass_s, f32),
        )
        if impl == "pallas_marked":
            with jax.named_scope("PALLAS_VMEM_REGION"):
                snp = ref.snap_rect(*args, eps=eps,
                                    compute_dtype=compute_dtype)
        else:
            snp = ref.snap_rect(*args, eps=eps, compute_dtype=compute_dtype)
        if mask_t is not None:
            (snp,) = _mask_rows(mask_t, snp)
        return snp
    n_t, n_s = pos_t.shape[0], pos_s.shape[0]
    nt_pad = _round_up(n_t, block_i)
    ns_pad = _round_up(n_s, block_j)
    with jax.named_scope(PACK):
        tgt = pack_targets(pos_t, vel_t, nt_pad, mask_t)
        src = pack_sources(pos_s, vel_s, mass_s, ns_pad)
        tacc = pack_acc_targets(acc_t, nt_pad)
        sacc = pack_acc_sources(acc_s, ns_pad)
    out = nbody_force.snap_packed(
        tgt, src, tacc, sacc, eps=eps, block_i=block_i, block_j=block_j,
        interpret=(impl == "pallas_interpret"),
        compute_dtype=compute_dtype,
    )
    with jax.named_scope(PACK):
        return out[:n_t, 0:3]


def acc_jerk_pot(pos, vel, mass, **kw):
    """Symmetric all-pairs (targets == sources) convenience wrapper."""
    return acc_jerk_pot_rect(pos, vel, pos, vel, mass, **kw)


def snap(pos, vel, acc, mass, **kw):
    """Symmetric all-pairs snap convenience wrapper."""
    return snap_rect(pos, vel, acc, pos, vel, acc, mass, **kw)


def default_impl() -> str:
    """Pallas kernels only lower on TPU; interpret everywhere else."""
    return "pallas" if jax.default_backend() == "tpu" else "pallas_interpret"
