"""Pure-jnp oracle for the all-pairs N-body force kernels.

This is the analogue of the paper's "golden reference": a naive, brute-force
direct-summation evaluation of accelerations, jerks (and snaps for the
6th-order Hermite scheme), run at whatever precision the caller requests
(float64 when x64 is enabled reproduces the paper's CPU golden run).

Conventions (G = 1, N-body units):
    acc_i  = sum_j m_j * r_ij / (r^2 + eps^2)^{3/2}
    jerk_i = sum_j m_j * [ v_ij / d3 + q * r_ij / d3 ],  q = -3 (r.v)/d2
    snap_i = sum_j [ m_j * a_ij / d3 - 6 alpha * J_ij - 3 beta * P_ij ]
with r_ij = r_j - r_i, v_ij = v_j - v_i, a_ij = a_j - a_i,
     d2 = r^2 + eps^2, alpha = (r.v)/d2, beta = (v.v + r.a)/d2 + alpha^2,
     P_ij / J_ij the pairwise acc/jerk contributions.

The potential phi_i = -sum_j m_j / sqrt(d2) is returned alongside for energy
diagnostics (paper Fig. 4 validation).

Mixed precision (``compute_dtype``): the Wormhole FPU the paper benchmarks
computes in reduced precision with fp32 I/O (unpack fp32 -> compute fp16 ->
pack fp32).  Passing ``compute_dtype="bfloat16"`` emulates that datapath at
the oracle level: every *per-pair* contribution is rounded through the
compute dtype before accumulation, and the source-axis reductions switch to
a compensated (Neumaier two-sum) summation so the accumulator error stays
O(1 ulp) instead of O(N) — the fp32-accumulate half of the Tensix pattern.
``compute_dtype=None`` (the default) is bit-identical to the historical
full-precision path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def compensated_sum(x, axis: int = 0):
    """Neumaier compensated sum along ``axis``.

    Maintains a running compensation term alongside the accumulator: each
    add performs a two-sum (``t = s + v``; the rounding error of that add is
    recovered exactly as ``(s - t) + v`` or ``(v - t) + s`` depending on
    which operand dominates) and folds the accumulated error back in at the
    end.  The result carries O(1 ulp) error independent of the number of
    summands — the property the kernel-side j-loop compensation mirrors.
    """
    x = jnp.moveaxis(x, axis, 0)

    def add(carry, v):
        s, c = carry
        t = s + v
        err = jnp.where(jnp.abs(s) >= jnp.abs(v), (s - t) + v, (v - t) + s)
        return (t, c + err), None

    # zeros_like(x[0]) carries x's varying manual axes inside a shard_map,
    # so the scan carry keeps one type across iterations
    zero = jnp.zeros_like(x[0])
    (s, c), _ = jax.lax.scan(add, (zero, zero), x)
    return s + c


def _precision_ops(compute_dtype):
    """(round-per-pair, reduce-over-axis) pair for a compute dtype.

    ``None`` keeps the historical full-precision expressions untouched;
    otherwise per-pair terms round through ``compute_dtype`` (fp32 in/out,
    reduced-precision arithmetic — the Tensix unpack/compute/pack shape) and
    reductions run compensated in fp32.
    """
    if compute_dtype is None:
        return (lambda x: x), jnp.sum
    cdt = jnp.dtype(compute_dtype)

    def rnd(x):
        return x.astype(cdt).astype(jnp.float32)

    return rnd, compensated_sum


#: pair-count ceiling of one dense rectangle evaluation (~2^26 pairs keeps
#: the fused (N_t, N_s, 3) temporaries around ~6 GB at fp64).  Larger
#: rectangles stream row chunks of the *target* side through ``lax.map``:
#: each output row is a row-local reduction over the full source axis, so
#: chunking the rows never reorders any sum — rectangles at or under the
#: ceiling take the historical single-fusion path untouched, and a
#: 65536-body sweep peaks at the chunk footprint instead of >100 GiB.
DENSE_PAIR_LIMIT = 1 << 26


def _map_row_chunks(fn, targets, n_s):
    """``fn(*targets)`` evaluated over row chunks of the target-side arrays
    when the rectangle exceeds :data:`DENSE_PAIR_LIMIT` pairs."""
    n_t = targets[0].shape[0]
    if n_t * max(n_s, 1) <= DENSE_PAIR_LIMIT:
        return fn(*targets)
    rows = min(n_t, max(1, DENSE_PAIR_LIMIT // n_s))
    pad = -n_t % rows
    chunked = tuple(
        jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        .reshape((-1, rows) + a.shape[1:]) for a in targets)
    out = jax.lax.map(lambda xs: fn(*xs), chunked)
    return jax.tree_util.tree_map(
        lambda o: o.reshape((-1,) + o.shape[2:])[:n_t], out)


def _pairwise_geometry(pos_t, pos_s, eps):
    """Displacements r_ij = r_j - r_i and softened inverse distances.

    Rectangular contract: axis 0 = target i (N_t), axis 1 = source j (N_s).
    A target that also appears in the source set self-cancels (dr = 0).
    """
    dr = pos_s[None, :, :] - pos_t[:, None, :]
    r2 = jnp.sum(dr * dr, axis=-1)
    d2 = r2 + jnp.asarray(eps, pos_t.dtype) ** 2
    # Self-interactions (exact zero displacement) contribute NOTHING — with
    # softening d2 = eps^2 > 0 there, so the guard must use the unsoftened
    # distance (otherwise the potential gains a spurious -m/eps per particle).
    safe = r2 > 0
    inv_r = jnp.where(safe, 1.0 / jnp.sqrt(jnp.where(safe, d2, 1.0)), 0.0)
    return dr, d2, inv_r


def acc_jerk_pot_rect(pos_t, vel_t, pos_s, vel_s, mass_s, *,
                      eps: float = 1e-7, compute_dtype=None):
    """Brute-force acc/jerk/potential of targets due to sources.

    Args:
        pos_t, vel_t: (N_t, 3) target positions/velocities.
        pos_s, vel_s: (N_s, 3) source positions/velocities.
        mass_s: (N_s,) source masses.
        eps: Plummer softening length (paper Appendix A: 1e-7).
        compute_dtype: reduced per-pair precision (e.g. ``"bfloat16"``) with
            compensated fp32 accumulation; ``None`` = full precision.

    Returns:
        acc (N_t, 3), jerk (N_t, 3), pot (N_t,) in ``pos_t.dtype``.
    """
    rnd, sum_ = _precision_ops(compute_dtype)

    def dense(pt, vt):
        dr, d2, inv_r = _pairwise_geometry(pt, pos_s, eps)
        inv_r3 = inv_r * inv_r * inv_r
        dv = vel_s[None, :, :] - vt[:, None, :]

        t = mass_s[None, :] * inv_r3                 # m_j / d^3
        rv = jnp.sum(dr * dv, axis=-1)               # r_ij . v_ij
        q = -3.0 * rv / jnp.where(d2 > 0, d2, 1.0)   # A_ij * v_r in the paper

        acc = sum_(rnd(t[:, :, None] * dr), axis=1)
        jerk = sum_(rnd(t[:, :, None] * (dv + q[:, :, None] * dr)), axis=1)
        pot = -sum_(rnd(mass_s[None, :] * inv_r), axis=1)
        return acc, jerk, pot

    return _map_row_chunks(dense, (pos_t, vel_t), pos_s.shape[0])


def acc_jerk_pot(pos, vel, mass, *, eps: float = 1e-7, compute_dtype=None):
    """Symmetric all-pairs form (targets == sources)."""
    return acc_jerk_pot_rect(pos, vel, pos, vel, mass, eps=eps,
                             compute_dtype=compute_dtype)


def snap_rect(
    pos_t, vel_t, acc_t, pos_s, vel_s, acc_s, mass_s, *,
    eps: float = 1e-7, compute_dtype=None,
):
    """Brute-force snap of targets due to sources, given accelerations.

    This is the second evaluation pass of the 6th-order Hermite scheme: it
    needs the acceleration of *both* interaction partners (a_ij = a_j - a_i),
    which is why the paper's single-pass device kernel (acc+jerk only) caps at
    4th order; see DESIGN.md §2.2.
    """
    rnd, sum_ = _precision_ops(compute_dtype)

    def dense(pt, vt, at):
        dr, d2, inv_r = _pairwise_geometry(pt, pos_s, eps)
        inv_r3 = inv_r * inv_r * inv_r
        d2s = jnp.where(d2 > 0, d2, 1.0)
        dv = vel_s[None, :, :] - vt[:, None, :]
        da = acc_s[None, :, :] - at[:, None, :]
        mass = mass_s

        t = mass[None, :] * inv_r3
        alpha = jnp.sum(dr * dv, axis=-1) / d2s
        beta = (jnp.sum(dv * dv, axis=-1) + jnp.sum(dr * da, axis=-1)) \
            / d2s + alpha * alpha

        p_pair = t[:, :, None] * dr                                    # A0
        j_pair = t[:, :, None] * dv - 3.0 * alpha[:, :, None] * p_pair  # A1
        s_pair = t[:, :, None] * da - 6.0 * alpha[:, :, None] * j_pair \
            - 3.0 * beta[:, :, None] * p_pair                           # A2
        return sum_(rnd(s_pair), axis=1)

    return _map_row_chunks(dense, (pos_t, vel_t, acc_t), pos_s.shape[0])


def snap(pos, vel, acc, mass, *, eps: float = 1e-7, compute_dtype=None):
    """Symmetric all-pairs snap (targets == sources)."""
    return snap_rect(pos, vel, acc, pos, vel, acc, mass, eps=eps,
                     compute_dtype=compute_dtype)


def acc_jerk_snap_pot(pos, vel, mass, *, eps: float = 1e-7,
                      compute_dtype=None):
    """Full two-pass evaluation: (acc, jerk, snap, pot)."""
    acc, jerk, pot = acc_jerk_pot(pos, vel, mass, eps=eps,
                                  compute_dtype=compute_dtype)
    snp = snap(pos, vel, acc, mass, eps=eps, compute_dtype=compute_dtype)
    return acc, jerk, snp, pot
