"""The plain reference: float64 direct summation and the sixth-order Hermite
step, written from the formulas alone.

Nothing here imports the simulator.  The pair formulas are those of the
float64 numpy oracle the simulator's smoke check used (``acc``, ``jerk``,
``pot``, ``snap`` of ``kernels/ref.py``), written once over an array module
``xp`` so that the same expressions run in numpy, run on the device under
``jax.numpy`` at float64, and can be traced to count their operations
(``work.py``).  The predictor and corrector are Nitadori & Makino (2008),
eqs. 5-6, as the simulator documents them.

Conventions (G = 1): r_ij = r_j - r_i, d2 = r^2 + eps^2, t = m_j / d^3,
alpha = (r.v) / d2, beta = (v.v + r.a) / d2 + alpha^2.  A source at exactly
the target's position contributes nothing (the self pair).
"""

from __future__ import annotations

import numpy as np

#: target rows per device call of the float64 sweeps: 256 rows x 131072
#: sources x 8 B is 256 MiB per temporary
ROWS = 256


def pair_geometry(xp, dx, dy, dz, eps):
    """Softened inverse distance of one pair; 0 for the self pair."""
    r2 = dx * dx + dy * dy + dz * dz
    d2 = r2 + eps * eps
    safe = r2 > 0
    inv = xp.where(safe, 1.0 / xp.sqrt(xp.where(safe, d2, 1.0)), 0.0)
    return d2, inv


def pass1_pair(xp, dr, dv, m, eps):
    """Per-pair acceleration, jerk and potential terms (before the sums)."""
    dx, dy, dz = dr
    vx, vy, vz = dv
    d2, inv = pair_geometry(xp, dx, dy, dz, eps)
    t = m * (inv * inv * inv)
    alpha = (dx * vx + dy * vy + dz * vz) / d2
    pa = (t * dx, t * dy, t * dz)
    pj = tuple(t * v - 3.0 * alpha * a for v, a in zip(dv, pa))
    return pa, pj, m * inv


def pass2_pair(xp, dr, dv, da, m, eps):
    """Per-pair snap term (before the sum)."""
    dx, dy, dz = dr
    vx, vy, vz = dv
    ax, ay, az = da
    d2, inv = pair_geometry(xp, dx, dy, dz, eps)
    t = m * (inv * inv * inv)
    alpha = (dx * vx + dy * vy + dz * vz) / d2
    beta = (vx * vx + vy * vy + vz * vz + dx * ax + dy * ay + dz * az) / d2 \
        + alpha * alpha
    pa = (t * dx, t * dy, t * dz)
    pj = tuple(t * v - 3.0 * alpha * a for v, a in zip(dv, pa))
    return tuple(t * a_ - 6.0 * alpha * j - 3.0 * beta * p
                 for a_, j, p in zip(da, pj, pa))


def _cols(x):
    return tuple(x[..., k] for k in range(3))


def forces(xp, pt, vt, ps, vs, ms, eps):
    """acc, jerk (N_t, 3) and pot (N_t,) of targets due to all sources."""
    dr = _cols(ps[None, :, :] - pt[:, None, :])
    dv = _cols(vs[None, :, :] - vt[:, None, :])
    pa, pj, pp = pass1_pair(xp, dr, dv, ms[None, :], eps)
    acc = xp.stack([c.sum(axis=1) for c in pa], axis=-1)
    jerk = xp.stack([c.sum(axis=1) for c in pj], axis=-1)
    return acc, jerk, -pp.sum(axis=1)


def snap(xp, pt, vt, at, ps, vs, as_, ms, eps):
    """snap (N_t, 3) of targets with accelerations ``at`` due to sources
    with accelerations ``as_``."""
    dr = _cols(ps[None, :, :] - pt[:, None, :])
    dv = _cols(vs[None, :, :] - vt[:, None, :])
    da = _cols(as_[None, :, :] - at[:, None, :])
    ps_ = pass2_pair(xp, dr, dv, da, ms[None, :], eps)
    return xp.stack([c.sum(axis=1) for c in ps_], axis=-1)


def predict(s, h):
    """Taylor prediction of positions and velocities over ``h``."""
    x, v, a, j, sn, c = (s[k] for k in ("pos", "vel", "acc", "jerk", "snap",
                                        "crackle"))
    xp_ = x + h * (v + h * (a / 2 + h * (j / 6 + h * (sn / 24 + h * c / 120))))
    vp_ = v + h * (a + h * (j / 2 + h * (sn / 6 + h * c / 24)))
    return xp_, vp_


def predict_acc(s, h):
    """Taylor prediction of the acceleration over ``h`` (through crackle),
    the snap pass's acceleration of a source that is not evaluated."""
    a, j, sn, c = (s[k] for k in ("acc", "jerk", "snap", "crackle"))
    return a + h * (j + h * (sn / 2 + h * c / 6))


def correct(s, acc, jerk, snp, h):
    """Sixth-order two-point Hermite corrector; returns (pos, vel)."""
    a0, j0, s0 = s["acc"], s["jerk"], s["snap"]
    v1 = s["vel"] + h / 2 * (a0 + acc) + h ** 2 / 10 * (j0 - jerk) \
        + h ** 3 / 120 * (s0 + snp)
    x1 = s["pos"] + h / 2 * (s["vel"] + v1) + h ** 2 / 10 * (a0 - acc) \
        + h ** 3 / 120 * (j0 + jerk)
    return x1, v1


class Device64:
    """The float64 sweeps above, jitted once per shape and run on the
    device in blocks of ``ROWS`` targets, so that they fit beside nothing
    else (the program's state is freed before the reference runs)."""

    def __init__(self, eps: float):
        import jax
        import jax.numpy as jnp

        self.jax, self.jnp = jax, jnp
        self.f_forces = jax.jit(
            lambda pt, vt, ps, vs, ms: forces(jnp, pt, vt, ps, vs, ms, eps))
        self.f_snap = jax.jit(
            lambda pt, vt, at, ps, vs, as_, ms:
            snap(jnp, pt, vt, at, ps, vs, as_, ms, eps))

    def _put(self, *xs):
        return [self.jnp.asarray(np.asarray(x, np.float64)) for x in xs]

    def _blocks(self, fn, targets, sources):
        """``fn`` over row blocks of the targets; numpy float64 out."""
        n = targets[0].shape[0]
        src = self._put(*sources)
        outs = []
        for lo in range(0, n, ROWS):
            tgt = self._put(*(t[lo:lo + ROWS] for t in targets))
            if tgt[0].shape[0] < ROWS:   # one shape: pad the last block
                pad = ROWS - tgt[0].shape[0]
                tgt = [self.jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
                       for t in tgt]
                outs.append(self.jax.tree_util.tree_map(
                    lambda o: np.asarray(o)[:ROWS - pad], fn(*tgt, *src)))
            else:
                outs.append(self.jax.tree_util.tree_map(np.asarray,
                                                        fn(*tgt, *src)))
        if isinstance(outs[0], tuple):
            return tuple(np.concatenate(parts) for parts in zip(*outs))
        return np.concatenate(outs)

    def forces(self, pt, vt, ps, vs, ms):
        return self._blocks(self.f_forces, (pt, vt), (ps, vs, ms))

    def snap(self, pt, vt, at, ps, vs, as_, ms):
        return self._blocks(self.f_snap, (pt, vt, at), (ps, vs, as_, ms))
