"""What decides ``correct``: the events that end the chunks of the window's
last whole step, against the float64 reference.

The harness keeps the states at each chunk end of the window's last whole
step.  Under block timesteps the chunks of a macro step are its first
event, the events up to the last tick, and the macro boundary itself; in
lockstep one chunk is one step.  Each particle's state is the one it was
last corrected to, at its own tick ``t_last``.

Each pair of consecutive states ``a`` -> ``b`` may decide the event at
``b``'s tick.  Its exactly known targets are the particles whose next step
from ``a`` ends at that tick (``t_last + period``): no event of theirs lies
in between, so the reference can predict them from ``a``, evaluate both
force passes and correct them, and ``b`` holds what the program made of
them.  The event is checked where every particle it corrected is exactly
known: then every source is too, predicted from ``a`` where the event
corrected it and from ``b`` where it did not (with the predicted
acceleration of the snap pass's sources, as the block scheme defines it).
That holds for the first event after a macro boundary (the finest
occupied level alone is active: its targets are gathered into a small
capacity bucket and scattered back) and for the boundary when the tick
before it ended a chunk (every row active), and for every lockstep step.
``k`` targets are drawn from the seed, evenly over the ``shards``
contiguous row ranges (the chips' target shards).

For each checked event:

* ``acc_err``, ``jerk_err``, ``pot_err``, ``snap_err``: the program's
  stored derivatives against the reference's at the predicted positions,
  max |program - reference| over max |reference| across the sample.
* ``step_err``: the program's corrected positions and velocities against
  the reference's corrector fed the reference's forces, over the step's
  change across the sample (the larger of the two).

The macro boundary's (every lockstep step's) event reports under these
names, a partial tick's with ``.partial`` after them.  A scatter that puts
the gathered targets' forces on other rows fails the partial tick's
numbers.

The window's energy drift is not compared (see PERF.md); ``energy_drift``
reports it from the program's own stored potentials, beside the compared
numbers.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench import reference as ref

NUMBERS = ("acc_err", "jerk_err", "pot_err", "snap_err", "step_err")
PARTIAL = ".partial"


def draw_sample(seed: int, rows: np.ndarray, shards: int,
                k: int) -> np.ndarray:
    """``k`` row indices drawn from ``seed`` among the ``rows`` set, an equal
    share from each of ``shards`` contiguous row ranges."""
    rng = np.random.default_rng([seed, 0xC0FFEE])
    n = rows.shape[0]
    out = []
    for s in range(shards):
        lo, hi = s * n // shards, (s + 1) * n // shards
        pool = lo + np.flatnonzero(rows[lo:hi])
        take = min(k // shards, pool.size)
        if take == 0:
            raise ValueError(f"shard {s} has no exactly known target")
        out.append(rng.choice(pool, take, replace=False))
    return np.sort(np.concatenate(out))


def normwise(x, r) -> float:
    return float(np.abs(x - r).max() / np.abs(r).max())


def event(dev64: ref.Device64, a: Dict[str, np.ndarray],
          b: Dict[str, np.ndarray], n_sub: int, dt_fine: float, seed: int,
          shards: int, k: int) -> Dict[str, float]:
    """The compared numbers of the event at ``b``'s tick, or none where it
    cannot be checked exactly (see the module docstring); ``n_sub`` ticks
    make a whole step of ``n_sub * dt_fine``."""
    sync = int(b["t_last"].max()) == 0
    tick = n_sub if sync else int(b["t_last"].max())
    exact = a["t_last"] + (n_sub >> a["levels"]) == tick
    corrected = np.ones_like(exact) if sync else b["t_last"] == tick
    if not exact.any() or np.any(corrected & ~exact):
        return {}
    idx = draw_sample(seed, exact, shards, k)

    h_a = ((tick - a["t_last"]) * dt_fine)[:, None]
    h_b = ((tick - b["t_last"]) * dt_fine)[:, None]
    xa, va = ref.predict(a, h_a)
    xb, vb = ref.predict(b, h_b)
    src_x = np.where(exact[:, None], xa, xb)
    src_v = np.where(exact[:, None], va, vb)
    m = b["mass"]
    # the snap pass's sources: fresh accelerations where the event
    # evaluated, predicted ones where it did not
    acc_s = ref.predict_acc(b, h_b)
    rows = np.flatnonzero(corrected)
    acc_s[rows] = dev64.forces(src_x[rows], src_v[rows], src_x, src_v, m)[0]
    acc, jerk, pot = dev64.forces(src_x[idx], src_v[idx], src_x, src_v, m)
    snp = dev64.snap(src_x[idx], src_v[idx], acc, src_x, src_v, acc_s, m)
    out = {
        "acc_err": normwise(b["acc"][idx], acc),
        "jerk_err": normwise(b["jerk"][idx], jerk),
        "pot_err": normwise(b["pot"][idx], pot),
        "snap_err": normwise(b["snap"][idx], snp),
    }
    p = {key: a[key][idx] for key in ("pos", "vel", "acc", "jerk", "snap")}
    x1, v1 = ref.correct(p, acc, jerk, snp, h_a[idx])
    out["step_err"] = max(
        float(np.abs(b["pos"][idx] - x1).max() / np.abs(x1 - p["pos"]).max()),
        float(np.abs(b["vel"][idx] - v1).max() / np.abs(v1 - p["vel"]).max()))
    return {(name if sync else name + PARTIAL): v for name, v in out.items()}


def compare(dev64: ref.Device64, states: List[Dict[str, np.ndarray]],
            n_sub: int, dt_fine: float, seed: int, shards: int,
            k: int) -> Dict[str, float]:
    """The compared numbers of every exactly checkable chunk-ending event
    of the whole step whose chunk-end ``states`` the window kept; the
    largest reading of a number where several events report it."""
    out: Dict[str, float] = {}
    for a, b in zip(states, states[1:]):
        for name, v in event(dev64, a, b, n_sub, dt_fine, seed, shards,
                             k).items():
            out[name] = max(v, out.get(name, v))
    return out


def energy_drift(start: Dict[str, np.ndarray],
                 end: Dict[str, np.ndarray]) -> float:
    """|dE/E| between two synchronized states, from the program's own
    stored potentials (not compared; see the module docstring)."""
    def energy(s):
        return float(np.sum(s["mass"] * (0.5 * np.sum(s["vel"] ** 2, 1)
                                          + 0.5 * s["pot"])))

    e0 = energy(start)
    return abs((energy(end) - e0) / e0)
