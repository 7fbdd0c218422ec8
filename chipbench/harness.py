"""One run of one cell: set-up, the measured window, the check.

The system under test is the simulator's public runner API
(``repro.sim.api``): ``build`` the run, ``step`` it chunk by chunk,
nothing else.  The traffic file says how the run is stepped: block
timesteps with the chunks of ``chunk_events`` in turn from each macro
boundary (its first event, the events up to its last tick, the boundary),
or a shared fixed step with one step per chunk.  Set-up builds the run and
steps it ``warm_steps`` times (from the traffic file) and on to a
whole-step edge, which compiles every program the window uses.  The window
then steps until ``seconds`` have passed and the last chunk ended on an
edge.  Simulated time only advances at edges (a macro boundary, a lockstep
step's end), so the window's rate is its wall seconds over whole simulated
steps.  The states at the chunk ends of the window's last whole step are
kept for the check.
"""

from __future__ import annotations

import contextlib
import dataclasses
import sys
import time
from typing import Any, Dict, Optional

import numpy as np

from chipbench import spec as spec_mod
from chipbench import tracing

#: far past any window: the run never ends or shortens its last step
T_END = 1.0e6
#: the window gives up on reaching an edge after this many times --seconds
EDGE_PATIENCE = 4.0
STATE_KEYS = ("pos", "vel", "acc", "jerk", "snap", "crackle", "pot", "mass")


def say(tag: str, **fields) -> None:
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          file=sys.stderr, flush=True)


def sim_config(api, cell: spec_mod.Cell, seed: int,
               overrides: Optional[Dict[str, Any]] = None):
    """The ``SimConfig`` of a cell: deployment from its configuration,
    stepping from its traffic, ``overrides`` for tests at small sizes.
    The lockstep path takes no tile sizes and runs the kernels' own, which
    are the configuration's."""
    c, t = cell.config, cell.traffic
    kw = dict(
        scenario=c["scenario"], n=c["n"], seed=seed, t_end=T_END,
        scenario_params=dict(c.get("scenario_params", {})), eta=c["eta"],
        order=c["order"], eps=c["eps"], kernel=c["kernel"], dtype=c["dtype"],
        strategy=t["strategy"], devices=cell.chips, validate_ic=False,
        diag_every=t["chunk_events"][0])
    if t["stepper"] == "block":
        kw.update(stepper="block", dt_max=t["dt_max"],
                  n_levels=t["n_levels"], compaction=t["compaction"],
                  block_i=c["block_i"], block_j=c["block_j"])
    else:
        kw.update(stepper="fixed", dt=t["dt"])
    kw.update(overrides or {})
    return api.SimConfig(**kw)


class Handle:
    """Uniform views of the three runner kinds' state between steps, and
    the step itself with the traffic's chunk pattern."""

    def __init__(self, runner, h, chunks=(1,)):
        self.runner, self.h = runner, h
        self.block = hasattr(h, "carry")
        self.chunks, self.turn = tuple(chunks), 0

    def step(self, jnp) -> bool:
        """One ``runner.step`` of the next chunk in the pattern (the runner
        reads its chunk length from the run's config at every step);
        whether it ended on an edge, where the pattern starts again."""
        k = self.chunks[self.turn % len(self.chunks)]
        if self.h.cfg.diag_every != k:
            self.h.cfg = dataclasses.replace(self.h.cfg, diag_every=k)
        self.runner.step(self.h)
        edge = self.on_edge(jnp)
        self.turn = 0 if edge else self.turn + 1
        return edge

    def state(self):
        h = self.h
        return h.batched if hasattr(h, "batched") else h.state

    def time(self) -> float:
        return float(np.min(np.asarray(self.state().time)))

    def refs(self):
        """The current state and carry (immutable, so holding them keeps
        them as they are now)."""
        return self.state(), (self.h.carry if self.block else None)

    def on_edge(self, jnp) -> bool:
        """True when every particle was just corrected (lockstep: always)."""
        if not self.block:
            return True
        return int(jnp.max(self.h.carry.t_last)) == 0

    def counts(self):
        """(pairs per pass, tiles, events) so far; lockstep counts steps."""
        if not self.block:
            return None
        c = self.h.carry
        return (float(np.sum(np.asarray(c.n_pairs, np.float64))),
                float(np.sum(np.asarray(c.n_tiles, np.float64))),
                float(np.max(np.asarray(c.n_events, np.float64))))


def to_host(state, carry) -> Dict[str, np.ndarray]:
    """The first member's state as float64 numpy arrays, with its block
    ``levels`` and ``t_last`` ticks (all 0 in lockstep)."""
    out = {}
    batched = np.ndim(state.time) > 0
    for k in STATE_KEYS:
        x = np.asarray(getattr(state, k), np.float64)
        out[k] = x[0] if batched else x
    out["time"] = float(np.min(np.asarray(state.time)))
    n = out["pos"].shape[0]
    for k in ("levels", "t_last"):
        x = (np.asarray(getattr(carry, k)) if carry is not None
             else np.zeros(n, np.int64))
        out[k] = (x[0] if x.ndim == 2 else x).astype(np.int64)
    return out


@dataclasses.dataclass
class Window:
    wall_s: float
    sim_t: float
    steps: int
    step_walls: list
    pairs: float
    tiles: float
    events: float
    edge_pairs: int          # steps that went edge to edge
    last: Any                # refs at each chunk end of the last whole step,
    #                          from the edge it started on to its end
    start: Any               # refs at the window's start
    compiles: dict


def run_window(handle: Handle, jax, jnp, seconds: float, compiles,
               dt_step: float, trace_ctx=None) -> Window:
    annotate = jax.profiler.TraceAnnotation
    start = handle.refs()
    t_sim0 = handle.time()
    c0 = handle.counts()
    mark = compiles.mark()
    last = None
    walls = []
    n_edge = 0
    seg, t_seg = [start], t_sim0        # chunk ends since the last edge
    with (annotate(tracing.WINDOW) if trace_ctx else contextlib.nullcontext()):
        t0 = time.perf_counter()
        while True:
            ts = time.perf_counter()
            with (annotate(tracing.STEP) if trace_ctx
                  else contextlib.nullcontext()):
                edge = handle.step(jnp)
            walls.append(time.perf_counter() - ts)
            seg.append(handle.refs())
            if edge:
                # one whole step from edge to edge: the check's states
                if handle.time() - t_seg == dt_step:
                    last = seg
                    n_edge += 1
                seg, t_seg = [seg[-1]], handle.time()
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds and edge:
                break
            if elapsed >= EDGE_PATIENCE * seconds:
                say("window", note="no whole-step edge reached; stopping")
                break
        wall = time.perf_counter() - t0
    c1 = handle.counts()
    pairs = tiles = events = 0.0
    if c0 is not None:
        pairs, tiles, events = (b - a for a, b in zip(c0, c1))
    return Window(
        wall_s=wall, sim_t=handle.time() - t_sim0, steps=len(walls),
        step_walls=walls, pairs=pairs, tiles=tiles, events=events,
        edge_pairs=n_edge, last=last, start=start,
        compiles=compiles.since(mark))


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def aarseth_dt(s: Dict[str, np.ndarray], eta: float) -> np.ndarray:
    """The Aarseth criterion without crackle, as the block levels use it."""
    def norm(x):
        return np.sqrt(np.sum(x * x, axis=1))

    a, j, sn = norm(s["acc"]), norm(s["jerk"]), norm(s["snap"])
    num, den = a * sn + j * j, sn * sn
    return eta * np.sqrt(num / np.maximum(den, 1e-30))
