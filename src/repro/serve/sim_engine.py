"""Continuous-batching simulation server: admit, advance, retire, backfill.

The batch-of-scenarios engine (``repro.sim.ensemble``) keeps the machine
saturated only while a whole ensemble is in flight; this module turns it
into a long-lived service.  A :class:`SimServer` holds a queue of
:class:`SimRequest`\\ s (a validated ``ScenarioSpec`` + stepper + ``t_end``)
and a set of **pods** — padded ``(B, cap)`` ensembles advanced in lockstep —
and on every scheduler tick:

1. **admits** queued requests into free pod slots (bucket-packing policy,
   below), bootstrapping each member's derivatives with the shared
   ``ensemble_initialize`` engine;
2. **advances** every pod by one engine chunk (``chunk_events`` macro-step
   boundaries — the only points where membership may change);
3. **retires** members whose sim time reached their deadline, streaming a
   versioned :class:`~repro.sim.telemetry.RunReport` per run;
4. **backfills** the freed slots from the queue.

**Admission policy (bucket packing).**  Pods are keyed by ``(stepper,
capacity ceiling)`` where the ceiling is
``ops.CapacityPlan.admission_cap(n)`` — the top capacity bucket a request of
``n`` bodies can ever select.  Every member of a pod therefore shares one
bucket-group signature, so the block engine's pre-lowered groups (and the
lowered XLA executables with them) are invariant under admit/retire/
backfill: after :meth:`SimServer.warmup` a steady-state trace runs with
**zero recompiles**, asserted via the ``engine.cache_miss`` counter.
Packing requests into cap-sized pods also launches at most the tiles of a
FIFO shared-``n_max`` pod (property-tested in ``tests/test_sim_server.py``).

**Retirement freezing.**  A retired slot keeps its ``n_active`` (so the
bucket groups never change) and keeps ``t_end <= time`` (so the engine
freezes the member whole); the vmapped engines touch members independently,
which makes batch-mates bit-identical across a neighbour's retire+backfill.

**Suspend/resume.**  :meth:`SimServer.suspend` checkpoints every pod's
array state (state + stepper carries, via ``repro.checkpoint.store``'s
atomic writer) plus a JSON manifest of queue/slot bookkeeping;
:meth:`SimServer.resume` rebuilds an equivalent server that continues
bit-identically (dtype-strict restore — see ``store.restore``).
"""

from __future__ import annotations

import collections
import dataclasses
import json
import math
import os
import time
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import store
from repro.core.nbody import ParticleState, zeros_like_state
from repro.kernels import nbody_force, ops
from repro.obs import metrics as obs_metrics
from repro.sim import ensemble as ens
from repro.sim import scenarios
from repro.sim import telemetry
from repro.sim.scenarios import ScenarioError, ScenarioSpec
from repro.sim.telemetry import RunReport

#: steppers with per-member deadline semantics (the fixed-dt mode shares one
#: global step count and cannot freeze a retired member mid-batch)
SERVABLE_STEPPERS = ("adaptive", "block")

SERVER_META = "server_meta.json"


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    """Engine profile shared by every pod of one server."""

    slots_per_pod: int = 4           # B of each padded ensemble
    n_max: int = 1024                # largest admissible request N
    chunk_events: int = 16           # engine chunk per scheduler tick
    order: int = 6
    eps: float = 1e-7
    impl: str = "xla"
    dtype: str = "fp32"              # kernel precision axis (state is f64)
    eta: float = 0.02
    dt_max: float = 0.0625
    n_levels: int = 8                # block pods
    compaction: str = "none"         # block pods ("none" | "gather")
    block_i: Optional[int] = None
    block_j: Optional[int] = None
    sources: str = "full"            # block pods ("full" | "neighbor")
    neighbor_radius: float = 0.25
    refresh_levels: int = 2
    devices: int = 1

    def validate(self) -> "ServerConfig":
        if self.slots_per_pod < 1:
            raise ValueError(
                f"slots_per_pod={self.slots_per_pod} must be >= 1")
        if self.devices >= 1 and self.slots_per_pod % self.devices:
            raise ValueError(
                f"slots_per_pod={self.slots_per_pod} must be a multiple of "
                f"devices={self.devices} (the batch axis shards evenly)")
        if self.chunk_events < 1:
            raise ValueError(
                f"chunk_events={self.chunk_events} must be >= 1")
        if self.dtype not in ops.DTYPES:
            raise ValueError(
                f"dtype must be one of {ops.DTYPES}; got {self.dtype!r}")
        if self.sources not in ops.SOURCES:
            raise ValueError(
                f"sources must be one of {ops.SOURCES}; got {self.sources!r}")
        if self.sources == "neighbor" and self.compaction != "none":
            raise ValueError(
                "sources='neighbor' gathers its own per-block source "
                "windows; it composes with compaction='none' only")
        if self.refresh_levels < 0:
            raise ValueError(
                f"refresh_levels={self.refresh_levels} must be >= 0")
        plan = self.plan()
        if self.n_max != plan.caps[-1]:
            raise ValueError(
                f"n_max={self.n_max} must be block_i-aligned "
                f"(next aligned value: {plan.caps[-1]})")
        return self

    @property
    def tile_shape(self) -> Tuple[int, int]:
        return (self.block_i or nbody_force.DEFAULT_BLOCK_I,
                self.block_j or nbody_force.DEFAULT_BLOCK_J)

    def plan(self) -> ops.CapacityPlan:
        """The full admission plan (the FIFO baseline's launch schedule)."""
        bi, bj = self.tile_shape
        return ops.CapacityPlan(self.n_max, self.n_max, bi, bj,
                                dtype=self.dtype)


@dataclasses.dataclass(frozen=True)
class SimRequest:
    """One scenario run to serve: what + how + until when."""

    spec: ScenarioSpec
    stepper: str = "adaptive"
    t_end: float = 0.25

    def validate(self, cfg: ServerConfig) -> "SimRequest":
        self.spec.validate()
        if self.spec.n is None:
            raise ScenarioError(
                "SimRequest.spec.n: unset; the server admits fully sized "
                "requests (call spec.with_n(...))")
        if self.spec.n > cfg.n_max:
            raise ValueError(
                f"SimRequest.spec.n: n={self.spec.n} exceeds the server's "
                f"n_max={cfg.n_max}")
        if self.stepper not in SERVABLE_STEPPERS:
            raise ValueError(
                f"SimRequest.stepper: {self.stepper!r} not servable; one of "
                f"{SERVABLE_STEPPERS} (fixed-dt runs share one global step "
                "count and cannot freeze at a per-member deadline)")
        if not self.t_end > 0.0:
            raise ValueError(
                f"SimRequest.t_end: {self.t_end} must be > 0")
        return self

    def describe(self) -> Dict[str, Any]:
        return {"scenario": self.spec.format(), "seed": self.spec.seed,
                "params": dict(self.spec.params), "stepper": self.stepper,
                "t_end": self.t_end}


# --------------------------------------------------------------------------
# admission policy (pure host math; property-tested)
# --------------------------------------------------------------------------
def packed_event_tiles(plan: ops.CapacityPlan, n: int) -> int:
    """Worst-case per-event kernel tiles for ``n`` bodies in its bucket pod.

    The pod's source extent is the request's capacity ceiling, so both grid
    axes shrink with the request — compare :func:`fifo_event_tiles`, where
    the source axis stays at ``n_max``.
    """
    cap = plan.admission_cap(n)
    pod = ops.CapacityPlan(cap, cap, plan.block_i, plan.block_j,
                           n_passes=plan.n_passes, dtype=plan.dtype)
    return int(pod.tiles_by_cap[len(pod.restrict(n).caps) - 1])


def fifo_event_tiles(plan: ops.CapacityPlan, n: int) -> int:
    """Worst-case per-event tiles for ``n`` bodies under FIFO admission into
    one shared ``n_max``-sized pod (the naive policy's launch schedule)."""
    return int(plan.tiles_by_cap[len(plan.restrict(n).caps) - 1])


@dataclasses.dataclass
class _Pending:
    request_id: int
    request: SimRequest
    t_submit: float


@dataclasses.dataclass
class _Slot:
    request_id: int
    request: SimRequest
    t_submit: float
    t_admit: float
    e0: float
    recorder: telemetry.TelemetryRecorder


class Pod:
    """One padded ``(B, cap)`` lockstep ensemble with per-slot deadlines.

    Free slots hold frozen placeholders: their ``n_active`` keeps the last
    occupant's value (bucket groups stay invariant) and their deadline sits
    at/below their sim time (the engine freezes them whole).
    """

    def __init__(self, cfg: ServerConfig, stepper: str, cap: int):
        self.cfg, self.stepper, self.cap = cfg, stepper, cap
        b = cfg.slots_per_pod
        state_dtype = jax.dtypes.canonicalize_dtype(jnp.float64)
        zero = zeros_like_state(jnp.zeros((cap, 3), state_dtype),
                                jnp.zeros((cap, 3), state_dtype),
                                jnp.zeros((cap,), state_dtype))
        self.batched: ParticleState = ens.stack_states([zero] * b)
        self.state_dtype = self.batched.pos.dtype
        self.n_active = np.full(b, cap, np.int64)
        self.t_end = np.zeros(b, np.float64)      # all frozen at t=0
        self.slots: List[Optional[_Slot]] = [None] * b
        self.h_prev = jnp.zeros(b, self.state_dtype)       # adaptive carry
        self.n_taken = jnp.zeros(b, jnp.int32)
        self.carry: Optional[ens.BlockCarry] = None        # block carry

    # ------------------------------------------------------------- geometry
    @property
    def size(self) -> int:
        return self.cfg.slots_per_pod

    def free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def occupied(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is not None]

    def _devices(self):
        return jax.devices()[: self.cfg.devices] \
            if self.cfg.devices > 1 else None

    def _engine_kw(self) -> Dict[str, Any]:
        cfg = self.cfg
        return dict(order=cfg.order, eps=cfg.eps, impl=cfg.impl,
                    dtype=cfg.dtype)

    # ------------------------------------------------------------ lifecycle
    def init_member(self, request: SimRequest
                    ) -> Tuple[ParticleState, float]:
        """Build + pad + bootstrap one member; returns ``(state, e0)``.

        Runs through the same ``ensemble_initialize`` engine as a fresh
        batch, at the pod's padded width, so an admitted member's
        derivatives are bit-identical to a cold ``(1, cap)`` start.
        """
        member = request.spec.build(dtype=self.state_dtype)
        if self.stepper == "block" and self.cfg.sources == "neighbor":
            # sort once at admission (row order is carry-aligned and must
            # never change mid-run) so contiguous index blocks are compact
            # spatial cells and the member's neighbor windows stay tight
            member = ens.spatial_sort_state(
                member, leaf=math.gcd(*self.cfg.tile_shape))
        b1 = ens.stack_states([scenarios.pad_state(member, self.cap)])
        b1 = ens.ensemble_initialize(
            b1, n_active=[request.spec.n], devices=None, **self._engine_kw())
        e0 = float(np.asarray(ens.batched_total_energy(b1))[0])
        return jax.tree_util.tree_map(lambda x: x[0], b1), e0

    def admit(self, pending: _Pending, slot: int, now: float) -> _Slot:
        cfg, req = self.cfg, pending.request
        member, e0 = self.init_member(req)
        self.batched = jax.tree_util.tree_map(
            lambda full, m: full.at[slot].set(m), self.batched, member)
        self.n_active[slot] = req.spec.n
        self.t_end[slot] = req.t_end
        if self.stepper == "adaptive":
            self.h_prev = self.h_prev.at[slot].set(0.0)   # "first step" mark
            self.n_taken = self.n_taken.at[slot].set(0)
        elif self.carry is not None:
            # a never-advanced pod has no carry yet: the batch-wide init at
            # its first advance bootstraps every member, this one included
            self.carry = ens.block_admit_member(
                self.carry, member, slot, req.t_end, eta=cfg.eta,
                dt_max=cfg.dt_max, n_levels=cfg.n_levels)
        recorder = telemetry.TelemetryRecorder({
            **req.describe(), "request_id": pending.request_id,
            "n": req.spec.n, "pod_cap": self.cap, "dtype": cfg.dtype,
            "impl": cfg.impl})
        recorder.record_snapshot(0, 0.0, energy=e0, de_rel=0.0)
        s = _Slot(request_id=pending.request_id, request=req,
                  t_submit=pending.t_submit, t_admit=now, e0=e0,
                  recorder=recorder)
        self.slots[slot] = s
        return s

    def advance(self) -> float:
        """One engine chunk; returns the chunk wall seconds (0.0 if idle)."""
        if not self.occupied():
            return 0.0
        cfg = self.cfg
        kw = dict(n_active=self.n_active, devices=self._devices(),
                  **self._engine_kw())
        t0 = time.perf_counter()
        if self.stepper == "adaptive":
            self.batched, self.h_prev, self.n_taken = \
                ens.ensemble_run_adaptive(
                    self.batched, t_end=self.t_end,
                    n_steps=cfg.chunk_events, h_prev=self.h_prev,
                    n_taken=self.n_taken, eta=cfg.eta, dt_max=cfg.dt_max,
                    **kw)
        else:
            self.batched, self.carry = ens.ensemble_run_block(
                self.batched, t_end=self.t_end, n_events=cfg.chunk_events,
                dt_max=cfg.dt_max, n_levels=cfg.n_levels, carry=self.carry,
                eta=cfg.eta, compaction=cfg.compaction,
                block_i=cfg.block_i, block_j=cfg.block_j,
                sources=cfg.sources, neighbor_radius=cfg.neighbor_radius,
                refresh_levels=cfg.refresh_levels, **kw)
        jax.block_until_ready(self.batched.pos)
        wall = time.perf_counter() - t0
        times = np.asarray(self.batched.time, np.float64)
        steps = self._per_slot_steps()
        for i in self.occupied():
            self.slots[i].recorder.record_step(int(steps[i]),
                                               float(times[i]), wall)
        return wall

    def _per_slot_steps(self) -> np.ndarray:
        if self.stepper == "adaptive":
            return np.asarray(self.n_taken, np.int64)
        if self.carry is None:
            return np.zeros(self.size, np.int64)
        return np.asarray(self.carry.n_events, np.int64)

    def finished_slots(self) -> List[int]:
        times = np.asarray(self.batched.time, np.float64)
        return [i for i in self.occupied() if times[i] >= self.t_end[i]]

    def retire(self, slot: int, now: float) -> RunReport:
        """Finalize one finished member's report and free its slot.

        The member's rows stay in place, frozen: ``n_active`` keeps its
        value (bucket-group invariance) and ``time >= t_end`` keeps the
        engine's freeze select active until a backfill overwrites the rows.
        """
        cfg, s = self.cfg, self.slots[slot]
        n = s.request.spec.n
        e = np.asarray(ens.batched_total_energy(self.batched), np.float64)
        e1 = float(e[slot])
        t_final = float(np.asarray(self.batched.time)[slot])
        steps = int(self._per_slot_steps()[slot])
        if self.stepper == "adaptive":
            pairs = [float(steps) * n * n]
            tiles = None
        else:
            pairs = [float(np.asarray(self.carry.n_pairs)[slot])]
            tiles = [float(np.asarray(self.carry.n_tiles)[slot])]
        de_rel = abs(e1 - s.e0) / max(abs(s.e0), np.finfo(np.float64).tiny)
        s.recorder.record_snapshot(steps, t_final, energy=e1, de_rel=de_rel)
        extra = {"e0": s.e0, "e1": e1, "de_rel": de_rel,
                 "t_final": t_final, "request_id": s.request_id,
                 "pod_cap": self.cap,
                 "admission_latency_s": s.t_admit - s.t_submit,
                 "turnaround_s": now - s.t_submit}
        if self.carry is not None and self.carry.nbr is not None:
            extra["neighbor_refreshes"] = int(
                np.asarray(self.carry.nbr.n_refresh)[slot])
            extra["neighbor_overflows"] = int(
                np.asarray(self.carry.nbr.n_overflow)[slot])
        report = s.recorder.finalize(
            n_bodies=self.cap, ensemble=1, n_devices=max(cfg.devices, 1),
            n_active=[n], per_run_steps=[steps], per_run_pairs=pairs,
            per_run_tiles=tiles, extra=extra)
        self.slots[slot] = None
        return report

    # ----------------------------------------------------- suspend / resume
    def state_tree(self) -> Dict[str, Any]:
        """The pod's array state as one checkpointable pytree."""
        tree: Dict[str, Any] = {
            "state": self.batched,
            "n_active": jnp.asarray(self.n_active, jnp.int32),
            "t_end": jnp.asarray(self.t_end, self.state_dtype),
        }
        if self.stepper == "adaptive":
            tree["h_prev"] = self.h_prev
            tree["n_taken"] = self.n_taken
        elif self.carry is not None:
            tree["carry"] = self.carry
        return tree

    def carry_template(self) -> ens.BlockCarry:
        """A zeros :class:`~repro.sim.ensemble.BlockCarry` with this pod's
        exact shapes/dtypes (the ``like`` tree of a dtype-strict restore)."""
        b, cap, cfg = self.size, self.cap, self.cfg
        bi, bj = cfg.tile_shape
        count_dtype = jax.dtypes.canonicalize_dtype(jnp.float64)
        n_caps = len(ops.CapacityPlan(cap, cap, bi, bj).caps)
        nbr = None
        if self.stepper == "block" and cfg.sources == "neighbor":
            nbt, nsb = -(-cap // bi), -(-cap // bj)
            nbr = ens.NeighborCarry(
                win_idx=jnp.zeros((b, nbt, nsb), jnp.int32),
                win_cnt=jnp.zeros((b, nbt), jnp.int32),
                acc_far=jnp.zeros((b, cap, 3), self.state_dtype),
                jerk_far=jnp.zeros((b, cap, 3), self.state_dtype),
                snap_far=jnp.zeros((b, cap, 3), self.state_dtype),
                pot_far=jnp.zeros((b, cap), self.state_dtype),
                t_ref=jnp.full((b,), -1, jnp.int32),
                n_refresh=jnp.zeros((b,), jnp.int32),
                n_overflow=jnp.zeros((b,), jnp.int32))
        return ens.BlockCarry(
            t_last=jnp.zeros((b, cap), jnp.int32),
            levels=jnp.zeros((b, cap), jnp.int32),
            dt_macro=jnp.zeros(b, self.state_dtype),
            n_pairs=jnp.zeros(b, count_dtype),
            n_events=jnp.zeros(b, jnp.int32),
            n_tiles=jnp.zeros(b, count_dtype),
            bucket_hits=jnp.zeros((b, n_caps), count_dtype),
            nbr=nbr)

    def load_tree(self, tree: Dict[str, Any]) -> None:
        self.batched = tree["state"]
        self.n_active = np.asarray(tree["n_active"], np.int64)
        self.t_end = np.asarray(tree["t_end"], np.float64)
        if self.stepper == "adaptive":
            self.h_prev = tree["h_prev"]
            self.n_taken = tree["n_taken"]
        else:
            self.carry = tree.get("carry")


class SimServer:
    """The long-lived scheduler over a queue and a dict of pods.

    All engine work runs under this server's own metrics registry, so
    ``serve.*`` gauges and the ``engine.cache_miss`` recompile counter are
    attributable to the service (snapshot via :meth:`metrics_snapshot`).
    """

    def __init__(self, cfg: Optional[ServerConfig] = None):
        self.cfg = (cfg or ServerConfig()).validate()
        self.plan = self.cfg.plan()
        self.registry = obs_metrics.MetricsRegistry()
        self.queue: Deque[_Pending] = collections.deque()
        self.pods: Dict[Tuple[str, int], Pod] = {}
        self.reports: List[RunReport] = []
        self._next_id = 0

    # ------------------------------------------------------------ submission
    def submit(self, request: SimRequest,
               now: Optional[float] = None) -> int:
        """Queue one validated request; returns its request id."""
        request.validate(self.cfg)
        self.plan.admission_cap(request.spec.n)   # range check
        rid = self._next_id
        self._next_id += 1
        self.queue.append(_Pending(request_id=rid, request=request,
                                   t_submit=self._now(now)))
        self._set_gauges()
        return rid

    def _now(self, now: Optional[float] = None) -> float:
        return time.perf_counter() if now is None else now

    def pod_for(self, request: SimRequest) -> Pod:
        """Get-or-create the ``(stepper, capacity ceiling)`` pod (the plan
        restriction on admission that keeps engine builds invariant)."""
        key = (request.stepper, self.plan.admission_cap(request.spec.n))
        pod = self.pods.get(key)
        if pod is None:
            pod = self.pods[key] = Pod(self.cfg, key[0], key[1])
        return pod

    # ------------------------------------------------------------- scheduler
    def _admit(self, now: float) -> int:
        """Bucket-packing admission: any queued request whose pod has a free
        slot is admitted (FIFO within each bucket); head-of-line requests
        whose pod is full never block a different bucket's backfill."""
        admitted = 0
        remaining: Deque[_Pending] = collections.deque()
        while self.queue:
            p = self.queue.popleft()
            pod = self.pod_for(p.request)
            slot = pod.free_slot()
            if slot is None:
                remaining.append(p)
                continue
            pod.admit(p, slot, now)
            admitted += 1
            self.registry.counter(
                "serve.requests_admitted", unit="requests").inc()
            self.registry.histogram(
                "serve.admission_latency_s", unit="s",
                help="submit -> admit wait").observe(now - p.t_submit)
        self.queue = remaining
        return admitted

    def step(self, now: Optional[float] = None) -> List[RunReport]:
        """One scheduler tick: admit, advance all pods one chunk, retire
        finished members, backfill the freed slots.  Returns the reports of
        the members retired this tick (also appended to ``self.reports``)."""
        now = self._now(now)
        retired: List[RunReport] = []
        with obs_metrics.use(self.registry):
            self._admit(now)
            for pod in self.pods.values():
                pod.advance()
            for pod in self.pods.values():
                for slot in pod.finished_slots():
                    report = pod.retire(slot, self._now())
                    self.registry.counter(
                        "serve.requests_retired", unit="requests").inc()
                    self.registry.histogram(
                        "serve.turnaround_s", unit="s",
                        help="submit -> retire latency").observe(
                        report["turnaround_s"])
                    retired.append(report)
            self._admit(self._now())   # backfill freed slots immediately
        self._set_gauges()
        self.reports.extend(retired)
        return retired

    def busy(self) -> bool:
        return bool(self.queue) or any(p.occupied()
                                       for p in self.pods.values())

    def run_until_drained(self, max_ticks: int = 100_000
                          ) -> List[RunReport]:
        """Tick until queue and pods are empty; returns the new reports."""
        out: List[RunReport] = []
        ticks = 0
        while self.busy():
            if ticks >= max_ticks:
                raise RuntimeError(
                    f"server not drained after {max_ticks} ticks "
                    f"(queue={len(self.queue)})")
            out.extend(self.step())
            ticks += 1
        return out

    def _set_gauges(self) -> None:
        slots = sum(p.size for p in self.pods.values()) or 1
        live = sum(len(p.occupied()) for p in self.pods.values())
        self.registry.gauge(
            "serve.queue_depth", unit="requests",
            help="requests waiting for a slot").set(float(len(self.queue)))
        self.registry.gauge(
            "serve.slot_occupancy", unit="fraction",
            help="live-slot fraction across pods").set(live / slots)

    # -------------------------------------------------------------- warmup
    def warmup(self, requests: List[SimRequest]) -> float:
        """Pre-lower every engine a request mix will touch.

        For each distinct ``(stepper, cap)`` the mix maps to, builds the pod,
        bootstraps a throwaway member (the ``(1, cap)`` admission path) and
        advances one chunk (the ``(B, cap)`` engines + the energy
        diagnostics).  Steady state after this runs with zero recompiles —
        returns the ``engine.cache_miss`` count the warmup itself spent.
        """
        before = self.cache_misses()
        seen = set()
        with obs_metrics.use(self.registry):
            for req in requests:
                req.validate(self.cfg)
                key = (req.stepper, self.plan.admission_cap(req.spec.n))
                if key in seen:
                    continue
                seen.add(key)
                pod = self.pod_for(req)
                slot = pod.free_slot()
                warm = _Pending(request_id=-1, request=req,
                                t_submit=self._now())
                pod.admit(warm, slot, self._now())     # (1, cap) admission
                pod.advance()                          # (B, cap) engines
                pod.retire(slot, self._now())          # diagnostics + report
                pod.admit(warm, slot, self._now())     # backfill into a carry
                pod.retire(slot, self._now())
                pod.t_end[slot] = 0.0                  # freeze the warm rows
        return self.cache_misses() - before

    def cache_misses(self) -> float:
        """Engine builds charged to this server (fresh XLA lowerings)."""
        metric = self.registry._metrics.get("engine.cache_miss")
        return float(metric.value) if metric is not None else 0.0

    def metrics_snapshot(self) -> Dict[str, Any]:
        return self.registry.snapshot()

    # ----------------------------------------------------- suspend / resume
    def _pod_dir(self, root: str, key: Tuple[str, int]) -> str:
        return os.path.join(root, f"pod_{key[0]}_{key[1]}")

    def suspend(self, ckpt_dir: str, step: int = 0) -> str:
        """Checkpoint every pod's arrays + the scheduler bookkeeping."""
        os.makedirs(ckpt_dir, exist_ok=True)
        pods_meta = {}
        for key, pod in self.pods.items():
            store.save(self._pod_dir(ckpt_dir, key), step, pod.state_tree())
            pods_meta["/".join(map(str, key))] = {
                "stepper": pod.stepper, "cap": pod.cap,
                "has_carry": pod.stepper == "block"
                and pod.carry is not None,
                "slots": [None if s is None else {
                    "request_id": s.request_id,
                    "request": s.request.describe(),
                    "t_submit": s.t_submit, "t_admit": s.t_admit,
                    "e0": s.e0,
                    "meta": s.recorder.meta,
                    "steps": [dataclasses.asdict(x)
                              for x in s.recorder.steps],
                    "snapshots": s.recorder.snapshots,
                } for s in pod.slots],
            }
        meta = {
            "config": dataclasses.asdict(self.cfg),
            "next_id": self._next_id,
            "step": step,
            "queue": [{"request_id": p.request_id,
                       "request": p.request.describe(),
                       "t_submit": p.t_submit} for p in self.queue],
            "pods": pods_meta,
        }
        path = os.path.join(ckpt_dir, SERVER_META)
        with open(path, "w") as f:
            json.dump(meta, f, indent=1)
        return path

    @staticmethod
    def _request_from_meta(d: Dict[str, Any]) -> SimRequest:
        spec = ScenarioSpec.parse(d["scenario"], seed=int(d["seed"]))
        spec = dataclasses.replace(spec, params=dict(d.get("params") or {}))
        return SimRequest(spec=spec, stepper=d["stepper"],
                          t_end=float(d["t_end"]))

    @classmethod
    def resume(cls, ckpt_dir: str) -> "SimServer":
        """Rebuild a suspended server; pods continue bit-identically."""
        with open(os.path.join(ckpt_dir, SERVER_META)) as f:
            meta = json.load(f)
        cfg = ServerConfig(**meta["config"])
        server = cls(cfg)
        server._next_id = int(meta["next_id"])
        for p in meta["queue"]:
            server.queue.append(_Pending(
                request_id=int(p["request_id"]),
                request=cls._request_from_meta(p["request"]),
                t_submit=float(p["t_submit"])))
        for key_s, pm in meta["pods"].items():
            stepper, cap = pm["stepper"], int(pm["cap"])
            pod = Pod(server.cfg, stepper, cap)
            like = pod.state_tree()
            if pm.get("has_carry"):
                like["carry"] = pod.carry_template()
            step, tree = store.restore_latest(
                server._pod_dir(ckpt_dir, (stepper, cap)), like)
            if tree is None:
                raise FileNotFoundError(
                    f"no checkpoint for pod {key_s} under {ckpt_dir}")
            pod.load_tree(tree)
            for i, sm in enumerate(pm["slots"]):
                if sm is None:
                    continue
                recorder = telemetry.TelemetryRecorder(sm["meta"])
                recorder.steps = [telemetry.StepSample(**x)
                                  for x in sm["steps"]]
                recorder.snapshots = list(sm["snapshots"])
                pod.slots[i] = _Slot(
                    request_id=int(sm["request_id"]),
                    request=cls._request_from_meta(sm["request"]),
                    t_submit=float(sm["t_submit"]),
                    t_admit=float(sm["t_admit"]),
                    e0=float(sm["e0"]), recorder=recorder)
            server.pods[(stepper, cap)] = pod
        server._set_gauges()
        return server
