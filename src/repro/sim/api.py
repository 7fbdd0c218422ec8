"""Public simulation API: composable build -> step -> collect runners.

The seed's driver exposed one monolithic ``run(cfg)`` whose private
``_run_single/_run_ensemble/_run_mixed/_run_block_strategy`` dispatch could
only be consumed whole.  This module factors that dispatch into a registry
of :class:`Runner` objects, each splitting its run into three composable
calls:

* ``build(cfg) -> RunHandle`` — construct initial conditions, evaluator and
  telemetry recorder, bootstrap derivatives, record the t=0 snapshot;
* ``step(handle) -> bool`` — advance one diagnostics chunk (the engine's
  macro-step boundary); returns True once the run has finished;
* ``collect(handle) -> RunReport`` — final diagnostics and the versioned
  telemetry report.

:func:`run` recomposes the three into the historical one-shot entry (the
CLI and benchmarks call it via the ``repro.sim.driver`` shim, byte-identical
telemetry); the serving layer (``repro.serve.sim_engine``) is the consumer
the split exists for — a server admits/advances/retires *many* interleaved
runs and cannot give any single one a private blocking loop.

Dispatch is data-driven: :data:`RUNNERS` maps a kind name to its runner, and
:func:`resolve_kind` picks the first registered runner whose ``matches``
accepts the config (registration order is the priority order, mirroring the
seed's if/elif chain exactly).
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hermite, nbody
from repro.core.evaluate import make_evaluator
from repro.core.strategies import STRATEGIES, make_strategy_evaluator
from repro.kernels import nbody_force, ops
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.sim import ensemble as ens
from repro.sim import scenarios, telemetry
from repro.sim.telemetry import RunReport

MAX_STEPS = 200_000


@dataclasses.dataclass(frozen=True)
class SimConfig:
    scenario: str = "plummer"
    n: int = 256
    seed: int = 0
    ensemble: int = 1
    t_end: float = 1.0
    dt: Optional[float] = None       # fixed step (stepper="fixed")
    stepper: Optional[str] = None    # "fixed" | "adaptive" | "block"
    #   (None infers: "fixed" when dt is given, else "adaptive")
    dt_max: float = 0.0625           # coarsest step (adaptive + block)
    n_levels: Optional[int] = 8      # block hierarchy depth (None => auto:
    #   per-member from the initial Aarseth dt distribution, clamped [1, 8])
    compaction: str = "none"         # "none" | "gather" (block stepper only)
    block_i: Optional[int] = None    # kernel tile shape override (block
    block_j: Optional[int] = None    #   stepper; None => kernel defaults)
    sources: str = "full"            # "full" | "neighbor" (Ahmad-Cohen
    #   near/far split; block stepper only — see docs/ensembles.md)
    neighbor_radius: float = 0.25    # AC window radius (simulation length)
    refresh_levels: int = 2          # far-field refresh: levels below macro
    eta: float = 0.02
    order: int = 6
    strategy: str = "single"
    devices: int = 1
    impl: Optional[str] = None
    kernel: Optional[str] = None     # "ref" | "pallas" (excludes impl)
    dtype: str = "fp32"              # "fp64" | "fp32" | "mixed" precision axis
    mix: Optional[Tuple[Tuple[str, int], ...]] = None  # heterogeneous batch
    pad: Optional[int] = None        # padded N_max (None => auto = max N)
    eps: float = 1e-7
    diag_every: int = 16             # steps between diagnostics snapshots
    scenario_params: Mapping[str, Any] = \
        dataclasses.field(default_factory=dict)
    validate_ic: bool = True
    out: Optional[str] = None        # JSON report path (None => don't write)
    trace: Optional[str] = None      # Chrome-trace/Perfetto JSON path
    #   (None => zero-overhead NullTracer; see repro.obs.trace)
    metrics_interval: int = 0        # chunks between in-run metrics-registry
    #   snapshots attached to the diagnostics series (0 => final only)

    def resolved_stepper(self) -> str:
        """Resolve (stepper, dt) to one of ``ensemble.STEPPERS``.

        An explicit ``stepper`` must be consistent with ``dt``: fixed mode
        needs a step, the adaptive/block modes choose their own (``dt_max``
        caps them) — a silently ignored ``dt`` would misreport the run.
        """
        stepper = self.stepper or ("fixed" if self.dt is not None
                                   else "adaptive")
        if stepper not in ens.STEPPERS:
            raise ValueError(
                f"unknown stepper {stepper!r}; one of {ens.STEPPERS}")
        if stepper == "fixed" and self.dt is None:
            raise ValueError("stepper='fixed' needs an explicit dt")
        if stepper != "fixed" and self.dt is not None:
            raise ValueError(
                f"stepper={stepper!r} chooses its own timestep; dt={self.dt} "
                "would be ignored (use dt_max to cap it)")
        if self.compaction != "none" and stepper != "block":
            raise ValueError(
                f"compaction={self.compaction!r} only applies to the block "
                "stepper (the lockstep modes evaluate every target)")
        if (self.block_i or self.block_j) and stepper != "block":
            raise ValueError(
                "block_i/block_j tile overrides only reach the block "
                f"stepper's kernels; stepper={stepper!r} would silently "
                "run at the kernel defaults")
        if self.sources not in ops.SOURCES:
            raise ValueError(
                f"sources must be one of {ops.SOURCES}; "
                f"got {self.sources!r}")
        if self.sources == "neighbor":
            if stepper != "block":
                raise ValueError(
                    "sources='neighbor' is the Ahmad-Cohen split of the "
                    f"block stepper's event loop; stepper={stepper!r} has "
                    "no regular/irregular levels to split")
            if self.compaction != "none":
                raise ValueError(
                    "sources='neighbor' gathers its own per-block source "
                    "windows; it composes with compaction='none' only")
            if self.strategy != "single":
                raise ValueError(
                    "sources='neighbor' runs on the vmapped batch engine "
                    f"only; strategy={self.strategy!r} shards full sources "
                    "(see docs/ensembles.md)")
            if self.mix is not None:
                raise ValueError(
                    "sources='neighbor' shares one window-capacity bucket "
                    "across the batch; a mixed-N ensemble would let its "
                    "widest member size every member's gather")
        if self.refresh_levels < 0:
            raise ValueError(
                f"refresh_levels={self.refresh_levels} must be >= 0")
        if self.n_levels is None and stepper != "block":
            raise ValueError(
                "n_levels=None (--levels auto) sizes the block hierarchy; "
                f"stepper={stepper!r} has no levels to size")
        return stepper

    def meta(self) -> Dict[str, Any]:
        meta = {
            "scenario": self.scenario, "n": self.n, "seed": self.seed,
            "ensemble": self.ensemble, "strategy": self.strategy,
            "t_end": self.t_end, "dt": self.dt, "order": self.order,
            "stepper": self.resolved_stepper(),
            "dtype": self.dtype,
            "params": dict(self.scenario_params),
        }
        if meta["stepper"] == "block":
            meta["dt_max"] = self.dt_max
            meta["n_levels"] = self.n_levels    # None until auto-resolved
            meta["compaction"] = self.compaction
            meta["sources"] = self.sources
            if self.sources == "neighbor":
                meta["neighbor_radius"] = self.neighbor_radius
                meta["refresh_levels"] = self.refresh_levels
        if meta["stepper"] == "adaptive":
            meta["dt_max"] = self.dt_max
        if self.mix is not None:
            meta["scenario"] = "mixed"
            meta["mix"] = [list(m) for m in self.mix]
            meta["pad"] = self.pad
            # the dataclass default n is meaningless for a mix; report the
            # requested N_max so meta agrees with the batch's n_bodies
            meta["n"] = self.pad if self.pad is not None \
                else max(n for _, n in self.mix)
        if self.kernel is not None:
            meta["kernel"] = self.kernel
        return meta


def validate_config(cfg: SimConfig) -> str:
    """Cross-field validation shared by :func:`run` and every ``build``.

    Returns the resolved stepper (the last check, so the error precedence
    matches the seed driver exactly).
    """
    if cfg.ensemble < 1:
        raise ValueError(f"ensemble={cfg.ensemble} must be >= 1")
    if cfg.metrics_interval < 0:
        raise ValueError(
            f"metrics_interval={cfg.metrics_interval} must be >= 0")
    if cfg.dtype not in ops.DTYPES:
        raise ValueError(
            f"dtype must be one of {ops.DTYPES}; got {cfg.dtype!r}")
    if cfg.dtype == "fp64" and (cfg.kernel is not None
                                or cfg.impl not in (None, "fp64")):
        raise ValueError(
            "dtype='fp64' runs the pure-jnp oracle (no kernel); an explicit "
            f"kernel={cfg.kernel!r}/impl={cfg.impl!r} would be silently "
            "ignored")
    if cfg.impl == "fp64" and cfg.dtype == "mixed":
        raise ValueError(
            "impl='fp64' (golden reference) conflicts with dtype='mixed' "
            "(reduced-precision kernel mode)")
    return cfg.resolved_stepper()


def _device_list(cfg: SimConfig):
    devs = jax.devices()
    if cfg.devices > len(devs):
        raise ValueError(
            f"requested {cfg.devices} devices, only {len(devs)} "
            f"{devs[0].platform} device(s) visible (on the CPU, force host "
            "devices with XLA_FLAGS=--xla_force_host_platform_device_count "
            "before importing jax; the sim_run CLI does this under "
            "JAX_PLATFORMS=cpu)")
    return devs[: cfg.devices]


def _build_states(cfg: SimConfig):
    return [
        scenarios.make(cfg.scenario, cfg.n, seed=cfg.seed + i,
                       validate=cfg.validate_ic, **dict(cfg.scenario_params))
        for i in range(cfg.ensemble)
    ]


def _mix_params(cfg: SimConfig) -> Dict[str, Dict[str, Any]]:
    """Distribute flat CLI params over the mix: each scenario takes the keys
    its registry spec accepts; a key no scenario accepts raises (same
    contract as the homogeneous path, where build() rejects it)."""
    flat = dict(cfg.scenario_params)
    out: Dict[str, Dict[str, Any]] = {}
    claimed = set()
    for name, _ in cfg.mix:
        spec = scenarios.get_spec(name)
        kw = {k: v for k, v in flat.items() if k in spec.defaults}
        claimed.update(kw)
        if kw:
            out[name] = kw
    orphans = set(flat) - claimed
    if orphans:
        raise scenarios.ScenarioError(
            f"parameter(s) {sorted(orphans)} not accepted by any scenario "
            f"in the mix {[name for name, _ in cfg.mix]}")
    return out


def _auto_levels(cfg: SimConfig, batched) -> list:
    """Per-member block hierarchy depth from the initial (post-initialize)
    Aarseth dt distribution, clamped to [1, 8] (``--levels auto``)."""
    dt_i = jax.vmap(
        lambda s: hermite.aarseth_dt_particles(s, eta=cfg.eta,
                                               dt_max=cfg.dt_max))(batched)
    depth = jax.vmap(
        lambda d: hermite.auto_n_levels(d, dt_max=cfg.dt_max))(dt_i)
    return [int(d) for d in np.asarray(depth)]


# --------------------------------------------------------------------------
# runner surface
# --------------------------------------------------------------------------
class RunHandle:
    """Mutable in-flight state of one run between :meth:`Runner.step` calls.

    Owned by the runner that built it; runners attach whatever stepper state
    they carry between chunks (engine carries, counters, the recorder) as
    plain attributes.  ``finished`` flips once the run needs no more steps.
    """

    def __init__(self, cfg: SimConfig, kind: str):
        self.cfg = cfg
        self.kind = kind
        self.recorder: Optional[telemetry.TelemetryRecorder] = None
        self.finished = False


class Runner:
    """One run mode: the build/step/collect triple behind a registry kind."""

    kind: str = ""

    def matches(self, cfg: SimConfig) -> bool:
        raise NotImplementedError

    def build(self, cfg: SimConfig) -> RunHandle:
        raise NotImplementedError

    def step(self, handle: RunHandle) -> bool:
        raise NotImplementedError

    def collect(self, handle: RunHandle) -> RunReport:
        raise NotImplementedError


RUNNERS: Dict[str, Runner] = {}


def register_runner(runner: Runner) -> Runner:
    """Register a runner under its ``kind``; registration order is the
    dispatch priority order of :func:`resolve_kind`."""
    if not runner.kind:
        raise ValueError("runner needs a non-empty kind")
    RUNNERS[runner.kind] = runner
    return runner


def resolve_kind(cfg: SimConfig) -> str:
    """Pick the registered kind for a config (first ``matches`` wins)."""
    validate_config(cfg)
    for kind, runner in RUNNERS.items():
        if runner.matches(cfg):
            return kind
    raise ValueError(f"no registered runner accepts {cfg!r}")


def get_runner(kind: str) -> Runner:
    try:
        return RUNNERS[kind]
    except KeyError:
        raise ValueError(
            f"unknown runner kind {kind!r}; "
            f"registered: {tuple(RUNNERS)}") from None


def _single_engine(evaluator, order: int, devices: tuple = ()):
    """A lockstep run's whole P-E-C step as one compiled program
    ``advance(state, dt)``, and its shared Aarseth step
    ``shared_dt(state, eta, dt_max)``.

    ``build`` makes them once per run (``engine.cache_miss.single`` reads
    1 per run), so each run traces the integrator and kernels as they are
    when it is built.  ``dt``, ``eta`` and ``dt_max`` are traced operands:
    a clamped last step or an adaptive dt compiles nothing new.  Under a
    distribution strategy over ``devices`` the step keeps the state in the
    strategy engine's row layout (:func:`ens._strategy_layout`), where
    ``build`` puts it, so its second call meets the layout of its first.
    The state is not donated: callers may hold the state a step started
    from.
    """
    ens._count_engine_build("single")

    @jax.jit
    def advance(state, dt):
        out = hermite.step(state, dt, evaluator, order=order)
        if devices:
            layout = ens._strategy_layout(devices, out.pos.shape[0])[0]
            out = jax.lax.with_sharding_constraint(out, layout)
        return out

    @jax.jit
    def shared_dt(state, eta, dt_max):
        return hermite.aarseth_dt(state, eta=eta, dt_max=dt_max)

    return advance, shared_dt


# --------------------------------------------------------------------------
# single run (per-step telemetry, any strategy, adaptive or fixed dt)
# --------------------------------------------------------------------------
class SingleRunner(Runner):
    kind = "single"

    def matches(self, cfg: SimConfig) -> bool:
        return cfg.mix is None and cfg.ensemble == 1 \
            and cfg.resolved_stepper() != "block"

    def build(self, cfg: SimConfig) -> RunHandle:
        validate_config(cfg)
        h = RunHandle(cfg, self.kind)
        state = _build_states(cfg)[0]
        # None lets make_evaluator pick the backend default; an explicit
        # impl+kernel pair is a conflict (e.g. fp64 vs a kernel switch)
        impl = ens.resolve_eval_impl(cfg.impl, cfg.kernel, default=None)
        devices = ()
        if cfg.strategy == "single":
            if impl == "fp64" or cfg.dtype == "fp64":
                # golden reference: a precision, not a kernel
                impl = "fp64"
                evaluator = make_evaluator(precision="fp64", order=cfg.order,
                                           eps=cfg.eps)
            else:
                impl = impl or ops.default_impl()
                evaluator = make_evaluator(order=cfg.order, eps=cfg.eps,
                                           impl=impl, dtype=cfg.dtype)
        elif cfg.strategy in STRATEGIES:
            if impl == "fp64" or cfg.dtype == "fp64":
                raise ValueError(
                    "fp64 (golden reference) only runs under "
                    "strategy='single'")
            impl = impl or "xla"
            devices = tuple(_device_list(cfg))
            evaluator = make_strategy_evaluator(
                cfg.strategy, devices=devices, order=cfg.order,
                eps=cfg.eps, impl=impl, dtype=cfg.dtype)
        else:
            raise ValueError(f"unknown strategy {cfg.strategy!r}")
        h.advance, h.shared_dt = _single_engine(evaluator, cfg.order,
                                                devices)

        h.recorder = telemetry.TelemetryRecorder({**cfg.meta(), "impl": impl})
        state = hermite.initialize(state, evaluator)
        if devices:
            state = jax.device_put(
                state, ens._strategy_layout(devices, cfg.n)[0])
        jax.block_until_ready(state.pos)
        h.e0 = float(nbody.total_energy(state))
        h.recorder.record_snapshot(0, 0.0, energy=h.e0, de_rel=0.0)
        h.state = state
        h.steps, h.h_prev, h.t = 0, None, float(state.time)
        h.finished = not h.t < cfg.t_end
        return h

    def step(self, h: RunHandle) -> bool:
        if h.finished:
            return True
        cfg = h.cfg
        tracer = obs_trace.get_tracer()
        with tracer.span("sim.step", step=h.steps + 1) as args:
            with tracer.span("sim.engine"):
                if cfg.dt is not None:
                    dt = cfg.dt
                else:
                    dt = float(h.shared_dt(h.state, cfg.eta, cfg.dt_max))
                    if h.h_prev is not None:  # rate-limit dt changes
                        dt = min(max(dt, 0.5 * h.h_prev), 2.0 * h.h_prev)
                    h.h_prev = dt
                dt = min(dt, cfg.t_end - h.t)
                t0 = time.perf_counter()
                state = h.advance(h.state, jnp.asarray(dt, h.state.dtype))
                jax.block_until_ready(state.pos)
            h.state = state
            h.steps += 1
            if args is not None:
                args["dt"] = dt
            with tracer.span("sim.readback"):
                h.t = float(state.time)
            with tracer.span("sim.diagnostics"):
                obs_metrics.registry().counter(
                    "sim.events", unit="events",
                    help="productive member-events (lockstep: member-steps)"
                ).inc()
                h.recorder.record_step(h.steps, h.t,
                                       time.perf_counter() - t0)
                if h.steps % cfg.diag_every == 0:
                    e = float(nbody.total_energy(state))
                    h.recorder.record_snapshot(
                        h.steps, h.t, energy=e,
                        de_rel=abs((e - h.e0) / h.e0))
        h.finished = not (h.t < cfg.t_end and h.steps < MAX_STEPS)
        return h.finished

    def collect(self, h: RunHandle) -> RunReport:
        cfg = h.cfg
        e1 = float(nbody.total_energy(h.state))
        return h.recorder.finalize(
            n_bodies=cfg.n, ensemble=1,
            n_devices=cfg.devices if cfg.strategy != "single" else 1,
            per_run_pairs=[float(h.steps) * cfg.n * cfg.n],
            metrics=obs_metrics.registry().snapshot(),
            extra={"e0": h.e0, "e1": e1,
                   "de_rel": abs((e1 - h.e0) / h.e0),
                   "t_final": float(h.state.time)})


# --------------------------------------------------------------------------
# single block run under a distribution strategy (shard-local compaction)
# --------------------------------------------------------------------------
class BlockStrategyRunner(Runner):
    """One run, its force evaluation sharded by ``cfg.strategy``: each shard
    compacts its own local active targets (``compaction="gather"``) and the
    report carries the per-shard launched tiles as ``grid_tiles_per_shard``.
    """

    kind = "block_strategy"

    def matches(self, cfg: SimConfig) -> bool:
        # a single block run under a distribution strategy shards the
        # *domain* (shard-local compaction, per-shard tile telemetry)
        # — batched block runs shard the batch axis instead, where
        # the strategy label only tags the report
        return cfg.mix is None and cfg.resolved_stepper() == "block" \
            and cfg.ensemble == 1 and cfg.strategy != "single"

    def build(self, cfg: SimConfig) -> RunHandle:
        validate_config(cfg)
        if cfg.strategy not in STRATEGIES:
            raise ValueError(f"unknown strategy {cfg.strategy!r}")
        h = RunHandle(cfg, self.kind)
        impl = ens.resolve_eval_impl(cfg.impl, cfg.kernel)
        if impl == "fp64" or cfg.dtype == "fp64":
            raise ValueError(
                "fp64 (golden reference) only runs under strategy='single'")
        devices = _device_list(cfg)
        state = _build_states(cfg)[0]
        # same tile shape for the bootstrap pass as for the event loop, so a
        # CLI run is bit-for-bit reproducible by ens.evolve_strategy_block
        evaluator = make_strategy_evaluator(
            cfg.strategy, devices=devices, order=cfg.order, eps=cfg.eps,
            impl=impl, dtype=cfg.dtype,
            block_i=cfg.block_i or nbody_force.DEFAULT_BLOCK_I,
            block_j=cfg.block_j or nbody_force.DEFAULT_BLOCK_J)

        h.recorder = telemetry.TelemetryRecorder({**cfg.meta(), "impl": impl})
        state = hermite.initialize(state, evaluator)
        jax.block_until_ready(state.pos)
        h.e0 = float(nbody.total_energy(state))
        h.recorder.record_snapshot(0, 0.0, energy=h.e0, de_rel=0.0)

        n_levels = cfg.n_levels
        if n_levels is None:  # --levels auto, from the initial dt spread
            dt_i = hermite.aarseth_dt_particles(state, eta=cfg.eta,
                                                dt_max=cfg.dt_max)
            n_levels = int(hermite.auto_n_levels(dt_i, dt_max=cfg.dt_max))
            h.recorder.meta["n_levels"] = n_levels
            h.recorder.meta["n_levels_auto"] = [n_levels]
        h.state, h.impl, h.n_levels = state, impl, n_levels
        h.carry = None
        h.done = 0
        h.ev_prev = h.tiles_prev = 0.0
        return h

    def step(self, h: RunHandle) -> bool:
        if h.finished:
            return True
        cfg = h.cfg
        if not h.done * cfg.diag_every < MAX_STEPS:
            h.finished = True
            return True
        tracer = obs_trace.get_tracer()
        reg = obs_metrics.registry()
        with tracer.span("sim.step", chunk=h.done + 1) as args:
            t0 = time.perf_counter()
            with tracer.span("sim.engine"):
                h.state, h.carry = ens.strategy_run_block(
                    h.state, t_end=cfg.t_end, n_events=cfg.diag_every,
                    dt_max=cfg.dt_max, n_levels=h.n_levels, carry=h.carry,
                    eta=cfg.eta, order=cfg.order, eps=cfg.eps, impl=h.impl,
                    strategy=cfg.strategy, compaction=cfg.compaction,
                    block_i=cfg.block_i, block_j=cfg.block_j,
                    devices=cfg.devices, dtype=cfg.dtype)
                jax.block_until_ready(h.state.pos)
            h.done += 1
            with tracer.span("sim.readback"):
                ev_now = float(h.carry.n_events)
                per_shard_now = np.asarray(h.carry.n_tiles, np.float64)
                t_now = float(h.state.time)
            tiles_now = float(per_shard_now.sum())
            if args is not None:
                args.update(events=int(ev_now - h.ev_prev),
                            tiles=tiles_now - h.tiles_prev)
            with tracer.span("sim.diagnostics"):
                reg.counter("sim.events", unit="events").inc(
                    ev_now - h.ev_prev)
                reg.counter("sim.tiles_launched", unit="tiles").inc(
                    tiles_now - h.tiles_prev)
                if per_shard_now.size and per_shard_now.mean() > 0:
                    reg.gauge(
                        "sim.shard_imbalance", unit="ratio",
                        help="max/mean per-shard launched tiles").set(
                        float(per_shard_now.max() / per_shard_now.mean()))
                h.ev_prev, h.tiles_prev = ev_now, tiles_now
                e = float(nbody.total_energy(h.state))
                h.recorder.record_step(int(ev_now), t_now,
                                       time.perf_counter() - t0)
                h.recorder.record_snapshot(
                    int(ev_now), t_now, energy=e,
                    de_rel=abs((e - h.e0) / h.e0),
                    **({"metrics": reg.snapshot()}
                       if cfg.metrics_interval
                       and h.done % cfg.metrics_interval == 0 else {}))
        if t_now >= cfg.t_end:
            h.finished = True
        return h.finished

    def collect(self, h: RunHandle) -> RunReport:
        cfg = h.cfg
        e1 = float(nbody.total_energy(h.state))
        per_shard = [float(t) for t in np.asarray(h.carry.n_tiles)]
        return h.recorder.finalize(
            n_bodies=cfg.n, ensemble=1, n_devices=cfg.devices,
            per_run_steps=[int(h.carry.n_events)],
            per_run_pairs=[float(h.carry.n_pairs)],
            per_run_tiles=[sum(per_shard)], per_shard_tiles=per_shard,
            metrics=obs_metrics.registry().snapshot(),
            extra={"e0": h.e0, "e1": e1,
                   "de_rel": abs((e1 - h.e0) / h.e0),
                   "t_final": float(h.state.time)})


# --------------------------------------------------------------------------
# batched ensembles (lockstep; fixed dt or per-run shared-adaptive dt)
# --------------------------------------------------------------------------
class EnsembleRunner(Runner):
    """Homogeneous ensemble: B copies of one scenario, seeds seed..seed+B-1,
    advanced by the shared lockstep loop (mask-aware engine calls, per-run
    diagnostics and n_active-honest telemetry)."""

    kind = "ensemble"

    def matches(self, cfg: SimConfig) -> bool:
        # the block engine lives in the (vmapped) ensemble path; a
        # single block run is just a B=1 batch
        return cfg.mix is None and (cfg.ensemble > 1
                                    or cfg.resolved_stepper() == "block")

    def _batch(self, cfg: SimConfig):
        batched = ens.stack_states(_build_states(cfg))
        n_active = [cfg.n] * cfg.ensemble
        runs_meta = [{"run": i, "scenario": cfg.scenario, "n": cfg.n,
                      "seed": cfg.seed + i} for i in range(cfg.ensemble)]
        return batched, n_active, runs_meta

    def build(self, cfg: SimConfig) -> RunHandle:
        stepper = validate_config(cfg)
        if cfg.strategy not in STRATEGIES and cfg.strategy != "single":
            raise ValueError(f"unknown strategy {cfg.strategy!r}")
        h = RunHandle(cfg, self.kind)
        batched, n_active, runs_meta = self._batch(cfg)
        if stepper == "block" and cfg.sources == "neighbor":
            # sort once at build (row order is carry-aligned for the whole
            # run) so contiguous index blocks are compact spatial cells and
            # the gathered neighbor windows stay tight
            batched = ens.spatial_sort_batched(
                batched, n_active,
                leaf=math.gcd(cfg.block_i or nbody_force.DEFAULT_BLOCK_I,
                              cfg.block_j or nbody_force.DEFAULT_BLOCK_J))
        impl = ens.resolve_eval_impl(cfg.impl, cfg.kernel)
        devices = _device_list(cfg) if cfg.devices > 1 else None
        h.b = ens.batch_size(batched)
        h.n_max = batched.pos.shape[1]
        h.n_active, h.runs_meta = n_active, runs_meta

        h.recorder = telemetry.TelemetryRecorder({**cfg.meta(), "impl": impl})
        reg = obs_metrics.registry()
        reg.gauge("sim.pad_waste", unit="fraction",
                  help="zero-mass padded slot fraction of the batch").set(
            1.0 - float(sum(n_active)) / (h.b * h.n_max))
        na = jnp.asarray(n_active, jnp.int32)
        h.kw = dict(n_active=na, order=cfg.order, eps=cfg.eps, impl=impl,
                    devices=devices, dtype=cfg.dtype)
        batched = ens.ensemble_initialize(batched, **h.kw)
        jax.block_until_ready(batched.pos)
        h.batched = batched
        h.e0 = np.asarray(ens.batched_total_energy(batched), np.float64)
        h.recorder.record_snapshot(0, 0.0, energy=h.e0.tolist(), de_rel=0.0)
        h.chunks_done = 0

        h.stepper = cfg.resolved_stepper()
        h.per_run_steps = h.per_run_tiles = None
        if h.stepper == "fixed":
            h.n_steps = max(1, int(round(cfg.t_end / cfg.dt)))
            h.done = 0
        elif h.stepper == "adaptive":
            # per-run shared-adaptive dt: each member steps at its own
            # Aarseth criterion; finished members freeze until the whole
            # batch is done
            h.h_prev = h.n_taken = None
            h.done = 0
            h.ev_prev = 0.0
        else:
            # hierarchical block timesteps: each member's active block is
            # evaluated per event; the engine *measures* its pairwise work
            # and the kernel grid tiles it launched (what compaction shrinks)
            n_levels = cfg.n_levels
            if n_levels is None:  # auto: size each member's hierarchy from
                # its initial Aarseth dt distribution, run at the deepest
                per_member = _auto_levels(cfg, batched)
                n_levels = max(per_member)
                h.recorder.meta["n_levels"] = n_levels
                h.recorder.meta["n_levels_auto"] = per_member
            h.n_levels = n_levels
            h.plan = ops.CapacityPlan(
                h.n_max, h.n_max, cfg.block_i or nbody_force.DEFAULT_BLOCK_I,
                cfg.block_j or nbody_force.DEFAULT_BLOCK_J, dtype=cfg.dtype)
            h.mask = np.arange(h.n_max)[None, :] \
                < np.asarray(n_active)[:, None]
            h.carry = None
            h.done = 0
            h.ev_prev = np.zeros(h.b)
            h.tiles_prev = np.zeros(h.b)
            h.pairs_prev = np.zeros(h.b)
            h.bound_total = 0.0
            h.nref_prev = h.nov_prev = 0.0
        return h

    def _snapshot(self, h: RunHandle, done, t_sim, wall) -> None:
        # one wall sample per chunk: lockstep ensembles sync at chunk ends
        cfg = h.cfg
        h.chunks_done += 1
        h.recorder.record_step(done, t_sim, wall)
        e = np.asarray(ens.batched_total_energy(h.batched), np.float64)
        h.recorder.record_snapshot(
            done, t_sim, energy=e.tolist(),
            de_rel=float(np.abs((e - h.e0) / h.e0).max()),
            **({"metrics": obs_metrics.registry().snapshot()}
               if cfg.metrics_interval
               and h.chunks_done % cfg.metrics_interval == 0 else {}))

    def step(self, h: RunHandle) -> bool:
        if h.finished:
            return True
        step_fn = {"fixed": self._step_fixed, "adaptive": self._step_adaptive,
                   "block": self._step_block}[h.stepper]
        with obs_trace.get_tracer().span(
                "sim.step", chunk=h.chunks_done + 1) as args:
            return step_fn(h, args)

    def _step_fixed(self, h: RunHandle, args) -> bool:
        cfg = h.cfg
        tracer = obs_trace.get_tracer()
        chunk = min(cfg.diag_every, h.n_steps - h.done)
        t0 = time.perf_counter()
        with tracer.span("sim.engine"):
            h.batched = ens.ensemble_run(h.batched, n_steps=chunk, dt=cfg.dt,
                                         **h.kw)
            jax.block_until_ready(h.batched.pos)
        h.done += chunk
        if args is not None:
            args["events"] = chunk * h.b
        with tracer.span("sim.diagnostics"):
            obs_metrics.registry().counter(
                "sim.events", unit="events").inc(chunk * h.b)
            self._snapshot(h, h.done, h.done * cfg.dt,
                           time.perf_counter() - t0)
        h.finished = h.done >= h.n_steps
        return h.finished

    def _step_adaptive(self, h: RunHandle, args) -> bool:
        cfg = h.cfg
        tracer = obs_trace.get_tracer()
        t0 = time.perf_counter()
        with tracer.span("sim.engine"):
            h.batched, h.h_prev, h.n_taken = ens.ensemble_run_adaptive(
                h.batched, t_end=cfg.t_end, n_steps=cfg.diag_every,
                h_prev=h.h_prev, n_taken=h.n_taken, eta=cfg.eta,
                dt_max=cfg.dt_max, **h.kw)
            jax.block_until_ready(h.batched.pos)
        h.done += 1
        with tracer.span("sim.readback"):
            n_taken = np.asarray(h.n_taken, np.float64)
            t_min = float(np.min(np.asarray(h.batched.time)))
        ev_now = float(n_taken.sum())
        if args is not None:
            args["events"] = int(ev_now - h.ev_prev)
        with tracer.span("sim.diagnostics"):
            obs_metrics.registry().counter(
                "sim.events", unit="events").inc(ev_now - h.ev_prev)
            h.ev_prev = ev_now
            self._snapshot(h, int(np.max(n_taken)), t_min,
                           time.perf_counter() - t0)
        h.finished = (t_min >= cfg.t_end
                      or h.done * cfg.diag_every >= MAX_STEPS)
        return h.finished

    def _step_block(self, h: RunHandle, args) -> bool:
        cfg = h.cfg
        tracer = obs_trace.get_tracer()
        reg = obs_metrics.registry()
        batch_hits = cfg.compaction == "gather"
        t0 = time.perf_counter()
        with tracer.span("sim.engine"):
            h.batched, h.carry = ens.ensemble_run_block(
                h.batched, t_end=cfg.t_end, n_events=cfg.diag_every,
                dt_max=cfg.dt_max, n_levels=h.n_levels, carry=h.carry,
                eta=cfg.eta, compaction=cfg.compaction,
                block_i=cfg.block_i, block_j=cfg.block_j,
                sources=cfg.sources, neighbor_radius=cfg.neighbor_radius,
                refresh_levels=cfg.refresh_levels, **h.kw)
            jax.block_until_ready(h.batched.pos)
        h.done += 1
        nbr = h.carry.nbr
        with tracer.span("sim.readback"):
            ev = np.asarray(h.carry.n_events, np.float64)
            tiles = np.asarray(h.carry.n_tiles, np.float64)
            pairs = np.asarray(h.carry.n_pairs, np.float64)
            t_min = float(np.min(np.asarray(h.batched.time)))
            if batch_hits:
                hits = np.asarray(h.carry.bucket_hits, np.float64)
            if nbr is not None:
                nref = float(np.asarray(nbr.n_refresh, np.float64).sum())
                nov = float(np.asarray(nbr.n_overflow, np.float64).sum())
                wc = np.asarray(nbr.win_cnt, np.float64)
        ev_d, tiles_d = ev - h.ev_prev, tiles - h.tiles_prev
        pairs_d = pairs - h.pairs_prev
        if args is not None:
            args.update(events=int(ev_d.sum()), tiles=float(tiles_d.sum()))
        with tracer.span("sim.diagnostics"):
            reg.counter("sim.events", unit="events").inc(float(ev_d.sum()))
            reg.counter("sim.tiles_launched", unit="tiles").inc(
                float(tiles_d.sum()))
            reg.counter(
                "sim.tiles_dense_baseline", unit="tiles",
                help="what compaction='none' would have enqueued").inc(
                float(ev_d.sum()) * h.plan.dense_tiles)
            # analytic a-priori tile bound: occupancy entry 0 (every real
            # particle) is the largest active set any tick of the block
            # schedule can see, so per member and event the launch can
            # never exceed the tiles of occ[0]'s capacity bucket
            occ0 = np.asarray(jax.vmap(
                lambda lv, m: hermite.block_level_occupancy(
                    lv, n_levels=h.n_levels, mask=m))(
                        h.carry.levels, jnp.asarray(h.mask)))[:, 0]
            for i in range(h.b):
                per_event = (int(h.plan.tiles(h.plan.bucket(
                    int(occ0[i])))) if cfg.compaction == "gather"
                             else h.plan.dense_tiles)
                h.bound_total += ev_d[i] * per_event
            reg.gauge("sim.tiles_occupancy_bound", unit="tiles",
                      help="analytic bound; launched <= bound").set(
                h.bound_total)
            for i in range(h.b):
                if ev_d[i] > 0 and h.n_active[i] > 0:
                    reg.histogram(
                        "sim.active_fraction", unit="fraction",
                        help="per-chunk mean active-target fraction"
                    ).observe(pairs_d[i]
                              / (ev_d[i] * float(h.n_active[i]) ** 2))
            if batch_hits:
                reg.gauge(
                    "sim.bucket_hits", unit="hits",
                    help="capacity-bucket switch hit counts (full "
                         "schedule, summed over members)").set(
                    [float(x) for x in hits.sum(axis=0)])
            if nbr is not None:
                reg.counter(
                    "sim.neighbor_refreshes", unit="refreshes",
                    help="Ahmad-Cohen window rebuilds (far-field "
                         "re-anchors, summed over members)").inc(
                    nref - h.nref_prev)
                reg.counter(
                    "sim.neighbor_overflow", unit="fallbacks",
                    help="refreshes whose widest active window fit no "
                         "bucket below the full source extent").inc(
                    nov - h.nov_prev)
                h.nref_prev, h.nov_prev = nref, nov
                nsb = nbr.win_idx.shape[-1]
                blk_valid = (np.arange(wc.shape[1])[None, :]
                             * h.plan.block_i) \
                    < np.asarray(h.n_active)[:, None]
                occ_hist = reg.histogram(
                    "sim.neighbor_occupancy", unit="fraction",
                    help="per-target-block neighbor window fraction of "
                         "the full source extent (sampled per chunk)")
                for v in (wc[blk_valid] / nsb).tolist():
                    occ_hist.observe(v)
            h.ev_prev, h.tiles_prev, h.pairs_prev = ev, tiles, pairs
            self._snapshot(h, int(np.max(ev)), t_min,
                           time.perf_counter() - t0)
        h.finished = (t_min >= cfg.t_end
                      or h.done * cfg.diag_every >= MAX_STEPS)
        return h.finished

    def collect(self, h: RunHandle) -> RunReport:
        cfg = h.cfg
        if h.stepper == "fixed":
            t_final = h.n_steps * cfg.dt
            per_run_pairs = [float(h.n_steps) * a * a for a in h.n_active]
            per_run_steps = per_run_tiles = None
        elif h.stepper == "adaptive":
            per_run_steps = [int(c) for c in np.asarray(h.n_taken)]
            t_final = float(np.min(np.asarray(h.batched.time)))
            per_run_pairs = [float(s) * a * a
                             for s, a in zip(per_run_steps, h.n_active)]
            per_run_tiles = None
        else:
            per_run_steps = [int(c) for c in np.asarray(h.carry.n_events)]
            t_final = float(np.min(np.asarray(h.batched.time)))
            per_run_pairs = [float(p) for p in np.asarray(h.carry.n_pairs)]
            per_run_tiles = [float(t) for t in np.asarray(h.carry.n_tiles)]

        e1 = np.asarray(ens.batched_total_energy(h.batched), np.float64)
        de = np.abs((e1 - h.e0) / h.e0)
        virial = np.asarray(ens.batched_virial_ratio(h.batched), np.float64)
        runs = [{**h.runs_meta[i], "e0": float(h.e0[i]), "e1": float(e1[i]),
                 "de_rel": float(de[i]), "virial_ratio": float(virial[i]),
                 "force_evals": per_run_pairs[i],
                 **({"steps": per_run_steps[i]} if per_run_steps else {}),
                 **({"grid_tiles": per_run_tiles[i]}
                    if per_run_tiles else {})}
                for i in range(h.b)]
        extra = {"e0": h.e0.tolist(), "e1": e1.tolist(),
                 "de_rel": float(de.max()), "t_final": t_final,
                 "runs": runs}
        if h.stepper == "block" and h.carry.nbr is not None:
            nref = np.asarray(h.carry.nbr.n_refresh, np.int64)
            nov = np.asarray(h.carry.nbr.n_overflow, np.int64)
            for i, r in enumerate(runs):
                r["neighbor_refreshes"] = int(nref[i])
                r["neighbor_overflows"] = int(nov[i])
            extra["neighbor_refreshes"] = int(nref.sum())
            extra["neighbor_overflows"] = int(nov.sum())
        return h.recorder.finalize(
            n_bodies=h.n_max, ensemble=h.b, n_devices=max(cfg.devices, 1),
            n_active=h.n_active, per_run_steps=per_run_steps,
            per_run_pairs=per_run_pairs, per_run_tiles=per_run_tiles,
            metrics=obs_metrics.registry().snapshot(),
            extra=extra)


class MixedRunner(EnsembleRunner):
    """Heterogeneous padded ensemble: one rectangular (B, N_max, ...) batch
    of different scenarios/N, zero-mass padding, per-run n_active mask."""

    kind = "mixed"

    def matches(self, cfg: SimConfig) -> bool:
        return cfg.mix is not None

    def _batch(self, cfg: SimConfig):
        specs = scenarios.make_mix(cfg.mix, seed=cfg.seed,
                                   repeat=cfg.ensemble,
                                   params=_mix_params(cfg))
        batched, n_active = scenarios.build_padded(
            specs, n_max=cfg.pad, validate=cfg.validate_ic)
        runs_meta = [{"run": i, "scenario": s.name, "n": s.n, "seed": s.seed}
                     for i, s in enumerate(specs)]
        return batched, [int(a) for a in np.asarray(n_active)], runs_meta


# registration order IS the dispatch priority (mirrors the seed's if/elif)
register_runner(MixedRunner())
register_runner(BlockStrategyRunner())
register_runner(EnsembleRunner())
register_runner(SingleRunner())


# --------------------------------------------------------------------------
# the recomposed one-shot entry
# --------------------------------------------------------------------------
def run(cfg: SimConfig) -> RunReport:
    """Run one configuration end-to-end and return its telemetry report.

    The monolithic convenience over the composable surface: resolve the
    runner, ``build``, drive ``step`` to completion, ``collect``.  Each run
    gets its own :class:`repro.obs.metrics.MetricsRegistry` (scoped as the
    module-current registry so the engine layers' emissions land in it)
    whose snapshot rides in the report under ``metrics``; with ``cfg.trace``
    a live :class:`repro.obs.trace.SpanTracer` is installed and the
    Chrome-trace JSON exported on completion (``trace_path`` in the report).
    """
    validate_config(cfg)
    tracer = obs_trace.SpanTracer() if cfg.trace else obs_trace.NullTracer()
    prev_tracer = obs_trace.set_tracer(tracer)
    try:
        with obs_metrics.use():
            obs_metrics.registry().gauge(
                "sim.dtype", unit="enum",
                help="precision axis of the run's force kernels").set(
                cfg.dtype)
            obs_metrics.registry().gauge(
                "sim.sources", unit="enum",
                help="force-source mode (full all-pairs vs Ahmad-Cohen "
                     "neighbor windows)").set(cfg.sources)
            runner = get_runner(resolve_kind(cfg))
            handle = runner.build(cfg)
            while not runner.step(handle):
                pass
            report = runner.collect(handle)
    finally:
        obs_trace.set_tracer(prev_tracer)
    if cfg.trace:
        report["trace_path"] = tracer.export(cfg.trace)
    if cfg.out:
        telemetry.write_report(report, cfg.out)
        report["report_path"] = cfg.out
    return report
