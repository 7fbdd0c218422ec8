"""Run the simulator's main path once on a TPU and check what comes out.

    python chip_smoke.py               # one chip: kernel, run, server
    python chip_smoke.py --four-chip   # four chips: the multi-chip paths only

One chip, three phases in one process, all on the compiled Pallas kernels:

(a) kernel: one acc/jerk/pot and one snap evaluation at N = 65536, checked
    against a float64 numpy oracle on 256 sampled targets x all sources; the
    compiled programs must contain a ``tpu_custom_call``.
(b) run: a ``binary_plummer`` star cluster at N = 65536 through
    ``repro.sim.api.run``: block timesteps, ``compaction="gather"``,
    ``kernel="pallas"``, fp32 kernels at the default 256x512 tiles, one
    ``dt_max`` macro step over ``N_LEVELS`` levels.  |dE/E| must stay within
    the fp32 tier and no engine may be rebuilt after the first chunk.
(c) server: a ``SimServer`` with block pods answers 4 ``binary_plummer``
    requests at N = 4096 and 8192; every report within the fp32 tier, no
    engine rebuilt and no XLA compile after ``warmup``.

``--four-chip`` runs, on four chips, the four strategies as N = 65536 block
runs (``BlockStrategyRunner``) and a few fixed-dt lockstep steps of each
strategy (``SingleRunner``), each against the same seed on one chip in this
process, on the forces at t = 0 and on |dE/E| (within ``DE_MATCH`` of the
one-chip value).

Each phase prints one line of its numbers.  The last line of standard output
is ``{"ok": true, "device": {...}}``, printed only when every check passed on
a TPU; anything else exits non-zero.  Compile and wall seconds are smoke
timings, cold or warm as the compile-cache line says, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N = 65536
SEED = 0
DT_MAX = 0.0625
SAMPLE = 256
#: the repo's fp32 energy tier (``DE_TIERS["fp32"]`` in benchmarks/bench_ci.py)
DE_TIER = 1e-4
#: normwise error bound, max|x - oracle| / max|oracle| over the sample.
#: tests/test_kernels.py holds the kernel to 2e-5 (acc, pot), 2e-4 (jerk) and
#: 5e-4 (snap) at N <= 1024.  The rounding error of an fp32 sum grows like
#: sqrt(N), so N = 65536 is allowed sqrt(65536 / 1024) = 8 times those.
TOL = {"acc": 1.6e-4, "pot": 1.6e-4, "jerk": 1.6e-3, "snap": 4e-3}
#: block hierarchy depth: the smallest step is dt_max / 2**(N_LEVELS - 1).
#: The default 8 levels clamp close encounters at these N to dt_max / 128:
#: binary_plummer N = 8192 then ends one macro step at |dE/E| = 1.6e-4 in
#: fp32 and in fp64 alike (the floor, not the precision); 10 levels give 9e-8
N_LEVELS = 10
#: the four-chip block runs hold several layouts to one trajectory; 9 levels
#: (|dE/E| = 8.7e-6 for that N = 8192 run) keep their five runs shorter
FOUR_CHIP_LEVELS = 9
SERVER_N = (4096, 4096, 8192, 8192)
LOCKSTEP_DT, LOCKSTEP_STEPS = 1.0 / 1024, 3
#: a four-chip run must end at the one-chip |dE/E| within this.  The layouts
#: differ only in the order of the fp32 force sums (bitwise equal at t = 0
#: except ring, 6e-7 normwise), which moves |dE/E| over a few hundred events
#: by far less than 1e-6; the runs measured so far agree to every printed
#: digit.  A shard that lost or double counted sources errs by O(1) in its
#: forces and by orders of magnitude more than 1e-6 in |dE/E|.
DE_MATCH = 1e-6

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    pass


def require(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


class Compiles:
    """XLA compilations and persistent-cache hits and misses, as
    ``jax.monitoring`` reports them."""

    def __init__(self, jax):
        self.count = self.hits = self.misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            self.count += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return self.count, self.seconds, self.hits, self.misses

    def since(self, mark):
        count, seconds, hits, misses = (
            now - then for now, then in zip(self.mark(), mark))
        state = "cold" if not hits else "partly_warm" if misses else "warm"
        cache = f"{state}({hits}_hits,{misses}_misses)"
        return dict(xla_compiles=count, compile_s=f"{seconds:.3f}",
                    compile_cache=cache)


def oracle(pos, vel, mass, acc, idx, eps=1e-7, chunk=32):
    """float64 all-pairs acc, jerk, pot and snap of targets ``idx`` against
    every source, in plain numpy (the formulas of ``kernels/ref.py``)."""
    import numpy as np

    p, v, m, a = (np.asarray(x, np.float64) for x in (pos, vel, mass, acc))
    out = {k: [] for k in ("acc", "jerk", "pot", "snap")}
    for lo in range(0, len(idx), chunk):
        i = idx[lo:lo + chunk]
        dr, dv, da = p[None] - p[i, None], v[None] - v[i, None], \
            a[None] - a[i, None]
        r2 = (dr * dr).sum(-1)
        d2 = r2 + eps * eps
        inv = np.where(r2 > 0, 1.0 / np.sqrt(np.where(r2 > 0, d2, 1.0)), 0.0)
        t = m[None] * inv ** 3
        alpha = (dr * dv).sum(-1) / d2
        beta = ((dv * dv).sum(-1) + (dr * da).sum(-1)) / d2 + alpha * alpha
        pair_a = t[..., None] * dr
        pair_j = t[..., None] * dv - 3.0 * alpha[..., None] * pair_a
        out["acc"].append(pair_a.sum(1))
        out["jerk"].append(pair_j.sum(1))
        out["pot"].append(-(m[None] * inv).sum(1))
        out["snap"].append((t[..., None] * da - 6.0 * alpha[..., None] * pair_j
                            - 3.0 * beta[..., None] * pair_a).sum(1))
    return {k: np.concatenate(x) for k, x in out.items()}


def normwise(x, ref) -> float:
    import numpy as np

    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(x - ref).max() / np.abs(ref).max())


def kernel_phase(jax, impl, kind):
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import nbody_force, ops
    from repro.sim import scenarios

    state = scenarios.make("binary_plummer", N, seed=SEED, validate=False)
    pos, vel, mass = (jnp.asarray(x, jnp.float32)
                      for x in (state.pos, state.vel, state.mass))
    t0 = time.perf_counter()
    pass1 = jax.jit(lambda p, v, m: ops.acc_jerk_pot_rect(
        p, v, p, v, m, impl=impl)).lower(pos, vel, mass).compile()
    pass2 = jax.jit(lambda p, v, a, m: ops.snap_rect(
        p, v, a, p, v, a, m, impl=impl)).lower(pos, vel, pos, mass).compile()
    compile_s = time.perf_counter() - t0
    for name, prog in (("acc/jerk/pot", pass1), ("snap", pass2)):
        require("tpu_custom_call" in prog.as_text(),
                f"kernel: no tpu_custom_call in the compiled {name} program")
    t0 = time.perf_counter()
    acc, jerk, pot = jax.block_until_ready(pass1(pos, vel, mass))
    snap = jax.block_until_ready(pass2(pos, vel, acc, mass))
    wall_s = time.perf_counter() - t0

    idx = np.random.default_rng(SEED).choice(N, SAMPLE, replace=False)
    ref = oracle(pos, vel, mass, acc, idx)
    got = {"acc": acc, "jerk": jerk, "pot": pot, "snap": snap}
    err = {k: normwise(np.asarray(got[k])[idx], ref[k]) for k in TOL}
    tiles = 2 * nbody_force.grid_tiles(N, N, nbody_force.DEFAULT_BLOCK_I,
                                       nbody_force.DEFAULT_BLOCK_J)
    say("kernel", impl=impl, device_kind=kind, n=N,
        compile_s=f"{compile_s:.3f}", wall_s=f"{wall_s:.3f}", events=1,
        grid_tiles_total=tiles, de_rel="n/a", tpu_custom_call="present",
        **{f"err_{k}": f"{v:.3e}(tol {TOL[k]:.1e})" for k, v in err.items()})
    for k, v in err.items():
        require(v <= TOL[k], f"kernel: {k} error {v:.3e} > {TOL[k]:.1e}")


def check_report(rep, impl, phase: str) -> None:
    require(rep["impl"] == impl,
            f"{phase}: report impl {rep['impl']!r} != {impl!r}")
    require(rep["device"]["platform"] == "tpu",
            f"{phase}: report device {rep['device']}")
    require(rep["de_rel"] <= DE_TIER,
            f"{phase}: |dE/E| {rep['de_rel']:.3e} > {DE_TIER:.0e}")


def run_phase(jax, compiles, impl, kind):
    from repro.sim import api

    cfg = api.SimConfig(
        scenario="binary_plummer", n=N, seed=SEED, t_end=DT_MAX,
        dt_max=DT_MAX, n_levels=N_LEVELS, stepper="block",
        compaction="gather", kernel="pallas", dtype="fp32", metrics_interval=1,
        # the float64 numpy virial check of the initial conditions is O(N^2)
        # on one host core (minutes at this N); the kernel phase checks the
        # potential on the device instead
        validate_ic=False)
    mark = compiles.mark()
    t0 = time.perf_counter()
    rep = api.run(cfg)
    phase_s = time.perf_counter() - t0
    # one metrics snapshot per chunk (metrics_interval=1)
    misses = [s["metrics"]["counters"]["engine.cache_miss"]["value"]
              for s in rep["snapshots"] if "metrics" in s]
    chunk_s = rep["step_wall_s"]
    say("run", impl=rep["impl"], device_kind=kind, n=N, t_end=DT_MAX,
        **compiles.since(mark), phase_wall_s=f"{phase_s:.3f}",
        chunks_wall_s=f"{rep['wall_s']:.3f}",
        chunk_wall_s_max=f"{chunk_s['max']:.3f}",
        chunk_wall_s_median=f"{chunk_s['median']:.3f}",
        chunks=len(misses), events=rep["steps"],
        grid_tiles_total=rep["grid_tiles_total"],
        de_rel=f"{rep['de_rel']:.3e}",
        engine_builds_after_first_chunk=misses[-1] - misses[0])
    check_report(rep, impl, "run")
    require(len(misses) >= 2, "run: finished in one chunk; nothing shows "
            "that later chunks reuse the compiled engine")
    require(misses[-1] == misses[0],
            f"run: engine rebuilt after the first chunk ({misses})")
    require(rep["t_final"] >= DT_MAX, f"run: stopped at {rep['t_final']}")


def server_phase(jax, compiles, impl, kind):
    from repro.serve.sim_engine import ServerConfig, SimRequest, SimServer
    from repro.sim.scenarios import ScenarioSpec

    server = SimServer(ServerConfig(
        slots_per_pod=2, n_max=max(SERVER_N), impl=impl,
        compaction="gather", dt_max=DT_MAX, n_levels=N_LEVELS))
    requests = [SimRequest(spec=ScenarioSpec(name="binary_plummer", n=n,
                                             seed=SEED + 1 + i).validate(),
                           stepper="block", t_end=DT_MAX)
                for i, n in enumerate(SERVER_N)]
    mark = compiles.mark()
    t0 = time.perf_counter()
    server.warmup(requests)
    warm_s = time.perf_counter() - t0
    warm = compiles.since(mark)
    builds = server.cache_misses()
    mark = compiles.mark()
    t0 = time.perf_counter()
    for r in requests:
        server.submit(r)
    reports = server.run_until_drained()
    wall_s = time.perf_counter() - t0
    after = compiles.since(mark)
    rebuilt = server.cache_misses() - builds
    say("server", impl=impl, device_kind=kind, n=",".join(map(str, SERVER_N)),
        warmup_compile_s=warm["compile_s"], warmup_wall_s=f"{warm_s:.3f}",
        compile_cache=warm["compile_cache"], wall_s=f"{wall_s:.3f}",
        requests=len(reports), events=sum(r["steps"] for r in reports),
        grid_tiles_total=sum(r["grid_tiles_total"] for r in reports),
        de_rel=",".join(f"{r['de_rel']:.3e}" for r in reports),
        engine_builds_after_warmup=rebuilt,
        xla_compiles_after_warmup=after["xla_compiles"])
    require(len(reports) == len(requests),
            f"server: {len(reports)} reports for {len(requests)} requests")
    for r in reports:
        check_report(r, impl, "server")
    require(rebuilt == 0, f"server: {rebuilt} engine builds after warmup")
    require(after["xla_compiles"] == 0,
            f"server: {after['xla_compiles']} XLA compiles after warmup")


def drive(cfg):
    """build -> step -> collect through the runner ``api.run`` uses; returns
    the forces right after the t = 0 bootstrap, the report, and each
    device's ``bytes_in_use`` while the run's state is still alive."""
    import jax
    import numpy as np

    from repro.obs import metrics as obs_metrics
    from repro.sim import api

    with obs_metrics.use():
        runner = api.get_runner(api.resolve_kind(cfg))
        h = runner.build(cfg)
        st = h.batched if hasattr(h, "batched") else h.state
        forces = {k: np.asarray(getattr(st, k)) for k in TOL}
        while not runner.step(h):
            pass
        in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
                  for d in jax.devices()]
        rep = runner.collect(h)
    return forces, rep, in_use


def four_chip_phase(jax, impl, kind):
    from repro.core.strategies import STRATEGIES
    from repro.sim import api

    devices = jax.devices()
    require(len(devices) == 4, f"--four-chip: {len(devices)} devices")

    def reference(label, cfg1):
        """The one-chip run every four-chip run of a family is held to."""
        t0 = time.perf_counter()
        f1, r1, _ = drive(cfg1)
        check_report(r1, impl, label)
        require(r1["device"]["count"] == 1, f"{label}: not on one device")
        say(label, impl=r1["impl"], device_kind=kind, devices=1,
            wall_s=f"{time.perf_counter() - t0:.3f}", events=r1["steps"],
            grid_tiles_total=r1.get("grid_tiles_total", "n/a"),
            de_rel=f"{r1['de_rel']:.3e}")
        return f1, r1

    def compare(label, ref, cfg4):
        (f1, r1), t0 = ref, time.perf_counter()
        f4, r4, in_use = drive(cfg4)
        err = {k: normwise(f4[k], f1[k]) for k in TOL}
        de_diff = abs(r4["de_rel"] - r1["de_rel"])
        say(label, impl=r4["impl"], device_kind=kind,
            devices=r4["device"]["count"],
            wall_s=f"{time.perf_counter() - t0:.3f}", events=r4["steps"],
            grid_tiles_total=r4.get("grid_tiles_total", "n/a"),
            grid_tiles_per_shard=r4.get("grid_tiles_per_shard", "n/a"),
            de_rel=f"{r4['de_rel']:.3e}", de_rel_1chip=f"{r1['de_rel']:.3e}",
            de_rel_diff=f"{de_diff:.3e}(tol {DE_MATCH:.0e})",
            bytes_in_use=",".join(map(str, in_use)),
            **{f"t0_diff_{k}": f"{v:.3e}" for k, v in err.items()})
        check_report(r4, impl, label)
        require(r4["device"]["count"] == 4, f"{label}: not on 4 devices")
        require(all(b > 0 for b in in_use),
                f"{label}: a device held no memory ({in_use})")
        for k, v in err.items():
            require(v <= TOL[k], f"{label}: t=0 {k} differs by {v:.3e}")
        require(de_diff <= DE_MATCH,
                f"{label}: |dE/E| {r4['de_rel']:.3e} vs {r1['de_rel']:.3e} "
                f"on one chip")

    block = dict(scenario="binary_plummer", n=N, seed=SEED, t_end=DT_MAX,
                 dt_max=DT_MAX, n_levels=FOUR_CHIP_LEVELS, stepper="block",
                 compaction="gather", kernel="pallas", validate_ic=False)
    ref = reference("block/1chip", api.SimConfig(**block))
    for s in STRATEGIES:
        compare(f"block/{s}", ref,
                api.SimConfig(**block, strategy=s, devices=4))
    lock = dict(scenario="binary_plummer", n=N, seed=SEED, dt=LOCKSTEP_DT,
                t_end=LOCKSTEP_STEPS * LOCKSTEP_DT, kernel="pallas",
                validate_ic=False)
    ref = reference("lockstep/1chip", api.SimConfig(**lock))
    for s in STRATEGIES:
        compare(f"lockstep/{s}", ref,
                api.SimConfig(**lock, strategy=s, devices=4))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip paths and their one-chip "
                         "references")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_enable_x64", True)
    from repro.launch import compile_cache
    from repro.sim import ensemble as ens

    cache = compile_cache.enable()
    compiles = Compiles(jax)
    dev = jax.devices()[0]
    platform, kind, count = dev.platform, dev.device_kind, len(jax.devices())
    say("device", platform=platform, device_kind=kind, count=count,
        compile_cache_dir=cache)
    try:
        require(platform == "tpu", f"no TPU: JAX found {platform!r}")
        impl = ens.resolve_eval_impl(None, "pallas")
        require(impl == "pallas", f"kernel='pallas' resolved to {impl!r}")
        if args.four_chip:
            four_chip_phase(jax, impl, kind)
        else:
            kernel_phase(jax, impl, kind)
            run_phase(jax, compiles, impl, kind)
            server_phase(jax, compiles, impl, kind)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
