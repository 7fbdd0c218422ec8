"""Measure a TPU's float32 vector (VPU) rate: the peak the force kernels'
roofline is held to, which no published table gives.

    python3 chipbench/vpu_probe.py

A Pallas kernel keeps ``chains`` independent (8, 128) float32 tiles in
vector registers and runs ``x = x * a + b`` on each for ``iters`` rounds:
one multiply and one add per element and round, counted as two operations,
the way ``work.py`` counts the force formulas.  Several chain counts are
timed (enough chains hide the ALUs' latency); the highest rate is the peak.
The last line of standard output is one JSON object with the rate and the
device it ran on.
"""

from __future__ import annotations

import functools
import json
import statistics
import sys
import time

ITERS = 4096
GRID = 256
REPS = 5
CALLS = 10
UNROLL = 8
CHAINS = (4, 8, 16, 24)


def _kernel(x_ref, o_ref, *, chains: int, iters: int):
    import jax
    import jax.numpy as jnp

    a, b = jnp.float32(0.999), jnp.float32(1e-3)
    xs = tuple(x_ref[8 * k:8 * (k + 1), :] for k in range(chains))

    def body(_, xs):               # UNROLL rounds per loop trip
        for _ in range(UNROLL):
            xs = tuple(x * a + b for x in xs)
        return xs

    xs = jax.lax.fori_loop(0, iters // UNROLL, body, xs)
    for k in range(chains):
        o_ref[8 * k:8 * (k + 1), :] = xs[k]


def build(chains: int, iters: int = ITERS, grid: int = GRID,
          interpret: bool = False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    rows = 8 * chains
    # int32 block indices: under x64 a bare 0 is an i64 that Mosaic refuses
    spec = pl.BlockSpec((rows, 128), lambda i: (i, jnp.int32(0)))
    call = pl.pallas_call(
        functools.partial(_kernel, chains=chains, iters=iters),
        grid=(grid,), in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((grid * rows, 128), jnp.float32),
        interpret=interpret)
    ops = 2.0 * grid * rows * 128 * iters
    return jax.jit(call), jnp.ones((grid * rows, 128), jnp.float32), ops


def measure() -> dict:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"vpu_probe: no TPU (JAX found {dev.platform!r})")
    rates = {}
    for chains in CHAINS:
        fn, x, ops = build(chains)
        jax.block_until_ready(fn(x))          # compile and warm
        times = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            for _ in range(CALLS):     # back to back: one host sync per rep
                y = fn(x)
            jax.block_until_ready(y)
            times.append((time.perf_counter() - t0) / CALLS)
        rates[chains] = ops / statistics.median(times)
        print(f"[vpu_probe] chains={chains} ops={ops:.6e} "
              f"median_s={statistics.median(times):.6f} "
              f"rate={rates[chains]:.6e}", file=sys.stderr, flush=True)
    best = max(rates, key=rates.get)
    return {"device_kind": dev.device_kind, "vpu_f32_ops_per_s": rates[best],
            "chains": best, "rates": {str(k): v for k, v in rates.items()}}


if __name__ == "__main__":
    print(json.dumps(measure()), flush=True)
