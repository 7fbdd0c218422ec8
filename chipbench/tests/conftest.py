"""The benchmark's own tests: small runs on the CPU (x64 on, as the
simulator's state is float64)."""

import os
import sys

import jax

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

jax.config.update("jax_enable_x64", True)


#: the cells cut to a size a CPU test holds; only the scale changes (the
#: block cell's chunks follow its shallower macro step)
SMALL = {
    "plummer64k_1chip.block": {"n": 1024, "block_i": 128, "block_j": 128,
                               "n_levels": 5, "chunk_events": [1, 14, 1]},
    "plummer64k_1chip.lockstep": {"n": 1024},
}
PEAKS = {"vpu_f32_ops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def small_run(cell, seed, cache_dir, overrides=None, seconds=1.0, trace=0):
    """One run of ``cell`` on the CPU at its small size; the result."""
    import time

    from chipbench import run

    t0 = time.perf_counter()
    return run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     str(seconds), "--trace", str(trace)], platform="cpu",
                    overrides={**SMALL[cell], **(overrides or {})},
                    t_process=t0, peaks=PEAKS, cache_dir=str(cache_dir))
