"""The four-chip cell, ``cluster128k_4chip.mesh_sharded_block``, at a size
the CPU holds on four host devices (one subprocess: the device count is
fixed before JAX starts), and the reader of its multi-chip metric on
synthetic traces."""

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import spec, tracing
from conftest import PEAKS

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "cluster128k_4chip.mesh_sharded_block"
#: the cell cut to a size a CPU test holds; only the scale changes (the
#: chunks follow the shallower macro step, as the one-chip block cell's)
SMALL = {"n": 2048, "block_i": 128, "block_j": 128, "n_levels": 5,
         "chunk_events": [1, 14, 1]}
SEED = 3000000041

_SCRIPT = r"""
import json, os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
root, cell, seed, cache = sys.argv[1:5]
small, control, peaks = (json.loads(a) for a in sys.argv[5:8])
sys.path[:0] = [root, os.path.join(root, "src")]
import jax
jax.config.update("jax_enable_x64", True)
from chipbench import run

out = {}
for label, ov in (("program", {}), ("control", control)):
    t0 = time.perf_counter()
    out[label] = run.main(
        ["--workload", cell, "--seed", seed, "--seconds", "1", "--trace",
         "0"], platform="cpu", overrides={**small, **ov}, t_process=t0,
        peaks=peaks, cache_dir=cache)
print(json.dumps(run.finite(out)))
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    from chipbench.calibrate import CONTROL

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-c", _SCRIPT, ROOT, CELL, str(SEED),
         str(tmp_path_factory.mktemp("cache")), json.dumps(SMALL),
         json.dumps(CONTROL), json.dumps(PEAKS)],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_small_run_on_four_devices_is_correct(runs):
    out = runs["program"]
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    assert out["checks"]["window_compiles"][0] == 0
    assert out["checks"]["edge_steps"][0] >= 1
    # both checked events of the window's last macro step were checkable
    assert {"acc_err", "acc_err.partial"} <= set(out["checks"])


def test_control_is_not_correct(runs):
    out = runs["control"]
    assert not out["correct"], out["checks"]


def test_cell_reads_the_multi_chip_metric():
    names = {m["name"] for m in spec.load_cell(CELL).per_layer}
    assert names == {"device_idle_share", "step_gap_share",
                     "engine_gap_share", "diagnostics_gap_share",
                     "pair_slot_fill", "force_kernel_roofline",
                     "force_kernel_busy_share", "collective_exposed_share"}
    for cell in ("plummer64k_1chip.block", "plummer64k_1chip.lockstep"):
        assert "collective_exposed_share" not in {
            m["name"] for m in spec.load_cell(cell).per_layer}


def _ctx(per_device):
    r = tracing.cut({dev: [tracing.Op(s, e, k) for s, e, k in ops]
                     for dev, ops in per_device.items()},
                    [(0, 100, tracing.WINDOW)])
    return types.SimpleNamespace(trace=r)


def test_collective_exposed_share_counts_only_exposed_time():
    read = spec.reader("collective_exposed_share")
    ctx = _ctx({
        # [0, 5) and [8, 10) exposed, [5, 8) under a fusion; [20, 30)
        # exposed: 17 of 100
        "/device:TPU:0": [(0, 10, "all-gather"), (5, 8, "fusion"),
                          (20, 30, "all-reduce"), (40, 50, "fusion")],
        # wholly under compute: 0 of 100
        "/device:TPU:1": [(0, 10, "all-gather"), (0, 12, "fusion")],
    })
    assert read(ctx) == pytest.approx((17.0 + 0.0) / 2)


def test_collective_exposed_share_is_none_without_collectives():
    read = spec.reader("collective_exposed_share")
    ctx = _ctx({"/device:TPU:0": [(0, 10, "fusion")],
                "/device:TPU:1": [(5, 50, "snap_packed")]})
    assert read(ctx) is None
