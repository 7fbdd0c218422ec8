"""Without a TPU, or with fewer chips than the cell asks for, the benchmark
exits non-zero and prints no result."""

import os
import subprocess
import sys

import pytest

from chipbench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.mark.parametrize("cell", ["plummer64k_1chip.block",
                                  "plummer64k_1chip.lockstep"])
def test_no_tpu_exits_without_result(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", cell, "--seed",
         "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == run.NO_CHIP, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "no result" in p.stderr


class _Dev:
    def __init__(self, platform):
        self.platform = platform


class _Jax:
    def __init__(self, devs):
        self._devs = devs

    def devices(self):
        return self._devs


def test_fewer_chips_than_the_cell_asks_for():
    with pytest.raises(run.NoChip, match="needs 4 chips"):
        run.check_devices(_Jax([_Dev("tpu")] * 1), 4)
    assert len(run.check_devices(_Jax([_Dev("tpu")] * 4), 4)) == 4


def test_other_platform_refused():
    with pytest.raises(run.NoChip, match="no TPU"):
        run.check_devices(_Jax([_Dev("cpu")] * 8), 1)


def test_no_result_outside_a_checkout(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files has
    no simulator to run: the run fails and prints no result."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "plummer64k_1chip.block", "--seed", "7", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
