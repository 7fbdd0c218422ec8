"""What keeps a run from hiding the device it ran on: the report names the
device and the resolved evaluation impl, the launchers force host devices
only when the CPU was asked for, and the compile cache sits where
``JAX_COMPILATION_CACHE_DIR`` says, else at one fixed path in the checkout."""

import os

import jax
import pytest

from repro.kernels import ops
from repro.launch import compile_cache
from repro.launch.sim_run import force_host_devices
from repro.sim import api


def _cfg(**kw):
    base = dict(scenario="plummer", n=16, t_end=0.02, dt=1.0 / 256,
                diag_every=4, validate_ic=False)
    base.update(kw)
    return api.SimConfig(**base)


@pytest.mark.parametrize("kw,impl", [
    ({}, "default"),
    ({"kernel": "pallas"}, "default"),
    ({"impl": "xla"}, "xla"),
    ({"dtype": "fp64"}, "fp64"),
    ({"ensemble": 2, "kernel": "ref"}, "xla"),
    ({"stepper": "block", "dt": None, "n_levels": 2, "kernel": "pallas"},
     "default"),
])
def test_report_records_device_and_impl(kw, impl):
    rep = api.run(_cfg(**kw))
    dev = jax.devices()[0]
    assert rep["impl"] == (ops.default_impl() if impl == "default" else impl)
    assert rep["device"] == {"platform": dev.platform,
                             "kind": dev.device_kind, "count": 1}


@pytest.mark.parametrize("platforms,forced", [
    ("cpu", True), ("tpu", False), (None, False)])
def test_force_host_devices_only_on_the_cpu(monkeypatch, platforms, forced):
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    force_host_devices(4)
    flags = os.environ.get("XLA_FLAGS")
    assert (flags == "--xla_force_host_platform_device_count=4") == forced


def test_force_host_devices_keeps_user_flags(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=somewhere")
    force_host_devices(4)
    assert os.environ["XLA_FLAGS"] == "--xla_dump_to=somewhere"


def test_compile_cache_directory(monkeypatch, tmp_path):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev  # JAX's own

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable() == compile_cache.REPO_CACHE
        assert jax.config.jax_compilation_cache_dir == compile_cache.REPO_CACHE
        root, name = os.path.split(compile_cache.REPO_CACHE)
        assert name == ".jax_cache"
        assert os.path.isfile(os.path.join(root, "pyproject.toml"))
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
