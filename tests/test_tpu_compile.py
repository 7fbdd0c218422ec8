"""The TPU compiler accepts the main path's programs at real widths.

Interpret mode runs the Pallas kernels as Python and cannot show what Mosaic
refuses: an i64 block index under x64, or a ``pallas_call`` left for GSPMD to
partition.  These tests compile, without a chip, for a described
``v5e:2x2`` topology, under the global x64 of ``conftest.py``.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and it keeps it until it exits,
so the tests compile in this process and live in this one file.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import nbody
from repro.core.evaluate import make_evaluator
from repro.core.strategies import make_strategy_evaluator
from repro.kernels import nbody_force, ops
from repro.sim import api
from repro.sim import ensemble as ens

N = 65536
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("the TPU compiler (libtpu) is not installed")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep such entries out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype", ["fp32", "mixed"])
def test_kernels_compile_at_full_width(one_chip, no_persistent_cache, dtype):
    """acc/jerk/pot and snap at N = 65536, default 256x512 tiles."""
    cd = ops.compute_dtype_for(dtype)
    tgt = _sds((N, 8), F32, one_chip)
    src = _sds((8, N), F32, one_chip)
    text1 = _compiled_text(lambda t, s: nbody_force.acc_jerk_pot_packed(
        t, s, compute_dtype=cd), tgt, src)
    text2 = _compiled_text(lambda t, s, ta, sa: nbody_force.snap_packed(
        t, s, ta, sa, compute_dtype=cd), tgt, src, tgt, src)
    assert "tpu_custom_call" in text1 and "tpu_custom_call" in text2


def _state_shapes(n, sharding):
    state = jax.eval_shape(lambda: nbody.zeros_like_state(
        jnp.zeros((n, 3)), jnp.zeros((n, 3)), jnp.zeros((n,))))
    assert state.pos.dtype == jnp.float64
    return jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, sharding), state)


def test_hermite_step_f64_state_compiles(one_chip, no_persistent_cache):
    """One 6th-order step: f64 state around the fp32 Pallas evaluation, as
    the lockstep runner's one compiled program."""
    advance, _ = api._single_engine(make_evaluator(impl="pallas"), 6)
    state = _state_shapes(N, one_chip)
    text = advance.lower(state, _sds((), jnp.float64, one_chip)
                         ).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("strategy", ["ring", "mesh_sharded"])
def test_strategy_evaluators_compile_on_four_chips(topo, no_persistent_cache,
                                                   strategy):
    """The ring and the lockstep mesh_sharded evaluators with the kernel,
    sharded over the four described chips: a Mosaic kernel cannot be
    partitioned automatically, so it must sit inside a shard_map."""
    devices = list(topo.devices)
    assert len(devices) == 4
    ev = make_strategy_evaluator(strategy, devices=devices, impl="pallas")
    rep = NamedSharding(Mesh(np.asarray(devices), ("d",)), P())
    text = _compiled_text(lambda p, v, m: tuple(ev(p, v, m)),
                          _sds((N, 3), jnp.float64, rep),
                          _sds((N, 3), jnp.float64, rep),
                          _sds((N,), jnp.float64, rep))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("engine", ["init/batch", "adaptive/batch"])
def test_batch_sharded_engines_compile_on_four_chips(
        topo, no_persistent_cache, engine):
    """A batch sharded over four chips: the t = 0 pass and the adaptive
    lockstep step run the vmapped kernel per shard."""
    kind = engine.split("/")[0]
    mesh = ens._batch_mesh(list(topo.devices))
    rep = NamedSharding(mesh, P())
    b = 4
    batched = jax.tree_util.tree_map(
        lambda x: _sds((b,) + x.shape, x.dtype, rep),
        _state_shapes(16384, rep))
    per_member = {dt: _sds((b,), dt, rep) for dt in (jnp.int32, jnp.float64)}
    if kind == "init":
        init, _ = ens._engine(6, 1e-7, "pallas", mesh, "fp32")
        lowered = init.lower(batched, per_member[jnp.int32])
    else:
        run = ens._adaptive_engine(6, 1e-7, "pallas", mesh, 0.02, 0.0625,
                                   "fp32")
        lowered = run.lower(batched, per_member[jnp.float64],
                            per_member[jnp.int32], per_member[jnp.int32],
                            per_member[jnp.float64], n_steps=2)
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("layout", ["one_chip", "batch"])
def test_block_engine_gather_compiles(topo, no_persistent_cache, layout):
    """The main path's event-loop program: block timesteps with the
    gathered-compaction capacity switch over the kernel, at N = 16384, on
    one chip or with the batch sharded over the four described chips."""
    n = 16384
    bi, bj = nbody_force.DEFAULT_BLOCK_I, nbody_force.DEFAULT_BLOCK_J
    if layout == "one_chip":
        mesh, b = None, 1
        sharding = SingleDeviceSharding(topo.devices[0])
    else:
        mesh, b = ens._batch_mesh(topo.devices), 4
        sharding = NamedSharding(mesh, P())
    groups = ens._bucket_groups(n, np.full(b, n), bi, bj, "gather")
    init, run = ens._block_engine(6, 1e-7, "pallas", mesh, 0.02, 0.0625, 8,
                                  "gather", bi, bj, groups, "fp32")
    batched = jax.tree_util.tree_map(
        lambda x: _sds((b,) + x.shape, x.dtype, sharding),
        _state_shapes(n, sharding))
    na = _sds((b,), jnp.int32, sharding)
    t_end = _sds((b,), jnp.float64, sharding)
    carry = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, sharding),
        jax.eval_shape(init, batched, na, t_end))
    text = run.lower(batched, carry, na, t_end, n_events=16).compile() \
        .as_text()
    assert "tpu_custom_call" in text


def test_block_engine_layer_scopes_reach_the_tpu_program(
        one_chip, no_persistent_cache):
    """The chip's own program of the event loop (N = 1024, gather, five
    levels): every instruction of the compiled scan carries an
    ``event_loop.*`` or ``compaction.*`` scope, or is one of the two kernel
    custom calls, named by their Pallas entry points."""
    from test_layer_scopes import check_layers, engine_text

    text = engine_text("pallas", one_chip)
    assert "tpu_custom_call" in text
    check_layers(text)


def test_mesh_sharded_block_engine_compiles_at_the_cell_size(
        topo, no_persistent_cache):
    """The four-chip cell's event-loop program: N = 131072 domain-decomposed
    over the four described chips by ``mesh_sharded``, 256 x 512 tiles,
    eight levels, gather compaction, 126 events.  Every instruction of its
    scan has its layer, and every collective the partitioner inserts a
    ``collective.*`` scope."""
    from test_layer_scopes import check_strategy_layers, strategy_engine_text

    text = strategy_engine_text(
        "pallas", topo.devices, n=131072, block_i=nbody_force.DEFAULT_BLOCK_I,
        block_j=nbody_force.DEFAULT_BLOCK_J, levels=8, events=126)
    assert "tpu_custom_call" in text and "num_partitions=4" in text
    check_strategy_layers(text)
