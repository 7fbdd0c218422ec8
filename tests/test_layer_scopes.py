"""Every instruction of the block engine's compiled event loop carries one
layer in its ``op_name`` metadata: an ``event_loop.*``, ``compaction.*`` or
(on several devices) ``collective.*`` named scope, or a force kernel's own
name.

This is what lets a device profile (taken with HLO protos) attribute each
device operation of the event loop to a layer.  The check runs on the
compiled HLO text: the block engine's scan (``event_loop.carry/while``),
its body and condition, and every computation they run through control
flow (the capacity switch's branches, nested loops).  An instruction traced
from the body function must carry a scope of its own below the scan;
instructions XLA makes there (copies, async copies, the scan's counter)
carry the scan's ``event_loop.carry`` or no ``op_name`` at all.
"""

import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench.tracing import COLLECTIVES  # noqa: E402
from repro.core import nbody  # noqa: E402
from repro.sim import ensemble as ens  # noqa: E402

N, BLOCK_I, BLOCK_J, LEVELS, EVENTS = 1024, 256, 512, 5, 14
KERNELS = ("acc_jerk_pot_packed", "snap_packed")
#: the one-device engine's layers; the strategy engine's add its collectives
LAYERS = ("event_loop", "compaction")
STRATEGY_LAYERS = LAYERS + ("collective",)
#: instructions that move no data and compute nothing
NO_WORK = ("parameter", "get-tuple-element", "tuple", "bitcast", "constant",
           "after-all", "opt-barrier")
CONTROL_FLOW = ("while", "conditional", "call")
SCAN = "event_loop.carry/while"
BODY = "/while/body/"


def _unwrap(segment):
    """``vmap(compaction.perm)`` -> ``compaction.perm``."""
    while segment.endswith(")") and "(" in segment:
        segment = segment[segment.index("(") + 1:-1]
    return segment


def scopes(op_name, layers=LAYERS):
    """The scopes of ``layers`` among the segments of an ``op_name``."""
    return [s for s in map(_unwrap, op_name.split("/"))
            if s.split(".", 1)[0] in layers and "." in s]


def _computations(text):
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            continue
        m = re.match(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$", line)
        if cur is not None and m:
            name, rest = m.groups()
            head = rest.split(", metadata=", 1)[0]
            op = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + head)
            op_name = re.search(r'op_name="([^"]*)"', rest)
            called = re.findall(r"\b(?:body|condition|true_computation|"
                                r"false_computation|to_apply)=%?([\w.\-]+)",
                                rest)
            branches = re.search(r"branch_computations=\{([^}]*)\}", rest)
            if branches:
                called += [c.strip().lstrip("%")
                           for c in branches.group(1).split(",")]
            calls = re.search(r"\bcalls=%?([\w.\-]+)", rest)
            cur.append((name, op.group(1) if op else "",
                        op_name.group(1) if op_name else "",
                        called + ([calls.group(1)] if calls and op
                                  and op.group(1) == "call" else [])))
    return comps


def scan_instructions(text):
    """(name, opcode, op_name) of every instruction the engine's scan runs
    on the device: its condition and body, through control flow, and not
    inside a force kernel's own computations."""
    comps = _computations(text)
    scan = [called for comp in comps.values()
            for _, opcode, op_name, called in comp
            if opcode == "while" and op_name.endswith(SCAN)]
    assert len(scan) == 1, f"expected one {SCAN} loop, found {len(scan)}"
    out, todo, seen = [], list(scan[0]), set()
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for name, opcode, op_name, called in comps[comp]:
            if kernel(name, op_name):
                out.append((name, opcode, op_name))
                continue
            if opcode in CONTROL_FLOW:
                todo.extend(called)
            elif opcode not in NO_WORK:
                out.append((name, opcode, op_name))
    return out


def kernel(name, op_name):
    """A force kernel: the compiled custom call is named by its Pallas
    entry point; interpreted, its operations sit under ``jit(<kernel>)``."""
    return (name.rsplit(".", 1)[0] in KERNELS
            or any(_unwrap(s) in KERNELS for s in op_name.split("/")))


def unscoped(instructions, layers=LAYERS):
    """Instructions without exactly the layer the rule gives them: a
    scope of ``layers`` of their own below the scan when traced from the
    body function, else the scan's ``event_loop.carry`` or nothing (XLA's
    own)."""
    bad = []
    for name, opcode, op_name in instructions:
        if kernel(name, op_name):
            continue
        if BODY + "closed_call/" in op_name:
            own = op_name.split(BODY, 1)[1].split("/", 1)[1]
            if not scopes(own, layers):
                bad.append((name, opcode, op_name))
        elif op_name and not (SCAN in op_name
                              and scopes(op_name, layers)):
            bad.append((name, opcode, op_name))
    return bad


def engine_text(impl, sharding=None):
    """The compiled text of the block engine's chunk program: gather
    compaction, N = 1024 with 256 x 512 tiles (three capacity buckets),
    five levels, 14 events."""
    groups = ens._bucket_groups(N, np.full(1, N), BLOCK_I, BLOCK_J,
                                "gather")
    init, run = ens._block_engine(6, 1e-7, impl, None, 0.02, 1 / 64, LEVELS,
                                  "gather", BLOCK_I, BLOCK_J, groups, "fp32")
    state = jax.eval_shape(lambda: nbody.zeros_like_state(
        jnp.zeros((N, 3)), jnp.zeros((N, 3)), jnp.zeros((N,))))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    batched = jax.tree_util.tree_map(lambda x: sds((1,) + x.shape, x.dtype),
                                     state)
    na, t_end = sds((1,), jnp.int32), sds((1,), jnp.float64)
    carry = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype),
                                   jax.eval_shape(init, batched, na, t_end))
    return run.lower(batched, carry, na, t_end,
                     n_events=EVENTS).compile().as_text()


def strategy_engine_text(impl, devices, n=4 * N, block_i=BLOCK_I,
                         block_j=BLOCK_J, levels=LEVELS, events=EVENTS):
    """The compiled text of the mesh_sharded strategy engine's chunk
    program, its run domain-decomposed over ``devices`` in the layout the
    engine keeps it in (by default N = 4096: each shard the one-device
    program's 1024 rows)."""
    devices = tuple(devices)
    init, run = ens._strategy_block_engine(
        "mesh_sharded", devices, 2, 6, 1e-7, impl, 0.02, 1 / 64, levels,
        "gather", block_i, block_j, "fp32")
    state_layout, carry_layout = ens._strategy_layout(devices, n)

    def placed(tree, layout):
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, layout)

    state = placed(jax.eval_shape(lambda: nbody.zeros_like_state(
        jnp.zeros((n, 3)), jnp.zeros((n, 3)), jnp.zeros((n,)))),
        state_layout)
    t_end = jax.ShapeDtypeStruct((), jnp.float64)
    carry = placed(jax.eval_shape(init, state, t_end), carry_layout)
    return run.lower(state, carry, t_end, n_events=events).compile() \
        .as_text()


def collectives(instructions):
    return [i for i in instructions if i[1].startswith(COLLECTIVES)]


def bare_collectives(instructions):
    """Collectives without a ``collective.*`` scope in their ``op_name``."""
    return [i for i in collectives(instructions)
            if not any(s.startswith("collective.")
                       for s in scopes(i[2], STRATEGY_LAYERS))]


def check_strategy_layers(text):
    """Every scan instruction of the strategy engine has its layer, every
    collective among them a ``collective.*`` scope (the gathers its specs
    imply and the event loop's reductions over sharded rows), and both
    kernels run inside the shards."""
    instrs = scan_instructions(text)
    bad = unscoped(instrs, STRATEGY_LAYERS)
    assert not bad, bad[:10]
    found = {s.split(".cap")[0] for _, _, op_name in instrs
             for s in scopes(op_name, STRATEGY_LAYERS)}
    assert {"event_loop.pre", "event_loop.post", "event_loop.carry",
            "compaction.perm", "compaction.bucket_index",
            "compaction.gather", "compaction.scatter",
            "compaction.scatter_sources", "collective.implied",
            "collective.all_reduce"} <= found, found
    assert collectives(instrs)
    assert not bare_collectives(instrs), bare_collectives(instrs)
    kernels = {k for name, _, op_name in instrs for k in KERNELS
               if kernel(name, op_name) and k in name + op_name}
    assert kernels == set(KERNELS)


def check_layers(text):
    """Every scan instruction has its layer, and the taxonomy's scopes and
    both kernels appear (``compaction.group``, the split of a batch into
    bucket groups, is nothing to compute for one member)."""
    instrs = scan_instructions(text)
    assert not unscoped(instrs), unscoped(instrs)[:10]
    found = {s.split(".cap")[0] for _, _, op_name in instrs
             for s in scopes(op_name)}
    assert {"event_loop.pre", "event_loop.post", "event_loop.carry",
            "compaction.perm", "compaction.bucket_index",
            "compaction.gather", "compaction.scatter",
            "compaction.scatter_sources", "compaction.pack"} <= found, found
    kernels = {k for name, _, op_name in instrs for k in KERNELS
               if kernel(name, op_name) and k in name + op_name}
    assert kernels == set(KERNELS)


def test_block_engine_scan_instructions_carry_one_layer():
    check_layers(engine_text("pallas_interpret"))


def test_unscoped_flags_an_instruction_without_a_layer():
    body = "jit(run)/event_loop.carry/while/body/closed_call/"
    instrs = [("fusion.1", "fusion", body + "event_loop.pre/mul"),
              ("copy.2", "copy", ""),
              ("add.3", "add", "jit(run)/event_loop.carry/while/body/add"),
              ("fusion.4", "fusion", body + "vmap(compaction.perm)/sort"),
              ("snap_packed.5", "custom-call", body + "pallas_call"),
              ("add.6", "add", body + "add"),
              ("mul.7", "multiply", "jit(run)/mul")]
    assert [i[0] for i in unscoped(instrs)] == ["add.6", "mul.7"]


def test_the_one_device_check_takes_no_collective_scope():
    """A one-device engine has no collectives: an instruction scoped only
    ``collective.*`` (a reduction mislabelled as multi-chip) has no layer
    there, though the strategy engine's check admits it."""
    body = "jit(run)/event_loop.carry/while/body/closed_call/"
    instrs = [("reduce.1", "reduce", body + "collective.all_reduce/min"),
              ("fusion.2", "fusion", body + "event_loop.pre/min")]
    assert [i[0] for i in unscoped(instrs)] == ["reduce.1"]
    assert not unscoped(instrs, STRATEGY_LAYERS)


_STRATEGY_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import jax
jax.config.update("jax_enable_x64", True)
from test_layer_scopes import strategy_engine_text
print(strategy_engine_text("pallas_interpret", jax.devices()))
"""


def test_mesh_sharded_engine_scan_instructions_carry_one_layer():
    """The four-device program of the mesh_sharded strategy engine."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-c", _STRATEGY_SCRIPT,
         os.path.dirname(os.path.abspath(__file__))],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    text = p.stdout
    assert "num_partitions=4" in text
    check_strategy_layers(text)


def test_a_collective_without_a_collective_scope_is_flagged():
    body = "jit(run)/event_loop.carry/while/body/closed_call/"
    instrs = [("all-gather.1", "all-gather",
               body + "jit(eval_padded)/collective.implied/shard_map"),
              ("all-reduce.2", "all-reduce",
               body + "event_loop.pre/reduce_min"),
              ("fusion.3", "fusion", body + "event_loop.pre/min")]
    assert [i[0] for i in collectives(instrs)] == ["all-gather.1",
                                                   "all-reduce.2"]
    assert not unscoped(instrs, STRATEGY_LAYERS)
    assert [i[0] for i in bare_collectives(instrs)] == ["all-reduce.2"]
