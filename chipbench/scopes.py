"""The program's own layer boundaries, read from a profiler trace.

Runner layer.  ``repro.obs.trace`` enters a ``jax.profiler.TraceAnnotation``
for every span, so each ``runner.step`` holds one ``sim.step`` span with
the children ``sim.engine`` (the engine call through its
``block_until_ready``), ``sim.readback`` (device-to-host reads of carry
counters, time or state) and ``sim.diagnostics`` (energy, the occupancy
bound, histograms, the telemetry snapshot).  They are host events of the
reduced trace (``tracing.Reduced.host``), on the device's clock.  The
device idle inside them is counted only within the steps' device envelopes
(``tracing.step_envelopes``), so that ``step_gap_share`` (idle outside the
envelopes), the idle inside ``sim.engine``, the idle inside ``sim.readback``
and ``sim.diagnostics``, and the rest of the idle inside the envelopes
partition ``device_idle_share``.  A program without these spans gives
None.

Device layers.  The engine's ``jax.named_scope``s reach the compiled HLO as
the ``op_name`` metadata of its instructions (``.../event_loop.pre/...``,
``.../vmap(compaction.perm)/...``).  A trace holds each program's
optimised HLO in its ``/host:metadata`` plane, keyed by program id
(``ProfileOptions.enable_hlo_proto``, on by default in JAX 0.9; the
recorded trace under ``tests/data`` holds them).  Each ``XLA Ops`` event
belongs to the ``XLA Modules`` event of its device that contains it in
time, whose name ends in ``(<program id>)``; the pair (program,
instruction) gives the instruction's layer:

1. a force kernel, by its kind (``metrics_context.FORCE_KERNELS``): ``kernels``;
2. else the deepest ``event_loop.`` or ``compaction.`` segment of its
   ``op_name`` (a transform's wrapper, ``vmap(...)``, is looked through):
   ``event_loop`` or ``compaction``;
3. else, for an instruction XLA made (a copy, an async copy, a fusion with
   no ``op_name``), the layer of its first operand;
4. else the layer of the control-flow instruction (``while``,
   ``conditional``, ``call``) whose computation holds it;
5. else none: work outside the engine's scopes (the runner's eager
   operations, energy).
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

from chipbench import tracing

#: the runner's program spans (children of ``sim.step``)
ENGINE = "sim.engine"
READBACK = "sim.readback"
DIAGNOSTICS = "sim.diagnostics"
#: layers of the engine's named scopes, and of the force kernels
SCOPED = ("event_loop", "compaction")
KERNELS = "kernels"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
CONTROL_FLOW = ("while", "conditional", "call")


# ------------------------------------------------------------ runner spans
def spans(r: tracing.Reduced, *names: str) -> List[tracing.Interval]:
    """The union of the host spans called ``names``, cut to the window."""
    w0, w1 = r.window
    return tracing.union((max(s, w0), min(e, w1)) for s, e, n in r.host
                         if n in names and e > w0 and s < w1)


def idle_in_steps(r: tracing.Reduced, dev: str,
                  within: List[tracing.Interval]) -> int:
    """Device idle time inside ``within`` and inside the steps' device
    envelopes, in ns."""
    idle = tracing.gaps(tracing.busy(r, dev), r.window)
    env = tracing.union(tracing.step_envelopes(r, dev))
    return tracing.length(tracing.intersect(tracing.intersect(idle, within),
                                            env))


def gap_share(r: tracing.Reduced, *names: str) -> Optional[float]:
    """Device idle inside the spans ``names`` (within the step envelopes)
    over the window, in %, mean over devices; None without such spans."""
    within = spans(r, *names)
    if not within or not r.window_ns:
        return None
    return tracing.mean_over_devices(
        r, lambda d: 100.0 * idle_in_steps(r, d, within) / r.window_ns)


# --------------------------------------------------------------- HLO scopes
def unwrap(segment: str) -> str:
    """``vmap(compaction.perm)`` -> ``compaction.perm``."""
    while segment.endswith(")") and "(" in segment:
        segment = segment[segment.index("(") + 1:-1]
    return segment


def scoped_layer(op_name: str) -> Optional[str]:
    """The layer of the deepest ``event_loop.``/``compaction.`` segment."""
    for seg in reversed(op_name.split("/")):
        head = unwrap(seg).split(".", 1)[0]
        if head in SCOPED and "." in unwrap(seg):
            return head
    return None


_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\((.*)$")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:body|condition|to_apply|true_computation|"
                     r"false_computation|calls)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def parse_hlo(text: str) -> Tuple[dict, dict]:
    """Instructions of an HLO module's text: ``{name: (opcode, op_name,
    first operand or '', computation)}``, and ``{computation: the
    control-flow instruction that runs it}``."""
    instrs, callers, comp = {}, {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(2)
            continue
        m = _INSTRUCTION.match(line) if comp else None
        if not m:
            continue
        name, rest = m.groups()
        op = _OPCODE.search(" " + rest.split(", metadata=", 1)[0])
        opcode, args = (op.group(1), op.group(2)) if op else ("", "")
        operand = _OPERAND.search(args)
        op_name = _OP_NAME.search(rest)
        instrs[name] = (opcode, op_name.group(1) if op_name else "",
                        operand.group(1) if operand else "", comp)
        if opcode in CONTROL_FLOW:
            called = _CALLED.findall(rest)
            branches = _BRANCHES.search(rest)
            if branches:
                called += [c.strip().lstrip("%")
                           for c in branches.group(1).split(",")]
            for c in called:
                callers[c] = name
    return instrs, callers


def instruction_layers(text: str, kernels: Iterable[str]) -> Dict[str, str]:
    """``{instruction name: layer}`` of one module's text; '' for none."""
    instrs, callers = parse_hlo(text)
    kernels = set(kernels)
    memo: Dict[str, str] = {}

    def layer(name: str, depth: int = 0) -> str:
        if name in memo:
            return memo[name]
        if name not in instrs or depth > 64:
            return ""
        memo[name] = ""          # a cycle resolves to none
        opcode, op_name, operand, comp = instrs[name]
        if tracing.kind_of(name) in kernels:
            out = KERNELS
        else:
            out = scoped_layer(op_name) or ""
            if not out and operand and not op_name:
                out = layer(operand, depth + 1)
            if not out and comp in callers:
                out = layer(callers[comp], depth + 1)
        memo[name] = out
        return out

    return {name: layer(name) for name in instrs}


# ---------------------------------------------------------------- the trace
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def _fields(buf: bytes):
    """(field number, value) of a serialized protobuf message; length-
    delimited values as bytes, varints as ints, fixed as raw bytes."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 1:
            value, i = buf[i:i + 8], i + 8
        elif kind == 5:
            value, i = buf[i:i + 4], i + 4
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def hlo_module_protos(xspace: bytes) -> Dict[str, bytes]:
    """``{program id: serialized HloModuleProto}`` from the metadata plane
    of a serialized XSpace: event metadata named ``<module>(<id>)`` (or
    keyed by the id) with an ``Hlo Proto`` stat that holds an HloProto."""
    out = {}
    for num, plane in _fields(xspace):
        if num != 1:                                  # XSpace.planes
            continue
        fields = list(_fields(plane))
        if dict(fields).get(2, b"").decode() != METADATA_PLANE:
            continue
        stat_names = {}
        for num2, entry in fields:
            if num2 == 5:                             # stat_metadata map
                meta = dict(_fields(dict(_fields(entry)).get(2, b"")))
                stat_names[meta.get(1, 0)] = meta.get(2, b"").decode()
        for num2, entry in fields:
            if num2 != 4:                             # event_metadata map
                continue
            meta = list(_fields(dict(_fields(entry)).get(2, b"")))
            name = dict(meta).get(2, b"").decode()
            pid = (program_id(name) if name.endswith(")")
                   else str(dict(meta).get(1, "")))
            for num3, stat in meta:
                st = dict(_fields(stat)) if num3 == 5 else {}
                if stat_names.get(st.get(1)) == HLO_PROTO_STAT and 6 in st:
                    module = dict(_fields(st[6])).get(1)  # HloProto.hlo_module
                    if module is not None:
                        out[pid] = module
    return out


def program_id(module_event: str) -> str:
    """``jit_run(1234)`` -> ``1234``."""
    return module_event.rsplit("(", 1)[-1].rstrip(")")


def module_texts(xspace: bytes) -> Dict[str, str]:
    """``{program id: HLO text}`` of every program the trace holds."""
    from jax._src.lib import xla_client

    load = xla_client._xla.HloModule.from_serialized_hlo_module_proto
    return {pid: load(proto).to_string()
            for pid, proto in hlo_module_protos(xspace).items()}


def join(modules: List[Tuple[int, int, str]],
         ops: List[Tuple[int, int, str]]) -> List[Tuple[int, int, str, str]]:
    """Each op ``(start, end, instruction)`` with the program id of the
    module event that contains it in time ('' if none does)."""
    modules = sorted(modules)
    out, k = [], 0
    for s, e, name in sorted(ops):
        while k < len(modules) and modules[k][1] < s:
            k += 1
        pid = ""
        if k < len(modules) and modules[k][0] <= s and e <= modules[k][1]:
            pid = program_id(modules[k][2])
        out.append((s, e, name, pid))
    return out


def device_layers(path: str, window: tracing.Interval, kernels: Iterable[str]
                  ) -> Dict[str, List[Tuple[int, int, str]]]:
    """``{device plane: [(start, end, layer)]}`` for the ``XLA Ops`` events
    of an ``.xplane.pb``, cut to ``window``; container instructions are
    dropped as in ``tracing.reduce_xplane``."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        xspace = f.read()
    layers = {pid: instruction_layers(text, kernels)
              for pid, text in module_texts(xspace).items()}
    w0, w1 = window
    out = {}
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        if not plane.name.startswith(tracing.DEVICE_PREFIX):
            continue
        modules, ops = [], []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                modules = [(int(ev.start_ns), int(ev.end_ns), ev.name)
                           for ev in line.events]
            elif line.name == tracing.OPS_LINE:
                ops = [(int(ev.start_ns), int(ev.end_ns),
                        ev.name.split(" = ", 1)[0].strip().lstrip("%"))
                       for ev in line.events]
        kept = []
        for s, e, name, pid in join(modules, ops):
            if e <= w0 or s >= w1 or tracing.kind_of(name) in \
                    tracing.CONTAINERS:
                continue
            kept.append((max(s, w0), min(e, w1),
                         layers.get(pid, {}).get(name, "")))
        out[plane.name] = kept
    return out


def layer_busy_share(layers: Dict[str, List[Tuple[int, int, str]]],
                     layer: str) -> Optional[float]:
    """Device time of the ops of ``layer`` over busy time, in %, mean over
    devices; None when no op of any device carries a scoped layer (a
    program without the scopes, or a trace without HLO protos)."""
    if not any(lay in SCOPED for ops in layers.values()
               for _, _, lay in ops):
        return None
    shares = []
    for ops in layers.values():
        busy = tracing.length(tracing.union((s, e) for s, e, _ in ops))
        mine = sum(e - s for s, e, lay in ops if lay == layer)
        shares.append(100.0 * mine / busy if busy else 0.0)
    return sum(shares) / len(shares) if shares else None
