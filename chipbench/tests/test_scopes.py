"""The program's layer boundaries in a trace (``scopes.py``) and the readers
built on them, on synthetic reduced traces, a small HLO module, a synthetic
XSpace and a profile taken on the CPU."""

import glob
import os

import pytest

from chipbench import metrics_context, scopes, spec, tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEV = "/device:TPU:0"
KERNELS = metrics_context.FORCE_KERNELS


def _ctx(reduced):
    return metrics_context.Context(trace=reduced, window=None, cell=None,
                                   cfg=None, peaks=None, chips=1)


def _read(name, reduced):
    return spec.reader(name)(_ctx(reduced))


def _two_steps():
    """Window [0, 200): two runner steps, each engine -> readback ->
    diagnostics, with device idle in every child and between the steps."""
    ops = [(10, 30, "fusion"), (35, 60, "snap_packed"),      # engine 1
           (70, 72, "copy"),                                 # diagnostics 1
           (110, 130, "fusion"), (140, 170, "snap_packed"),  # engine 2
           (182, 185, "fusion")]                             # diagnostics 2
    steps = [(5, 90, tracing.STEP), (100, 195, tracing.STEP)]
    host = [(6, 58, scopes.ENGINE), (61, 66, scopes.READBACK),
            (66, 80, scopes.DIAGNOSTICS),
            (102, 171, scopes.ENGINE), (171, 178, scopes.READBACK),
            (178, 190, scopes.DIAGNOSTICS),
            (0, 200, "PjRtClient::Execute")]
    r = tracing.cut({DEV: [tracing.Op(s, e, k) for s, e, k in ops]},
                    [(0, 200, tracing.WINDOW)] + steps, host)
    return r


def test_gap_readers_on_two_steps():
    r = _two_steps()
    # envelopes [10, 72) and [110, 185); idle inside them: [30, 35) and
    # [60, 70) in step 1, [130, 140) and [170, 182) in step 2; [60, 61)
    # lies between the first engine span and its readback
    assert _read("engine_gap_share", r) == pytest.approx(
        100 * (5 + 10 + 1) / 200)          # [30,35) [130,140) [170,171)
    assert _read("diagnostics_gap_share", r) == pytest.approx(
        100 * (9 + 11) / 200)              # [61,70) [171,182)


def test_gap_shares_partition_device_idle():
    """engine + diagnostics + step gap + the rest inside the envelopes is
    the device's idle share."""
    r = _two_steps()
    engine = _read("engine_gap_share", r)
    diag = _read("diagnostics_gap_share", r)
    step_gap = _read("step_gap_share", r)
    idle = _read("device_idle_share", r)
    inside = tracing.intersect(
        tracing.gaps(tracing.busy(r, DEV), r.window),
        tracing.union(tracing.step_envelopes(r, DEV)))
    spans = scopes.spans(r, scopes.ENGINE, scopes.READBACK,
                         scopes.DIAGNOSTICS)
    rest = 100 * (tracing.length(inside) - tracing.length(
        tracing.intersect(inside, spans))) / r.window_ns
    assert rest == pytest.approx(100 * 1 / 200)
    assert engine + diag + step_gap + rest == pytest.approx(idle)
    assert engine + diag + step_gap <= idle


def test_gap_readers_give_nothing_without_program_spans():
    """A program whose runner opens no ``sim.*`` spans (the recorded
    lockstep step, taken before the runner had them) reads nothing."""
    r = tracing.reduce_xplane(os.path.join(DATA, "trace_small.xplane.pb"))
    assert not scopes.spans(r, scopes.ENGINE)
    assert _read("engine_gap_share", r) is None
    assert _read("diagnostics_gap_share", r) is None


HLO = '''HloModule m, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%lt (a: f32[], b: f32[]) -> pred[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %c = pred[] compare(%a, %b), direction=LT
}

%body (p: (f32[8], s32[])) -> (f32[8], s32[]) {
  %p = (f32[8]{0}, s32[]) parameter(0)
  %x = f32[8]{0} get-tuple-element(%p), index=0
  %i = s32[] get-tuple-element(%p), index=1
  %mul.1 = f32[8]{0} multiply(%x, %x), metadata={op_name="B/event_loop.pre/mul"}
  %copy.2 = f32[8]{0} copy(%mul.1)
  %copy.3 = f32[8]{0} copy(%x)
  %one = s32[] constant(1)
  %add.4 = s32[] add(%i, %one), metadata={op_name="jit(run)/event_loop.carry/while/body/add"}
  %snap_packed.5 = f32[8]{0} custom-call(%copy.2), custom_call_target="tpu_custom_call", metadata={op_name="B/vmap(compaction.bucket_index)/jit(snap_packed)/pallas_call"}
  %sort.6 = f32[8]{0} sort(%snap_packed.5), dimensions={0}, to_apply=%lt, metadata={op_name="B/vmap(compaction.perm)/jit(argsort)/sort"}
  %add.10 = f32[8]{0} add(%sort.6, %copy.3), metadata={op_name="B/compaction.scatter.cap256/add"}
  ROOT %t = (f32[8]{0}, s32[]) tuple(%add.10, %add.4)
}

%cond (q: (f32[8], s32[])) -> pred[] {
  %q = (f32[8]{0}, s32[]) parameter(0)
  %j = s32[] get-tuple-element(%q), index=1
  %ten = s32[] constant(10)
  ROOT %lt.7 = pred[] compare(%j, %ten), direction=LT, metadata={op_name="jit(run)/event_loop.carry/while/cond/lt"}
}

ENTRY %main (x0: f32[8]) -> f32[8] {
  %x0 = f32[8]{0} parameter(0)
  %zero = s32[] constant(0)
  %init = (f32[8]{0}, s32[]) tuple(%x0, %zero)
  %while.8 = (f32[8]{0}, s32[]) while(%init), condition=%cond, body=%body, metadata={op_name="jit(run)/event_loop.carry/while"}
  %y = f32[8]{0} get-tuple-element(%while.8), index=0
  ROOT %add.9 = f32[8]{0} add(%y, %x0), metadata={op_name="jit(run)/add"}
}
'''.replace("B/", "jit(run)/event_loop.carry/while/body/closed_call/")


def _module():
    from jax._src.lib import xla_client

    return xla_client._xla.hlo_module_from_text(HLO)


def test_instruction_layers_of_a_small_module():
    got = scopes.instruction_layers(_module().to_string(), KERNELS)
    assert {k: got[k] for k in ("mul.1", "copy.2", "copy.3", "add.4",
                                "snap_packed.5", "sort.6", "add.10",
                                "lt.7", "add.9")} == {
        "mul.1": "event_loop",          # its own scope
        "copy.2": "event_loop",         # XLA's copy: its operand's layer
        "copy.3": "event_loop",         # of the loop state: the loop's
        "add.4": "event_loop",          # the scan's counter
        "snap_packed.5": scopes.KERNELS,  # a kernel, by its kind
        "sort.6": "compaction",         # vmap(compaction.perm)
        "add.10": "compaction",
        "lt.7": "event_loop",
        "add.9": "",                    # outside the engine's scopes
    }


def test_scoped_layer_takes_the_deepest_scope():
    assert scopes.scoped_layer(
        "jit(run)/event_loop.carry/while/body/closed_call/"
        "vmap(compaction.gather.cap256)/gather") == "compaction"
    assert scopes.scoped_layer("jit(run)/event_loop.post/mul") == "event_loop"
    assert scopes.scoped_layer("jit(multiply)/mul") is None
    assert scopes.scoped_layer("jit(run)/event_loop/mul") is None


def test_join_by_containment():
    modules = [(0, 100, "jit_run(11)"), (120, 130, "jit_multiply(12)")]
    ops = [(5, 20, "fusion.1"), (125, 126, "multiply.2"),
           (101, 110, "copy.3")]
    assert scopes.join(modules, ops) == [
        (5, 20, "fusion.1", "11"), (101, 110, "copy.3", ""),
        (125, 126, "multiply.2", "12")]


def _escape(data: bytes) -> str:
    return "".join(f"\\{b:03o}" for b in data)


def _hlo_proto(module: bytes) -> bytes:
    """An HloProto holding ``module`` (field 1, length-delimited)."""
    n, size = len(module), b""
    while True:
        size += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            break
    return b"\x0a" + size + module


def _xspace(module: bytes) -> bytes:
    """A trace of one device: program 11 (the module) with four ops, then
    an eager program 12 with one op; and the metadata plane."""
    from jax.profiler import ProfileData

    def ev(mid, start_ns, dur_ns):
        return (f"events {{ metadata_id: {mid} offset_ps: {start_ns * 1000} "
                f"duration_ps: {dur_ns * 1000} }}")

    names = {1: "jit_run(11)", 2: "jit_multiply(12)",
             3: "%mul.1 = f32[8]{0} multiply(%x, %x)",
             4: "%snap_packed.5 = f32[8]{0} custom-call(%copy.2)",
             5: "%sort.6 = f32[8]{0} sort(%snap_packed.5)",
             6: "%while.8 = (f32[8]{0}, s32[]) while(%init)",
             7: "%multiply.1 = f32[8]{0} multiply(%a, %b)"}
    meta = " ".join(f'event_metadata {{ key: {k} value {{ id: {k} '
                    f'name: "{v}" }} }}' for k, v in names.items())
    device = (f'planes {{ id: 1 name: "{DEV}" '
              f'lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000 '
              f'{ev(1, 0, 100)} {ev(2, 150, 20)} }} '
              f'lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000 '
              f'{ev(6, 0, 100)} {ev(3, 0, 10)} {ev(4, 10, 60)} '
              f'{ev(5, 80, 20)} {ev(7, 155, 10)} }} {meta} }}')
    metadata = (f'planes {{ id: 2 name: "/host:metadata" '
                f'stat_metadata {{ key: 1 value {{ id: 1 name: '
                f'"Hlo Proto" }} }} '
                f'event_metadata {{ key: 11 value {{ id: 11 name: '
                f'"jit_run(11)" stats {{ metadata_id: 1 bytes_value: '
                f'"{_escape(_hlo_proto(module))}" }} }} }} }}')
    return ProfileData.text_proto_to_serialized_xspace(device + metadata)


def test_device_layers_of_a_synthetic_trace(tmp_path):
    module = _module().as_serialized_hlo_module_proto()
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(module))
    assert list(scopes.hlo_module_protos(path.read_bytes())) == ["11"]
    layers = scopes.device_layers(str(path), (1000, 1160), KERNELS)
    # the while is a container; the eager op is cut by the window
    assert layers == {DEV: [(1000, 1010, "event_loop"),
                            (1010, 1070, scopes.KERNELS),
                            (1080, 1100, "compaction"),
                            (1155, 1160, "")]}
    busy = 10 + 60 + 20 + 5
    assert scopes.layer_busy_share(layers, "event_loop") == \
        pytest.approx(100 * 10 / busy)
    assert scopes.layer_busy_share(layers, "compaction") == \
        pytest.approx(100 * 20 / busy)
    assert scopes.layer_busy_share(
        {DEV: [(0, 10, scopes.KERNELS), (10, 20, "")]}, "compaction") is None


def test_hlo_protos_of_a_cpu_profile(tmp_path):
    """A profile taken with HLO protos holds each program's optimised HLO,
    whose instructions carry the program's scopes."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("event_loop.pre"):
            y = jnp.sin(x) * 2.0
        with jax.named_scope("compaction.perm"):
            return y[jnp.argsort(y)]

    x = jnp.arange(64.0)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = True
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    texts = scopes.module_texts(open(path, "rb").read())
    (text,) = [t for t in texts.values() if t.startswith("HloModule jit_f")]
    layers = set(scopes.instruction_layers(text, KERNELS).values())
    assert {"event_loop", "compaction"} <= layers


def test_recorded_trace_joins_ops_to_their_programs():
    """The recorded v5e lockstep step (default profiler options) already
    holds each program's HLO: nearly every op joins a program
    whose HLO has the instruction.  The eager integrator carries no
    engine scope, so only the kernels get a layer."""
    from jax.profiler import ProfileData

    path = os.path.join(DATA, "trace_small.xplane.pb")
    raw = open(path, "rb").read()
    texts = scopes.module_texts(raw)
    assert len(texts) >= 20
    (plane,) = [p for p in ProfileData.from_serialized_xspace(raw).planes
                if p.name == DEV]
    lines = {line.name: [(int(e.start_ns), int(e.end_ns), e.name)
                         for e in line.events] for line in plane.lines}
    ops = [(s, e, n.split(" = ", 1)[0].lstrip("%"))
           for s, e, n in lines[tracing.OPS_LINE]]
    joined = scopes.join(lines[scopes.MODULES_LINE], ops)
    found = sum(pid in texts and name in scopes.parse_hlo(texts[pid])[0]
                for _, _, name, pid in joined)
    assert found >= 0.98 * len(ops)
    r = tracing.reduce_xplane(path)
    layers = scopes.device_layers(path, r.window, KERNELS)
    assert {lay for _, _, lay in layers[DEV]} == {scopes.KERNELS, ""}
    assert scopes.layer_busy_share(layers, "event_loop") is None
