"""The reduction from a profiler trace to the per-layer metrics.

``data/trace_small.xplane.pb`` was recorded on a TPU v5e: one lockstep step
at N = 65536 under the lockstep cell's settings (a window of one step,
``--seconds 0 --trace 1``).  The synthetic cases pin the interval
arithmetic."""

import os
import types

import pytest

from chipbench import metrics_context, spec, tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL = os.path.join(DATA, "trace_small.xplane.pb")


def test_kind_of_instruction_text():
    assert tracing.kind_of(
        "%snap_packed.31 = f32[4096,8]{1,0} custom-call(f32[4096,8] %a)") \
        == "snap_packed"
    assert tracing.kind_of("%while.57 = (u32[]) while((u32[]) %t)") == "while"
    assert tracing.kind_of("%copy-start = (f32[3]) copy-start(%x)") \
        == "copy-start"
    assert tracing.kind_of("%pad.145.clone = f32[8] pad(%x)") == "pad.145.clone"


def _reduced(ops, spans, host=()):
    return tracing.cut({"/device:TPU:0": [tracing.Op(s, e, k)
                                          for s, e, k in ops]},
                       [(0, 100, tracing.WINDOW)] + list(spans), list(host))


def test_busy_gaps_and_envelopes():
    r = _reduced([(10, 20, "a"), (15, 30, "b"), (50, 60, "snap_packed"),
                  (70, 80, "c")],
                 [(5, 40, tracing.STEP), (45, 90, tracing.STEP)],
                 [(40, 50, "PJRT_LoadedExecutable_Execute")])
    dev = "/device:TPU:0"
    assert tracing.busy(r, dev) == [(10, 30), (50, 60), (70, 80)]
    assert tracing.gaps(tracing.busy(r, dev), r.window) == [
        (0, 10), (30, 50), (60, 70), (80, 100)]
    assert tracing.step_envelopes(r, dev) == [(10, 30), (50, 80)]
    # the longest gap, [30, 50), lies mostly outside both steps
    name, secs = tracing.top_gaps(r, 1)[0]
    assert secs == 20e-9
    assert name.endswith("PJRT_LoadedExecutable_Execute")


def test_exposed_collective():
    r = _reduced([(0, 10, "all-gather-start"), (5, 8, "fusion"),
                  (20, 30, "all-gather"), (40, 50, "fusion")], [])
    # [0, 5) and [8, 10) and [20, 30) have a collective and nothing else
    assert tracing.exposed_collective(r, "/device:TPU:0") == 17


def test_window_cut_clips_ops():
    r = _reduced([(-10, 5, "a"), (95, 120, "b"), (200, 300, "c")], [])
    ops = r.ops["/device:TPU:0"]
    assert [(o.start, o.end) for o in ops] == [(0, 5), (95, 100)]


@pytest.fixture(scope="module")
def small():
    if not os.path.exists(SMALL):
        pytest.fail(f"missing recorded trace {SMALL}")
    return tracing.reduce_xplane(SMALL)


def test_recorded_trace_reduces(small):
    assert list(small.ops) == ["/device:TPU:0"]
    assert len(small.spans) == 1                    # one runner.step
    kinds = {o.kind for o in small.ops["/device:TPU:0"]}
    assert set(metrics_context.FORCE_KERNELS) <= kinds
    assert not kinds & set(tracing.CONTAINERS)
    ops = small.ops["/device:TPU:0"]
    assert all(small.window[0] <= o.start <= o.end <= small.window[1]
               for o in ops)


def test_recorded_trace_metrics(small):
    cell = spec.load_cell("plummer64k_1chip.lockstep")
    cfg = types.SimpleNamespace(stepper="fixed", n=65536, order=6,
                                block_i=None, block_j=None)
    window = types.SimpleNamespace(steps=1, pairs=0.0, events=0.0,
                                   tiles=0.0, wall_s=small.window_ns / 1e9)
    ctx = metrics_context.Context(
        trace=small, window=window, cell=cell, cfg=cfg,
        peaks=spec.peaks("TPU v5 lite"), chips=1)
    got = {m["name"]: spec.reader(m["name"])(ctx) for m in cell.per_layer}
    assert set(got) == {"device_idle_share", "step_gap_share",
                        "force_kernel_roofline", "force_kernel_busy_share"}
    for name, v in got.items():
        assert v is not None and 0.0 <= v <= 100.0, (name, v)
    assert got["force_kernel_busy_share"] > 90.0
    assert 0.0 < got["force_kernel_roofline"] <= 100.0
