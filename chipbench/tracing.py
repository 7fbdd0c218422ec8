"""Capture a profiler trace of the measured window and reduce it to
intervals that the per-layer metric readers read.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Each TPU is a plane named ``/device:TPU:<k>``; its ``XLA Ops``
line holds one event per HLO instruction the device ran, named by the
instruction's text (``%snap_packed.31 = f32[...] custom-call(...)``).  On a
TPU v5e under JAX 0.9 these events carry no ``jax.named_scope`` path: an
operation is known by its instruction name alone (``Op.kind`` strips the
``%`` and the ``.<n>`` suffix).  Control-flow instructions (``while``,
``conditional``, ``call``) are listed too, spanning the operations they run;
they are containers, not work, and are dropped.  The host's planes hold the
harness's own ``TraceAnnotation`` spans (``WINDOW``, ``STEP``).  All
timestamps are on one clock, in nanoseconds.

Reduction, for each device, over the window only:

* busy: the union of its operations' intervals;
* idle gaps: the window minus busy, each named by the harness span the gap
  overlaps most;
* kind time: the summed durations of operations of given kinds;
* exposed collective time: time in which a collective runs and no other
  operation does.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Callable, Dict, Iterable, List, Optional, Tuple

#: the harness's host spans
WINDOW = "chipbench.window"
STEP = "runner.step"
BETWEEN = "between steps"
#: the device line that holds one event per operation
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
#: instruction kinds that only hold other instructions
CONTAINERS = ("while", "conditional", "call")
#: instruction kinds of collectives (prefix match on the kind)
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all", "send", "recv",
               "collective-broadcast")

Interval = Tuple[int, int]


def kind_of(name: str) -> str:
    """``%snap_packed.31 = f32[...] custom-call(...)`` -> ``snap_packed``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


@dataclasses.dataclass
class Op:
    start: int
    end: int
    kind: str

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Reduced:
    """A trace cut to the window: ops per device, host spans."""

    window: Interval
    ops: Dict[str, List[Op]]
    spans: List[Tuple[int, int, str]]
    host: List[Tuple[int, int, str]] = dataclasses.field(default_factory=list)

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_xplane(path: str) -> Reduced:
    """Read an ``.xplane.pb`` into a :class:`Reduced` cut to the window."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans, host, ops = [], [], {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    kind = kind_of(ev.name)
                    if kind not in CONTAINERS:
                        dev.append(Op(int(ev.start_ns), int(ev.end_ns),
                                      kind))
            ops[plane.name] = dev
        elif not plane.name.startswith("/device:"):
            for line in plane.lines:
                for ev in line.events:
                    item = (int(ev.start_ns), int(ev.end_ns), ev.name)
                    (spans if ev.name in (WINDOW, STEP) else host).append(item)
    return cut(ops, spans, host)


def cut(ops: Dict[str, List[Op]], spans, host=()) -> Reduced:
    windows = [(s, e) for s, e, n in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    w0, w1 = windows[-1]
    kept = {}
    for dev, evs in ops.items():
        kept[dev] = sorted(
            (Op(max(o.start, w0), min(o.end, w1), o.kind)
             for o in evs if o.end > w0 and o.start < w1),
            key=lambda o: o.start)
    steps = sorted((s, e, n) for s, e, n in spans
                   if n == STEP and e > w0 and s < w1)
    host = [(s, e, n) for s, e, n in host if e > w0 and s < w1 and e > s]
    return Reduced((w0, w1), kept, steps, host)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy: List[Interval], window: Interval) -> List[Interval]:
    out, t = [], window[0]
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < window[1]:
        out.append((t, window[1]))
    return out


def busy(r: Reduced, dev: str) -> List[Interval]:
    return union((o.start, o.end) for o in r.ops[dev])


def matching(r: Reduced, dev: str, pred: Callable[[Op], bool]) -> List[Op]:
    return [o for o in r.ops[dev] if pred(o)]


def of_kind(*kinds: str) -> Callable[[Op], bool]:
    return lambda o: o.kind in kinds


def is_collective(o: Op) -> bool:
    return o.kind.lower().startswith(COLLECTIVES)


def exposed_collective(r: Reduced, dev: str) -> int:
    coll = union((o.start, o.end) for o in r.ops[dev] if is_collective(o))
    other = union((o.start, o.end) for o in r.ops[dev]
                  if not is_collective(o))
    return length(coll) - length(intersect(coll, other))


def _most_overlap(items, s: int, e: int, default: str) -> str:
    best, name = 0, default
    for a, b, n in items:
        ov = min(b, e) - max(a, s)
        if ov > best:
            best, name = ov, n
    return name


def _innermost(items, s: int, e: int) -> str:
    """The shortest item covering at least half of [s, e); else the one
    that overlaps it most; '' if none does."""
    half = (e - s) / 2
    covering = [(b - a, n) for a, b, n in items if min(b, e) - max(a, s) >= half]
    if covering:
        return min(covering)[1]
    return _most_overlap(items, s, e, "")


def span_of(r: Reduced, s: int, e: int) -> str:
    """What the host was doing in [s, e): the harness span that overlaps it
    most (``BETWEEN`` if none), then the innermost host runtime event."""
    span = _most_overlap(r.spans, s, e, BETWEEN)
    event = _innermost(r.host, s, e)
    return f"{span}: {event}" if event else span


def step_envelopes(r: Reduced, dev: str) -> List[Interval]:
    """For each harness step span, the interval from the first to the last
    device operation that started inside it."""
    out = []
    evs = r.ops[dev]
    k = 0
    for a, b, _ in r.spans:
        while k < len(evs) and evs[k].start < a:
            k += 1
        first = last = None
        j = k
        while j < len(evs) and evs[j].start < b:
            first = evs[j].start if first is None else first
            last = evs[j].end if last is None else max(last, evs[j].end)
            j += 1
        if first is not None:
            out.append((first, last))
    return out


def top_ops(r: Reduced, k: int = 10) -> List[list]:
    """The ``k`` operation kinds with the most device time, in seconds,
    averaged over the devices."""
    tot: Dict[str, int] = {}
    for evs in r.ops.values():
        for o in evs:
            tot[o.kind] = tot.get(o.kind, 0) + o.dur
    n = max(len(r.ops), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n / 1e9] for name, ns in best]


def top_gaps(r: Reduced, k: int = 10) -> List[list]:
    """The ``k`` longest idle gaps over all devices, each named by the
    harness span it fell in, in seconds."""
    found = []
    for dev in r.ops:
        found.extend(gaps(busy(r, dev), r.window))
    found.sort(key=lambda g: g[0] - g[1])
    return [[span_of(r, s, e), (e - s) / 1e9] for s, e in found[:k]]


def mean_over_devices(r: Reduced, fn: Callable[[str], float]) -> Optional[float]:
    if not r.ops:
        return None
    return sum(fn(d) for d in r.ops) / len(r.ops)
