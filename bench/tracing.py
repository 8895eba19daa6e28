"""Reduces a JAX profiler trace to what the per-layer metrics read.

Device operations are the events of each device plane's ``XLA Ops`` line
(``/device:TPU:0`` and so on). A trace recorded on the CPU has no device
plane; there the operations are the host events that carry an ``hlo_op``
stat, which is how the tests exercise this code.

Each operation falls in one class, by its HLO opcode:

- ``kernel``: a Pallas kernel, a ``custom-call`` to ``tpu_custom_call``;
- ``sort``: an XLA ``sort``;
- ``other``: everything else, apart from the loops and calls whose bodies
  appear as operations of their own (``CONTAINERS``).

The window is the benchmark's own ``bench/window`` span on the host. Busy
time is the union of the device operations' intervals clipped to it, per
device and then averaged over devices. An idle gap is an interval of the
window in which no operation ran on a device; it is labelled with the
innermost ``bench/`` host span that covers its middle.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import warnings
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench/window"
SPAN_PREFIX = "bench/"
# Operations whose time is that of the operations they run; they count for
# busy time but not in any class, so nothing is counted twice.
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    name: str
    start: float        # seconds on the trace clock
    end: float
    klass: str


@dataclasses.dataclass
class Summary:
    window: Tuple[float, float]
    devices: int
    busy_s: float                       # averaged over devices
    class_s: Dict[str, float]           # summed over devices
    top_ops: List[Tuple[str, float]]    # by summed duration
    idle_gaps: List[Tuple[str, float]]  # longest first

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def opcode(name: str) -> str:
    """The HLO opcode of a device event. On a TPU the event's name is the
    instruction's text, ``%name = <shape> <opcode>(<operands>), ...``; on
    the CPU it is the instruction's name, ``<opcode>.<n>``."""
    if " = " not in name:
        return name.split(".", 1)[0]
    rest = name.split(" = ", 1)[1].lstrip()
    if rest.startswith("("):                  # a tuple shape
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    return rest.strip().split("(", 1)[0]


def classify(name: str) -> str:
    op = opcode(name)
    if op == "custom-call" and "tpu_custom_call" in name:
        return "kernel"
    if op == "sort":
        return "sort"
    if op in CONTAINERS:
        return "container"
    return "other"


def _stats(event) -> dict:
    # jaxlib builds the stats type on first use and warns that it has no
    # module; the warning says nothing about the trace.
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(event.stats)


def read_events(pd):
    """(device ops by device, host spans) from a ``ProfileData``."""
    ops: Dict[str, List[Op]] = collections.defaultdict(list)
    spans = []
    device_planes = [p for p in pd.planes
                     if p.name.startswith("/device:")
                     and "CUSTOM" not in p.name.upper()]
    for plane in device_planes:
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                s = e.start_ns * 1e-9
                ops[plane.name].append(Op(
                    e.name, s, s + e.duration_ns * 1e-9, classify(e.name)))
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                s = e.start_ns * 1e-9
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name, s, s + e.duration_ns * 1e-9))
                elif not device_planes and not e.name.startswith("end: "):
                    if "hlo_op" in _stats(e):
                        ops["/host:CPU"].append(Op(
                            e.name, s, s + e.duration_ns * 1e-9,
                            classify(e.name)))
    return ops, spans


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _label(t, spans):
    covering = [(e - s, name) for name, s, e in spans
                if s <= t <= e and name != WINDOW_SPAN]
    return min(covering)[1] if covering else "none"


def summarize(pd, top: int = 10) -> Summary:
    return reduce(*read_events(pd), top=top)


def reduce(ops: Dict[str, List[Op]], spans, top: int = 10) -> Summary:
    """The summary of device ``ops`` (by device) within the window that the
    host ``spans`` (name, start, end) mark."""
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    w0, w1 = max(windows, key=lambda w: w[1] - w[0])
    if not ops:
        raise ValueError("the trace holds no device operation")
    busy, gaps = [], []
    class_s = collections.Counter()
    by_name = collections.Counter()
    for dev_ops in ops.values():
        clipped = [(max(o.start, w0), min(o.end, w1), o) for o in dev_ops
                   if o.end > w0 and o.start < w1]
        for s, e, o in clipped:
            if o.klass != "container":
                class_s[o.klass] += e - s
                by_name[o.name] += e - s
        merged = _union([(s, e) for s, e, _ in clipped])
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((_label(0.5 * (s + e), spans), e - s))
    gaps.sort(key=lambda g: -g[1])
    return Summary(
        window=(w0, w1), devices=len(ops),
        busy_s=sum(busy) / len(busy),
        class_s={k: class_s.get(k, 0.0) for k in ("kernel", "sort", "other")},
        top_ops=by_name.most_common(top),
        idle_gaps=gaps[:top],
    )


def load(trace_dir: str):
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(files, key=os.path.getmtime))
