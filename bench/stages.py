#!/usr/bin/env python3
"""Splits a traced window's device time by pipeline stage, and its idle
time by the host span it fell in.

    python3 bench/stages.py --workload train.backlog --seed 7 --seconds 10

From the root of a checkout, on the chip. Runs the cell as
``bench/run.py --trace 1`` does and prints its result line; then one more
JSON line with what the trace says beyond it:

- ``stage_ms``: device time per completed frame of each stage of
  ``core.pipeline.STAGES``, and of the operations outside every stage
  scope (``unscoped``), summed as ``bench/tracing.py`` sums its classes;
- ``class_by_stage``: the same seconds split by class (``sort``,
  ``kernel``, ``other``), so that where each sort and kernel ran shows;
- ``idle_by_span``: seconds of the window in which no operation ran on the
  device, by the innermost host span they fell in: the benchmark's
  ``bench/`` spans and the program's live spans (those that carry the
  ``mono`` anchor of ``repro.obs``), else ``none``; ``idle_gaps`` the
  longest gaps with the spans they crossed;
- ``anchors``: the program's live spans in the window, and the spread of
  trace clock minus ``mono`` over them (one offset places every program
  stamp on the trace clock).

The fused program runs every stage under ``jax.named_scope("gstg/<stage>")``.
A TPU trace's operation events carry no op name, so an operation's stage
is read from the compiled served program's op metadata
(``Renderer.program_text``) by instruction name. ``--hlo PATH`` also
writes that program's text to PATH.

``bench/run.py`` drops its trace once ``bench/tracing.py`` has summarized
it; this tool wraps that summary to reduce the same trace twice.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import tracing  # noqa: E402

ANCHOR_STAT = "mono"
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
_INSTR = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|to_apply|body)=%?([\w.\-]+)")


def program_stages(hlo_text: str) -> dict:
    """Instruction name -> stage (None outside every scope) over every
    computation of a compiled program's text. An instruction that XLA made
    (a fusion, a wrapped op) and left without an op name takes the stage
    of the computation it calls: that of its root, else the most common
    among its instructions."""
    from repro.core.pipeline import stage_of

    instrs, comps, comp = [], {}, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = comps.setdefault(m.group(1), {"root": None, "all": []})
            continue
        m = _INSTR.match(line)
        if m is None or comp is None:
            continue
        op = _OP_NAME.search(line)
        stage = stage_of(op.group(1)) if op else None
        instrs.append((m.group(2), stage, _CALLS.findall(line)))
        comp["all"].append(stage)
        if m.group(1):
            comp["root"] = stage

    def comp_stage(name):
        c = comps.get(name)
        if c is None:
            return None
        if c["root"] is not None:
            return c["root"]
        scoped = collections.Counter(x for x in c["all"] if x)
        return scoped.most_common(1)[0][0] if scoped else None

    out = {}
    for name, stage, calls in instrs:
        for callee in calls:
            if stage is not None:
                break
            stage = comp_stage(callee)
        out[name] = stage
    return out


def instruction(name: str) -> str:
    """The instruction name of a device event: a TPU event is named by the
    instruction's text, ``%name = ...``; a CPU one by the name."""
    if " = " in name:
        name = name.split(" = ", 1)[0]
    return name.strip().lstrip("%")


def read(pd):
    """(device ops, host spans) of a ``ProfileData``: ops as (device, name,
    start, end, class, stats), spans as (name, start, end, mono or None)."""
    ops, spans = [], []
    device_planes = [p for p in pd.planes
                     if p.name.startswith("/device:")
                     and "CUSTOM" not in p.name.upper()]
    for plane in device_planes:
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                s = e.start_ns * 1e-9
                stats = tracing._stats(e)
                ops.append((plane.name, e.name, s, s + e.duration_ns * 1e-9,
                            tracing.classify(e.name), stats))
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("end: "):
                    continue
                s = e.start_ns * 1e-9
                end = s + e.duration_ns * 1e-9
                stats = None
                if e.name.startswith(tracing.SPAN_PREFIX) or "/" in e.name:
                    stats = tracing._stats(e)
                    if e.name.startswith(tracing.SPAN_PREFIX) \
                            or ANCHOR_STAT in stats:
                        spans.append((e.name, s, end,
                                      _float(stats.get(ANCHOR_STAT))))
                if not device_planes:
                    stats = tracing._stats(e) if stats is None else stats
                    if "hlo_op" in stats:
                        ops.append(("/host:CPU", e.name, s, end,
                                    tracing.classify(e.name), stats))
    return ops, spans


def _float(v):
    try:
        return float(v)
    except (TypeError, ValueError):
        return None


def _innermost(t, spans):
    covering = [(e - s, name) for name, s, e, _ in spans
                if s <= t <= e and name != tracing.WINDOW_SPAN]
    return min(covering)[1] if covering else "none"


def reduce(ops, spans, frames, stages_by_instr, module, top=10) -> dict:
    """Stage times per frame, idle time by span and the anchors of one
    traced window (``bench/tracing.py``'s window, union and classes). An
    op's stage is that of its instruction in the served program's text
    (``program_stages``); ops of other programs (``hlo_module``, where the
    trace names it) are unscoped."""
    from repro.core.pipeline import STAGES

    windows = [(s, e) for name, s, e, _ in spans
               if name == tracing.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {tracing.WINDOW_SPAN} span")
    w0, w1 = max(windows, key=lambda w: w[1] - w[0])
    by_device = collections.defaultdict(list)
    for op in ops:
        by_device[op[0]].append(op)
    stage_s = collections.Counter()
    class_by_stage = collections.defaultdict(collections.Counter)
    idle = collections.Counter()
    gaps = []
    for dev_ops in by_device.values():
        clipped = [(max(s, w0), min(e, w1), name, klass, stats)
                   for _dev, name, s, e, klass, stats in dev_ops
                   if e > w0 and s < w1]
        for s, e, name, klass, stats in clipped:
            if klass == "container":
                continue
            stage = None
            if str(stats.get("hlo_module", module)).startswith(module):
                stage = stages_by_instr.get(instruction(name))
            stage = stage or "unscoped"
            stage_s[stage] += e - s
            class_by_stage[stage][klass] += e - s
        merged = tracing._union([(s, e) for s, e, *_ in clipped])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            cuts = sorted({s, e} | {t for _n, a, b, _m in spans
                                    for t in (a, b) if s < t < e})
            crossed = []
            for a, b in zip(cuts, cuts[1:]):
                label = _innermost(0.5 * (a + b), spans)
                idle[label] += b - a
                if label not in crossed:
                    crossed.append(label)
            gaps.append((e - s, crossed))
    devices = max(len(by_device), 1)
    per_frame = 1e3 / devices / max(frames, 1)
    offsets = [s - mono for name, s, e, mono in spans
               if mono is not None and w0 <= s <= w1]
    gaps.sort(key=lambda g: -g[0])
    return {
        "stage_ms": {k: per_frame * stage_s.get(k, 0.0)
                     for k in STAGES + ("unscoped",)},
        "class_by_stage": {k: dict(v) for k, v in class_by_stage.items()},
        "idle_by_span": dict(idle.most_common()),
        "idle_gaps": [[d, names] for d, names in gaps[:top]],
        "anchors": {"spans": len(offsets),
                    "offset_spread_s": (max(offsets) - min(offsets)
                                        if offsets else None)},
    }


def served_program(server, cell):
    """The compiled text of the batch program the cell's server runs."""
    from bench.poses import orbit_pose
    from bench.run import _program, orbit_of
    from repro.serving.bucketing import padded_size
    from repro.sharding.policies import data_extent

    cfg, make_camera, _req, _open = _program(cell,
                                             list(server.mesh.devices.flat))
    conf, mix = cell.config, cell.traffic
    n = int(mix["max_batch"])
    cams = [make_camera(orbit_pose(0.1 * i, orbit_of(cell), conf["width"],
                                   conf["height"])) for i in range(n)]
    handle = server.commit(conf["name"], cfg)
    return handle.program_text(
        cams, pad_to=padded_size(n, data_extent(server.mesh)))


@contextlib.contextmanager
def keeping(cell):
    """While open, a traced run of ``cell`` given the yielded ``hooks``
    leaves in ``kept`` its server, the compiled served program's stages by
    instruction and the trace's events."""
    kept = {}
    summarize = tracing.summarize

    def keep_server(server):
        kept["server"] = server
        return server

    def summarize_and_keep(pd, top=10):
        try:
            text = kept["text"] = served_program(kept["server"], cell)
            kept["module"] = text.split(None, 2)[1].rstrip(",")
            kept["stages_by_instr"] = program_stages(text)
            kept["events"] = read(pd)
        except Exception as exc:  # noqa: BLE001 — the run goes on
            kept["error"] = repr(exc)
        return summarize(pd, top)

    tracing.summarize = summarize_and_keep
    try:
        yield kept, {"server": keep_server}
    finally:
        tracing.summarize = summarize


def split(kept, frames: int) -> dict:
    """``reduce`` of what ``keeping`` kept, over ``frames`` window
    frames."""
    ops, spans = kept["events"]
    out = reduce(ops, spans, frames, kept["stages_by_instr"], kept["module"])
    out["frames"] = frames
    return out


def main(argv=None) -> int:
    from bench import run
    from bench.spec import load_cell

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--hlo", help="write the served program's text here")
    args, rest = ap.parse_known_args(argv)
    cell = load_cell(args.workload)
    with keeping(cell) as (kept, hooks):
        rc = run.run(["--workload", args.workload, *rest, "--trace", "1"],
                     hooks=hooks)
    if rc != 0 or "events" not in kept:
        print(json.dumps({"error": kept.get("error", "no trace kept")}))
        return rc or 1
    if args.hlo:
        Path(args.hlo).write_text(kept["text"])
    # Every request but the set-up's warm batch is a window frame.
    frames = kept["server"].stats.completed - int(cell.traffic["max_batch"])
    print(json.dumps(split(kept, frames)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
