#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload train.backlog --seeds 1,2,3 --seconds 10

For each seed, in one process (the program compiles once): one run's
set-up and window exactly as ``bench/run.py`` serves them, then the
seeded sample of frames against the reference at float32 ``HIGHEST``
(the program's reading) and the same reference computed with 3-pass
bfloat16 products, the control, put in the program's place. Both are
judged by ``bench/check.py`` against the configuration's limits. Prints
one JSON line per seed with both verdicts and both readings of every
number compared, and a last line with the largest program reading, the
smallest control reading of each, and how many seeds each side was
correct on. The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import check  # noqa: E402
from bench.run import (NO_CHIP, enable_cache, look_for_chips,  # noqa: E402
                       references, serve)
from bench.spec import load_cell  # noqa: E402


def readings(cell, seed, seconds, hooks=None):
    served = serve(cell, seed, seconds, hooks=hooks)
    conf = cell.config
    cap, limits = conf["correct"]["pair_capacity"], conf["correct"]["limits"]
    shape = (conf["height"], conf["width"], 3)
    frames = served.sample()
    t = time.monotonic()
    ref, _ = references(served, cap, passes=6)
    t_ref = time.monotonic() - t
    low, _ = references(served, cap, passes=3)
    ok, program = check.judge(frames, ref, limits, shape,
                              served.attempted - len(served.done))
    control_ok, control = check.judge(
        [SimpleNamespace(image=c) for c in low], ref, limits, shape, 0)
    return {"seed": seed, "frames": len(served.done),
            "compared": len(frames), "reference_s": t_ref,
            "frames_per_s": served.frames_per_s,
            "program_correct": ok, "control_correct": control_ok,
            "program": {k: program[k][0] for k in check.NUMBERS},
            "control": {k: control[k][0] for k in check.NUMBERS}}


def main(argv=None, require_chip=True, bench=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, bench)

    import jax

    enable_cache(jax)
    if require_chip:
        why = look_for_chips(jax, int(cell.entry["chips"]))
        if why:
            print(f"control: {why}", file=sys.stderr)
            return NO_CHIP
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        rows.append(readings(cell, seed, args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({
        "workload": cell.name, "seeds": len(rows),
        "program_max": {k: max(r["program"][k] for r in rows)
                        for k in check.NUMBERS},
        "control_min": {k: min(r["control"][k] for r in rows)
                        for k in check.NUMBERS},
        "program_correct": sum(r["program_correct"] for r in rows),
        "control_correct": sum(r["control_correct"] for r in rows),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
