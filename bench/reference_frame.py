#!/usr/bin/env python3
"""References frames of a cell's configuration on the chip, with no server:
how many pairs, how many bands, how long and how much device memory the
check of one frame takes. For sizing ``correct.pair_capacity`` of a
configuration before a cell serves it.

    python3 bench/reference_frame.py --workload rubble_eighth.backlog \\
        --seed 7 --set gaussians=9060000 --set sh_degree=3

The scene is drawn from ``--seed`` as a run draws it, with the
configuration's top-level keys overridden by ``--set key=<JSON value>``;
the poses are the first ``--frames`` of the cell's traffic for that seed.
Each frame is rendered twice: the first compiles, the second does not.
Prints one JSON line per frame and a last line with the device's peak
memory (in use plus reserved for programs' temporaries). Exits 3 without
a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402

from bench import reference  # noqa: E402
from bench.poses import orbit_pose  # noqa: E402
from bench.run import (NO_CHIP, _peak_bytes, enable_cache,  # noqa: E402
                       look_for_chips, orbit_of)
from bench.scene import make_scene  # noqa: E402
from bench.spec import load_cell  # noqa: E402


def main(argv=None, require_chip=True, bench=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--frames", type=int, default=1)
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=JSON")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, bench)
    conf = dict(cell.config)
    for item in args.set:
        key, value = item.split("=", 1)
        conf[key] = json.loads(value)

    import jax

    enable_cache(jax)
    if require_chip:
        why = look_for_chips(jax, 1)
        if why:
            print(f"reference_frame: {why}", file=sys.stderr)
            return NO_CHIP
    t = time.monotonic()
    scene = make_scene(args.seed, conf)
    jax.block_until_ready(scene)
    scene_s = time.monotonic() - t
    cap = int(conf["correct"]["pair_capacity"])
    poses = cell.kind.angles(cell.traffic, np.random.default_rng([args.seed,
                                                                  1]))
    for _ in range(args.frames):
        pose = orbit_pose(next(poses), orbit_of(cell), conf["width"],
                          conf["height"])
        pairs, rows, bands = reference.band_plan(scene, pose, capacity=cap)
        seconds = []
        for _ in range(2):
            t = time.monotonic()
            img, counts = reference.render(scene, pose, capacity=cap,
                                           with_counts=True)
            img = np.asarray(img)
            seconds.append(time.monotonic() - t)
        print(json.dumps({
            "angle": pose.angle, "pairs": pairs, "capacity": cap,
            "bands": bands,
            "band_pairs": [int(rows[a:b].sum()) for a, b in bands],
            "seconds": seconds, "counts": counts,
            "image_mean": float(img.mean()),
            "finite": bool(np.isfinite(img).all())}), flush=True)
        del img
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    print(json.dumps({
        "workload": cell.name, "seed": args.seed,
        "set": dict(i.split("=", 1) for i in args.set),
        "gaussians": int(conf["gaussians"]),
        "scene_bytes": int(sum(a.nbytes for a in scene.values())),
        "scene_s": scene_s, "device": dev.device_kind,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "peak_bytes_reserved": stats.get("peak_bytes_reserved"),
        "memory_peak_bytes": _peak_bytes([dev])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
