#!/usr/bin/env python3
"""Measures the longest group and tile entry lists of frames on a cell's
orbit, which a configuration's ``group_capacity`` and ``tile_capacity``
are sized from.

    python3 bench/size_tables.py --workload rubble_eighth.backlog --seeds 1,2,3 --frames 8

For each seed: the configuration's scene and the first ``--frames`` poses
of the mix's path, through the program's own frontend (projection,
identification and the group sort) and its bitmask stage, at the
configuration's sizes. Prints each frame's longest group list (before the
table clamps it) and longest tile list, then a last line with the largest
of each over all frames. A tile list is exact only while every group list
fits ``group_capacity``; the last line says whether they did. A one-off:
the capacities in the configuration file are set from its last line with
the headroom ``PERF.md`` states.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402

from bench.poses import orbit_pose  # noqa: E402
from bench.run import enable_cache, orbit_of  # noqa: E402
from bench.scene import make_scene  # noqa: E402
from bench.spec import load_cell  # noqa: E402


def longest_lists(cfg, width, height, znear, zfar):
    """A jitted ``(scene, R, t, fx, fy, cx, cy) -> (longest group list,
    longest tile list, span overflow)`` over the program's stages."""
    import jax
    import jax.numpy as jnp

    from repro.core.camera import Camera
    from repro.core.pipeline import _grid, render_frontend
    from repro.core.stages import get_backend, mask_tile_lengths

    def one(scene, R, t, fx, fy, cx, cy):
        cam = Camera(R=R, t=t, fx=fx, fy=fy, cx=cx, cy=cy, width=width,
                     height=height, znear=znear, zfar=zfar)
        front = render_frontend(scene, cam, cfg)
        grid = _grid(cam, cfg)
        masks = get_backend(cfg.backend).bitmasks(
            front.proj, front.table, grid, cfg.boundary_tile)
        tiles = mask_tile_lengths(front.table, masks, grid)
        return (jnp.max(front.table.lengths), jnp.max(tiles),
                front.span_overflow)

    return jax.jit(one)


def main(argv=None, bench=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--frames", type=int, default=8)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, bench)

    import jax
    import jax.numpy as jnp

    from repro.core.gaussians import GaussianScene
    from repro.core.pipeline import RenderConfig

    enable_cache(jax)
    conf = cell.config
    cfg = RenderConfig(**conf["render"])
    orbit = orbit_of(cell)
    fn = longest_lists(cfg, conf["width"], conf["height"],
                       float(orbit["znear"]), float(orbit["zfar"]))
    group = tile = span = 0
    for seed in [int(s) for s in args.seeds.split(",")]:
        scene = GaussianScene(**make_scene(seed, conf))
        angles = cell.kind.angles(cell.traffic,
                                  np.random.default_rng([seed, 1]))
        for _ in range(args.frames):
            p = orbit_pose(next(angles), orbit, conf["width"],
                           conf["height"])
            g, t, s = (int(v) for v in fn(
                scene, jnp.asarray(p.R), jnp.asarray(p.t),
                jnp.float32(p.fx), jnp.float32(p.fy), jnp.float32(p.cx),
                jnp.float32(p.cy)))
            group, tile, span = max(group, g), max(tile, t), max(span, s)
            print(json.dumps({"seed": seed, "angle": p.angle,
                              "group_list": g, "tile_list": t,
                              "span_overflow": s}), flush=True)
    print(json.dumps({
        "workload": cell.name, "longest_group_list": group,
        "longest_tile_list": tile, "span_overflow": span,
        "group_capacity": cfg.group_capacity,
        "tile_capacity": cfg.tile_capacity,
        "tile_lists_exact": group <= cfg.group_capacity}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
