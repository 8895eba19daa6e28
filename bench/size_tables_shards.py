#!/usr/bin/env python3
"""Measures the longest group and tile entry lists of a Gaussian-sharded
configuration's orbit frames, on one chip, one scene shard at a time.

    python3 bench/size_tables_shards.py --workload rubble.paper_4chip --seeds 1,2,3 --frames 8 --parts 8

``bench/size_tables.py`` runs a configuration's whole frontend on one
chip, which paper-size rubble does not fit. A bin's entry list is the sum
of its lists over any partition of the Gaussians, which is how the
sharded program's merge counts it (exact pre-clamp totals). So this tool
projects and identifies ``--parts`` contiguous parts of the Gaussians in
turn (by default the configuration's ``scene_shards`` shards) with the
program's own stages, and adds up:

- each group's list: the shard's valid (Gaussian, group) pairs, the
  lengths the sharded program's merge returns;
- each tile's list: those pairs whose Gaussian passes the configuration's
  tile boundary test against the tile, for the tiles of the group inside
  the image: the reference backend's bitmask (``core/bitmask.py``), which
  the Pallas kernel computes to float32 rounding.

Prints each frame's longest group and tile list, then a last line with
the largest of each over all frames, and whether any Gaussian overflowed
the candidate window. A one-off: the capacities in the configuration file
are set from its last line with the headroom ``PERF.md`` states.
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
from types import SimpleNamespace  # noqa: E402

from bench.poses import orbit_pose  # noqa: E402
from bench.run import enable_cache, orbit_of  # noqa: E402
from bench.scene import make_scene  # noqa: E402
from bench.spec import load_cell  # noqa: E402


# The projected fields each boundary method reads (core/boundary.py).
TEST_FIELDS = {"aabb": ("mean2d", "radius"),
               "obb": ("mean2d", "eigvec", "eigval"),
               "ellipse": ("mean2d", "conic"),
               "ellipse_opacity": ("mean2d", "conic", "alpha")}


def shard_lists(cfg, width, height, znear, zfar):
    """A jitted ``(shard, R, t, fx, fy, cx, cy) -> (group lists (G,), the
    lists of each group's member tiles (G, tiles per group), span
    overflow)`` of one part of the Gaussians."""
    import jax
    import jax.numpy as jnp

    from repro.core.boundary import boundary_test
    from repro.core.camera import Camera
    from repro.core.gaussians import GaussianScene
    from repro.core.grouping import identify, tile_rect_in_group
    from repro.core.pipeline import _grid, _project_in_chunks
    from repro.core.stages import get_backend

    backend = get_backend(cfg.backend)

    def one(shard, R, t, fx, fy, cx, cy):
        cam = Camera(R=R, t=t, fx=fx, fy=fy, cx=cx, cy=cy, width=width,
                     height=height, znear=znear, zfar=zfar)
        grid = _grid(cam, cfg)
        proj = _project_in_chunks(backend, GaussianScene(**shard), cam)
        pairs = identify(proj, grid, "group", cfg.boundary_group)
        G, gf = grid.num_groups, grid.gf
        gid = jnp.minimum(pairs.bin_id, G - 1)
        # Each pair's Gaussian, with the fields its tile test reads, tested
        # against the group's member tiles inside the image: a bitmask.
        near = SimpleNamespace(**{
            f: getattr(proj, f)[pairs.gauss_idx]
            for f in TEST_FIELDS[cfg.boundary_tile]})
        mask = jnp.zeros_like(pairs.bin_id)
        for slot in range(grid.tiles_per_group):
            tx = gid % grid.n_groups_x * gf + slot % gf
            ty = gid // grid.n_groups_x * gf + slot // gf
            rect = tile_rect_in_group(grid, gid, jnp.int32(slot))
            hit = ((tx < grid.n_tiles_x) & (ty < grid.n_tiles_y)
                   & boundary_test(cfg.boundary_tile, near, rect))
            mask = mask | (hit.astype(jnp.int32) << slot)
        # Invalid pairs carry bin G and sort last; a group's pairs are a
        # segment of the sorted list, its tiles' entries the set bits there.
        bins, mask = jax.lax.sort((pairs.bin_id, mask), num_keys=1,
                                  is_stable=False)
        edges = jnp.searchsorted(bins, jnp.arange(G + 1, dtype=jnp.int32))
        tile = []
        for slot in range(grid.tiles_per_group):
            ones = jnp.cumsum((mask >> slot) & 1)
            ones = jnp.concatenate([jnp.zeros((1,), ones.dtype), ones])
            tile.append(ones[edges[1:]] - ones[edges[:-1]])
        return jnp.diff(edges), jnp.stack(tile, axis=1), pairs.n_span_overflow

    return jax.jit(one)


def main(argv=None, bench=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--frames", type=int, default=8)
    ap.add_argument("--parts", type=int, default=None,
                    help="contiguous parts the Gaussians are counted in "
                    "(default: the configuration's scene_shards)")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, bench)

    import jax
    import jax.numpy as jnp

    from repro.core.pipeline import RenderConfig

    enable_cache(jax)
    conf = cell.config
    cfg = RenderConfig(**conf["render"])
    orbit = orbit_of(cell)
    fn = shard_lists(cfg, conf["width"], conf["height"],
                     float(orbit["znear"]), float(orbit["zfar"]))
    shards = args.parts or cfg.scene_shards
    group = tile = span = 0
    devices = jax.devices()
    for seed in [int(s) for s in args.seeds.split(",")]:
        scene = make_scene(seed, conf)
        size = -(-conf["gaussians"] // shards)
        # The parts go round the visible chips, which count them at once.
        parts = [jax.device_put(
            {k: v[d * size:(d + 1) * size] for k, v in scene.items()},
            devices[d % len(devices)]) for d in range(shards)]
        del scene
        angles = cell.kind.angles(cell.traffic,
                                  np.random.default_rng([seed, 1]))
        for _ in range(args.frames):
            p = orbit_pose(next(angles), orbit, conf["width"],
                           conf["height"])
            cam = (jnp.asarray(p.R), jnp.asarray(p.t), jnp.float32(p.fx),
                   jnp.float32(p.fy), jnp.float32(p.cx), jnp.float32(p.cy))
            counts = [fn(part, *jax.device_put(cam, part["sh"].device))
                      for part in parts]
            g, t, s = (sum(np.asarray(c[i], np.int64) for c in counts)
                       for i in range(3))
            g, t, s = int(g.max()), int(t.max()), int(s)
            group, tile, span = max(group, g), max(tile, t), max(span, s)
            print(json.dumps({"seed": seed, "angle": p.angle,
                              "group_list": g, "tile_list": t,
                              "span_overflow": s}), flush=True)
    print(json.dumps({
        "workload": cell.name, "longest_group_list": group,
        "longest_tile_list": tile, "span_overflow": span,
        "group_capacity": cfg.group_capacity,
        "tile_capacity": cfg.tile_capacity}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
