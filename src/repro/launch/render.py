"""GS-TG rendering driver: render paper scenes (synthetic stand-ins) with the
tile-grouping pipeline, report stats + cost-model projections.

  PYTHONPATH=src python -m repro.launch.render --scene train --mode gstg
  PYTHONPATH=src python -m repro.launch.render --scene train --backend pallas
  repro-render --scene train --mode gstg          # console-script entry

Either backend goes through the SAME session-style engine handle
(``repro.engine.open``, DESIGN.md §11): the scene is committed once, the
render is jit-cached per camera geometry, and one render produces both the
image and the RenderStats that feed the accelerator cost model. This is the
CI engine-handle smoke for both backends (scripts/check.sh).
"""
from __future__ import annotations

import argparse
import os
import time
from collections import defaultdict

import numpy as np

from benchmarks.common import scene_and_camera
from repro import engine
from repro.core.cost_model import GSTG_ASIC, estimate
from repro.core.pipeline import RenderConfig, render_cache_info
from repro.obs import get_tracer, trace_env_enabled
from repro.utils.compile_cache import enable_compile_cache


def _stage_table(events) -> str:
    """Per-stage device-time table from the tracer's ``category == "stage"``
    spans (ms, aggregated by span name over however many renders ran)."""
    agg = defaultdict(lambda: [0, 0.0])
    for e in events:
        if e.category == "stage":
            agg[e.name][0] += 1
            agg[e.name][1] += e.duration_s
    if not agg:
        return "  (no stage spans recorded)"
    lines = [f"  {'stage':<18s} {'calls':>5s} {'total ms':>9s} {'mean ms':>9s}"]
    for name, (calls, tot) in sorted(agg.items(), key=lambda kv: -kv[1][1]):
        lines.append(f"  {name:<18s} {calls:>5d} {tot * 1e3:>9.3f} "
                     f"{tot * 1e3 / calls:>9.3f}")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scene", default="train")
    ap.add_argument("--mode", default="gstg",
                    choices=["gstg", "tile_baseline", "group_baseline"])
    ap.add_argument("--tile", type=int, default=16)
    ap.add_argument("--group", type=int, default=64)
    ap.add_argument("--boundary-group", default="ellipse")
    ap.add_argument("--boundary-tile", default="ellipse")
    ap.add_argument("--backend", default="reference",
                    choices=["reference", "pallas"],
                    help="stage implementation the engine dispatches to")
    ap.add_argument("--use-kernels", action="store_true",
                    help="deprecated alias for --backend pallas")
    ap.add_argument("--scene-shards", type=int, default=1,
                    help="commit the scene gaussian-sharded D ways "
                         "(DESIGN.md §10/§11)")
    ap.add_argument("--gaussians", type=int, default=None)
    ap.add_argument("--width", type=int, default=None,
                    help="override camera width (smoke renders)")
    ap.add_argument("--height", type=int, default=None,
                    help="override camera height (smoke renders)")
    ap.add_argument("--capacity", type=int, default=1024,
                    help="group/tile table capacity")
    ap.add_argument("--autotune", action="store_true",
                    help="ignore --tile/--group/--capacity and open the "
                         "handle with tile_params='auto' (DESIGN.md §13): "
                         "the first render pays the tuning sweep — or hits "
                         "the persisted autotune cache — and commits the "
                         "tuned knobs")
    ap.add_argument("--stats", action="store_true",
                    help="print executable-cache statistics after the render "
                         "(+ a per-stage device-time table when timing is on "
                         "via REPRO_TRACE=1 or --trace-json)")
    ap.add_argument("--trace-json", default=None,
                    help="write a Chrome trace (Perfetto-loadable) of the "
                         "measured render's per-stage device spans; implies "
                         "fenced per-stage timing (DESIGN.md §14)")
    args = ap.parse_args()
    enable_compile_cache()

    backend = "pallas" if args.use_kernels else args.backend
    # Fenced per-stage timing: each backend stage becomes its own jit'd
    # program with a block_until_ready fence (bitwise-identical image; the
    # fences serialize stages, so the end-to-end walltime is NOT the headline
    # number while timing is on).
    timing = trace_env_enabled() or bool(args.trace_json)
    tracer = get_tracer()
    if timing:
        tracer.enable()
    scene, cam = scene_and_camera(
        args.scene, args.gaussians, width=args.width, height=args.height
    )
    cfg = RenderConfig(
        mode=args.mode,
        tile=args.tile,
        group=args.group,
        boundary_group=args.boundary_group,
        boundary_tile=args.boundary_tile,
        tile_capacity=args.capacity,
        group_capacity=args.capacity,
        span=6,
        backend=backend,
        scene_shards=args.scene_shards,
        timing=timing,
    )
    with engine.open(
        scene, cfg, tile_params="auto" if args.autotune else None
    ) as renderer:
        if timing:
            # Warm render pays the per-stage compiles; clear its spans so the
            # measured render's table/trace shows steady-state device time.
            renderer.render(cam)
            tracer.clear()
        t0 = time.time()
        out = renderer.render(cam)   # ONE render: image + stats, any backend
        img, stats = np.asarray(out.image), out.stats
        dt = time.time() - t0

        print(f"scene={args.scene} mode={args.mode} backend={backend} "
              f"{img.shape} in {dt:.2f}s"
              + (f" tile_params={renderer.tile_params}"
                 if args.autotune else ""))
        print(f"  visible gaussians : {int(stats.n_visible)}")
        print(f"  sort keys         : {int(stats.n_pairs_sort)}")
        print(f"  alpha ops         : {int(stats.alpha_ops)}")
        print(f"  overflow          : {int(stats.overflow)}")
        cost = estimate(
            stats, GSTG_ASIC,
            boundary_group=args.boundary_group,
            boundary_tile=args.boundary_tile,
            mode=args.mode, execution="asic",
        )
        print(f"  accelerator model : total={cost.total_s*1e3:.3f}ms "
              f"(pre={cost.preprocess_s*1e3:.3f} sort={cost.sort_s*1e3:.3f} "
              f"bgm={cost.bitmask_s*1e3:.3f} raster={cost.raster_s*1e3:.3f} "
              f"dram={cost.dram_s*1e3:.3f})  energy={cost.energy_j*1e3:.2f}mJ")
        if args.stats:
            for kind, info in render_cache_info().items():
                print(f"  jit cache [{kind:6s}] : hits={info['hits']} "
                      f"misses={info['misses']} currsize={info['currsize']}/"
                      f"{info['maxsize']}")
            if timing:
                print("  per-stage device time (fenced, steady-state):")
                print(_stage_table(tracer.events()))
        if args.trace_json:
            os.makedirs(os.path.dirname(args.trace_json) or ".", exist_ok=True)
            tracer.write_chrome_trace(args.trace_json)
            print(f"  wrote {args.trace_json}")
    # save a PPM for quick eyeballing (no image deps offline)
    out_path = f"results/render_{args.scene}_{args.mode}_{backend}.ppm"
    os.makedirs("results", exist_ok=True)
    with open(out_path, "wb") as f:
        h, w, _ = img.shape
        f.write(f"P6\n{w} {h}\n255\n".encode())
        f.write((np.clip(img, 0, 1) * 255).astype(np.uint8).tobytes())
    print(f"  wrote {out_path}")


if __name__ == "__main__":
    main()
