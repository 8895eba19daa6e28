"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (plus a JSON dump per benchmark
under results/bench/). Figures covered:
  Table I     -> bench_sharing     Fig 12 -> bench_boundaries
  Fig 3/5/7/11-> bench_autotune    Fig 13 -> bench_stages
  (the tile/group sweep)           Fig 14/15 -> bench_accel
plus the wall-time microbenchmark of the JAX renderer itself.
bench_autotune additionally refreshes ``BENCH_autotune_<host>.json`` at the
repo root — the committed perf trajectory (DESIGN.md §13) — and
bench_stream refreshes ``BENCH_stream_<host>.json``, the stream-session
exact-reuse speedup trajectory (DESIGN.md §15).
"""
from __future__ import annotations

import json
import os
import time
import traceback


def main() -> None:
    from benchmarks import (
        bench_accel,
        bench_autotune,
        bench_boundaries,
        bench_gateway,
        bench_render_walltime,
        bench_residency,
        bench_scene_scale,
        bench_serving,
        bench_sharing,
        bench_stages,
        bench_stream,
    )

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    os.makedirs("results/bench", exist_ok=True)
    suites = [
        ("table1_sharing", bench_sharing.run),
        ("autotune_sweep", bench_autotune.run),
        ("fig12_boundaries", bench_boundaries.run),
        ("fig13_stages", bench_stages.run),
        ("fig1415_accel", bench_accel.run),
        ("render_walltime", bench_render_walltime.run),
        ("serving", bench_serving.run),
        ("scene_scale", bench_scene_scale.run),
        ("stream_reuse", bench_stream.run),
        ("gateway_fleet", bench_gateway.run),
        ("residency_overcommit", bench_residency.run),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        t0 = time.time()
        try:
            result = fn()
            with open(f"results/bench/{name}.json", "w") as f:
                json.dump(result, f, indent=1, default=str)
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{name},0.0,FAILED")
        finally:
            print(f"# {name} took {time.time()-t0:.1f}s")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
