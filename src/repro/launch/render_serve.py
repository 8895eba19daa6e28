"""Render-serving driver: synthetic Poisson load through the RenderServer.

  PYTHONPATH=src python -m repro.launch.render_serve --requests 32 --rate 60
  PYTHONPATH=src python -m repro.launch.render_serve --backend pallas --devices 2
  PYTHONPATH=src python -m repro.launch.render_serve --devices 2 \
      --scene-shards 2 --parity-check   # gaussian-sharded scenes, DESIGN.md §10

Generates an open-loop Poisson arrival stream over a mix of scenes and
resolutions (so the bucketer has real work to do), replays it through
queue -> bucketing -> committed engine handles (``RenderServer`` is a thin
loop over ``repro.engine.Renderer``s, DESIGN.md §11), and reports per-bucket
latency, throughput, and executable-cache counters. ``--devices N`` on CPU forces N
virtual host devices (XLA flag set BEFORE jax initializes — which is why the
arg parsing below happens before any repro/jax import) so the sharded path
is exercisable on a laptop.

Exits non-zero if any request was lost or p99 is not finite — the CI smoke
in scripts/check.sh relies on this.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="reference",
                    choices=["reference", "pallas"])
    ap.add_argument("--devices", type=int, default=None,
                    help="shard dispatches over N devices (CPU: forces N "
                         "virtual host devices; must run before jax init)")
    ap.add_argument("--scene-shards", type=int, default=1,
                    help="shard the GAUSSIAN axis D ways over the mesh "
                         "'model' axis (DESIGN.md §10); must divide the "
                         "device count to be physically sharded, otherwise "
                         "the shard axis stays logical")
    ap.add_argument("--device-budget-mb", type=float, default=None,
                    help="simulated per-device HBM cap counting the "
                         "persistent scene parameters (full size "
                         "replicated; 1/D physically sharded) PLUS the "
                         "transient per-camera projected features "
                         "(DESIGN.md §12). A single scene over the cap "
                         "even alone still refuses to serve; scenes that "
                         "fit individually but not TOGETHER page in/out "
                         "LRU through the server's residency manager "
                         "(DESIGN.md §17) — bitwise-invisibly")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="disable the admission-time residency prefetch "
                         "(a queued request's paged-out scene normally "
                         "pages back in before its dispatch)")
    ap.add_argument("--parity-check", action="store_true",
                    help="re-render every completed request on the "
                         "replicated single-camera path and require BITWISE "
                         "identical images (the scene-sharded CI smoke)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=60.0,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--streams", type=int, default=0,
                    help="serve N interactive camera STREAMS instead of the "
                         "stateless request mix (DESIGN.md §15): each stream "
                         "replays an orbit path in frame order through its "
                         "own stream session (exact-reuse frontend cache + "
                         "speculative pre-binning); frames of different "
                         "streams interleave round-robin in the arrival "
                         "process")
    ap.add_argument("--stream-frames", type=int, default=24,
                    help="frames per stream (orbit poses cycle every 16 "
                         "frames, so longer streams lap into the exact-reuse "
                         "cache)")
    ap.add_argument("--spec-depth", type=int, default=2,
                    help="per-stream speculation queue depth (predictions "
                         "pending beyond it drop oldest-first; 0 disables "
                         "speculation)")
    ap.add_argument("--stream-cache-frames", type=int, default=32,
                    help="per-stream frontend-cache capacity (poses, LRU)")
    ap.add_argument("--scenes", default="train,truck",
                    help="comma-separated scene ids to serve")
    ap.add_argument("--gaussians", type=int, default=1500,
                    help="gaussians per synthetic scene")
    ap.add_argument("--resolutions", default="128x128,192x128",
                    help="comma-separated WxH mix; each request draws one "
                         "(each distinct resolution is its own bucket)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait", type=float, default=0.05,
                    help="bucket flush deadline (s)")
    ap.add_argument("--queue-depth", type=int, default=128)
    ap.add_argument("--mode", default="gstg",
                    choices=["gstg", "tile_baseline", "group_baseline"])
    ap.add_argument("--capacity", type=int, default=512)
    ap.add_argument("--autotune", action="store_true",
                    help="open every handle with tile_params='auto' "
                         "(DESIGN.md §13): the first dispatch per (scene, "
                         "resolution) pays a tuning sweep — or hits the "
                         "persisted autotune cache — then serves the tuned "
                         "tile/group/capacity")
    ap.add_argument("--no-realtime", action="store_true",
                    help="replay arrivals as fast as possible (throughput mode)")
    ap.add_argument("--trace-json", default=None,
                    help="write a Chrome trace (load in Perfetto / "
                         "chrome://tracing) of every stage/serving/request "
                         "span; the old stats summary + per-request records "
                         "ride along under the top-level 'summary' key")
    ap.add_argument("--metrics-json", default=None,
                    help="write a repro.metrics/v1 snapshot of the process "
                         "metrics registry (serving.* / engine.* counters, "
                         "gauges, latency histograms)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def _parse_resolutions(spec: str):
    out = []
    for item in spec.split(","):
        w, h = item.lower().split("x")
        out.append((int(w), int(h)))
    return out


def main(argv=None):
    args = parse_args(argv)

    # Virtual host devices must be configured before jax touches the backend.
    if args.devices and args.devices > 1 and "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={args.devices}"
            ).strip()

    import jax
    import numpy as np

    from repro.core.camera import orbit_cameras
    from repro.core.gaussians import scene_like_paper
    from repro.core.pipeline import RenderConfig
    from repro.launch.mesh import make_render_mesh
    from repro.obs import get_registry, get_tracer, trace_env_enabled
    from repro.serving.queue import RenderRequest
    from repro.serving.server import RenderServer, poisson_arrivals
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    # Asking for a trace (or metrics) file opts the process into span
    # recording; REPRO_TRACE=1 additionally turns on fenced per-stage device
    # timing (RenderConfig.timing — a different, per-stage-jit executable;
    # bitwise-identical images, see DESIGN.md §14).
    tracer = get_tracer()
    if args.trace_json or args.metrics_json:
        tracer.enable()
    timing = trace_env_enabled()

    n_dev = len(jax.devices())
    use_dev = min(args.devices or n_dev, n_dev)
    if args.devices and args.devices > n_dev:
        print(f"warning: requested {args.devices} devices, have {n_dev}")
    from repro.launch.mesh import render_mesh_shards

    shards = max(args.scene_shards, 1)
    phys_shards = render_mesh_shards(use_dev, shards)
    if shards > 1 and phys_shards == 1:
        print(f"note: scene_shards={shards} does not divide "
              f"{use_dev} devices; shard axis stays logical")
    mesh = make_render_mesh(use_dev, scene_shards=phys_shards)

    scene_ids = [s.strip() for s in args.scenes.split(",") if s.strip()]
    scenes = {
        sid: scene_like_paper(jax.random.key(i), sid, args.gaussians)
        for i, sid in enumerate(scene_ids)
    }

    cfg = RenderConfig(
        mode=args.mode,
        backend=args.backend,
        group_capacity=args.capacity,
        tile_capacity=args.capacity,
        span=6,
        scene_shards=shards,
        timing=timing,
    )

    # Camera pools per resolution: orbit viewpoints, drawn round-robin per
    # request so repeated signatures exercise the executable cache.
    resolutions = _parse_resolutions(args.resolutions)
    pools = {(w, h): orbit_cameras(16, 4.5, w, h) for w, h in resolutions}

    rng = np.random.default_rng(args.seed)
    if args.streams > 0:
        # Stream mode: N orbiting viewers, frames interleaved round-robin
        # across streams (arrival order preserves per-stream frame order —
        # the property the stream-affinity bucketing relies on).
        total = args.streams * args.stream_frames
        offsets = poisson_arrivals(total, args.rate, seed=args.seed)
        load = []
        i = 0
        for frame in range(args.stream_frames):
            for s in range(args.streams):
                res = resolutions[s % len(resolutions)]
                sid = scene_ids[s % len(scene_ids)]
                cam = pools[res][frame % len(pools[res])]
                load.append((offsets[i], RenderRequest(
                    i, sid, cam, cfg, stream_id=f"s{s}")))
                i += 1
    else:
        total = args.requests
        offsets = poisson_arrivals(total, args.rate, seed=args.seed)
        load = []
        for i, t in enumerate(offsets):
            res = resolutions[rng.integers(len(resolutions))]
            sid = scene_ids[rng.integers(len(scene_ids))]
            cam = pools[res][i % len(pools[res])]
            load.append((t, RenderRequest(i, sid, cam, cfg)))

    server = RenderServer(
        scenes,
        mesh=mesh,
        max_batch=args.max_batch,
        max_wait=args.max_wait,
        queue_depth=args.queue_depth,
        scene_shards=shards,
        device_budget_mb=args.device_budget_mb,
        autotune=args.autotune,
        # Serving tunes on the critical path of the first dispatch, so keep
        # the measured phase short; the cost-model phase still prunes the
        # full default grid.
        autotune_opts={"top_k": 2, "warmup": 1, "reps": 2}
        if args.autotune else None,
        stream_cache_frames=args.stream_cache_frames,
        spec_depth=args.spec_depth,
        prefetch=not args.no_prefetch,
    )

    # Pre-commit every scene through the engine handle (DESIGN.md §11): the
    # simulated device-HBM cap is enforced per scene at commit time — a
    # scene over the budget even ALONE (after shard escalation) fails fast
    # here instead of mid-stream. Scenes that fit individually but not
    # TOGETHER do commit: the server's residency manager pages the cold
    # ones out LRU and back in on demand (DESIGN.md §17).
    for sid in scene_ids:
        try:
            handle = server.commit(sid, cfg)
        except ValueError as e:
            print(f"render_serve: FAILED (scene {sid!r}: {e})")
            server.close()
            return 2
        if args.device_budget_mb is not None:
            hs = handle.stats()
            print(f"scene {sid!r}: "
                  f"{hs['scene_mb_per_device'] + hs['feature_mb_per_device']:.2f}"
                  f" MB/device ({hs['scene_mb_per_device']:.2f} params + "
                  f"{hs['feature_mb_per_device']:.2f} per-camera features, "
                  f"gather={hs['feature_gather']}) within "
                  f"{args.device_budget_mb} MB budget "
                  f"(shards={hs['physical_shards']}, "
                  f"resident={handle.resident})")

    if args.streams > 0:
        print(f"serving {args.streams} streams x {args.stream_frames} frames "
              f"@ {args.rate:.0f} req/s (spec_depth={args.spec_depth}, "
              f"backend={args.backend}, devices={use_dev}, "
              f"scene_shards={shards})")
    else:
        print(f"serving {total} requests @ {args.rate:.0f} req/s "
              f"({len(scene_ids)} scenes x {len(resolutions)} resolutions, "
              f"backend={args.backend}, devices={use_dev}, "
              f"scene_shards={shards})")
    results = server.run(load, realtime=not args.no_realtime)
    print(server.stats.format())
    rs = server.residency.stats()
    print(f"residency: page_ins={rs['page_ins']} "
          f"page_outs={rs['page_outs']} evictions={rs['evictions']} "
          f"hits={rs['hits']} prefetches={rs['prefetches']} "
          f"resident={rs['resident_entries']}/{rs['entries']} "
          f"({rs['resident_mb']:.2f} MB"
          + (f" / {rs['budget_mb']:.2f} MB budget)" if rs["budget_mb"]
             else ", unbudgeted)"))
    if args.streams > 0:
        # Quiesce speculation before any snapshot: in-flight spec runs
        # would otherwise race the trace/metrics dumps below.
        for s in server._streams.values():
            s.wait_spec_idle(timeout=30)
    stream_summaries = server.stream_stats() if args.streams > 0 else {}
    for name, st in sorted(stream_summaries.items()):
        print(f"stream {name}: frames={st['frames']} "
              f"hit_rate={st['hit_rate']:.2f} "
              f"(hits={st['hits']} misses={st['misses']}) "
              f"spec: runs={st['spec_runs']} hits={st['spec_hits']} "
              f"dropped={st['spec_dropped']} discarded={st['spec_discarded']}")
    if args.autotune:
        for (sid, _), handle in sorted(
            server._renderers.items(), key=lambda kv: kv[0][0]
        ):
            print(f"autotuned {sid!r}: tile_params={handle.tile_params}")

    parity_failures = 0
    if args.parity_check:
        import dataclasses as _dc

        from repro import engine
        from repro.serving.bucketing import padded_size
        from repro.sharding.policies import data_extent

        # Compare through the SAME padded dispatch shape the server compiles
        # (pad_to=max_batch over the same mesh) — only the gaussian layout
        # differs, which is exactly the invariant under test. (Eager render()
        # or an unpadded B=1 batch is NOT the reference: a differently-shaped
        # program may fuse differently, moving fp rounding by ~1 ulp for
        # sharded and replicated alike.)
        cfg_repl = _dc.replace(cfg, scene_shards=1)
        pad_shape = padded_size(args.max_batch, data_extent(mesh))
        by_id = {r.request_id: r for _, r in load}
        refs = {
            sid: engine.open(scenes[sid], cfg_repl, mesh=mesh)
            for sid in scene_ids
        }
        for rid, res in sorted(results.items()):
            req = by_id[rid]
            if getattr(req, "stream_id", None) is not None:
                # Stream frames ran the single-camera split path; their
                # stateless reference is the single-camera fused program
                # (bitwise-identical by the §15 invariant) — NOT the padded
                # batch program, whose different shape may fuse differently.
                expect = np.asarray(refs[req.scene_id].render(req.camera).image)
            else:
                expect = np.asarray(
                    refs[req.scene_id]
                    .render_batch([req.camera], pad_to=pad_shape)
                    .image[0]
                )
            if not (expect == res.image).all():
                parity_failures += 1
                print(f"parity MISMATCH: request {rid} (scene "
                      f"{req.scene_id!r}) diverges from the "
                      f"{'stateless' if req.stream_id else 'replicated'} path")
        for ref in refs.values():
            ref.close()
        print(f"parity-check: {len(results) - parity_failures}/{len(results)} "
              f"bitwise-identical to the replicated path")

    if args.trace_json:
        # Chrome trace-event format (repro.trace/v1): traceEvents carry the
        # stage/serving/request spans; the pre-existing stats summary and
        # per-request records ride under "summary" (Perfetto ignores unknown
        # top-level keys, old consumers read doc["summary"]).
        doc = tracer.chrome_trace()
        doc["summary"] = {
            "config": vars(args),
            "devices": use_dev,
            **server.stats.summary(),
            "requests": [
                {
                    "request_id": r.request_id,
                    "latency_ms": r.latency_s * 1e3,
                    "batch_size": r.batch_size,
                    "signature": repr(r.signature),
                    "deadline_missed": r.deadline_missed,
                }
                for r in sorted(results.values(), key=lambda r: r.request_id)
            ],
        }
        with open(args.trace_json, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {args.trace_json} "
              f"({len(doc['traceEvents'])} events, {doc['dropped']} dropped)")

    if args.metrics_json:
        # Snapshot BEFORE close(): Renderer.close() drops its per-handle
        # engine.<name>.* gauges, and the traced smoke validator cross-checks
        # them against the trace.
        with open(args.metrics_json, "w") as f:
            json.dump(get_registry().snapshot(), f, indent=2)
        print(f"wrote {args.metrics_json}")

    server.close()   # releases every committed handle (jit caches + layouts)

    # CI assertions: nothing lost, latency distribution sane, parity holds.
    lost = total - len(results) - server.stats.rejected
    p99 = server.stats.summary()["p99_ms"]
    ok = (
        lost == 0 and len(results) > 0 and math.isfinite(p99)
        and parity_failures == 0
    )
    # Stream smokes must actually exercise reuse: a stream run whose
    # sessions never hit the exact-reuse cache (hit_rate 0 with laps in the
    # load) would silently stop testing the tentpole.
    if args.streams > 0 and args.stream_frames > 16:
        hits = sum(st["hits"] for st in stream_summaries.values())
        if hits == 0:
            ok = False
            print("render_serve: stream load lapped its orbit but recorded "
                  "0 exact-reuse hits")
    print(f"render_serve: {'OK' if ok else 'FAILED'} "
          f"(completed={len(results)}/{total}, "
          f"rejected={server.stats.rejected}, lost={lost}, p99={p99:.1f}ms, "
          f"parity_failures={parity_failures})")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
