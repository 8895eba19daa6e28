"""Gateway fleet driver: Poisson load over N render workers + chaos hook.

  PYTHONPATH=src python -m repro.launch.render_gateway --workers 2 \
      --devices-per-worker 2 --requests 24 --kill-worker auto --kill-after 4

Spawns a worker fleet — subprocess children by default (each with its OWN
jax runtime and virtual-device set, speaking line-JSON over pipes), or
in-process with ``--inproc`` — fronted by a :class:`RenderGateway`
(admission, scene-affinity + stream-sticky routing, heartbeats, failover;
DESIGN.md §16), replays a Poisson arrival stream through it, and reports
fleet-level latency/routing/failover stats. ``--kill-worker/--kill-after``
is the chaos hook: the named worker is SIGKILLed (subprocess) or
flag-killed (inproc) mid-load and the run must still complete every
request — the CI smoke in scripts/check.sh gates on exactly that, plus
``--parity-check`` proving failover is invisible in the pixels.

One process per chip: on a TPU this process holds the devices, so the fleet
must be ``--inproc`` (worker i on its own device where the host has enough);
subprocess workers are refused there.

Exits non-zero if any request was lost, p99 is not finite, parity fails,
or an induced kill produced no failover.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--devices-per-worker", type=int, default=1,
                    help="virtual host devices per worker (each subprocess "
                         "worker forces this count in its own runtime; "
                         "inproc workers share one runtime of this size)")
    ap.add_argument("--inproc", action="store_true",
                    help="in-process workers (one shared jax runtime, each "
                         "worker on its own devices where the host has "
                         "enough) instead of subprocess children; required "
                         "on a TPU, where one process holds the chips")
    ap.add_argument("--backend", default="reference",
                    choices=["reference", "pallas"])
    ap.add_argument("--scenes", default="train,truck")
    ap.add_argument("--gaussians", type=int, default=1500)
    ap.add_argument("--scene-shards", type=int, default=1)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=60.0)
    ap.add_argument("--streams", type=int, default=0,
                    help="serve N camera streams (stream_id-sticky routing) "
                         "instead of the stateless mix")
    ap.add_argument("--stream-frames", type=int, default=16)
    ap.add_argument("--resolutions", default="96x96")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-wait", type=float, default=0.05)
    ap.add_argument("--queue-depth", type=int, default=256)
    ap.add_argument("--worker-queue-depth", type=int, default=128)
    ap.add_argument("--mode", default="gstg",
                    choices=["gstg", "tile_baseline", "group_baseline"])
    ap.add_argument("--capacity", type=int, default=512)
    ap.add_argument("--device-budget-mb", type=float, default=None,
                    help="per-device HBM cap forwarded to EVERY worker: "
                         "each worker's RenderServer pages its committed "
                         "scenes in/out LRU against this budget "
                         "(DESIGN.md §17), and the gateway's router "
                         "prefers workers holding the request's scene "
                         "resident")
    ap.add_argument("--max-retries", type=int, default=3)
    ap.add_argument("--heartbeat-timeout", type=float, default=30.0)
    ap.add_argument("--kill-worker", default=None,
                    help="worker id to kill mid-load ('auto' = first)")
    ap.add_argument("--kill-after", type=int, default=None,
                    help="kill once this many requests completed")
    ap.add_argument("--parity-check", action="store_true",
                    help="re-render every completed request on a direct "
                         "single-server handle and require BITWISE identical "
                         "images (failover must be invisible in the pixels)")
    ap.add_argument("--no-realtime", action="store_true")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip the per-worker warmup dispatch (first real "
                         "dispatch then pays jit compile under heartbeat "
                         "timing)")
    ap.add_argument("--trace-json", default=None)
    ap.add_argument("--metrics-json", default=None)
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

    # The parent runtime sizes itself like ONE worker: subprocess children
    # inherit XLA_FLAGS (same virtual-device count in their own runtimes),
    # and the parity reference must render over the same mesh extent.
    dpw = max(args.devices_per_worker, 1)
    if dpw > 1 and "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={dpw}"
            ).strip()

    import jax
    import numpy as np

    from repro.core.camera import orbit_cameras
    from repro.core.gaussians import scene_like_paper
    from repro.core.pipeline import RenderConfig
    from repro.gateway import RenderGateway
    from repro.launch.mesh import make_render_mesh, render_mesh_shards
    from repro.obs import get_registry, get_tracer
    from repro.serving.queue import RenderRequest
    from repro.serving.server import poisson_arrivals
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if jax.default_backend() != "cpu" and not args.inproc:
        # This process now holds the chip(s): a subprocess worker could not
        # open them, and two processes never share one chip.
        print(f"render_gateway: subprocess workers cannot share the "
              f"{jax.default_backend()} devices this process holds; pass "
              f"--inproc (one in-process worker per device)",
              file=sys.stderr)
        return 2

    tracer = get_tracer()
    if args.trace_json or args.metrics_json:
        tracer.enable()

    scene_ids = [s.strip() for s in args.scenes.split(",") if s.strip()]
    shards = max(args.scene_shards, 1)
    cfg = RenderConfig(
        mode=args.mode,
        backend=args.backend,
        group_capacity=args.capacity,
        tile_capacity=args.capacity,
        span=6,
        scene_shards=shards,
    )

    # -- fleet ----------------------------------------------------------------
    worker_ids = [f"w{i}" for i in range(max(args.workers, 1))]
    if args.inproc:
        from repro.gateway.worker import InprocWorker

        devs = jax.devices()
        use_dev = min(dpw, len(devs))

        def worker_mesh(i):
            # Worker i takes the next use_dev devices, wrapping around when
            # workers x devices-per-worker exceeds the host: on a four-chip
            # host four one-chip workers each get their own chip.
            own = [devs[(i * use_dev + k) % len(devs)] for k in range(use_dev)]
            return make_render_mesh(own, render_mesh_shards(use_dev, shards))

        scenes = {
            sid: scene_like_paper(jax.random.key(i), sid, args.gaussians)
            for i, sid in enumerate(scene_ids)
        }
        workers = [
            InprocWorker(
                wid, scenes, mesh=worker_mesh(i),
                max_batch=args.max_batch, max_wait=args.max_wait,
                queue_depth=args.worker_queue_depth, scene_shards=shards,
                device_budget_mb=args.device_budget_mb,
            )
            for i, wid in enumerate(worker_ids)
        ]
    else:
        from repro.gateway.transport import SubprocessWorker, worker_argv

        specs = [f"{sid}:{i}" for i, sid in enumerate(scene_ids)]
        extra = [
            "--gaussians", str(args.gaussians),
            "--scene-shards", str(shards),
            "--max-batch", str(args.max_batch),
            "--max-wait", str(args.max_wait),
            "--queue-depth", str(args.worker_queue_depth),
            "--mode", args.mode,
            "--backend", args.backend,
            "--capacity", str(args.capacity),
        ]
        if args.device_budget_mb is not None:
            extra += ["--device-budget-mb", str(args.device_budget_mb)]
        print(f"spawning {len(worker_ids)} workers x {dpw} devices ...")
        workers = [
            SubprocessWorker(
                wid, scene_ids,
                worker_argv(wid, specs, devices=dpw, extra=extra),
                max_batch=args.max_batch,
            )
            for wid in worker_ids
        ]

    gw = RenderGateway(
        workers,
        queue_depth=args.queue_depth,
        max_retries=args.max_retries,
        heartbeat_timeout_s=args.heartbeat_timeout,
        devices_per_worker=dpw,
    )

    # Pre-commit scenes round-robin (worker i gets scene i, i+N, ...): the
    # affinity signal the router prefers — and warm every worker's compiled
    # program per (scene, resolution) signature so heartbeat timing sees
    # steady-state dispatches, not jit compiles.
    resolutions = _parse_resolutions(args.resolutions)
    pools = {(w, h): orbit_cameras(16, 4.5, w, h) for w, h in resolutions}
    for i, sid in enumerate(scene_ids):
        workers[i % len(workers)].commit(sid, cfg)
    if not args.no_warmup:
        warm_id = -1
        for w in workers:
            for sid in scene_ids:
                for res in resolutions:
                    w.dispatch([RenderRequest(
                        warm_id, sid, pools[res][0], cfg)])
                    warm_id -= 1

    # -- load -----------------------------------------------------------------
    rng = np.random.default_rng(args.seed)
    if args.streams > 0:
        total = args.streams * args.stream_frames
        offsets = poisson_arrivals(total, args.rate, seed=args.seed)
        load, i = [], 0
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

    kill_worker = args.kill_worker
    if kill_worker == "auto":
        kill_worker = worker_ids[0]
    print(f"gateway: {total} requests @ {args.rate:.0f} req/s over "
          f"{len(workers)} workers ({'inproc' if args.inproc else 'subproc'}"
          f", {dpw} devices each"
          + (f", killing {kill_worker} after {args.kill_after}"
             if kill_worker else "") + ")")
    results = gw.run(
        load,
        realtime=not args.no_realtime,
        kill_worker=kill_worker,
        kill_after=args.kill_after if kill_worker else None,
    )
    summary = gw.summary()
    print(gw.format())
    if args.device_budget_mb is not None:
        # Residency roll call: cached on the parent (subprocess replies
        # piggyback the set), a server property for inproc — no RPC, safe
        # even for a killed worker.
        for w in workers:
            try:
                resident = sorted(w.resident_scene_ids())
            except Exception:       # noqa: BLE001 — reporting only
                resident = ["?"]
            print(f"worker {w.worker_id}: "
                  f"resident={','.join(resident) or '-'} / "
                  f"committed={','.join(sorted(w.committed_scene_ids()))}")

    # -- parity ---------------------------------------------------------------
    parity_failures = 0
    if args.parity_check:
        import dataclasses as _dc

        from repro import engine
        from repro.serving.bucketing import padded_size
        from repro.sharding.policies import data_extent

        n_dev = len(jax.devices())
        use_dev = min(dpw, n_dev)
        mesh = make_render_mesh(use_dev, render_mesh_shards(use_dev, shards))
        ref_scenes = {
            sid: scene_like_paper(jax.random.key(i), sid, args.gaussians)
            for i, sid in enumerate(scene_ids)
        }
        cfg_repl = _dc.replace(cfg, scene_shards=1)
        pad = padded_size(args.max_batch, data_extent(mesh))
        by_id = {r.request_id: r for _, r in load}
        refs = {
            sid: engine.open(ref_scenes[sid], cfg_repl, mesh=mesh)
            for sid in scene_ids
        }
        for rid, res in sorted(results.items()):
            req = by_id[rid]
            expect = np.asarray(
                refs[req.scene_id]
                .render_batch([req.camera], pad_to=pad)
                .image[0]
            )
            if not (expect == np.asarray(res.image)).all():
                parity_failures += 1
                print(f"parity MISMATCH: request {rid} "
                      f"(worker {res.worker_id}, attempts {res.attempts})")
        for ref in refs.values():
            ref.close()
        retried = sum(1 for r in results.values() if r.attempts > 1)
        print(f"parity-check: {len(results) - parity_failures}/"
              f"{len(results)} bitwise-identical to the direct handle "
              f"({retried} of them failover retries)")

    if args.trace_json:
        doc = tracer.chrome_trace()
        doc["summary"] = {
            "config": vars(args),
            **summary,
            "requests": [
                {
                    "request_id": r.request_id,
                    "latency_ms": r.latency_s * 1e3,
                    "worker_id": r.worker_id,
                    "attempts": r.attempts,
                }
                for r in sorted(results.values(), key=lambda r: r.request_id)
            ],
        }
        with open(args.trace_json, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {args.trace_json} "
              f"({len(doc['traceEvents'])} events, {doc['dropped']} dropped)")

    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(get_registry().snapshot(), f, indent=2)
        print(f"wrote {args.metrics_json}")

    gw.close()

    # CI assertions: nothing lost, latency sane, parity holds, and an
    # induced kill must actually have exercised failover.
    lost = total - len(results) - summary["rejected"] - summary["failed"]
    p99 = summary["p99_ms"]
    ok = (
        lost == 0
        and summary["failed"] == 0
        and len(results) > 0
        and math.isfinite(p99)
        and parity_failures == 0
        and (kill_worker is None or summary["failovers"] >= 1)
    )
    print(f"render_gateway: {'OK' if ok else 'FAILED'} "
          f"(completed={len(results)}/{total}, "
          f"rejected={summary['rejected']}, failed={summary['failed']}, "
          f"lost={lost}, retries={summary['retries']}, "
          f"failovers={summary['failovers']}, p99={p99:.1f}ms)")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
