"""Subprocess worker entrypoint: one RenderServer behind line-JSON stdio.

  python -m repro.gateway.worker_main --worker-id w0 --scenes train:0 \
      --devices 2 --gaussians 1500 --max-batch 8

Counterpart of :class:`repro.gateway.transport.SubprocessWorker`. stdout is
RESERVED for the protocol: the real fd 1 is dup'd away for the JSON channel
and fd 1 is re-pointed at stderr before jax loads, so any library print or
warning lands in the log stream instead of corrupting the wire.

Scene construction mirrors ``repro.launch.render_serve`` exactly —
``scene_like_paper(jax.random.key(i), sid, gaussians)`` with ``i`` the
scene's GLOBAL index (shipped as ``sid:i`` in ``--scenes``) — so a worker
hosting any subset of the fleet's scenes builds each one bit-identically
to a direct single-server run, and renders it through the same padded
dispatch shape. That is the whole parity story: the gateway can hand a
request to any worker (or retry it on another after a death) and the
pixels cannot tell.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker-id", required=True)
    ap.add_argument("--scenes", required=True,
                    help="comma-separated sid:global_index pairs; the index "
                         "keys the synthetic scene RNG (parity with the "
                         "single-server scene enumeration)")
    ap.add_argument("--devices", type=int, default=None,
                    help="virtual host devices for THIS worker (set via "
                         "XLA_FLAGS before jax initializes)")
    ap.add_argument("--gaussians", type=int, default=1500)
    ap.add_argument("--scene-shards", type=int, default=1)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait", type=float, default=0.05)
    ap.add_argument("--queue-depth", type=int, default=128)
    ap.add_argument("--mode", default="gstg",
                    choices=["gstg", "tile_baseline", "group_baseline"])
    ap.add_argument("--backend", default="reference",
                    choices=["reference", "pallas"])
    ap.add_argument("--capacity", type=int, default=512)
    ap.add_argument("--device-budget-mb", type=float, default=None)
    return ap.parse_args(argv)


def _emit(out, doc: dict) -> None:
    out.write(json.dumps(doc) + "\n")
    out.flush()


def main(argv=None) -> int:
    args = parse_args(argv)

    # Claim the protocol channel, then point fd 1 at stderr so stray prints
    # (jax banners, library warnings) cannot corrupt the wire.
    proto = os.fdopen(os.dup(1), "w", buffering=1)
    os.dup2(2, 1)
    sys.stdout = sys.stderr

    if args.devices and args.devices > 1 and "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} "
                f"--xla_force_host_platform_device_count={args.devices}"
            ).strip()

    import jax
    import numpy as np

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.core.camera import Camera
    from repro.core.gaussians import scene_like_paper
    from repro.core.pipeline import RenderConfig
    from repro.gateway.transport import decode_array, encode_array
    from repro.launch.mesh import make_render_mesh, render_mesh_shards
    from repro.serving.queue import RenderRequest
    from repro.serving.server import RenderServer

    n_dev = len(jax.devices())
    use_dev = min(args.devices or n_dev, n_dev)
    shards = max(args.scene_shards, 1)
    phys = render_mesh_shards(use_dev, shards)
    mesh = make_render_mesh(use_dev, scene_shards=phys)

    scene_index = {}
    for spec in args.scenes.split(","):
        sid, _, idx = spec.strip().rpartition(":")
        scene_index[sid] = int(idx)
    scenes = {
        sid: scene_like_paper(jax.random.key(i), sid, args.gaussians)
        for sid, i in scene_index.items()
    }
    cfg = RenderConfig(
        mode=args.mode,
        backend=args.backend,
        group_capacity=args.capacity,
        tile_capacity=args.capacity,
        span=6,
        scene_shards=shards,
    )
    server = RenderServer(
        scenes,
        mesh=mesh,
        max_batch=args.max_batch,
        max_wait=args.max_wait,
        queue_depth=args.queue_depth,
        scene_shards=shards,
        device_budget_mb=args.device_budget_mb,
    )

    def decode_camera(doc: dict) -> Camera:
        return Camera(
            R=np.asarray(decode_array(doc["R"])),
            t=np.asarray(decode_array(doc["t"])),
            fx=doc["fx"], fy=doc["fy"], cx=doc["cx"], cy=doc["cy"],
            width=doc["width"], height=doc["height"],
            znear=doc["znear"], zfar=doc["zfar"],
        )

    def committed() -> list:
        return sorted(server.committed_scene_ids)

    def resident() -> list:
        return sorted(server.resident_scene_ids)

    def do_dispatch(msg: dict) -> dict:
        reqs = [
            RenderRequest(
                request_id=r["request_id"],
                scene_id=r["scene_id"],
                camera=decode_camera(r["camera"]),
                cfg=cfg,
                stream_id=r.get("stream_id"),
            )
            for r in msg["requests"]
        ]
        for req in reqs:
            if not server.submit(req):
                server.drain()
                if not server.submit(req):
                    raise RuntimeError(
                        f"queue jammed at depth {server.queue.maxsize}"
                    )
        server.drain()
        results = []
        for req in reqs:
            res = server.results.pop(req.request_id, None)
            if res is None:
                raise RuntimeError(f"lost request {req.request_id}")
            results.append({
                "request_id": req.request_id,
                "image": encode_array(np.asarray(res.image)),
                "latency_s": res.latency_s,
                "batch_size": res.batch_size,
            })
        return {"results": results}

    _emit(proto, {
        "ready": True,
        "worker_id": args.worker_id,
        "devices": use_dev,
        "scenes": sorted(scenes),
        "pid": os.getpid(),
    })
    print(f"[{args.worker_id}] up: {len(scenes)} scenes, "
          f"{use_dev} devices, backend={args.backend}", file=sys.stderr)

    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        msg = json.loads(line)
        rep = {"id": msg.get("id"), "ok": True}
        try:
            op = msg["op"]
            if op == "ping":
                pass
            elif op == "commit":
                server.commit(msg["scene_id"], cfg)
            elif op == "dispatch":
                rep.update(do_dispatch(msg))
            elif op == "shutdown":
                rep["committed"] = committed()
                rep["resident"] = resident()
                _emit(proto, rep)
                break
            else:
                raise ValueError(f"unknown op {op!r}")
            rep["committed"] = committed()
            # Residency piggybacks on every reply, same as the committed
            # set: the parent's placement data stays fresh with no extra RPC.
            rep["resident"] = resident()
        except Exception as e:            # noqa: BLE001 — report, don't die
            rep = {"id": msg.get("id"), "ok": False,
                   "error": f"{type(e).__name__}: {e}"}
        _emit(proto, rep)

    server.close()
    print(f"[{args.worker_id}] shut down", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
