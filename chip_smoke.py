#!/usr/bin/env python3
"""Chip smoke test: the GS-TG serving path on a TPU with compiled kernels.

    python chip_smoke.py               # one chip, phases (a)-(d)
    python chip_smoke.py --four-chips  # four chips: the gaussian-sharded path

One chip:
  (a) device   - the platform is a TPU and the Pallas kernels compile
                 (``default_interpret()`` is false, the HLO holds the
                 ``tpu_custom_call`` kernels);
  (b) serve    - ``RenderServer`` serves orbit-camera requests of the train
                 scene at its paper size (1,026,000 Gaussians, 1959x1090),
                 gstg with tile 16 / group 64 on the pallas backend, and every
                 frame is lossless (``overflow == span_overflow == 0``);
  (c) parity   - the same requests through ``backend="reference"`` on the same
                 chip: images and integer counters within the bounds below;
  (d) goldens  - the committed golden scenes rendered on the chip against
                 their CPU reference images (catches bfloat16 matmul passes).

Four chips (``--four-chips``, nothing else runs): rubble at native
4608x3456 and SH degree 3 with ``scene_shards=4`` on a (data=1, model=4)
mesh, the backend split by group band. Every frame is lossless and finite,
and its band entries add up to its bitmask tests.
  (1) a quarter of the paper's Gaussians (2,265,000), the most whose
      replicated one-chip render fits, against that render of the same
      cameras: images within ``SHARDED_MAX_ABS``, frontend counters equal;
  (2) the paper size (9,060,000 Gaussians). No one-chip render of it fits;
      the benchmark's cell ``rubble.paper_4chip`` checks it against the
      plain reference (``bench/check.py``).

Times printed on the way are set-up and information, not benchmark numbers.
The last line of stdout is ``{"ok": true, "device": {...}}``; any failure
exits non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
GOLDEN = ROOT / "tests" / "golden"

# (c) Pallas vs reference on one chip. Both run the same XLA frontend, so
# the frontend counters are exact. The raster differs by float32
# association (lane-scan vs cumprod, lane sums vs a matmul) and by Mosaic vs
# XLA exp/divide; where that moves an alpha across 1/255 or q = 9, or a
# transmittance across T_EPS, one pixel changes by at most about
# op * exp(-4.5) < 0.0112. So: a max-abs bound that admits a flip, a
# mean-abs bound that does not admit a systematic error, and counters
# within a relative 1e-5 (flips are rare).
PARITY_MAX_ABS = 0.02
PARITY_MEAN_ABS = 1e-5
EXACT_COUNTERS = ("n_visible", "n_pairs_sort")
NEAR_COUNTERS = ("tile_entries", "alpha_ops", "blend_ops")
COUNTER_REL = 1e-5
# (d) chip vs CPU reference goldens (64x64 scenes): float32 geometry agrees
# to a few ulp; bfloat16 passes shift splat centres by ~0.1 px and images
# by ~1e-2.
GOLDEN_MAX_ABS = 1e-3
# Four chips (1): the split render against the replicated one, and the
# table sizes of its quarter-size scene: the paper size's longest lists,
# 49k (group) and 13.7k (tile) at 1/64 of the Gaussians scaled by 64, over
# four, with room.
SHARDED_MAX_ABS = 1e-5
QUARTER_GAUSSIANS = 2_265_000
QUARTER_CAPACITY = (16384, 6144)


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def phase_device(jax):
    """(a) The device is a TPU and kernels compile (no interpreter)."""
    from repro.kernels.ops import default_interpret

    devs = jax.devices()
    d = devs[0]
    print(f"[a] devices: {devs}")
    print(f"[a] platform={d.platform} kind={d.device_kind} count={len(devs)}")
    check(d.platform == "tpu", f"platform is {d.platform!r}, not 'tpu'")
    check(not default_interpret(),
          "default_interpret() is true: Pallas kernels would be interpreted")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def _lower_text(cfg, scene, cam):
    """StableHLO of the engine's render program for this camera."""
    import jax
    import jax.numpy as jnp

    from repro.core.pipeline import _background_array, _render_with_traced_camera

    one = _render_with_traced_camera(
        cfg, cam.width, cam.height, cam.znear, cam.zfar)
    return jax.jit(one).lower(
        scene, jnp.asarray(cam.R), jnp.asarray(cam.t),
        jnp.float32(cam.fx), jnp.float32(cam.fy),
        jnp.float32(cam.cx), jnp.float32(cam.cy), _background_array(None),
    ).as_text()


def _stats_rows(out, n):
    import numpy as np

    return [
        {k: float(np.asarray(v)[i]) for k, v in vars(out.stats).items()}
        for i in range(n)
    ]


def phase_serve(jax, args):
    """(b) Serve the train scene at paper scale through RenderServer."""
    import numpy as np

    from repro.configs.gs_scenes import PAPER_SCENES, SERVE_SPECS
    from repro.core.camera import orbit_cameras
    from repro.core.gaussians import scene_like_paper
    from repro.core.pipeline import CameraBatch, RenderConfig
    from repro.serving.bucketing import padded_size
    from repro.serving.queue import RenderRequest
    from repro.serving.server import RenderServer
    from repro.sharding.policies import data_extent

    spec, serve = PAPER_SCENES["train"], SERVE_SPECS["train"]
    check(serve.gaussians == spec.paper_gaussians,
          "the train deployment is served at its paper size")
    t0 = time.perf_counter()
    scene = scene_like_paper(jax.random.key(args.seed), "train",
                             serve.gaussians)
    jax.block_until_ready(scene)
    cams = orbit_cameras(args.requests, serve.orbit_radius * spec.extent,
                         spec.width, spec.height)
    cfg = RenderConfig(
        mode="gstg", tile=16, group=64, backend="pallas",
        group_capacity=serve.group_capacity,
        tile_capacity=serve.tile_capacity, span=serve.span,
    )
    print(f"[b] scene train: {scene.num_gaussians} Gaussians, "
          f"{spec.width}x{spec.height}, {len(cams)} orbit requests at "
          f"{serve.orbit_radius} extents; group_capacity="
          f"{cfg.group_capacity} tile_capacity={cfg.tile_capacity} "
          f"span={cfg.span} (set-up {time.perf_counter() - t0:.1f}s)")

    hlo = _lower_text(cfg, scene, cams[0])
    n_kernels = hlo.count("tpu_custom_call")
    print(f"[b] render program holds {n_kernels} tpu_custom_call ops")
    check(n_kernels >= 2, "no compiled Pallas kernel in the render program")

    server = RenderServer({"train": scene}, max_batch=args.max_batch,
                          max_wait=0.0)
    try:
        t0 = time.perf_counter()
        server.submit(RenderRequest(-1, "train", cams[0], cfg))
        server.drain()
        print(f"[b] set-up: compile + first request "
              f"{time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        for i, cam in enumerate(cams):
            check(server.submit(RenderRequest(i, "train", cam, cfg)),
                  f"request {i} rejected")
        server.drain()
        dt = time.perf_counter() - t0
        print(f"[b] informational: {len(cams)} warm requests in {dt:.2f}s "
              f"({dt / len(cams):.3f}s per request, batches of "
              f"{args.max_batch}; host clock, not a benchmark)")
        images = [np.asarray(server.results[i].image)
                  for i in range(len(cams))]

        # Counters of the same requests through the same committed handle
        # and program (the server keeps images only).
        handle = server.commit("train", cfg)
        pad = padded_size(args.max_batch, data_extent(server.mesh))
        stats = []
        for i in range(0, len(cams), args.max_batch):
            part = cams[i:i + args.max_batch]
            out = handle.render_batch(CameraBatch.from_cameras(part),
                                      pad_to=pad)
            again = np.asarray(out.image)
            for j in range(len(part)):
                check(np.array_equal(again[j], images[i + j]),
                      f"request {i + j}: re-render of the served program "
                      "differs")
            stats += _stats_rows(out, len(part))
    finally:
        server.close()

    for i, (img, st) in enumerate(zip(images, stats)):
        check(img.shape == (spec.height, spec.width, 3),
              f"request {i}: image shape {img.shape}")
        check(np.isfinite(img).all(), f"request {i}: non-finite pixels")
        print(f"[b] request {i}: visible={st['n_visible']:.0f} "
              f"sort_keys={st['n_pairs_sort']:.0f} "
              f"tile_entries={st['tile_entries']:.0f} "
              f"alpha_ops={st['alpha_ops']:.0f} "
              f"overflow={st['overflow']:.0f} "
              f"span_overflow={st['span_overflow']:.0f} "
              f"mean={img.mean():.4f}")
        check(st["overflow"] == 0 and st["span_overflow"] == 0,
              f"request {i} is not lossless: overflow={st['overflow']}, "
              f"span_overflow={st['span_overflow']}")
    return scene, cams, cfg, images, stats


def phase_parity(jax, scene, cams, cfg, images, stats):
    """(c) The same requests through the reference backend."""
    import numpy as np

    from repro import engine

    ref_cfg = dataclasses.replace(cfg, backend="reference")
    t0 = time.perf_counter()
    with engine.open(scene, ref_cfg, devices=1) as ref:
        for i, cam in enumerate(cams):
            out = ref.render_batch([cam])
            img = np.asarray(out.image[0])
            st = _stats_rows(out, 1)[0]
            diff = np.abs(img - images[i])
            over = int((diff > 1e-4).sum())
            print(f"[c] request {i}: max|d|={diff.max():.3e} "
                  f"mean|d|={diff.mean():.3e} channels>1e-4={over} "
                  + " ".join(f"d{k}={st[k] - stats[i][k]:+.0f}"
                             for k in EXACT_COUNTERS + NEAR_COUNTERS))
            check(diff.max() <= PARITY_MAX_ABS,
                  f"request {i}: max abs diff {diff.max()} > "
                  f"{PARITY_MAX_ABS}")
            check(diff.mean() <= PARITY_MEAN_ABS,
                  f"request {i}: mean abs diff {diff.mean()} > "
                  f"{PARITY_MEAN_ABS}")
            for k in EXACT_COUNTERS:
                check(st[k] == stats[i][k],
                      f"request {i}: {k} {st[k]} != {stats[i][k]}")
            for k in NEAR_COUNTERS:
                check(abs(st[k] - stats[i][k])
                      <= COUNTER_REL * max(abs(st[k]), 1.0),
                      f"request {i}: {k} {st[k]} vs {stats[i][k]}")
            check(st["overflow"] == 0 and st["span_overflow"] == 0,
                  f"request {i}: reference render is not lossless")
    print(f"[c] reference parity held for {len(cams)} requests "
          f"({time.perf_counter() - t0:.1f}s incl. compile)")


def phase_goldens(jax):
    """(d) Committed golden scenes on the chip vs their CPU references."""
    import numpy as np

    from repro.core import GaussianScene, make_camera
    from repro.core.pipeline import RenderConfig

    spec = importlib.util.spec_from_file_location(
        "golden_generate", GOLDEN / "generate.py")
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    files = sorted(GOLDEN.glob("*.npz"))
    check(files, f"no golden scenes under {GOLDEN}")
    for path in files:
        data = np.load(path)
        scene = GaussianScene(**{
            f.name: data[f"scene_{f.name}"]
            for f in dataclasses.fields(GaussianScene)
        })
        cam_kw = json.loads(bytes(data["camera_json"]).decode())
        cam = make_camera(**cam_kw)
        cfg_kw = json.loads(bytes(data["config_json"]).decode())
        want = data["image_reference"]
        for backend in ("reference", "pallas"):
            out = gen.render_one_jit(
                scene, cam, RenderConfig(backend=backend, **cfg_kw))
            img = np.asarray(out.image)
            diff = float(np.abs(img - want).max())
            print(f"[d] golden {path.stem} {backend}: max|d| vs CPU "
                  f"reference {diff:.3e}")
            check(img.shape == want.shape, f"{path.stem}: shape {img.shape}")
            check(diff <= GOLDEN_MAX_ABS,
                  f"golden {path.stem}/{backend}: {diff} > {GOLDEN_MAX_ABS}")


def _device_bytes(device):
    """Live and peak bytes the runtime reports for ``device``."""
    stats = device.memory_stats() or {}
    return (f"bytes_in_use={stats.get('bytes_in_use', 'not reported')} "
            f"peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use', 'not reported')}")


def _plan(handle, cam):
    """The compiler's per-device memory plan of the handle's program."""
    m = handle.memory_analysis(cam)
    return (f"arguments={m.argument_size_in_bytes} "
            f"outputs={m.output_size_in_bytes} temp={m.temp_size_in_bytes}")


def _render_all(jax, handle, cams):
    """Each camera's output on the host, with its stats as floats."""
    import numpy as np

    outs = []
    for cam in cams:
        out = jax.tree.map(np.asarray, handle.render(cam))
        outs.append((out, {k: float(v) for k, v in vars(out.stats).items()}))
    return outs


def _split_render(jax, devs, scene, cfg, cams, label):
    """``cams`` rendered with ``scene`` Gaussian-sharded over ``devs``, the
    backend split by group band; each frame checked lossless, finite and
    with band entries that add up to its bitmask tests."""
    import numpy as np

    from repro import engine
    from repro.launch.mesh import make_render_mesh

    scene_bytes = sum(x.nbytes for x in jax.tree.leaves(scene))
    mesh4 = make_render_mesh(devs, scene_shards=4)
    print(f"[4] {label}: {scene.num_gaussians} Gaussians, SH degree "
          f"{scene.sh_degree}, {len(cams)} cameras; group_capacity="
          f"{cfg.group_capacity} tile_capacity={cfg.tile_capacity} "
          f"span={cfg.span}; mesh {dict(mesh4.shape)}")
    t0 = time.perf_counter()
    with engine.open(scene, cfg, mesh=mesh4) as h:
        st = h.stats()
        check(st["physical_shards"] == 4,
              f"scene is not physically 4-way sharded: {st}")
        print(f"[4] commit: physical_shards={st['physical_shards']} "
              f"scene_mb_per_device={st['scene_mb_per_device']:.1f} "
              f"feature_mb_per_device={st['feature_mb_per_device']:.1f}")
        for d in devs:
            held = sum(s.data.nbytes for leaf in jax.tree.leaves(
                h.committed_scene) for s in leaf.addressable_shards
                if s.device == d)
            print(f"[4] {d}: committed scene bytes {held} "
                  f"(whole scene {scene_bytes})")
        outs = _render_all(jax, h, cams)
        print(f"[4] split renders {time.perf_counter() - t0:.1f}s "
              "(set-up, incl. compile)")
        for i, (out, stats) in enumerate(outs):
            print(f"[4] camera {i}: band entries {out.bands.tolist()} "
                  f"overflow={stats['overflow']:.0f} "
                  f"span_overflow={stats['span_overflow']:.0f} "
                  f"visible={stats['n_visible']:.0f}")
            check(np.isfinite(out.image).all(),
                  f"{label} camera {i}: non-finite pixels")
            check(stats["overflow"] == 0 and stats["span_overflow"] == 0,
                  f"{label} camera {i}: not lossless")
            check(out.bands.shape == (4,)
                  and out.bands.sum() * 16 == stats["n_bit_tests"],
                  f"{label} camera {i}: band entries {out.bands} do not "
                  "add up")
        print(f"[4] split program, per device: {_plan(h, cams[0])}")
        for d in devs:
            print(f"[4] {d} after the split renders: {_device_bytes(d)}")
    return outs


def phase_four_chips(jax, args):
    """Rubble Gaussian-sharded over four chips, the backend split by group
    band: at a quarter of its Gaussians against the replicated one-chip
    render, then at its paper size."""
    import numpy as np

    from repro import engine
    from repro.configs.gs_scenes import PAPER_SCENES, SERVE_SPECS
    from repro.core.camera import orbit_cameras
    from repro.core.gaussians import scene_like_paper
    from repro.core.pipeline import RenderConfig
    from repro.launch.mesh import make_render_mesh

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    spec, serve = PAPER_SCENES["rubble"], SERVE_SPECS["rubble"]
    cams = orbit_cameras(args.four_chip_cameras,
                         serve.orbit_radius * spec.extent,
                         spec.width, spec.height)

    def config(capacity, shards):
        return RenderConfig(
            mode="gstg", tile=16, group=64, backend="pallas",
            group_capacity=capacity[0], tile_capacity=capacity[1],
            span=serve.span, scene_shards=shards)

    # (1) a quarter of the Gaussians: split against replicated on one chip.
    key = jax.random.key(args.seed)
    scene = scene_like_paper(key, "rubble", QUARTER_GAUSSIANS, sh_degree=3)
    split = _split_render(jax, devs, scene, config(QUARTER_CAPACITY, 4),
                          cams, "quarter")
    t0 = time.perf_counter()
    with engine.open(scene, config(QUARTER_CAPACITY, 1),
                     mesh=make_render_mesh(devs[:1])) as h:
        replicated = _render_all(jax, h, cams)
        print(f"[4] one-chip replicated renders "
              f"{time.perf_counter() - t0:.1f}s (set-up, incl. compile)")
        print(f"[4] replicated program: {_plan(h, cams[0])}")
        print(f"[4] {devs[0]} after the replicated renders: "
              f"{_device_bytes(devs[0])}")
    for i, ((out_s, st_s), (out_r, st_r)) in enumerate(
            zip(split, replicated)):
        diff = float(np.abs(out_s.image - out_r.image).max())
        print(f"[4] camera {i}: max|d| split vs replicated {diff:.3e}")
        check(diff <= SHARDED_MAX_ABS,
              f"camera {i}: split vs replicated {diff} > {SHARDED_MAX_ABS}")
        for k in ("n_visible", "n_pairs_sort", "tile_entries"):
            check(st_s[k] == st_r[k], f"camera {i}: {k} {st_s[k]} != {st_r[k]}")
    del scene, split, replicated

    # (2) the paper size.
    scene = scene_like_paper(key, "rubble", serve.gaussians, sh_degree=3)
    _split_render(jax, devs, scene,
                  config((serve.group_capacity, serve.tile_capacity), 4),
                  cams, f"paper size ({spec.paper_gaussians})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip gaussian-sharded phase")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-batch", type=int, default=2)
    ap.add_argument("--four-chip-cameras", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: FAILED: no repro package under {SRC}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(SRC))
    try:
        import jax

        from repro.utils.compile_cache import enable_compile_cache

        print(f"compile cache: {enable_compile_cache()}")
        device = phase_device(jax)
        if args.four_chips:
            phase_four_chips(jax, args)
        else:
            served = phase_serve(jax, args)
            phase_parity(jax, *served)
            phase_goldens(jax)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
