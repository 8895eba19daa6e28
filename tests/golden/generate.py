"""Regenerate the golden-image regression fixtures (tests/test_golden.py).

Run from the repo root ONLY when the renderer's output is *supposed* to
change (a numerics-affecting feature with a reviewed diff):

    PYTHONPATH=src python tests/golden/generate.py

Each fixture ``<name>.npz`` is fully self-contained: the scene ARRAYS are
stored (not a PRNG seed — a jax.random implementation change must not be
able to move the pin; regeneration re-renders over the stored arrays and
draws a scene only for a fixture that has no file yet), together with the
camera, the RenderConfig kwargs, and the rendered image per backend. ``checksums.json`` pins the sha256 of
every stored array so accidental regeneration or fixture drift is loud in
review. The images are tiny (64px-side scenes) to keep the fixtures a few
tens of KB and the renders inside the fast test lane.

Backends are pinned separately: reference and pallas images agree only to
fp reassociation in some configurations (DESIGN.md §6), so each backend is
compared bitwise against ITS OWN golden.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent

# (name, scene kwargs, camera kwargs, RenderConfig kwargs). Three tiny
# deterministic scenes covering the gstg ellipse path, the aabb lossless
# combo with degree-1 SH, and the per-tile baseline.
FIXTURES = [
    (
        "mini_gstg",
        dict(seed=11, num_gaussians=96, extent=2.2, sh_degree=0),
        dict(eye=(0.0, 0.9, 3.6), target=(0.0, 0.0, 0.0), width=64, height=64),
        dict(tile=16, group=32, mode="gstg", boundary_group="ellipse",
             boundary_tile="ellipse", group_capacity=128, tile_capacity=128,
             span=4, chunk=16),
    ),
    (
        "aabb_lossless",
        dict(seed=23, num_gaussians=120, extent=2.6, sh_degree=1),
        dict(eye=(1.2, 0.7, 3.2), target=(0.0, 0.1, 0.0), width=64, height=64),
        dict(tile=16, group=32, mode="gstg", boundary_group="aabb",
             boundary_tile="aabb", group_capacity=128, tile_capacity=128,
             span=4, chunk=16),
    ),
    (
        "tile_base",
        dict(seed=37, num_gaussians=80, extent=2.0, sh_degree=0),
        dict(eye=(-0.8, 1.1, 3.0), target=(0.0, 0.0, 0.0), width=64,
             height=48),
        dict(tile=16, group=32, mode="tile_baseline", boundary_tile="ellipse",
             group_capacity=128, tile_capacity=128, span=4, chunk=16),
    ),
]

BACKENDS = ("reference", "pallas")


def _sha256(arr: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(arr).tobytes()).hexdigest()


def render_one_jit(scene, cam, cfg):
    """Render through the SAME jit'd traced-camera closure the engine
    handle compiles (core/pipeline.py::_render_with_traced_camera) — the
    goldens pin the production (jit) numerics, which differ from the eager
    oracle by ~1 ulp of fusion rounding (DESIGN.md §10)."""
    import jax
    import jax.numpy as jnp

    from repro.core.pipeline import (
        _background_array,
        _render_with_traced_camera,
    )

    one = _render_with_traced_camera(
        cfg, cam.width, cam.height, cam.znear, cam.zfar
    )
    return jax.jit(one)(
        scene,
        jnp.asarray(cam.R), jnp.asarray(cam.t),
        jnp.float32(cam.fx), jnp.float32(cam.fy),
        jnp.float32(cam.cx), jnp.float32(cam.cy),
        _background_array(None),
    )


def build_fixture(name, scene_kw, cam_kw, cfg_kw):
    import jax

    from repro.core import GaussianScene, make_camera, random_scene
    from repro.core.pipeline import RenderConfig

    path = HERE / f"{name}.npz"
    if path.exists():
        # Re-render over the committed scene arrays: the pinned inputs stay
        # put even when jax.random draws differently.
        data = np.load(path)
        scene = GaussianScene(**{
            f.name: data[f"scene_{f.name}"]
            for f in dataclasses.fields(GaussianScene)
        })
    else:
        scene = random_scene(
            jax.random.key(scene_kw["seed"]),
            scene_kw["num_gaussians"],
            extent=scene_kw["extent"],
            sh_degree=scene_kw["sh_degree"],
        )
    cam = make_camera(**cam_kw)
    payload = {
        f"scene_{f.name}": np.asarray(getattr(scene, f.name))
        for f in dataclasses.fields(scene)
    }
    payload["camera_json"] = np.frombuffer(
        json.dumps(cam_kw).encode(), dtype=np.uint8
    )
    payload["config_json"] = np.frombuffer(
        json.dumps(cfg_kw).encode(), dtype=np.uint8
    )
    for backend in BACKENDS:
        cfg = RenderConfig(backend=backend, **cfg_kw)
        out = render_one_jit(scene, cam, cfg)
        img = np.asarray(out.image)
        assert int(np.asarray(out.stats.overflow)) == 0, (name, backend)
        assert np.isfinite(img).all(), (name, backend)
        payload[f"image_{backend}"] = img
    return payload


def main() -> None:
    checksums = {}
    for name, scene_kw, cam_kw, cfg_kw in FIXTURES:
        payload = build_fixture(name, scene_kw, cam_kw, cfg_kw)
        np.savez(HERE / f"{name}.npz", **payload)
        checksums[name] = {
            key: _sha256(arr) for key, arr in sorted(payload.items())
        }
        print(f"wrote {name}.npz "
              f"({sum(a.nbytes for a in payload.values()) / 1024:.1f} KB)")
    with open(HERE / "checksums.json", "w") as f:
        json.dump(checksums, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote checksums.json ({len(checksums)} fixtures)")


if __name__ == "__main__":
    main()
