"""The benchmark's own scene generator: a synthetic 3D-GS scene from a seed.

A copy of the program's clustered stand-in generator (``random_scene`` /
``scene_like_paper`` in ``repro.core.gaussians``), kept here so that no
change to the program can move the scene under the yardstick. The whole
scene is drawn on the device in one jitted call.

Parameterisation (3D-GS): ``means3d`` (N, 3), ``log_scales`` (N, 3) per-axis
log standard deviations, ``quats`` (N, 4) unnormalised rotations (w, x, y,
z), ``opacity`` (N,) pre-sigmoid logits, ``sh`` (N, (degree + 1)^2, 3)
spherical-harmonics colour: the degree-0 term from a uniform colour, the
higher ones 0.1 times a standard normal, as the program's generator draws
them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

SH_C0 = 0.28209479177387814


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole number, also past 32 bits."""
    key = jax.random.key(seed % 2**32)
    return jax.random.fold_in(key, (seed // 2**32) % 2**32)


@functools.partial(jax.jit, static_argnames=(
    "num_gaussians", "extent", "per_cluster", "jitter", "scale_range",
    "opacity_range", "sh_degree"))
def _draw(key, *, num_gaussians, extent, per_cluster, jitter, scale_range,
          opacity_range, sh_degree):
    n = num_gaussians
    k1, k2, k3, k4, k5, k6, k7 = jax.random.split(key, 7)
    n_clusters = max(1, n // per_cluster)
    centers = jax.random.uniform(k1, (n_clusters, 3), minval=-extent,
                                 maxval=extent)
    assign = jax.random.randint(k2, (n,), 0, n_clusters)
    means = centers[assign] + jax.random.normal(k3, (n, 3)) * (extent * jitter)
    log_scales = jax.random.uniform(k4, (n, 3), minval=scale_range[0],
                                    maxval=scale_range[1])
    quats = jax.random.normal(k5, (n, 4))
    opacity = jax.random.uniform(k6, (n,), minval=opacity_range[0],
                                 maxval=opacity_range[1])
    rgb = jax.random.uniform(k7, (n, 3), minval=0.05, maxval=0.95)
    sh = ((rgb - 0.5) / SH_C0)[:, None, :]
    n_sh = (sh_degree + 1) ** 2
    if n_sh > 1:
        rest = 0.1 * jax.random.normal(jax.random.fold_in(k7, 1),
                                       (n, n_sh - 1, 3))
        sh = jnp.concatenate([sh, rest], axis=1)
    return {
        "means3d": means.astype(jnp.float32),
        "log_scales": log_scales.astype(jnp.float32),
        "quats": quats.astype(jnp.float32),
        "opacity": opacity.astype(jnp.float32),
        "sh": sh.astype(jnp.float32),
    }


def make_scene(seed: int, scene_cfg: dict) -> dict:
    """The configuration's scene, drawn on the device from ``seed``.

    ``scene_cfg`` is a configuration file.
    """
    return _draw(
        seed_key(seed),
        num_gaussians=int(scene_cfg["gaussians"]),
        extent=float(scene_cfg["extent"]),
        per_cluster=int(scene_cfg["per_cluster"]),
        jitter=float(scene_cfg["cluster_jitter"]),
        scale_range=tuple(scene_cfg["log_scale_range"]),
        opacity_range=tuple(scene_cfg["opacity_logit_range"]),
        sh_degree=int(scene_cfg["sh_degree"]),
    )
