"""Gaussian scene container + synthetic scene generation.

A scene is a pytree of learnable parameters (the 3D-GS parameterization):
    means3d   (N, 3)   world-space centers
    log_scales(N, 3)   per-axis log std-dev
    quats     (N, 4)   rotation quaternions (unnormalized; normalized on use)
    opacity   (N,)     pre-sigmoid opacity logits
    sh        (N, K, 3) spherical-harmonics color coefficients (K = (deg+1)^2)
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import HIGHEST

SH_C0 = 0.28209479177387814  # Y_0^0
# Coefficients per colour channel of SH degrees 0-3, (degree + 1)^2.
SH_COEFFS = (1, 4, 9, 16)



@jax.tree_util.register_dataclass
@dataclasses.dataclass
class GaussianScene:
    means3d: jnp.ndarray
    log_scales: jnp.ndarray
    quats: jnp.ndarray
    opacity: jnp.ndarray
    sh: jnp.ndarray

    @property
    def num_gaussians(self) -> int:
        return self.means3d.shape[0]

    @property
    def sh_degree(self) -> int:
        return int(round(self.sh.shape[1] ** 0.5)) - 1

    def astype(self, dtype) -> "GaussianScene":
        return jax.tree.map(lambda x: x.astype(dtype), self)


def rgb_to_sh0(rgb: jnp.ndarray) -> jnp.ndarray:
    """Inverse of the degree-0 SH color decode (3D-GS convention)."""
    return (rgb - 0.5) / SH_C0


def sh0_to_rgb(sh0: jnp.ndarray) -> jnp.ndarray:
    return sh0 * SH_C0 + 0.5


def quat_to_rotmat(q: jnp.ndarray) -> jnp.ndarray:
    """(..., 4) quaternion (w, x, y, z) -> (..., 3, 3) rotation matrix."""
    q = q / (jnp.linalg.norm(q, axis=-1, keepdims=True) + 1e-12)
    w, x, y, z = q[..., 0], q[..., 1], q[..., 2], q[..., 3]
    r00 = 1 - 2 * (y * y + z * z)
    r01 = 2 * (x * y - w * z)
    r02 = 2 * (x * z + w * y)
    r10 = 2 * (x * y + w * z)
    r11 = 1 - 2 * (x * x + z * z)
    r12 = 2 * (y * z - w * x)
    r20 = 2 * (x * z - w * y)
    r21 = 2 * (y * z + w * x)
    r22 = 1 - 2 * (x * x + y * y)
    rows = [
        jnp.stack([r00, r01, r02], axis=-1),
        jnp.stack([r10, r11, r12], axis=-1),
        jnp.stack([r20, r21, r22], axis=-1),
    ]
    return jnp.stack(rows, axis=-2)


def covariance3d(log_scales: jnp.ndarray, quats: jnp.ndarray) -> jnp.ndarray:
    """Sigma = R S S^T R^T, (N, 3, 3)."""
    R = quat_to_rotmat(quats)
    S = jnp.exp(log_scales)
    M = R * S[..., None, :]  # R @ diag(S)
    return jnp.matmul(M, jnp.swapaxes(M, -1, -2), precision=HIGHEST)


def random_scene(
    key: jax.Array,
    num_gaussians: int,
    extent: float = 4.0,
    scale_range=(-4.6, -1.9),
    opacity_range=(-4.5, 3.5),
    sh_degree: int = 0,
    cluster: bool = True,
) -> GaussianScene:
    """Synthetic scene with clustered Gaussians (mimics real-scene tile-sharing
    statistics better than uniform: real 3D-GS scenes are strongly clustered).
    ``sh_degree`` 0-3 draws (degree + 1)^2 coefficients per channel: the
    degree-0 term from a uniform colour, the others 0.1 times a normal.
    """
    if not 0 <= sh_degree <= 3:
        raise ValueError(f"sh_degree must be 0-3, got {sh_degree}")
    k1, k2, k3, k4, k5, k6, k7 = jax.random.split(key, 7)
    if cluster:
        n_clusters = max(1, num_gaussians // 64)
        centers = jax.random.uniform(
            k1, (n_clusters, 3), minval=-extent, maxval=extent
        )
        assign = jax.random.randint(k2, (num_gaussians,), 0, n_clusters)
        jitter = jax.random.normal(k3, (num_gaussians, 3)) * (extent * 0.08)
        means = centers[assign] + jitter
    else:
        means = jax.random.uniform(
            k1, (num_gaussians, 3), minval=-extent, maxval=extent
        )
    log_scales = jax.random.uniform(
        k4, (num_gaussians, 3), minval=scale_range[0], maxval=scale_range[1]
    )
    quats = jax.random.normal(k5, (num_gaussians, 4))
    opacity = jax.random.uniform(
        k6, (num_gaussians,), minval=opacity_range[0], maxval=opacity_range[1]
    )
    n_sh = (sh_degree + 1) ** 2
    rgb = jax.random.uniform(k7, (num_gaussians, 3), minval=0.05, maxval=0.95)
    sh = jnp.zeros((num_gaussians, n_sh, 3))
    sh = sh.at[:, 0, :].set(rgb_to_sh0(rgb))
    if n_sh > 1:
        hk = jax.random.fold_in(k7, 1)
        sh = sh.at[:, 1:, :].set(
            0.1 * jax.random.normal(hk, (num_gaussians, n_sh - 1, 3))
        )
    return GaussianScene(
        means3d=means.astype(jnp.float32),
        log_scales=log_scales.astype(jnp.float32),
        quats=quats.astype(jnp.float32),
        opacity=opacity.astype(jnp.float32),
        sh=sh.astype(jnp.float32),
    )


def scene_like_paper(key: jax.Array, name: str,
                     num_gaussians: Optional[int] = None,
                     sh_degree: int = 0) -> GaussianScene:
    """Synthetic stand-in scaled to the paper's six evaluation scenes.

    Pretrained 3D-GS-30k checkpoints are not shipped offline; these scenes match
    the *statistics that drive the paper's effect* (Gaussian count scale, spatial
    clustering, screen-space footprint distribution), which is what Table I /
    Figs 5,7 measure.
    """
    from repro.configs.gs_scenes import PAPER_SCENES

    spec = PAPER_SCENES[name]
    n = num_gaussians if num_gaussians is not None else spec.synthetic_gaussians
    return random_scene(
        key,
        n,
        extent=spec.extent,
        sh_degree=sh_degree,
        cluster=True,
    )
