"""Shared feature layout for the GS-TG kernels.

Kernels consume gathered per-bin Gaussian features in an SoA (feature-major)
layout (F, K): the K entry axis maps to VPU lanes, features to sublanes. K is
padded to a multiple of 128 (lane width); F is 16 so fp32 blocks tile the
(8, 128) VMEM layout exactly.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.projection import proj_take
from repro.utils import round_up

F_MEAN_X = 0
F_MEAN_Y = 1
F_CONIC_A = 2
F_CONIC_B = 3
F_CONIC_C = 4
F_OPACITY = 5   # 0 for invalid entries
F_RGB_R = 6
F_RGB_G = 7
F_RGB_B = 8
F_RADIUS = 9
F_EIGVEC_X = 10
F_EIGVEC_Y = 11
F_EIGVAL_1 = 12
F_EIGVAL_2 = 13
F_DEPTH = 14
F_VALID = 15
NUM_FEATURES = 16

LANE = 128


def pack_features(
    proj,
    gauss_idx: jnp.ndarray,
    entry_valid: jnp.ndarray,
    multiple: int = LANE,
):
    """Gather Projected fields into (B, NUM_FEATURES, K_pad) fp32 blocks.

    gauss_idx/entry_valid: (B, K). Invalid entries get opacity 0 (=> alpha 0 in
    the raster kernel) and valid flag 0. ``multiple`` sets the K padding
    granularity: a multiple of LANE that the kernel's raster chunk divides.

    ``proj`` may be a flat ``Projected`` or a ``ShardedProjected`` kept in
    the per-shard layout (DESIGN.md §12): the gathers route through
    ``proj_take``, so the kernel-facing packed block is built straight from
    the owning shards without ever materializing the flat full-N features —
    and is bitwise-identical to the flat-gathered block.
    """
    B, K = gauss_idx.shape
    K_pad = round_up(max(K, 1), max(int(multiple), 1))
    v = entry_valid

    def g(field, ch=None):
        out = proj_take(proj, field, gauss_idx)
        if ch is not None:
            out = out[..., ch]
        return jnp.where(v, out, 0.0).astype(jnp.float32)

    feats = [
        g("mean2d", 0),
        g("mean2d", 1),
        g("conic", 0),
        g("conic", 1),
        g("conic", 2),
        g("alpha"),
        g("rgb", 0),
        g("rgb", 1),
        g("rgb", 2),
        g("radius"),
        g("eigvec", 0),
        g("eigvec", 1),
        g("eigval", 0),
        g("eigval", 1),
        g("depth"),
        v.astype(jnp.float32),
    ]
    packed = jnp.stack(feats, axis=1)  # (B, F, K)
    if K_pad != K:
        packed = jnp.pad(packed, ((0, 0), (0, 0), (0, K_pad - K)))
    return packed
