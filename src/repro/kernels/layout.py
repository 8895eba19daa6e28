"""Shared feature layout for the GS-TG kernels.

Kernels consume gathered per-bin Gaussian features in an SoA (feature-major)
layout (F, K): the K entry axis maps to VPU lanes, features to sublanes. K is
padded to a multiple of 128 (lane width); F is 16 so fp32 blocks tile the
(8, 128) VMEM layout exactly.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.projection import PROJECTED_FIELD_WIDTHS, proj_take
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

# (Projected field, channel) of each packed feature row F_MEAN_X ..
# F_DEPTH; the last row, F_VALID, is the entry's valid flag.
PACKED_ROWS = (
    ("mean2d", 0), ("mean2d", 1), ("conic", 0), ("conic", 1), ("conic", 2),
    ("alpha", None), ("rgb", 0), ("rgb", 1), ("rgb", 2), ("radius", None),
    ("eigvec", 0), ("eigvec", 1), ("eigval", 0), ("eigval", 1),
    ("depth", None),
)
# The Projected fields ``pack_features`` reads: what a chip of the
# group-split backend gathers from every other chip (core/pipeline.py).
PACKED_FIELDS = tuple(dict.fromkeys(name for name, _ in PACKED_ROWS))


def packed_bytes_per_gaussian() -> int:
    """Bytes of ``PACKED_FIELDS`` one Gaussian holds: 15 float32."""
    return 4 * sum(PROJECTED_FIELD_WIDTHS[f] for f in PACKED_FIELDS)


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

    feats = [g(name, ch) for name, ch in PACKED_ROWS]
    feats.append(v.astype(jnp.float32))
    packed = jnp.stack(feats, axis=1)  # (B, F, K)
    if K_pad != K:
        packed = jnp.pad(packed, ((0, 0), (0, 0), (0, K_pad - K)))
    return packed
