"""Tile-wise rasterization (paper Fig 1 right: alpha-computation + blending).

Pure-jnp differentiable reference. Consumes a tile-level BinTable (each tile's
depth-ordered entry list — produced either by the per-tile baseline binning or
by GS-TG's group-sort + bitmask compaction; both yield the same table, which
is the losslessness property).

Alpha rule (both pipelines, kernel and reference — this exact rule is what
makes any conservative boundary method lossless, see DESIGN.md):
    q     = (p - mu)^T Conic (p - mu)
    alpha = min(opacity * exp(-q/2), ALPHA_MAX)
    alpha = 0  if q > 9 (3-sigma)  or  alpha < 1/255
Blending is front-to-back with per-pixel early exit when transmittance drops
below T_EPS (identical chunked masking in both pipelines => identical fp op
order => bitwise-equal images).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.grouping import BinTable, GridSpec
from repro.core.projection import Projected, QMAX_3SIGMA, proj_take
from repro.utils import HIGHEST

ALPHA_MIN = 1.0 / 255.0
ALPHA_MAX = 0.99
T_EPS = 1e-4


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RasterOut:
    image: jnp.ndarray        # (H, W, 3)
    alpha_ops: jnp.ndarray    # (): per-pixel alpha computations executed
    blend_ops: jnp.ndarray    # (): blends that actually contributed
    processed: jnp.ndarray    # (num_tiles,): entries processed per tile


def tile_pixel_coords(grid: GridSpec) -> jnp.ndarray:
    """(num_tiles, T*T, 2) pixel-center coordinates per tile."""
    T = grid.tile
    tix = jnp.arange(grid.num_tiles, dtype=jnp.int32)
    tx = (tix % grid.n_tiles_x) * T
    ty = (tix // grid.n_tiles_x) * T
    px = jnp.arange(T, dtype=jnp.float32) + 0.5
    xx, yy = jnp.meshgrid(px, px, indexing="xy")
    offs = jnp.stack([xx.reshape(-1), yy.reshape(-1)], axis=-1)  # (T*T, 2)
    base = jnp.stack([tx, ty], axis=-1).astype(jnp.float32)
    return base[:, None, :] + offs[None, :, :]


def alpha_at(pix, mean2d, conic, opacity):
    """Alpha with the q<=9 and 1/255 cutoffs. Shapes broadcast; returns (...)."""
    d = pix - mean2d
    q = (
        conic[..., 0] * d[..., 0] * d[..., 0]
        + 2.0 * conic[..., 1] * d[..., 0] * d[..., 1]
        + conic[..., 2] * d[..., 1] * d[..., 1]
    )
    a = opacity * jnp.exp(-0.5 * q)
    a = jnp.minimum(a, ALPHA_MAX)
    return jnp.where((q > QMAX_3SIGMA) | (a < ALPHA_MIN), 0.0, a)


def rasterize(
    proj: Projected,
    table: BinTable,
    grid: GridSpec,
    background: jnp.ndarray | None = None,
    chunk: int = 32,
    early_exit: bool = True,
) -> RasterOut:
    """Rasterize all tiles. Differentiable w.r.t. scene features (the discrete
    ordering is treated as constant, as in standard 3D-GS training)."""
    if background is None:
        background = jnp.zeros((3,), jnp.float32)
    num_tiles, K = table.gauss_idx.shape
    assert num_tiles == grid.num_tiles
    T = grid.tile
    P = T * T
    pix = tile_pixel_coords(grid)  # (num_tiles, P, 2)

    # proj_take handles flat AND shard-kept features (DESIGN.md §12): the
    # global table indices decompose to (shard, local) and each entry's
    # features come from its owning shard, bitwise-equal to the flat gather.
    mean2d = proj_take(proj, "mean2d", table.gauss_idx)   # (num_tiles, K, 2)
    conic = proj_take(proj, "conic", table.gauss_idx)
    rgb = proj_take(proj, "rgb", table.gauss_idx)
    opac = jnp.where(
        table.entry_valid, proj_take(proj, "alpha", table.gauss_idx), 0.0
    )

    n_chunks = -(-K // chunk)
    pad = n_chunks * chunk - K
    if pad:
        padk = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        mean2d, conic, rgb, opac = map(padk, (mean2d, conic, rgb, opac))

    def render_tile(pix_t, m_all, cn_all, cl_all, op_all):
        def tile_body(carry, xs):
            t_run, c_run, a_ops, b_ops = carry
            m, cn, cl, op = xs  # (chunk, ...)
            alpha = alpha_at(
                pix_t[:, None, :], m[None, :, :], cn[None, :, :], op[None, :]
            )  # (P, chunk)
            one_m = 1.0 - alpha
            cp = jnp.cumprod(one_m, axis=1)
            excl = jnp.concatenate([jnp.ones_like(cp[:, :1]), cp[:, :-1]], axis=1)
            t_before = excl * t_run[:, None]  # transmittance BEFORE each entry
            w = alpha * t_before
            if early_exit:
                # Exact per-entry early exit: T is monotone non-increasing, so
                # gating each entry by its own T_before reproduces the
                # sequential 'break' semantics — and is bitwise insensitive to
                # interleaved zero-alpha entries (they leave T unchanged),
                # which is what makes every conservative boundary-method combo
                # exactly lossless.
                live = t_before > T_EPS
                w = jnp.where(live, w, 0.0)
            else:
                live = jnp.ones_like(w, dtype=jnp.bool_)
            c_run = c_run + jnp.matmul(w, cl, precision=HIGHEST)
            t_run = t_run * cp[:, -1]
            a_ops = a_ops + jnp.sum(
                live.astype(jnp.int32) * (op > 0).astype(jnp.int32)[None, :]
            )
            b_ops = b_ops + jnp.sum((w > 0).astype(jnp.int32))
            return (t_run, c_run, a_ops, b_ops), None

        resh = lambda a: a.reshape(n_chunks, chunk, *a.shape[1:])
        carry = (
            jnp.ones((P,), jnp.float32),
            jnp.zeros((P, 3), jnp.float32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
        )
        (t_run, c_run, a_ops, b_ops), _ = jax.lax.scan(
            tile_body, carry, (resh(m_all), resh(cn_all), resh(cl_all), resh(op_all))
        )
        color = c_run + t_run[:, None] * background[None, :]
        return color, a_ops, b_ops

    colors, a_ops, b_ops = jax.vmap(render_tile)(pix, mean2d, conic, rgb, opac)

    img = colors.reshape(grid.n_tiles_y, grid.n_tiles_x, T, T, 3)
    img = img.transpose(0, 2, 1, 3, 4).reshape(
        grid.n_tiles_y * T, grid.n_tiles_x * T, 3
    )
    img = img[: grid.height, : grid.width]

    processed = jnp.sum(table.entry_valid.astype(jnp.int32), axis=1)
    return RasterOut(
        image=img,
        alpha_ops=jnp.sum(a_ops),
        blend_ops=jnp.sum(b_ops),
        processed=processed,
    )
