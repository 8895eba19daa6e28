"""Pure-jnp oracles for the Pallas kernels (independent implementations).

Each oracle recomputes the kernel's output with straightforward dense jnp ops
(no chunking, no early-exit skipping — per-entry T_before gating only), so a
kernel/oracle match validates both the math and the chunked control flow.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.layout import (
    F_CONIC_A,
    F_CONIC_B,
    F_CONIC_C,
    F_EIGVAL_1,
    F_EIGVAL_2,
    F_EIGVEC_X,
    F_EIGVEC_Y,
    F_MEAN_X,
    F_MEAN_Y,
    F_OPACITY,
    F_RADIUS,
    F_RGB_B,
    F_RGB_G,
    F_RGB_R,
    F_VALID,
)

ALPHA_MIN = 1.0 / 255.0
ALPHA_MAX = 0.99
T_EPS = 1e-4
QMAX = 9.0
SIGMA_CUT = 3.0


def group_origins(grid) -> jnp.ndarray:
    """(num_groups, 2) float32 pixel origin of each group."""
    g = jnp.arange(grid.num_groups, dtype=jnp.int32)
    return jnp.stack(
        [(g % grid.n_groups_x) * grid.group, (g // grid.n_groups_x) * grid.group],
        axis=-1,
    ).astype(jnp.float32)


def tile_origins(grid) -> jnp.ndarray:
    """(num_tiles, 2) float32 pixel origin of each tile."""
    t = jnp.arange(grid.num_tiles, dtype=jnp.int32)
    return jnp.stack(
        [(t % grid.n_tiles_x) * grid.tile, (t // grid.n_tiles_x) * grid.tile],
        axis=-1,
    ).astype(jnp.float32)


def tiles_in_image(grid) -> jnp.ndarray:
    """(num_groups, tpg) bool: member tile lies inside the image."""
    g = jnp.arange(grid.num_groups, dtype=jnp.int32)[:, None]
    s = jnp.arange(grid.tiles_per_group, dtype=jnp.int32)[None, :]
    gf = grid.gf
    tx = (g % grid.n_groups_x) * gf + s % gf
    ty = (g // grid.n_groups_x) * gf + s // gf
    return (tx < grid.n_tiles_x) & (ty < grid.n_tiles_y)


def _pixels(origin, tile_px):
    lin = jnp.arange(tile_px * tile_px, dtype=jnp.float32)
    px = origin[0] + jnp.mod(lin, tile_px) + 0.5
    py = origin[1] + jnp.floor(lin / tile_px) + 0.5
    return px, py


def _alphas(feat, px, py):
    mx, my = feat[F_MEAN_X], feat[F_MEAN_Y]
    dx = px[:, None] - mx[None, :]
    dy = py[:, None] - my[None, :]
    q = (
        feat[F_CONIC_A][None, :] * dx * dx
        + 2.0 * feat[F_CONIC_B][None, :] * dx * dy
        + feat[F_CONIC_C][None, :] * dy * dy
    )
    a = jnp.minimum(feat[F_OPACITY][None, :] * jnp.exp(-0.5 * q), ALPHA_MAX)
    return jnp.where((q > QMAX) | (a < ALPHA_MIN), 0.0, a)


def _blend(a, feat):
    """(P, K) alphas -> (4, P) rgb+T with per-entry early-exit gating."""
    one_m = 1.0 - a
    cp = jnp.cumprod(one_m, axis=1)
    t_before = jnp.concatenate([jnp.ones_like(cp[:, :1]), cp[:, :-1]], axis=1)
    w = jnp.where(t_before > T_EPS, a * t_before, 0.0)
    rgb = jnp.stack(
        [w @ feat[F_RGB_R], w @ feat[F_RGB_G], w @ feat[F_RGB_B]], axis=0
    )
    return jnp.concatenate([rgb, cp[:, -1][None, :]], axis=0)


def ref_raster_tiles(feat, tile_origin, tile_px: int):
    """Oracle for raster_tile_kernel: (num_tiles, 4, P)."""

    def one(f, origin):
        px, py = _pixels(origin, tile_px)
        return _blend(_alphas(f, px, py), f)

    return jax.vmap(one)(feat, tile_origin)


def ref_raster_group_fused(feat, masks, group_origin, tile_px: int, gf: int):
    """Oracle for raster_group_fused_kernel: (num_groups, gf^2, 4, P)."""
    tpg = gf * gf

    def one_tile(f, m, origin, slot):
        ox = origin[0] + (slot % gf) * tile_px
        oy = origin[1] + (slot // gf) * tile_px
        px, py = _pixels(jnp.array([ox, oy]), tile_px)
        a = _alphas(f, px, py)
        keep = ((m.astype(jnp.uint32) >> slot.astype(jnp.uint32)) & 1) > 0
        a = jnp.where(keep[None, :], a, 0.0)
        return _blend(a, f)

    def one_group(f, m, origin):
        slots = jnp.arange(tpg, dtype=jnp.int32)
        return jax.vmap(lambda s: one_tile(f, m, origin, s))(slots)

    return jax.vmap(one_group)(feat, masks, group_origin)


def ref_bitmask(feat, group_origin, tile_in_image, tile_px: int, gf: int,
                method: str = "ellipse"):
    """Oracle for bitmask_kernel via the core boundary tests."""
    from repro.core import boundary

    tpg = gf * gf
    num_groups, F, K = feat.shape

    class P:  # adapter exposing boundary-test fields, (G, K, 1) broadcast
        mean2d = jnp.stack([feat[:, F_MEAN_X], feat[:, F_MEAN_Y]], axis=-1)[:, :, None, :]
        radius = feat[:, F_RADIUS][:, :, None]
        conic = jnp.stack(
            [feat[:, F_CONIC_A], feat[:, F_CONIC_B], feat[:, F_CONIC_C]], axis=-1
        )[:, :, None, :]
        eigvec = jnp.stack([feat[:, F_EIGVEC_X], feat[:, F_EIGVEC_Y]], axis=-1)[:, :, None, :]
        eigval = jnp.stack([feat[:, F_EIGVAL_1], feat[:, F_EIGVAL_2]], axis=-1)[:, :, None, :]

    slots = jnp.arange(tpg, dtype=jnp.float32)
    x0 = group_origin[:, 0][:, None, None] + (slots % gf)[None, None, :] * tile_px
    y0 = group_origin[:, 1][:, None, None] + jnp.floor(slots / gf)[None, None, :] * tile_px
    rect = (x0, y0, x0 + tile_px, y0 + tile_px)
    hit = boundary.boundary_test(method, P, rect)  # (G, K, tpg)
    valid = feat[:, F_VALID] > 0.5
    hit = hit & valid[:, :, None] & (tile_in_image[:, None, :])
    weights = jnp.uint32(1) << jnp.arange(tpg, dtype=jnp.uint32)
    return jnp.sum(hit.astype(jnp.uint32) * weights[None, None, :], axis=-1,
                   dtype=jnp.uint32)


def ref_sort(keys, payload):
    """Oracle for bitonic_sort_kernel (ascending by key; ties unordered —
    compare via composite where needed in tests)."""
    order = jnp.argsort(keys, axis=-1, stable=True)
    return (
        jnp.take_along_axis(keys, order, axis=-1),
        jnp.take_along_axis(payload, order, axis=-1),
    )
