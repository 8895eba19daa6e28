"""Pallas TPU kernel for the Bitmask Generation Module (BGM, paper Fig 10).

Per group entry, runs the chosen boundary test against each of the gf^2
member tiles and packs the results into a uint32 bitmask. The ASIC's four
tile-check units become VPU lanes: each lane block of entries tests all
member tiles with the tile loop unrolled at trace time (static gf^2 <= 16).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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
    F_RADIUS,
    F_VALID,
    LANE,
    NUM_FEATURES,
)
from repro.kernels.ops import on_every_device, resolve_interpret

QMAX = 9.0
SIGMA_CUT = 3.0


def _aabb(mx, my, r, x0, y0, x1, y1):
    return (mx + r >= x0) & (mx - r <= x1) & (my + r >= y0) & (my - r <= y1)


def _obb(mx, my, ux, uy, l1, l2, x0, y0, x1, y1):
    vx, vy = -uy, ux
    e1 = SIGMA_CUT * jnp.sqrt(jnp.maximum(l1, 0.0))
    e2 = SIGMA_CUT * jnp.sqrt(jnp.maximum(l2, 0.0))
    cx, cy = 0.5 * (x0 + x1), 0.5 * (y0 + y1)
    hx, hy = 0.5 * (x1 - x0), 0.5 * (y1 - y0)
    dx, dy = mx - cx, my - cy
    sep_x = jnp.abs(dx) > hx + jnp.abs(ux) * e1 + jnp.abs(vx) * e2
    sep_y = jnp.abs(dy) > hy + jnp.abs(uy) * e1 + jnp.abs(vy) * e2
    sep_u = jnp.abs(dx * ux + dy * uy) > e1 + hx * jnp.abs(ux) + hy * jnp.abs(uy)
    sep_v = jnp.abs(dx * vx + dy * vy) > e2 + hx * jnp.abs(vx) + hy * jnp.abs(vy)
    return ~(sep_x | sep_y | sep_u | sep_v)


def _ellipse(mx, my, A, B, C, x0, y0, x1, y1):
    C_s = jnp.where(jnp.abs(C) > 1e-12, C, 1e-12)
    A_s = jnp.where(jnp.abs(A) > 1e-12, A, 1e-12)

    def q_at(px, py):
        dx, dy = px - mx, py - my
        return A * dx * dx + 2.0 * B * dx * dy + C * dy * dy

    def edge_v(xe):
        ys = jnp.clip(my - (B / C_s) * (xe - mx), y0, y1)
        return q_at(xe, ys)

    def edge_h(ye):
        xs = jnp.clip(mx - (B / A_s) * (ye - my), x0, x1)
        return q_at(xs, ye)

    qmin = jnp.minimum(
        jnp.minimum(edge_v(x0), edge_v(x1)), jnp.minimum(edge_h(y0), edge_h(y1))
    )
    inside = (mx >= x0) & (mx <= x1) & (my >= y0) & (my <= y1)
    return jnp.where(inside, 0.0, qmin) <= QMAX


def bitmask_kernel(
    feat: jnp.ndarray,          # (num_groups, F, K)
    grid,                       # GridSpec: group origins + in-image tiles
    method: str = "ellipse",
    interpret: bool | None = None,
    group_offset: jnp.ndarray | int = 0,
) -> jnp.ndarray:
    """Returns (num_groups, K) uint32 bitmasks.

    Group g has its top-left pixel at ((g % n_groups_x) * group,
    (g // n_groups_x) * group), a function of the grid index; member tiles
    outside the image get no bits. The grid runs over (group, lane block).

    Row i of ``feat`` is group ``group_offset + i`` (an int32 scalar,
    possibly traced). It reaches the kernel as a prefetched scalar, so each
    chip of a band split runs the same program on its own band of the
    image's groups. Rows past the image's groups (the padding of the last
    band) get no bits.
    """
    num_groups, F, K = feat.shape
    assert F == NUM_FEATURES
    gf, tile_px = grid.gf, grid.tile
    block = next(b for b in (512, 256, LANE) if K % b == 0)

    def kernel(off_ref, feat_ref, out_ref):
        g = off_ref[0] + pl.program_id(0)
        gx = g % grid.n_groups_x
        gy = g // grid.n_groups_x
        fb = feat_ref[0]                     # (F, block)
        row = lambda f: fb[f:f + 1, :]       # (1, block)
        mx, my = row(F_MEAN_X), row(F_MEAN_Y)
        valid = row(F_VALID) > 0.5
        mask = jnp.zeros((1, block), jnp.int32)
        for slot in range(gf * gf):  # static unroll: the 4 tile-check units
            tx = jnp.full((1, 1), gx * gf + slot % gf, jnp.int32)
            ty = jnp.full((1, 1), gy * gf + slot // gf, jnp.int32)
            x0 = (tx * tile_px).astype(jnp.float32)
            y0 = (ty * tile_px).astype(jnp.float32)
            x1, y1 = x0 + tile_px, y0 + tile_px
            if method == "aabb":
                hit = _aabb(mx, my, row(F_RADIUS), x0, y0, x1, y1)
            elif method == "obb":
                hit = _obb(
                    mx, my,
                    row(F_EIGVEC_X), row(F_EIGVEC_Y),
                    row(F_EIGVAL_1), row(F_EIGVAL_2),
                    x0, y0, x1, y1,
                )
            else:
                hit = _ellipse(
                    mx, my,
                    row(F_CONIC_A), row(F_CONIC_B), row(F_CONIC_C),
                    x0, y0, x1, y1,
                )
            in_image = (tx < grid.n_tiles_x) & (ty < grid.n_tiles_y)
            hit = hit & valid & in_image
            mask = mask | (hit.astype(jnp.int32) << slot)
        out_ref[0] = mask

    interpret = resolve_interpret(interpret)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(num_groups, K // block),
            in_specs=[pl.BlockSpec((1, F, block),
                                   lambda g, k, off: (g, 0, k))],
            out_specs=pl.BlockSpec((1, 1, block),
                                   lambda g, k, off: (g, 0, k)),
        ),
        out_shape=jax.ShapeDtypeStruct((num_groups, 1, K), jnp.int32),
        interpret=interpret,
        name="gstg_bitmask",
    )
    out = on_every_device(call, interpret)(
        jnp.reshape(group_offset, (1,)).astype(jnp.int32), feat)
    return jax.lax.bitcast_convert_type(out.reshape(num_groups, K),
                                        jnp.uint32)
