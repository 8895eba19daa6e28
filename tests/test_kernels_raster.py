"""Pallas raster kernels vs pure-jnp oracle: shape/dtype sweeps (interpret)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_camera, random_scene
from repro.core.bitmask import compact_tiles, generate_bitmasks
from repro.core.grouping import GridSpec, bin_pairs, identify
from repro.core.pipeline import RenderConfig, render
from repro.core.projection import project
from repro.kernels import ref as kref
from repro.kernels.layout import pack_features
from repro.kernels.raster_tile import raster_group_fused_kernel, raster_tile_kernel


def _tables(seed=1, w=96, h=96, tile=16, group=64, gcap=256, tcap=128):
    # Smallest scene/grid that still exercises the kernels in interpret
    # mode (multiple tiles AND groups, K > one chunk): interpret-mode cost
    # scales with pixels x entries, and these oracle comparisons dominated
    # the fast lane at 128x128/700.
    scene = random_scene(jax.random.key(seed), 400, extent=3.0)
    cam = make_camera((0, 1.0, 4.5), (0, 0, 0), w, h)
    proj = project(scene, cam)
    grid = GridSpec(w, h, tile, group, span=4)
    pairs = identify(proj, grid, "group", "ellipse")
    gtable = bin_pairs(pairs, grid.num_groups, gcap)
    masks = generate_bitmasks(proj, gtable, grid, "ellipse")
    ttable = compact_tiles(gtable, masks, grid, tcap)
    return proj, grid, gtable, masks, ttable


@pytest.mark.parametrize(
    "tile,chunk",
    [
        # Fast lane keeps the default tile=16 layout; the other tile/chunk
        # layouts (chunks are whole multiples of the 128-lane width; 32 px
        # tiles span several pixel blocks) ride the slow lane.
        (16, 128),
        pytest.param(8, 128, marks=pytest.mark.slow),
        pytest.param(16, 256, marks=pytest.mark.slow),
        pytest.param(32, 128, marks=pytest.mark.slow),
    ],
)
def test_raster_tile_kernel_vs_oracle(tile, chunk):
    group = tile * 4
    proj, grid, _, _, ttable = _tables(tile=tile, group=group, tcap=128)
    feat = pack_features(proj, ttable.gauss_idx, ttable.entry_valid,
                         multiple=chunk)
    origins = kref.tile_origins(grid)
    out_k = raster_tile_kernel(feat, grid.n_tiles_x, tile, chunk=chunk,
                               interpret=True)
    out_r = kref.ref_raster_tiles(feat, origins, tile)
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_r), atol=3e-6, rtol=1e-5
    )


@pytest.mark.parametrize("gf", [2, 4])
def test_fused_kernel_vs_oracle(gf):
    tile = 16
    proj, grid, gtable, masks, _ = _tables(tile=tile, group=tile * gf)
    feat = pack_features(proj, gtable.gauss_idx, gtable.entry_valid)
    origins = kref.group_origins(grid)
    out_k = raster_group_fused_kernel(
        feat, masks.masks, grid.n_groups_x, tile, gf, chunk=128,
        interpret=True
    )
    out_r = kref.ref_raster_group_fused(feat, masks.masks, origins, tile, gf)
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_r), atol=3e-6, rtol=1e-5
    )


def test_kernel_pipeline_matches_core():
    """End-to-end: pallas backend == reference backend through render()."""
    import dataclasses

    # Smallest shape that still exercises the kernels end-to-end: 2x2
    # groups (gf=4 bitmask lanes in play), multi-chunk K, non-trivial
    # occupancy. The big-scene kernel coverage lives in the slow-lane
    # oracle matrix below.
    scene = random_scene(jax.random.key(5), 400, extent=3.0)
    cam = make_camera((0, 1.0, 4.5), (0, 0, 0), 96, 96)
    cfg = RenderConfig(group_capacity=256, tile_capacity=256)
    ref_img = render(scene, cam, cfg).image
    img = render(scene, cam, dataclasses.replace(cfg, backend="pallas")).image
    np.testing.assert_allclose(
        np.asarray(img), np.asarray(ref_img), atol=5e-6, rtol=1e-5
    )


def test_raster_kernel_empty_tiles():
    """Tiles with zero entries produce pure transmittance=1 output."""
    proj, grid, _, _, ttable = _tables(seed=9)
    import dataclasses

    empty = dataclasses.replace(
        ttable,
        entry_valid=jnp.zeros_like(ttable.entry_valid),
        lengths=jnp.zeros_like(ttable.lengths),
    )
    feat = pack_features(proj, empty.gauss_idx, empty.entry_valid)
    out = raster_tile_kernel(feat, grid.n_tiles_x, 16, chunk=128,
                             interpret=True)
    out = np.asarray(out)
    assert np.allclose(out[:, :3, :], 0.0)
    assert np.allclose(out[:, 3, :], 1.0)
