import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_camera, random_scene
from repro.core.bitmask import generate_bitmasks
from repro.core.grouping import GridSpec, bin_pairs, identify
from repro.core.projection import project
from repro.kernels import ref as kref
from repro.kernels.bitmask_gen import bitmask_kernel
from repro.kernels.layout import pack_features


def _setup(method, gf, seed=0):
    # Smallest interpret-mode shapes that still cover the kernel's lane
    # logic: >1 group on each axis at both gf values, K > one block.
    tile = 16
    w = h = 96
    scene = random_scene(jax.random.key(seed), 400, extent=3.0)
    cam = make_camera((0, 1.0, 4.5), (0, 0, 0), w, h)
    proj = project(scene, cam)
    grid = GridSpec(w, h, tile, tile * gf, span=4)
    pairs = identify(proj, grid, "group", method)
    gtable = bin_pairs(pairs, grid.num_groups, 256)
    feat = pack_features(proj, gtable.gauss_idx, gtable.entry_valid)
    return proj, grid, gtable, feat


@pytest.mark.parametrize("method", ["aabb", "obb", "ellipse"])
@pytest.mark.parametrize("gf", [2, 4])
def test_bitmask_kernel_vs_oracle(method, gf):
    proj, grid, gtable, feat = _setup(method, gf)
    origins = kref.group_origins(grid)
    in_img = kref.tiles_in_image(grid)
    got = bitmask_kernel(feat, grid, method=method, interpret=True)
    want = kref.ref_bitmask(feat, origins, in_img, grid.tile, gf, method)
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("method", ["aabb", "obb", "ellipse"])
def test_bitmask_kernel_vs_core(method):
    """Kernel masks == core generate_bitmasks (the XLA substrate path)."""
    proj, grid, gtable, feat = _setup(method, 4, seed=3)
    core = generate_bitmasks(proj, gtable, grid, method)
    got = bitmask_kernel(feat, grid, method=method, interpret=True)
    assert (np.asarray(got) == np.asarray(core.masks)).all()
