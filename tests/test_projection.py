import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_camera, random_scene
from repro.core.projection import project


def test_projection_shapes_and_finiteness(small_scene, cam128):
    proj = project(small_scene, cam128)
    n = small_scene.num_gaussians
    assert proj.mean2d.shape == (n, 2)
    assert proj.conic.shape == (n, 3)
    assert proj.depth.shape == (n,)
    for field in ("mean2d", "cov2d", "conic", "radius", "rgb", "alpha"):
        v = getattr(proj, field)
        assert bool(jnp.isfinite(v[proj.valid]).all()), field


def test_culling_behind_camera(cam128):
    scene = random_scene(jax.random.key(2), 100, extent=2.0)
    # Move all gaussians behind the camera -> all culled.
    far_behind = scene.means3d + jnp.array([0.0, 0.0, 100.0])
    scene = dataclasses.replace(scene, means3d=far_behind)
    proj = project(scene, cam128)
    assert int(proj.valid.sum()) == 0


def test_cov2d_positive_definite(small_scene, cam128):
    proj = project(small_scene, cam128)
    a, b, c = proj.cov2d[:, 0], proj.cov2d[:, 1], proj.cov2d[:, 2]
    det = a * c - b * b
    valid = proj.valid
    assert bool((a[valid] > 0).all())
    assert bool((det[valid] > 0).all())


def test_eigval_order_and_radius(small_scene, cam128):
    proj = project(small_scene, cam128)
    v = proj.valid
    lam1, lam2 = proj.eigval[:, 0], proj.eigval[:, 1]
    assert bool((lam1[v] >= lam2[v] - 1e-5).all())
    np.testing.assert_allclose(
        np.asarray(proj.radius[v]),
        3.0 * np.sqrt(np.asarray(lam1[v])),
        rtol=1e-5,
    )
    # circumscribed radius bounds both axis extents
    assert bool((proj.radius[v] >= proj.axis_radius[v].max(-1) - 1e-4).all())


def test_rgb_in_range(small_scene, cam128):
    proj = project(small_scene, cam128)
    assert bool((proj.rgb >= 0).all()) and bool((proj.rgb <= 1).all())
    assert bool((proj.alpha >= 0).all()) and bool((proj.alpha <= 1).all())


# ---------------------------------------------------------------------------
# eval_sh against a plain transcription of 3D-GS's sh_utils.eval_sh
# ---------------------------------------------------------------------------

_C0 = 0.28209479177387814
_C1 = 0.4886025119029199
_C2 = [1.0925484305920792, -1.0925484305920792, 0.31539156525252005,
       -1.0925484305920792, 0.5462742152960396]
_C3 = [-0.5900435899266435, 2.890611442640554, -0.4570457994644658,
       0.3731763325901154, -0.4570457994644658, 1.445305721320277,
       -0.5900435899266435]


def _eval_sh_3dgs(deg, sh, dirs):
    """3D-GS's ``eval_sh(deg, sh, dirs)`` (utils/sh_utils.py) in float32
    jax.numpy, sh as (N, 3, K) as it stores them, then offset and clipped
    as the program decodes colour."""
    result = _C0 * sh[..., 0]
    if deg > 0:
        x, y, z = dirs[..., 0:1], dirs[..., 1:2], dirs[..., 2:3]
        result = (result - _C1 * y * sh[..., 1] + _C1 * z * sh[..., 2]
                  - _C1 * x * sh[..., 3])
        if deg > 1:
            xx, yy, zz = x * x, y * y, z * z
            xy, yz, xz = x * y, y * z, x * z
            result = (result
                      + _C2[0] * xy * sh[..., 4]
                      + _C2[1] * yz * sh[..., 5]
                      + _C2[2] * (2.0 * zz - xx - yy) * sh[..., 6]
                      + _C2[3] * xz * sh[..., 7]
                      + _C2[4] * (xx - yy) * sh[..., 8])
            if deg > 2:
                result = (result
                          + _C3[0] * y * (3 * xx - yy) * sh[..., 9]
                          + _C3[1] * xy * z * sh[..., 10]
                          + _C3[2] * y * (4 * zz - xx - yy) * sh[..., 11]
                          + _C3[3] * z * (2 * zz - 3 * xx - 3 * yy)
                          * sh[..., 12]
                          + _C3[4] * x * (4 * zz - xx - yy) * sh[..., 13]
                          + _C3[5] * z * (xx - yy) * sh[..., 14]
                          + _C3[6] * x * (xx - 3 * yy) * sh[..., 15])
    return jnp.clip(result + 0.5, 0.0, 1.0)


def _eval_sh_degree_01(sh, dirs):
    """The program's degree 0-1 evaluation as it stood before degrees 2-3
    were added, operation for operation."""
    from repro.core.gaussians import SH_C0
    from repro.core.projection import SH_C1

    rgb = SH_C0 * sh[:, 0, :]
    if sh.shape[1] >= 4:
        x, y, z = dirs[:, 0:1], dirs[:, 1:2], dirs[:, 2:3]
        rgb = rgb + SH_C1 * (-y * sh[:, 1, :] + z * sh[:, 2, :]
                             - x * sh[:, 3, :])
    return jnp.clip(rgb + 0.5, 0.0, 1.0)


def _sh_inputs(seed, n_coeffs, n=4096):
    k1, k2 = jax.random.split(jax.random.key(seed))
    sh = 0.8 * jax.random.normal(k1, (n, n_coeffs, 3), jnp.float32)
    d = jax.random.normal(k2, (n, 3), jnp.float32)
    return sh, d / jnp.linalg.norm(d, axis=-1, keepdims=True)


@pytest.mark.parametrize("degree", [0, 1, 2, 3])
def test_eval_sh_matches_3dgs_basis(degree):
    """eval_sh at degrees 0-3 agrees with the plain float32 transcription
    of 3D-GS's basis on random coefficients and unit directions, to float32
    rounding of the summation order (the two group the products
    differently)."""
    from repro.core.projection import eval_sh

    sh, dirs = _sh_inputs(degree, (degree + 1) ** 2)
    got = eval_sh(sh, dirs)
    want = _eval_sh_3dgs(degree, jnp.swapaxes(sh, 1, 2), dirs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-6)
    # Every coefficient matters: dropping the last one moves the colour.
    if degree:
        cut = sh.at[:, -1, :].set(0.0)
        assert not np.allclose(np.asarray(eval_sh(cut, dirs)),
                               np.asarray(got), atol=1e-4)


@pytest.mark.parametrize("degree", [0, 1])
@pytest.mark.parametrize("jit", [False, True])
def test_eval_sh_degrees_0_1_bitwise_unchanged(degree, jit):
    from repro.core.projection import eval_sh

    sh, dirs = _sh_inputs(10 + degree, (degree + 1) ** 2)
    wrap = jax.jit if jit else (lambda f: f)
    np.testing.assert_array_equal(
        np.asarray(wrap(eval_sh)(sh, dirs)),
        np.asarray(wrap(_eval_sh_degree_01)(sh, dirs)))


@pytest.mark.parametrize("n_coeffs", [2, 5, 25])
def test_eval_sh_refuses_other_coefficient_counts(n_coeffs):
    from repro.core.projection import eval_sh

    sh, dirs = _sh_inputs(0, n_coeffs, n=8)
    with pytest.raises(ValueError, match="coefficients"):
        eval_sh(sh, dirs)
