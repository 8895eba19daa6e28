"""bench/reference.py: spherical harmonics of degrees 0 to 3 against a
float64 evaluation and the sphere's own orthonormality, images of SH
degrees 0 and 1 pinned, and the banded render against one pass."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.poses import orbit_pose
from bench.scene import make_scene
from bench.spec import BENCH, load_json

C0 = 0.28209479177387814
C1 = 0.4886025119029199
C2 = [1.0925484305920792, -1.0925484305920792, 0.31539156525252005,
      -1.0925484305920792, 0.5462742152960396]
C3 = [-0.5900435899266435, 2.890611442640554, -0.4570457994644658,
      0.3731763325901154, -0.4570457994644658, 1.445305721320277,
      -0.5900435899266435]

# Reference images of the tiny configuration (scene seed 7, orbit angle
# 0.4 at 3.0 extents) before SH degrees 2 and 3 were added:
# bench/tests/reference_image.py.
PINNED = {
    "0": "ebd23d6eb72e79f833ccb1ec374ba457928af765990e219866544f6cfaca91a9",
    "1": "501680aec707d42d6285ca02ce68e8beb0534a165cad734d5027a39802b47877",
}


def basis64(d):
    """The 16 real SH basis functions 3D-GS evaluates, times their
    constants, in float64, as written in its ``eval_sh``."""
    x, y, z = d[:, 0], d[:, 1], d[:, 2]
    xx, yy, zz = x * x, y * y, z * z
    return np.stack([
        np.full_like(x, C0), -C1 * y, C1 * z, -C1 * x,
        C2[0] * x * y, C2[1] * y * z, C2[2] * (2 * zz - xx - yy),
        C2[3] * x * z, C2[4] * (xx - yy),
        C3[0] * y * (3 * xx - yy), C3[1] * x * y * z,
        C3[2] * y * (4 * zz - xx - yy),
        C3[3] * z * (2 * zz - 3 * xx - 3 * yy),
        C3[4] * x * (4 * zz - xx - yy), C3[5] * z * (xx - yy),
        C3[6] * x * (xx - 3 * yy),
    ], axis=1)


def _directions(n, seed):
    d = np.random.default_rng(seed).normal(size=(n, 3))
    return (d / np.linalg.norm(d, axis=1, keepdims=True)).astype(np.float32)


@pytest.mark.parametrize("degree", [2, 3])
def test_sh_colour_agrees_with_a_float64_evaluation(degree):
    k = (degree + 1) ** 2
    d = _directions(10_000, 11 + degree)
    sh = np.random.default_rng(degree).normal(size=(10_000, k, 3)) \
        .astype(np.float32)
    got = np.asarray(reference.sh_colour(jnp.asarray(sh), jnp.asarray(d)))
    want = np.einsum("nk,nkc->nc", basis64(d.astype(np.float64))[:, :k],
                     sh.astype(np.float64))
    assert np.abs(got - want).max() <= 1e-6


def test_the_sh_basis_is_orthonormal_on_the_sphere():
    """Over a Fibonacci sphere of 200,000 points the mean of Y_i Y_j is
    delta_ij / (4 pi): checks the constants whatever their transcription."""
    n = 200_000
    i = np.arange(n) + 0.5
    z = 1.0 - 2.0 * i / n
    phi = np.pi * (1.0 + 5.0 ** 0.5) * i
    r = np.sqrt(1.0 - z * z)
    d = jnp.asarray(np.stack([r * np.cos(phi), r * np.sin(phi), z], 1),
                    jnp.float32)
    cols = []
    for k in range(16):
        sh = np.zeros((1, 16, 3), np.float32)
        sh[0, k, 0] = 1.0
        sh = jnp.broadcast_to(jnp.asarray(sh), (n, 16, 3))
        cols.append(np.asarray(reference.sh_colour(sh, d))[:, 0])
    y = np.stack(cols, 1).astype(np.float64)
    gram = y.T @ y / n
    assert np.abs(gram - np.eye(16) / (4 * np.pi)).max() <= 1e-3


def test_a_coefficient_count_of_no_degree_is_refused():
    scene = {"sh": jnp.zeros((2, 5, 3)), "means3d": jnp.zeros((2, 3))}
    with pytest.raises(ValueError, match="not 5 coefficients"):
        reference._sh_colour(scene, jnp.eye(3), jnp.zeros(3), 6)


@pytest.fixture(scope="module")
def pinned_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_cpu_max_isa=AVX2")
    out = subprocess.run(
        [sys.executable, str(BENCH / "tests" / "reference_image.py")],
        env=env, capture_output=True, text=True, timeout=300, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("degree", ["0", "1"])
def test_images_of_sh_degree_0_and_1_are_as_pinned(pinned_run, degree):
    assert pinned_run[degree] == PINNED[degree]


def _tiny_frame(sh_degree=1):
    conf = load_json(BENCH / "tests" / "data" / "tiny.json")
    orbit = dict(conf["orbit"], radius=3.0 * conf["extent"])
    pose = orbit_pose(0.4, orbit, conf["width"], conf["height"])
    return make_scene(7, dict(conf, sh_degree=sh_degree)), pose


def test_bands_hold_whole_rows_within_the_capacity():
    assert reference.bands([5, 5, 5, 20, 1, 0], 10) == [
        (0, 2), (2, 3), (3, 4), (4, 6)]
    assert reference.bands([3, 3], 10) == [(0, 2)]


@pytest.mark.parametrize("share", [0.3, 0.01])
def test_the_banded_reference_is_bitwise_one_pass(share):
    """0.3: bands of several rows; 0.01: rows that each hold more pairs
    than the capacity, which fall back to power-of-two lists."""
    scene, pose = _tiny_frame()
    one, c1 = reference.render(scene, pose, with_counts=True)
    pairs = c1["box_pairs"]
    cap = max(1, int(share * pairs))
    planned, rows, bands = reference.band_plan(scene, pose, capacity=cap)
    assert planned == pairs == rows.sum()
    assert len(bands) >= 3
    assert reference.band_plan(scene, pose, capacity=pairs)[2] == [
        (0, len(rows))]
    if share < 0.1:
        assert any(rows[r0:r1].sum() > cap for r0, r1 in bands)
    banded, c2 = reference.render(scene, pose, capacity=cap,
                                  with_counts=True)
    one, banded = np.asarray(one), np.asarray(banded)
    assert one.max() > 0.1
    assert banded.shape == one.shape and banded.dtype == one.dtype
    assert np.array_equal(banded.view(np.uint32), one.view(np.uint32))
    assert c2 == c1
