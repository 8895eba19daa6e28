"""bench/work.py and the reference's work counts, against hand counts on a
scene of three Gaussians."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, work
from bench.poses import Pose

W = H = 64
FX = 0.5 * W / math.tan(math.radians(30.0))


def _scene():
    # Camera at the origin looking down +z. Two Gaussians overlap at the
    # centre (the nearer nearly opaque), one sits alone at a corner.
    means = np.array([[0.0, 0.0, 5.0], [0.1, 0.0, 6.0], [-2.0, -2.0, 5.0]],
                     np.float32)
    scales = np.array([0.25, 0.3, 0.15], np.float32)
    logits = np.array([4.0, 1.0, 0.5], np.float32)
    return {
        "means3d": jnp.asarray(means),
        "log_scales": jnp.asarray(np.log(np.repeat(scales[:, None], 3, 1))),
        "quats": jnp.asarray(np.tile([1.0, 0.0, 0.0, 0.0], (3, 1)),
                             jnp.float32),
        "opacity": jnp.asarray(logits),
        "sh": jnp.asarray(np.array([[[0.5, 0.2, 0.1]], [[0.1, 0.9, 0.3]],
                                    [[0.7, 0.7, 0.7]]], np.float32)
                          - 0.5) / np.float32(reference.SH_C0),
    }, means, scales, logits


def _hand_counts(means, scales, logits):
    """Sequential per-pixel loop over the Gaussians in depth order."""
    order = np.argsort(means[:, 2], kind="stable")
    ys, xs = np.mgrid[0:H, 0:W] + 0.5
    T = np.ones((H, W))
    touch = set()
    evals = blends = 0
    for g in order:
        x, y, z = means[g]
        mx, my = FX * x / z + W / 2, FX * y / z + H / 2
        j0, j2 = FX / z, -FX * x / z**2
        j1, j3 = FX / z, -FX * y / z**2
        s2 = scales[g] ** 2
        a = (j0 * j0 + j2 * j2) * s2 + 0.3
        b = j2 * j3 * s2
        c = (j1 * j1 + j3 * j3) * s2 + 0.3
        det = a * c - b * b
        dx, dy = xs - mx, ys - my
        q = (c * dx * dx - 2 * b * dx * dy + a * dy * dy) / det
        op = 1 / (1 + math.exp(-logits[g]))
        alpha = np.minimum(op * np.exp(-0.5 * q), 0.99)
        alpha = np.where((q > 9) | (alpha < 1 / 255), 0.0, alpha)
        live = T > 1e-4
        for ty in range(H // 16):
            for tx in range(W // 16):
                sl = (slice(16 * ty, 16 * ty + 16),
                      slice(16 * tx, 16 * tx + 16))
                if (alpha[sl] > 0).any():
                    touch.add((g, ty, tx))
                    evals += int(live[sl].sum())
        blends += int(((alpha > 0) & live).sum())
        T = T * (1 - alpha)
    return {"touch_pairs": len(touch), "alpha_evals": evals,
            "blends": blends}


def test_reference_counts_match_a_hand_count():
    scene, means, scales, logits = _scene()
    pose = Pose(R=np.eye(3, dtype=np.float32), t=np.zeros(3, np.float32),
                fx=FX, fy=FX, cx=W / 2, cy=H / 2, width=W, height=H,
                znear=0.2, zfar=1000.0, angle=0.0)
    _, counts = reference.render(scene, pose, with_counts=True)
    hand = _hand_counts(means, scales, logits)
    assert counts["visible"] == 3
    for k in ("touch_pairs", "alpha_evals", "blends"):
        assert counts[k] == hand[k], k
    assert hand["touch_pairs"] >= 5 and hand["blends"] > 100


def test_frame_work_applies_the_per_operation_constants():
    counts = {"gaussians": 3, "sh_coeffs": 4, "touch_pairs": 7,
              "alpha_evals": 1000, "blends": 300}
    w = work.frame_work(counts, W, H)
    raster = 1000 * work.ALPHA_OPS + 300 * work.BLEND_OPS
    assert w["kernel_ops"] == raster
    assert w["frame_ops"] == raster + 3 * (275 + 46)
    assert w["kernel_bytes"] == 7 * work.FEATURE_BYTES + W * H * 12
    assert w["frame_bytes"] == w["kernel_bytes"] + 3 * (44 + 4 * 12)
    w0 = work.frame_work({**counts, "sh_coeffs": 1}, W, H)
    assert w["frame_ops"] - w0["frame_ops"] == 3 * (46 - 12)


def test_least_time_names_the_bound():
    peak = {"ops_per_s": 100.0, "bytes_per_s": 10.0}
    assert work.least_time(1000.0, 50.0, peak) == (10.0, "compute")
    t, bound = work.least_time(100.0, 50.0, peak)
    assert (t, bound) == (pytest.approx(5.0), "memory")
