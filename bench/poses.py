"""Camera poses on an orbit: the benchmark's copy of the program's pinhole
look-at camera (``repro.core.camera.look_at`` / ``make_camera`` /
``orbit_cameras``), so that a change to the program cannot move the poses.

World-to-camera extrinsics with OpenCV conventions: +z looks into the scene,
x right, y down; intrinsics (fx, fy, cx, cy) in pixels, square pixels.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Pose:
    R: np.ndarray      # (3, 3) world -> camera rotation, float32
    t: np.ndarray      # (3,) world -> camera translation, float32
    fx: float
    fy: float
    cx: float
    cy: float
    width: int
    height: int
    znear: float
    zfar: float
    angle: float       # orbit angle in radians, for the record


def look_at(eye, target, up=(0.0, 1.0, 0.0)):
    eye = np.asarray(eye, np.float32)
    target = np.asarray(target, np.float32)
    up = np.asarray(up, np.float32)
    fwd = target - eye
    fwd = fwd / (np.linalg.norm(fwd) + 1e-12)
    right = np.cross(fwd, up)
    right = right / (np.linalg.norm(right) + 1e-12)
    down = np.cross(fwd, right)
    R = np.stack([right, down, fwd], axis=0)
    t = -R @ eye
    return R.astype(np.float32), t.astype(np.float32)


def orbit_pose(angle: float, orbit: dict, width: int, height: int) -> Pose:
    """The pose at ``angle`` on the orbit described by ``orbit``: radius in
    world units, elevation as a share of the radius, horizontal field of
    view in degrees, near and far planes."""
    r = float(orbit["radius"])
    eye = (r * np.cos(angle), r * float(orbit["elevation"]),
           r * np.sin(angle))
    R, t = look_at(eye, (0.0, 0.0, 0.0))
    fx = 0.5 * width / np.tan(0.5 * np.deg2rad(float(orbit["fov_x_deg"])))
    return Pose(R=R, t=t, fx=float(fx), fy=float(fx), cx=width / 2.0,
                cy=height / 2.0, width=int(width), height=int(height),
                znear=float(orbit["znear"]), zfar=float(orbit["zfar"]),
                angle=float(angle))
