"""Closed-loop traffic: the next requests go in only when the last ones are
back, so the queue holds ``max_batch`` requests at every dispatch.

Mix keys:

- ``max_batch``, ``max_wait``: the server's batching settings;
- ``orbit_extents``: orbit radius in scene extents;
- ``path``: how the pose moves from request to request, from an orbit
  angle drawn from the seed:
  - ``scatter``: by the golden angle, so poses spread round the orbit and
    none repeats;
  - ``drag``: by a fixed step of ``step_deg`` degrees (drawn from the seed
    within that range, either way round), like a user dragging the camera.

The window starts at its first submit. Nothing is submitted once
``seconds`` have passed since then, and the window ends when the last
frame submitted inside it is back: at most one batch past ``seconds``.
"""
from __future__ import annotations

import math

GOLDEN_ANGLE = math.pi * (3.0 - math.sqrt(5.0))


def angles(mix, rng):
    """Endless orbit angles for this mix, drawn from ``rng``."""
    angle = float(rng.uniform(0.0, 2.0 * math.pi))
    if mix["path"] == "scatter":
        step = GOLDEN_ANGLE
    elif mix["path"] == "drag":
        lo, hi = mix["step_deg"]
        step = math.radians(float(rng.uniform(lo, hi)))
        step *= 1.0 if rng.integers(2) else -1.0
    else:
        raise ValueError(f"unknown path {mix['path']!r}")
    while True:
        yield angle
        angle += step


def warm(session, mix, poses):
    """One full dispatch of the cell's batch shape, outside the window."""
    for _ in range(int(mix["max_batch"])):
        session.submit(next(poses))
    session.drain()


def drive(session, mix, seconds, poses):
    target = int(mix["max_batch"])
    start = None
    while start is None or session.now() - start < seconds:
        while session.in_flight() < target:
            ok = session.submit(next(poses))
            if start is None:
                start = session.frames[-1].submit_t
            if not ok:
                break
        session.step()
    session.drain()
