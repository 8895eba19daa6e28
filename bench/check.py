"""Decides ``correct``: served frames against the plain reference.

The frames compared are a sample, drawn from the seed, of those the window
served, as the host received them. Each is rendered again by
``bench/reference.py`` from the same scene arrays and pose, once the
program's state is freed, and three numbers are read per frame:

- ``mean_abs``: mean |served - reference| over pixels and channels. Sound
  frames differ only where float32 rounding moves an alpha across a cut-off
  (q = 9, 1/255, T = 1e-4), which is rare; a systematic error in the
  geometry (a lower-precision projection, a shifted splat) moves every
  edge a little, and this number with it.
- ``off_share``: share of pixels with any channel off by more than 1/255,
  one step of an 8-bit image. A dropped entry, a lost tile or a swapped
  frame moves it.
- ``max_abs``: the largest single difference.

The worst frame's number is held against its limit, which the
configuration file states under ``correct``. ``correct`` is also false if
any request of the window was refused, never came back, or came back with
the wrong shape or a non-finite pixel.
"""
from __future__ import annotations

import numpy as np

OFF_STEP = 1.0 / 255.0
NUMBERS = ("mean_abs", "off_share", "max_abs")


def compare(image, ref) -> dict:
    d = np.abs(np.asarray(image, np.float64) - np.asarray(ref, np.float64))
    return {
        "mean_abs": float(d.mean()),
        "off_share": float((d.max(axis=-1) > OFF_STEP).mean()),
        "max_abs": float(d.max()),
    }


def worst(readings) -> dict:
    return {k: max(r[k] for r in readings) for k in NUMBERS}


def judge(frames, refs, limits, shape, failed_requests):
    """``(correct, numbers)`` for the served ``frames`` (with images) against
    ``refs`` (reference images, the same order); ``numbers`` maps each
    compared quantity to ``[value, limit]``, the structural counts included.
    """
    bad = 0
    readings = []
    for f, ref in zip(frames, refs):
        img = np.asarray(f.image)
        if img.shape != tuple(shape) or not np.isfinite(img).all():
            bad += 1
            continue
        readings.append(compare(img, ref))
    numbers = {}
    if readings:
        w = worst(readings)
        numbers = {k: [w[k], float(limits[k])] for k in NUMBERS}
    numbers["frames_compared"] = [len(readings), len(frames)]
    numbers["requests_failed"] = [failed_requests + bad, 0]
    ok = (bool(readings) and bad == 0 and failed_requests == 0
          and all(numbers[k][0] <= numbers[k][1] for k in NUMBERS))
    return ok, numbers


def report_lines(numbers) -> list:
    out = []
    for k, (value, limit) in numbers.items():
        rel = ">=" if k == "frames_compared" else "<="
        out.append(f"check {k}: {value!r} (limit {rel} {limit!r})")
    return out
