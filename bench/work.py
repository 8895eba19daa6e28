"""The work a frame needs, in operations and bytes, whatever renders it.

Counted from the 3D-GS forward formula, never from the program: not from
its counters, its padded table shapes or its HLO. The inputs are counts of
one frame that the benchmark's own reference takes (``reference.render``
with ``with_counts``) on the frames a traced run compares, averaged over
them:

- ``gaussians``: every Gaussian is projected (culling needs the projection);
- ``sh_coeffs``: spherical-harmonics coefficients per colour channel, 1
  for degree 0 and 4 for degree 1;
- ``touch_pairs``: (Gaussian, 16 px tile) pairs whose q <= 9 region reaches
  a pixel centre of the tile with alpha >= 1/255;
- ``alpha_evals``: alpha evaluations those pairs need at pixels that are
  still live (transmittance above 1e-4) when the pair comes up;
- ``blends``: evaluations that contributed to a pixel.

Operations are float32 additions, multiplications, divisions, square
roots, exponentials and comparisons, one each:

- per projection, ``PROJECT_OPS`` = 275 and the colour: world to camera,
  18 (3x3 by 3 and the translation); perspective divide and pixel map, 7;
  quaternion normalisation, 12; rotation matrix, 27; scales (exp) into R S, 12;
  Sigma = M M^T, 30; R Sigma R^T, 90; Jacobian, 8; J Sigma' J^T, 45;
  low-pass, 2; determinant, 3; conic, 4; 3-sigma extents, 4; sigmoid, 3;
  culling tests, 10. The colour, ``COLOUR_OPS``: degree 0 with the +0.5
  and the clamp, 12; degree 1 adds the unit view direction, 13 (offset
  from the camera centre 3, norm with its epsilon 7, divide 3), and the
  three further coefficients per channel, 21: 46;
- per alpha evaluation, ``ALPHA_OPS`` = 18: offset, 2; quadratic form, 9;
  exp(-q/2), 2; times opacity, 1; clamp to 0.99, 1; the q and 1/255
  cut-offs with their select, 2; the transmittance test, 1;
- per blend, ``BLEND_OPS`` = 9: weight alpha*T, 1; colour accumulate, 6;
  T * (1 - alpha), 2.

Sorting moves keys and does no arithmetic, so it adds no operations.

Bytes are the least traffic to and from device memory:

- the scene, read once: float32 mean 3, scale 3, rotation 4 and opacity
  1 per Gaussian, ``SCENE_BYTES`` 44, and 3 colour coefficients per
  ``sh_coeffs``, 12 bytes each;
- per touching pair, the Gaussian's 9 screen-space float32 (mean 2,
  conic 3, colour 3, opacity 1) = ``FEATURE_BYTES`` 36, read by the tile;
- the image, written once: 3 float32 per pixel = ``PIXEL_BYTES`` 12.

The kernel layer (bitmask and rasterization) needs the alpha and blend
operations and the feature and image bytes; the whole frame needs all of
it.
"""
from __future__ import annotations

PROJECT_OPS = 275
COLOUR_OPS = {1: 12, 4: 46}
ALPHA_OPS = 18
BLEND_OPS = 9
SCENE_BYTES = 44
COEFF_BYTES = 12
FEATURE_BYTES = 36
PIXEL_BYTES = 12


def frame_work(counts: dict, width: int, height: int) -> dict:
    """Operations and bytes of one frame, for the kernels and in whole."""
    raster_ops = (counts["alpha_evals"] * ALPHA_OPS
                  + counts["blends"] * BLEND_OPS)
    raster_bytes = (counts["touch_pairs"] * FEATURE_BYTES
                    + width * height * PIXEL_BYTES)
    n_sh = int(counts["sh_coeffs"])
    per_gaussian_ops = PROJECT_OPS + COLOUR_OPS[n_sh]
    per_gaussian_bytes = SCENE_BYTES + n_sh * COEFF_BYTES
    return {
        "kernel_ops": raster_ops,
        "kernel_bytes": raster_bytes,
        "frame_ops": counts["gaussians"] * per_gaussian_ops + raster_ops,
        "frame_bytes": counts["gaussians"] * per_gaussian_bytes
        + raster_bytes,
    }


def least_time(ops: float, nbytes: float, peak: dict):
    """``(seconds, bound)``: the least time the chip could take for this work
    at its peaks, and which peak bounds it (``compute`` or ``memory``)."""
    t_ops = ops / float(peak["ops_per_s"])
    t_mem = nbytes / float(peak["bytes_per_s"])
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
