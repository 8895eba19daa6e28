"""Plain float32 reference renderer for 3D Gaussian Splatting.

This is the image a frame must equal. It is written from the 3D-GS forward
formula and shares no code with the program under test. Each step does the
obvious thing:

1. Project every Gaussian (EWA splatting): camera-space mean, 2D mean,
   2D covariance with the 0.3 px low-pass on the diagonal, its inverse
   (the conic), spherical-harmonics colour of degree 0 to 3 (the real
   basis 3D-GS evaluates) along the direction from the camera centre to
   the mean, and sigmoid opacity. A Gaussian is kept
   when it lies between the near and far planes, its 3-sigma box touches the
   image, its 2D covariance is not degenerate, and its opacity is over
   1/255.
2. Order the kept Gaussians by depth, ties by index.
3. List, for every 16 px tile, the Gaussians whose 3-sigma box (grown by
   one pixel) overlaps it, in that order (one entry per (Gaussian, tile)
   pair, stably sorted by tile).
4. Blend every pixel front to back over its tile's list, one entry at a
   time:
       q     = d^T Conic d,  d = pixel centre - mean
       alpha = min(opacity * exp(-q / 2), 0.99), and 0 where q > 9 or
               alpha < 1/255
       an entry contributes alpha * T * colour while the transmittance T
       before it is above 1e-4; T then becomes T * (1 - alpha).
   The background is black.

A frame whose pairs exceed the pair-list capacity it is given is rendered
in bands of whole tile rows, each band's pairs within that capacity: every
Gaussian's box is clipped to the band's rows, so each tile keeps the same
list in the same order, and the image is bitwise that of one pass (a pass
with more loop steps only blends further entries of alpha 0).

Because the alpha rule zeroes everything outside q <= 9, any list that
holds every Gaussian whose q <= 9 region reaches a pixel gives the same
image; the one-pixel margin in step 3 only adds entries of alpha 0.

``passes`` sets the precision of the geometry's products. 6 is float32
(matrix products at ``Precision.HIGHEST``). 3 computes each product from
bfloat16 halves as lo*hi + hi*lo + hi*hi, which is what ``Precision.HIGH``
does on a TPU, the same on every backend: that is the lower-precision
control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

SH_C0 = 0.28209479177387814
SH_C1 = 0.4886025119029199
SH_C2 = (1.0925484305920792, -1.0925484305920792, 0.31539156525252005,
         -1.0925484305920792, 0.5462742152960396)
SH_C3 = (-0.5900435899266435, 2.890611442640554, -0.4570457994644658,
         0.3731763325901154, -0.4570457994644658, 1.445305721320277,
         -0.5900435899266435)
SH_COEFFS = (1, 4, 9, 16)
COV_BLUR = 0.3
Q_MAX = 9.0
ALPHA_MIN = 1.0 / 255.0
ALPHA_MAX = 0.99
T_MIN = 1e-4
BOX_MARGIN = 1.0
UNROLL = 4
HIGHEST = jax.lax.Precision.HIGHEST


def _split_bf16(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _mul(a, b, passes):
    """a * b, or its 3-pass bfloat16 form."""
    if passes == 6:
        return a * b
    ah, al = _split_bf16(jnp.asarray(a, jnp.float32))
    bh, bl = _split_bf16(jnp.asarray(b, jnp.float32))
    return (al * bh + ah * bl) + ah * bh


def _matmul(a, b, passes):
    if passes == 6:
        return jnp.matmul(a, b, precision=HIGHEST)
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    return (mm(al, bh) + mm(ah, bl)) + mm(ah, bh)


def _rotation(q):
    """Rows of the rotation of unnormalised quaternions (w, x, y, z), each
    entry an (N,) array."""
    q = q / (jnp.linalg.norm(q, axis=-1, keepdims=True) + 1e-12)
    w, x, y, z = q[:, 0], q[:, 1], q[:, 2], q[:, 3]
    return (
        (1 - 2 * (y * y + z * z), 2 * (x * y - w * z), 2 * (x * z + w * y)),
        (2 * (x * y + w * z), 1 - 2 * (x * x + z * z), 2 * (y * z - w * x)),
        (2 * (x * z - w * y), 2 * (y * z + w * x), 1 - 2 * (x * x + y * y)),
    )


def _sh_colour(scene, R, t, passes):
    """3D-GS view-dependent colour before the +0.5 offset, along the unit
    direction from the camera centre -R^T t to the mean."""
    sh = scene["sh"]
    if sh.shape[1] not in SH_COEFFS:
        raise ValueError(f"the reference evaluates SH degree 0 to 3, not "
                         f"{sh.shape[1]} coefficients")
    if sh.shape[1] == 1:
        return SH_C0 * sh[:, 0, :]
    centre = -_matmul(R.T, t[:, None], passes)[:, 0]
    d = scene["means3d"] - centre[None, :]
    d = d / (jnp.linalg.norm(d, axis=-1, keepdims=True) + 1e-12)
    return sh_colour(sh, d, passes)


def sh_colour(sh, d, passes=6):
    """sum_k C_k Y_k(d) sh_k over the (N, K, 3) coefficients ``sh`` and the
    (N, 3) unit directions d = (x, y, z), as 3D-GS's ``eval_sh``:

    - degree 0: C0 sh_0;
    - degree 1 adds C1 (-y sh_1 + z sh_2 - x sh_3);
    - degree 2 adds C2[0] xy sh_4 + C2[1] yz sh_5 + C2[2] (2zz - xx - yy)
      sh_6 + C2[3] xz sh_7 + C2[4] (xx - yy) sh_8;
    - degree 3 adds C3[0] y (3xx - yy) sh_9 + C3[1] xyz sh_10 + C3[2]
      y (4zz - xx - yy) sh_11 + C3[3] z (2zz - 3xx - 3yy) sh_12 + C3[4]
      x (4zz - xx - yy) sh_13 + C3[5] z (xx - yy) sh_14 + C3[6]
      x (xx - 3yy) sh_15.

    Every product of direction components and of a basis function with a
    coefficient goes through ``_mul``; the constants scale in float32.
    """
    rgb = SH_C0 * sh[:, 0, :]
    if sh.shape[1] == 1:
        return rgb
    m = functools.partial(_mul, passes=passes)
    x, y, z = d[:, 0:1], d[:, 1:2], d[:, 2:3]
    rgb = rgb + SH_C1 * (m(-y, sh[:, 1, :]) + m(z, sh[:, 2, :])
                         - m(x, sh[:, 3, :]))
    if sh.shape[1] == 4:
        return rgb
    xx, yy, zz = m(x, x), m(y, y), m(z, z)
    xy, yz, xz = m(x, y), m(y, z), m(x, z)
    basis = [xy, yz, 2.0 * zz - xx - yy, xz, xx - yy]
    for k, (c, b) in enumerate(zip(SH_C2, basis), start=4):
        rgb = rgb + c * m(b, sh[:, k, :])
    if sh.shape[1] == 9:
        return rgb
    basis = [m(y, 3.0 * xx - yy), m(xy, z), m(y, 4.0 * zz - xx - yy),
             m(z, 2.0 * zz - 3.0 * xx - 3.0 * yy), m(x, 4.0 * zz - xx - yy),
             m(z, xx - yy), m(x, xx - 3.0 * yy)]
    for k, (c, b) in enumerate(zip(SH_C3, basis), start=9):
        rgb = rgb + c * m(b, sh[:, k, :])
    return rgb


def project(scene, cam, *, width, height, znear, zfar, passes):
    """Per-Gaussian screen features; ``cam`` holds R, t, fx, fy, cx, cy.

    Per-Gaussian 3x3 products are written out entry by entry over (N,)
    arrays: an (N, 3, 3) array would be padded to (N, 8, 128) on a TPU.
    """
    R, t = cam["R"], cam["t"]
    fx, fy, cx, cy = cam["fx"], cam["fy"], cam["cx"], cam["cy"]
    m = functools.partial(_mul, passes=passes)
    p = _matmul(scene["means3d"], R.T, passes) + t[None, :]
    x, y, z = p[:, 0], p[:, 1], p[:, 2]
    zs = jnp.maximum(z, 1e-6)
    mx = fx * x / zs + cx
    my = fy * y / zs + cy

    # Sigma = M M^T with M = rotation * scales (columns scaled).
    rot = _rotation(scene["quats"])
    s = jnp.exp(scene["log_scales"])
    M = [[rot[i][k] * s[:, k] for k in range(3)] for i in range(3)]
    sig = [[sum(m(M[i][k], M[j][k]) for k in range(3)) for j in range(3)]
           for i in range(3)]
    # Camera frame: R Sigma R^T.
    RS = [[sum(m(R[i, k], sig[k][j]) for k in range(3)) for j in range(3)]
          for i in range(3)]
    sc = [[sum(m(RS[i][k], R[j, k]) for k in range(3)) for j in range(3)]
          for i in range(3)]
    # J = [[fx/z, 0, -fx x/z^2], [0, fy/z, -fy y/z^2]]; cov = J sc J^T.
    j00, j02 = fx / zs, -fx * x / (zs * zs)
    j11, j12 = fy / zs, -fy * y / (zs * zs)
    JS0 = [m(j00, sc[0][k]) + m(j02, sc[2][k]) for k in range(3)]
    JS1 = [m(j11, sc[1][k]) + m(j12, sc[2][k]) for k in range(3)]
    a = m(JS0[0], j00) + m(JS0[2], j02) + COV_BLUR
    b = m(JS0[1], j11) + m(JS0[2], j12)
    c = m(JS1[1], j11) + m(JS1[2], j12) + COV_BLUR
    det = a * c - b * b
    ok_det = det > 1e-12
    dsafe = jnp.where(ok_det, det, 1.0)
    conic = jnp.stack([c / dsafe, -b / dsafe, a / dsafe], -1)
    # The q <= 9 ellipse spans 3 sqrt(a) in x and 3 sqrt(c) in y.
    ex = 3.0 * jnp.sqrt(jnp.maximum(a, 0.0))
    ey = 3.0 * jnp.sqrt(jnp.maximum(c, 0.0))
    opac = jax.nn.sigmoid(scene["opacity"])
    rgb = jnp.clip(_sh_colour(scene, R, t, passes) + 0.5, 0.0, 1.0)
    valid = ((z > znear) & (z < zfar) & ok_det & (opac > ALPHA_MIN)
             & (mx + ex > 0) & (mx - ex < width)
             & (my + ey > 0) & (my - ey < height))
    return {"mx": mx, "my": my, "conic": conic, "depth": z, "rgb": rgb,
            "opac": opac, "valid": valid, "ex": ex, "ey": ey}


def _tile_boxes(pr, tile, ntx, nty):
    """Inclusive tile ranges of each Gaussian's grown 3-sigma box, in depth
    order (ties by index), and the number of tiles each covers."""
    key = jnp.where(pr["valid"], pr["depth"], jnp.inf)
    order = jnp.argsort(key, stable=True)
    g = {k: v[order] for k, v in pr.items()}

    def rng(m, e, n):
        lo = jnp.floor((m - e - BOX_MARGIN) / tile).astype(jnp.int32)
        hi = jnp.floor((m + e + BOX_MARGIN) / tile).astype(jnp.int32)
        return jnp.clip(lo, 0, n - 1), jnp.clip(hi, 0, n - 1)

    x0, x1 = rng(g["mx"], g["ex"], ntx)
    y0, y1 = rng(g["my"], g["ey"], nty)
    count = jnp.where(g["valid"], (x1 - x0 + 1) * (y1 - y0 + 1), 0)
    return g, (x0, x1, y0, y1), count


@functools.partial(jax.jit, static_argnames=(
    "width", "height", "znear", "zfar", "tile", "passes"))
def _front(scene, cam, *, width, height, znear, zfar, tile, passes):
    pr = project(scene, cam, width=width, height=height, znear=znear,
                 zfar=zfar, passes=passes)
    ntx, nty = -(-width // tile), -(-height // tile)
    g, box, count = _tile_boxes(pr, tile, ntx, nty)
    return g, box, count, jnp.sum(count), jnp.sum(g["valid"])


@functools.partial(jax.jit, static_argnames=(
    "width", "height", "tile", "capacity", "with_counts"))
def _back(g, box, count, *, width, height, tile, capacity, with_counts,
          row0=None):
    """Blends the (width, height) image of the tiles' lists. With ``row0``,
    the image is a band whose first tile row is the frame's row ``row0``,
    and ``box`` holds tile rows counted from it."""
    ntx, nty = -(-width // tile), -(-height // tile)
    nt = ntx * nty
    x0, x1, y0, y1 = box
    n = count.shape[0]
    # Expand every Gaussian into one slot per covered tile.
    gid = jnp.repeat(jnp.arange(n, dtype=jnp.int32), count,
                     total_repeat_length=capacity)
    first = jnp.cumsum(count) - count
    slot = jnp.arange(capacity, dtype=jnp.int32)
    used = slot < jnp.sum(count)
    local = slot - first[gid]
    w = x1[gid] - x0[gid] + 1
    tid = (y0[gid] + local // w) * ntx + x0[gid] + local % w
    tid = jnp.where(used, tid, nt)
    tid, gid = jax.lax.sort((tid, gid), num_keys=1, is_stable=True)
    per_tile = jnp.bincount(tid, length=nt + 1)[:nt]
    start = jnp.cumsum(per_tile) - per_tile

    feat = jnp.concatenate([
        g["mx"][:, None], g["my"][:, None], g["conic"], g["rgb"],
        jnp.where(g["valid"], g["opac"], 0.0)[:, None],
    ], axis=1)                                                 # (n, 9)
    tix = jnp.arange(nt, dtype=jnp.int32)
    off = jnp.arange(tile, dtype=jnp.float32) + 0.5
    px = ((tix % ntx) * tile).astype(jnp.float32)[:, None, None] \
        + off[None, None, :]
    ty = tix // ntx if row0 is None else tix // ntx + row0
    py = (ty * tile).astype(jnp.float32)[:, None, None] \
        + off[None, :, None]
    px = jnp.broadcast_to(px, (nt, tile, tile)).reshape(nt, tile * tile)
    py = jnp.broadcast_to(py, (nt, tile, tile)).reshape(nt, tile * tile)

    def entry(j, carry):
        T, C, cnt = carry
        inside = j < per_tile
        e = gid[jnp.clip(start + j, 0, capacity - 1)]
        f = feat[e]                                            # (nt, 9)
        dx = px - f[:, 0:1]
        dy = py - f[:, 1:2]
        q = (f[:, 2:3] * dx * dx + 2.0 * f[:, 3:4] * dx * dy
             + f[:, 4:5] * dy * dy)
        a = jnp.minimum(f[:, 8:9] * jnp.exp(-0.5 * q), ALPHA_MAX)
        a = jnp.where((q > Q_MAX) | (a < ALPHA_MIN) | ~inside[:, None],
                      0.0, a)
        live = T > T_MIN
        wgt = jnp.where(live, a * T, 0.0)
        C = C + wgt[:, :, None] * f[:, None, 5:8]
        if with_counts:
            touch = jnp.any(a > 0.0, axis=1)
            cnt = cnt + jnp.stack([
                jnp.sum(touch),
                jnp.sum(jnp.where(touch[:, None] & live, 1, 0)),
                jnp.sum(wgt > 0.0),
            ]).astype(jnp.float32)
        return T * (1.0 - a), C, cnt

    def chunk(i, carry):
        for u in range(UNROLL):
            carry = entry(i * UNROLL + u, carry)
        return carry

    steps = (jnp.max(per_tile) + UNROLL - 1) // UNROLL
    init = (jnp.ones((nt, tile * tile), jnp.float32),
            jnp.zeros((nt, tile * tile, 3), jnp.float32),
            jnp.zeros((3,), jnp.float32))
    _, C, cnt = jax.lax.fori_loop(0, steps, chunk, init)
    img = C.reshape(nty, ntx, tile, tile, 3).transpose(0, 2, 1, 3, 4)
    img = img.reshape(nty * tile, ntx * tile, 3)[:height, :width]
    return img, cnt


def _capacity(pairs: int, capacity: int | None) -> int:
    if capacity is not None and pairs <= capacity:
        return int(capacity)
    return 1 << max(10, math.ceil(math.log2(max(pairs, 1))))


@functools.partial(jax.jit, static_argnames=("nty",))
def _row_pairs(box, count, *, nty):
    """(Gaussian, tile) pairs in each tile row."""
    x0, x1, y0, y1 = box
    w = jnp.where(count > 0, x1 - x0 + 1, 0)
    diff = jnp.zeros(nty + 1, jnp.int32).at[y0].add(w).at[y1 + 1].add(-w)
    return jnp.cumsum(diff)[:nty]


@jax.jit
def _band_boxes(box, count, r0, r1):
    """The boxes clipped to tile rows r0 .. r1 - 1, in rows counted from r0,
    and the tiles each then covers."""
    x0, x1, y0, y1 = box
    y0, y1 = jnp.maximum(y0, r0), jnp.minimum(y1, r1 - 1)
    count = jnp.where((count > 0) & (y0 <= y1),
                      (x1 - x0 + 1) * (y1 - y0 + 1), 0)
    return (x0, x1, y0 - r0, y1 - r0), count


def bands(row_pairs, capacity: int) -> list:
    """Runs ``(r0, r1)`` of whole tile rows, each with at most ``capacity``
    pairs unless it is a single row that holds more on its own."""
    out, r0, acc = [], 0, 0
    for r, n in enumerate(int(n) for n in row_pairs):
        if r > r0 and acc + n > capacity:
            out.append((r0, r))
            r0, acc = r, 0
        acc += n
    out.append((r0, len(row_pairs)))
    return out


def _banded(g, box, count, *, width, height, tile, capacity, with_counts):
    """``_back`` band by band; the image rows of each band and the sums of
    their counts. A band renders a power-of-two number of tile rows (rows
    past its own are empty and dropped), so few programs are compiled."""
    nty = -(-height // tile)
    rows = np.asarray(_row_pairs(box, count, nty=nty))
    parts, cnt = [], np.zeros(3)
    for r0, r1 in bands(rows, capacity):
        span = min(1 << math.ceil(math.log2(r1 - r0)), nty)
        bbox, bcount = _band_boxes(box, count, r0, r1)
        img, c = _back(g, bbox, bcount, width=width, height=span * tile,
                       tile=tile, with_counts=with_counts,
                       capacity=_capacity(int(rows[r0:r1].sum()), capacity),
                       row0=jnp.int32(r0))
        parts.append(img[:(r1 - r0) * tile])
        cnt += np.asarray(c, np.float64)
    return jnp.concatenate(parts, axis=0)[:height], cnt


def pose_arrays(pose) -> dict:
    return {"R": jnp.asarray(pose.R, jnp.float32),
            "t": jnp.asarray(pose.t, jnp.float32),
            "fx": jnp.float32(pose.fx), "fy": jnp.float32(pose.fy),
            "cx": jnp.float32(pose.cx), "cy": jnp.float32(pose.cy)}


def band_plan(scene, pose, *, capacity, tile=16, passes=6):
    """``(pairs, row_pairs, bands)`` of ``pose``'s frame: its (Gaussian,
    tile) pairs, those of each tile row, and the bands ``render`` draws it
    in with ``capacity`` (one when the pairs fit)."""
    g, box, count, pairs, _ = _front(
        scene, pose_arrays(pose), width=pose.width, height=pose.height,
        znear=pose.znear, zfar=pose.zfar, tile=tile, passes=passes)
    del g
    rows = np.asarray(_row_pairs(box, count, nty=-(-pose.height // tile)))
    pairs = int(pairs)
    plan = [(0, len(rows))] if pairs <= capacity else bands(rows, capacity)
    return pairs, rows, plan


def render(scene, pose, *, tile=16, passes=6, capacity=None,
           with_counts=False):
    """The reference image (H, W, 3) of ``pose`` as a device array, and with
    ``with_counts`` the frame's work counts: Gaussians, colour
    coefficients per channel, visible Gaussians, (Gaussian,
    tile) pairs of the grown boxes, pairs whose q <= 9 region reaches a
    pixel centre of the tile with alpha >= 1/255 ("touching"), alpha
    evaluations those pairs need at pixels still live, and blends that
    contributed. ``capacity`` is the most pairs one pass lists: a frame
    with more is rendered in bands of tile rows (``bands``)."""
    kw = dict(width=pose.width, height=pose.height)
    g, box, count, pairs, visible = _front(
        scene, pose_arrays(pose), znear=pose.znear, zfar=pose.zfar,
        tile=tile, passes=passes, **kw)
    pairs = int(pairs)
    if capacity is None or pairs <= capacity:
        img, cnt = _back(g, box, count, tile=tile,
                         capacity=_capacity(pairs, capacity),
                         with_counts=with_counts, **kw)
    else:
        img, cnt = _banded(g, box, count, tile=tile, capacity=capacity,
                           with_counts=with_counts, **kw)
    if not with_counts:
        return img, None
    cnt = np.asarray(cnt, np.float64)
    return img, {"gaussians": int(scene["means3d"].shape[0]),
                 "sh_coeffs": int(scene["sh"].shape[1]),
                 "visible": int(visible), "box_pairs": pairs,
                 "touch_pairs": float(cnt[0]), "alpha_evals": float(cnt[1]),
                 "blends": float(cnt[2])}
