"""Preprocessing stage of the 3D-GS pipeline (paper Fig 1, left).

Computes, per Gaussian: depth D, 2D center, 2D covariance (+ its conic
inverse), screen-space radius (3-sigma rule, as in the original 3D-GS), view
color from SH, and the frustum-culling validity mask.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.camera import Camera
from repro.core.gaussians import SH_C0, SH_COEFFS, GaussianScene, covariance3d
from repro.utils import HIGHEST

# Low-pass filter added to the 2D covariance diagonal (anti-aliasing), exactly
# as in the reference 3D-GS rasterizer.
COV2D_BLUR = 0.3
# 3-sigma rule for the Gaussian's screen extent (paper §II-B).
SIGMA_CUT = 3.0
# Power threshold matching alpha >= 1/255 for the *ellipse* boundary:
# alpha = opa * exp(-q/2) >= 1/255  <=>  q <= 2*ln(255*opa).  The 3-sigma rule
# corresponds to q <= 9; we use q<=9 (paper) and keep the opacity-aware bound
# available as a beyond-paper optimization.
QMAX_3SIGMA = SIGMA_CUT * SIGMA_CUT

SH_C1 = 0.4886025119029199
SH_C2 = (1.0925484305920792, -1.0925484305920792, 0.31539156525252005,
         -1.0925484305920792, 0.5462742152960396)
SH_C3 = (-0.5900435899266435, 2.890611442640554, -0.4570457994644658,
         0.3731763325901154, -0.4570457994644658, 1.445305721320277,
         -0.5900435899266435)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Projected:
    """Per-Gaussian screen-space features (all (N, ...))."""

    mean2d: jnp.ndarray      # (N, 2) pixel coords
    cov2d: jnp.ndarray       # (N, 3) upper-triangular (a, b, c): [[a, b], [b, c]]
    conic: jnp.ndarray       # (N, 3) inverse covariance, same packing
    depth: jnp.ndarray       # (N,)
    radius: jnp.ndarray      # (N,) 3-sigma screen radius (pixels)
    axis_radius: jnp.ndarray # (N, 2) 3-sigma per screen axis (AABB half-extent)
    eigvec: jnp.ndarray      # (N, 2) major-axis unit vector (for OBB)
    eigval: jnp.ndarray      # (N, 2) eigenvalues (major, minor) of cov2d
    rgb: jnp.ndarray         # (N, 3) decoded view-dependent color
    alpha: jnp.ndarray       # (N,) sigmoid opacity
    valid: jnp.ndarray       # (N,) bool frustum/size cull mask


# Per-field trailing widths of Projected — the basis of the per-camera
# activation term in the device-budget model (engine/handle.py): every field
# is float32 except ``valid`` (bool, 1 byte).
PROJECTED_FIELD_WIDTHS = {
    "mean2d": 2, "cov2d": 3, "conic": 3, "depth": 1, "radius": 1,
    "axis_radius": 2, "eigvec": 2, "eigval": 2, "rgb": 3, "alpha": 1,
    "valid": 1,
}


def projected_bytes_per_gaussian() -> int:
    """Bytes of projected per-camera features one (padded) gaussian costs.

    This is the N-proportional transient the feature-sharded gathers divide
    by D (DESIGN.md §12): with ``feature_gather != 'flat'`` each device
    materializes only its own ``N/D`` rows of every field below.
    """
    # Guard against schema drift: a Projected field added without updating
    # the widths dict would silently undercount the device-budget model.
    assert set(PROJECTED_FIELD_WIDTHS) == {
        f.name for f in dataclasses.fields(Projected)
    }, "PROJECTED_FIELD_WIDTHS out of sync with Projected's fields"
    return sum(
        w * (1 if name == "valid" else 4)
        for name, w in PROJECTED_FIELD_WIDTHS.items()
    )


@dataclasses.dataclass
class ShardedProjected:
    """Projected features kept in the per-shard layout (DESIGN.md §12).

    ``shards`` holds the ordinary :class:`Projected` arrays with a leading
    ``(D, Ns)`` shard axis — the direct output of the per-shard projection
    stage, NEVER concatenated to the flat padded ``(D * Ns, ...)`` view (the
    concat is the full-N per-camera allocation feature sharding removes).
    ``gather`` (static metadata) selects how downstream consumers fetch an
    entry's features from its owning shard:

      * ``'index'`` — plain 2-D indexed gather ``field[shard, local]``; the
        right strategy on one device or a logical-only shard axis.
      * ``'psum'``  — owner-masked per-shard gathers summed across the shard
        axis ON THE RAW BIT PATTERNS (exactly one shard owns each entry, so
        the integer sum reproduces the owner's float bits exactly). Under a
        2-D ``('data', 'model')`` mesh the sum over the sharded axis lowers
        to partial per-device gathers + an all-reduce — the Megatron-style
        collective form that never materializes full-N features per device.

    Both strategies are bitwise-identical to the flat gather
    ``concat(shards)[global_idx]`` because gathers commute with
    concatenation: ``flat[g] == shards[g // Ns, g % Ns]``.

    Differentiability: ``'index'`` (the default resolution of ``'auto'``)
    is an ordinary gather and differentiates like the flat path; ``'psum'``
    routes floats through a bit view (``bitcast_convert_type``) and is
    inference-only — exactly the serving paths the engine handle commits it
    for. Training with a sharded scene stays on ``'index'``/``'flat'``.
    """

    shards: Projected        # every field with leading (D, Ns) axes
    gather: str = "index"    # static: 'index' | 'psum'

    @property
    def num_shards(self) -> int:
        return self.shards.depth.shape[0]

    @property
    def shard_size(self) -> int:
        return self.shards.depth.shape[1]

    @property
    def valid(self) -> jnp.ndarray:
        """(D, Ns) cull mask — reductions over it equal the flat ones."""
        return self.shards.valid


jax.tree_util.register_dataclass(
    ShardedProjected, data_fields=["shards"], meta_fields=["gather"]
)

FEATURE_GATHER_STRATEGIES = ("index", "psum", "flat")


def _gather_owner_sum(x: jnp.ndarray, shard: jnp.ndarray, local: jnp.ndarray):
    """Owner-masked gather-and-sum over the shard axis, bit-exact.

    ``x``: (D, Ns, *F); ``shard``/``local``: any index shape. Each shard
    contributes its own rows where it owns the entry and zero bits
    elsewhere; the cross-shard sum runs on the raw bit patterns (uint view),
    so exactly-one-owner implies the result is the owner's bits verbatim —
    float signed zeros, NaN payloads and all. This is the form GSPMD
    partitions as per-device gathers + all-reduce when the leading axis lays
    over the mesh 'model' axis (sharding/policies.py::feature_shard_pspec).
    """
    D = x.shape[0]
    if jnp.issubdtype(x.dtype, jnp.floating):
        bits = {2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}[x.dtype.itemsize]
        view, restore = (
            jax.lax.bitcast_convert_type(x, bits),
            lambda v: jax.lax.bitcast_convert_type(v, x.dtype),
        )
    elif x.dtype == jnp.bool_:
        view, restore = x.astype(jnp.uint8), lambda v: v.astype(jnp.bool_)
    else:
        view, restore = x, lambda v: v

    def contrib(d, xd):
        own = shard == d
        g = xd[jnp.where(own, local, 0)]
        mask = own.reshape(own.shape + (1,) * (g.ndim - own.ndim))
        return jnp.where(mask, g, jnp.zeros((), view.dtype))

    # Pin the accumulator dtype: under x64, jnp.sum would promote a uint32
    # bit-view to uint64 and the bitcast back to float32 would then SPLIT a
    # trailing dimension. Exactly one contribution is nonzero, so the
    # same-width sum cannot overflow.
    out = jnp.sum(
        jax.vmap(contrib)(jnp.arange(D, dtype=shard.dtype), view),
        axis=0,
        dtype=view.dtype,
    )
    return restore(out)


def proj_take(proj, name: str, idx: jnp.ndarray) -> jnp.ndarray:
    """Gather field ``name`` of a flat OR sharded Projected at global
    gaussian indices ``idx`` — THE single gather primitive every downstream
    consumer (reference bitmask/raster gathers, the pallas feature packer)
    routes through, so the (shard, local) index decomposition lives in one
    place and the bitwise-parity argument is made once (DESIGN.md §12)."""
    if not isinstance(proj, ShardedProjected):
        return getattr(proj, name)[idx]
    x = getattr(proj.shards, name)
    shard, local = jnp.divmod(idx, jnp.int32(proj.shard_size))
    if proj.gather == "psum":
        return _gather_owner_sum(x, shard, local)
    return x[shard, local]


def proj_valid_count(proj) -> jnp.ndarray:
    """Visible-gaussian count for flat or sharded features (exact integer
    reduction, so the shard-summed total equals the flat one bitwise)."""
    return jnp.sum(proj.valid.astype(jnp.int32))


def eval_sh(sh: jnp.ndarray, dirs: jnp.ndarray) -> jnp.ndarray:
    """View-dependent colour from spherical harmonics, as 3D-GS's
    ``eval_sh``, of the degree the coefficient count gives: 1, 4, 9 or 16
    coefficients for degrees 0 to 3. Any other count is refused.

    sh: (N, K, 3); dirs: (N, 3) unit view directions (x, y, z).

    - degree 0: C0 sh_0;
    - degree 1 adds C1 (-y sh_1 + z sh_2 - x sh_3);
    - degree 2 adds C2[0] xy sh_4 + C2[1] yz sh_5 + C2[2] (2zz - xx - yy)
      sh_6 + C2[3] xz sh_7 + C2[4] (xx - yy) sh_8;
    - degree 3 adds C3[0] y (3xx - yy) sh_9 + C3[1] xyz sh_10 + C3[2]
      y (4zz - xx - yy) sh_11 + C3[3] z (2zz - 3xx - 3yy) sh_12 + C3[4]
      x (4zz - xx - yy) sh_13 + C3[5] z (xx - yy) sh_14 + C3[6]
      x (xx - 3yy) sh_15.

    The colour is offset by 0.5 and clipped to [0, 1].
    """
    n_sh = sh.shape[1]
    if n_sh not in SH_COEFFS:
        raise ValueError(
            f"spherical harmonics of degree 0-3 have {SH_COEFFS} "
            f"coefficients; got {n_sh}")
    rgb = SH_C0 * sh[:, 0, :]
    if n_sh >= 4:
        x, y, z = dirs[:, 0:1], dirs[:, 1:2], dirs[:, 2:3]
        rgb = rgb + SH_C1 * (-y * sh[:, 1, :] + z * sh[:, 2, :] - x * sh[:, 3, :])
    if n_sh >= 9:
        xx, yy, zz = x * x, y * y, z * z
        xy, yz, xz = x * y, y * z, x * z
        basis = (xy, yz, 2.0 * zz - xx - yy, xz, xx - yy)
        for k, (c, b) in enumerate(zip(SH_C2, basis), start=4):
            rgb = rgb + c * b * sh[:, k, :]
    if n_sh == 16:
        basis = (y * (3.0 * xx - yy), xy * z, y * (4.0 * zz - xx - yy),
                 z * (2.0 * zz - 3.0 * xx - 3.0 * yy),
                 x * (4.0 * zz - xx - yy), z * (xx - yy),
                 x * (xx - 3.0 * yy))
        for k, (c, b) in enumerate(zip(SH_C3, basis), start=9):
            rgb = rgb + c * b * sh[:, k, :]
    return jnp.clip(rgb + 0.5, 0.0, 1.0)


def project(scene: GaussianScene, cam: Camera) -> Projected:
    """The preprocessing stage: features + culling (paper Fig 1)."""
    R = jnp.asarray(cam.R)
    t = jnp.asarray(cam.t)
    p_cam = jnp.matmul(scene.means3d, R.T, precision=HIGHEST) + t[None, :]
    x, y, z = p_cam[:, 0], p_cam[:, 1], p_cam[:, 2]
    z_safe = jnp.maximum(z, 1e-6)

    mean2d = jnp.stack(
        [cam.fx * x / z_safe + cam.cx, cam.fy * y / z_safe + cam.cy], axis=-1
    )

    # --- 2D covariance via the projective Jacobian (EWA splatting) ---
    cov3d = covariance3d(scene.log_scales, scene.quats)      # (N, 3, 3)
    cov3d_cam = jnp.einsum("ij,njk,lk->nil", R, cov3d, R,
                           precision=HIGHEST)                  # R Σ R^T
    inv_z = 1.0 / z_safe
    inv_z2 = inv_z * inv_z
    # J = [[fx/z, 0, -fx x / z^2], [0, fy/z, -fy y / z^2]]
    j00 = cam.fx * inv_z
    j02 = -cam.fx * x * inv_z2
    j11 = cam.fy * inv_z
    j12 = -cam.fy * y * inv_z2
    zeros = jnp.zeros_like(j00)
    J = jnp.stack(
        [
            jnp.stack([j00, zeros, j02], axis=-1),
            jnp.stack([zeros, j11, j12], axis=-1),
        ],
        axis=-2,
    )  # (N, 2, 3)
    cov2d_full = jnp.matmul(
        jnp.matmul(J, cov3d_cam, precision=HIGHEST),
        jnp.swapaxes(J, -1, -2), precision=HIGHEST,
    )                                                          # (N, 2, 2)
    a = cov2d_full[:, 0, 0] + COV2D_BLUR
    b = cov2d_full[:, 0, 1]
    c = cov2d_full[:, 1, 1] + COV2D_BLUR
    cov2d = jnp.stack([a, b, c], axis=-1)

    det = a * c - b * b
    det_safe = jnp.maximum(det, 1e-12)
    conic = jnp.stack([c / det_safe, -b / det_safe, a / det_safe], axis=-1)

    # Eigen-decomposition of [[a,b],[b,c]] (closed form).
    mid = 0.5 * (a + c)
    disc = jnp.sqrt(jnp.maximum(mid * mid - det, 1e-12))
    lam1 = mid + disc  # major
    lam2 = jnp.maximum(mid - disc, 1e-12)  # minor
    radius = SIGMA_CUT * jnp.sqrt(jnp.maximum(lam1, 1e-12))
    # Major-axis direction: eigenvector of lam1.
    ex = jnp.where(jnp.abs(b) > 1e-9, b, lam1 - c)
    ey = jnp.where(jnp.abs(b) > 1e-9, lam1 - a, jnp.zeros_like(b))
    # Degenerate (already axis-aligned): fall back to x-axis.
    enorm = jnp.sqrt(ex * ex + ey * ey)
    ex = jnp.where(enorm > 1e-9, ex / jnp.maximum(enorm, 1e-12), 1.0)
    ey = jnp.where(enorm > 1e-9, ey / jnp.maximum(enorm, 1e-12), 0.0)
    eigvec = jnp.stack([ex, ey], axis=-1)
    eigval = jnp.stack([lam1, lam2], axis=-1)

    # Tight per-axis 3-sigma extents (AABB of the ellipse, not of the circle).
    axis_radius = SIGMA_CUT * jnp.sqrt(
        jnp.maximum(jnp.stack([a, c], axis=-1), 1e-12)
    )

    # --- color + opacity ---
    cam_pos = -jnp.matmul(R.T, t, precision=HIGHEST)
    dirs = scene.means3d - cam_pos[None, :]
    dirs = dirs / (jnp.linalg.norm(dirs, axis=-1, keepdims=True) + 1e-12)
    rgb = eval_sh(scene.sh, dirs)
    alpha = jax.nn.sigmoid(scene.opacity)

    # --- culling (paper Fig 1: invisible Gaussians removed) ---
    in_front = z > cam.znear
    not_far = z < cam.zfar
    on_screen = (
        (mean2d[:, 0] + radius > 0.0)
        & (mean2d[:, 0] - radius < cam.width)
        & (mean2d[:, 1] + radius > 0.0)
        & (mean2d[:, 1] - radius < cam.height)
    )
    big_enough = det > 1e-12
    visible_alpha = alpha > (1.0 / 255.0)
    valid = in_front & not_far & on_screen & big_enough & visible_alpha

    # Sanitize culled Gaussians: behind-camera projections can overflow f32
    # (inf/inf = NaN conics), and a NaN feature would poison rasterization
    # through 0*NaN even at zero opacity (NaN fails every cutoff comparison).
    def _clean(x, default):
        mask = valid if x.ndim == 1 else valid[:, None]
        return jnp.where(mask, jnp.nan_to_num(x, posinf=1e30, neginf=-1e30), default)

    ident2 = jnp.array([1.0, 0.0, 1.0], jnp.float32)
    return Projected(
        mean2d=_clean(mean2d, 0.0),
        cov2d=_clean(cov2d, ident2),
        conic=_clean(conic, ident2),
        depth=_clean(z, jnp.inf),
        radius=_clean(radius, 0.0),
        axis_radius=_clean(axis_radius, 0.0),
        eigvec=_clean(eigvec, jnp.array([1.0, 0.0], jnp.float32)),
        eigval=_clean(eigval, 1.0),
        rgb=_clean(rgb, 0.0),
        alpha=_clean(alpha, 0.0),
        valid=valid,
    )
