"""Pallas TPU kernel for the Rasterization Module (RM, paper Fig 10).

Two entry points:

  * ``raster_tile_kernel`` — per-tile rasterization over pre-compacted,
    depth-sorted entry lists (the RM after its FIFO stage). Used by both the
    per-tile baseline and GS-TG (whose FIFO compaction ran upstream).
  * ``raster_group_fused_kernel`` — the fused GS-TG RM: consumes *group*
    entry lists plus per-entry tile bitmasks and applies the bitwise-AND
    valid-flag filter in-register (paper's 8-wide AND/OR logic becomes lane
    predication), so no compacted per-tile tables ever materialize in HBM.
    ``tile_capacity`` bounds each member tile's virtual FIFO: mask-selected
    entries past the capacity are dropped in-register, mirroring the
    reference compaction clamp.

Both kernels optionally return the engine's RenderStats counters (pass
``with_stats=True``): per-tile (alpha_ops, blend_ops) accumulated alongside
the blend, with the reference semantics (core/raster.py).

TPU mapping (vs the ASIC):
  - the grid iterates tiles (or group x member-tile) and blocks of at most
    PIXEL_BLOCK of their T*T pixels; each step streams the entry list in ``chunk``-wide slices (a
    multiple of the 128-lane width). Pixels sit on sublanes and entries on
    lanes: every per-(pixel, entry) quantity is a (P, chunk) array.
  - tile/group pixel origins are a function of the grid index
    (``pl.program_id``), so no origin table is DMA'd.
  - front-to-back blending keeps its sequential semantics: each entry's
    transmittance is the product of (1 - alpha) over every entry before it
    in depth order. Inside a chunk that prefix product (and the FIFO clamp's
    prefix count) is a Hillis-Steele scan of log2(chunk) lane rotations; the
    running transmittance carries between chunks.
  - early exit is block-granular: when every pixel's transmittance is below
    T_EPS the remaining chunks are skipped (lax.cond), the TPU analogue of
    the per-Gaussian FIFO drain. Chunks past the end of the entry list are
    skipped the same way, so a kernel's work follows the list length and
    not the table capacity. Per-entry exactness is preserved by gating
    each entry's weight on its own T_before (see core/raster.py). Passing
    ``early_exit=False`` disables both the gate and the skip, matching the
    reference's exhaustive blend.
  - the kernel writes one (8, P) block per tile: rows r, g, b, final
    transmittance, and the per-pixel alpha/blend op counts (integers below
    2^24, exact in float32), so the counters need no separate output.

Tolerance against core/raster.py. The scan multiplies the same factors in a
different association than ``jnp.cumprod`` and the colour sums run over
lanes instead of a matmul, so the two agree to float32 rounding: a few ulp
per chunk, about 1e-6 absolute on a [0, 1] image (the interpret-mode tests
hold 5e-6). Counters agree exactly except where a rounding difference moves
a transmittance or alpha across one of its thresholds (T_EPS, 1/255, q = 9);
such a flip changes one pixel by at most the contribution of the entries it
gates.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import (
    F_CONIC_A,
    F_CONIC_B,
    F_CONIC_C,
    F_MEAN_X,
    F_MEAN_Y,
    F_OPACITY,
    F_RGB_B,
    F_RGB_G,
    F_RGB_R,
    F_VALID,
    LANE,
    NUM_FEATURES,
)
from repro.kernels.ops import on_every_device, resolve_interpret

ALPHA_MIN = 1.0 / 255.0
ALPHA_MAX = 0.99
T_EPS = 1e-4
QMAX = 9.0
OUT_ROWS = 8  # r, g, b, T, alpha_ops, blend_ops, 2 rows of padding
PIXEL_BLOCK = 256  # pixels per grid step: (256, chunk) f32 temporaries fit VMEM


def _lane_scan(x, op, identity):
    """Inclusive prefix scan of ``x`` along its last (lane) axis.

    Hillis-Steele: log2(n) rotations, each combining every lane with the one
    ``step`` lanes before it (lanes without such a predecessor combine with
    ``identity``)."""
    axis = x.ndim - 1
    n = x.shape[axis]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    step = 1
    while step < n:
        prev = pltpu.roll(x, step, axis)
        x = op(x, jnp.where(lane >= step, prev, identity))
        step *= 2
    return x


def _blend_chunk(fc, px, py, carry, *, early_exit, mask_chunk=None,
                 tile_bit=None, tile_capacity=None):
    """Blend one feature chunk ``fc`` (F, BK) into the running carry.

    ``px``/``py`` are (P, 1) pixel centres; the carry holds (P, 1) columns
    (transmittance, r, g, b, alpha_ops, blend_ops) and the (1, 1) count of
    entries this tile's virtual FIFO has streamed so far."""
    t_run, r_acc, g_acc, b_acc, a_ops, b_ops, kept = carry
    row = lambda f: fc[f:f + 1, :]          # (1, BK)
    op = row(F_OPACITY)

    dx = px - row(F_MEAN_X)                  # (P, BK)
    dy = py - row(F_MEAN_Y)
    q = (row(F_CONIC_A) * dx * dx + 2.0 * row(F_CONIC_B) * dx * dy
         + row(F_CONIC_C) * dy * dy)
    a = jnp.minimum(op * jnp.exp(-0.5 * q), ALPHA_MAX)
    a = jnp.where((q > QMAX) | (a < ALPHA_MIN), 0.0, a)

    # Which entries belong to this tile's (virtual) compacted list — used both
    # to filter alphas (GS-TG RM) and to count alpha ops like the reference.
    valid_entry = op > 0.0                   # (1, BK)
    if mask_chunk is not None:
        # GS-TG RM filter: keep entries whose bitmask covers this tile. The
        # compaction stream is mask & entry-valid — the same predicate
        # core/bitmask.compact_tiles streams by.
        keep = ((mask_chunk >> tile_bit) & 1) > 0
        stream = keep & (row(F_VALID) > 0.5)
        if tile_capacity is not None:
            # Virtual FIFO clamp: position of each streamed entry in this
            # tile's compaction list; entries past the capacity are dropped,
            # exactly like the reference compaction clamp.
            s = stream.astype(jnp.int32)
            pos = kept + _lane_scan(s, jnp.add, 0) - 1
            kept = kept + jnp.sum(s, axis=1, keepdims=True)
            stream = stream & (pos < tile_capacity)
        valid_entry = valid_entry & stream
        a = jnp.where(stream, a, 0.0)

    cp = _lane_scan(1.0 - a, jnp.multiply, 1.0)          # inclusive product
    lane = jax.lax.broadcasted_iota(jnp.int32, cp.shape, 1)
    excl = jnp.where(lane == 0, 1.0, pltpu.roll(cp, 1, 1))
    t_before = t_run * excl
    if early_exit:
        live = t_before > T_EPS
        w = jnp.where(live, a * t_before, 0.0)
        live_valid = live & valid_entry
    else:
        w = a * t_before
        live_valid = jnp.broadcast_to(valid_entry, w.shape)
    colour = lambda acc, f: acc + jnp.sum(w * row(f), axis=1, keepdims=True)
    count = lambda acc, m: acc + jnp.sum(m.astype(jnp.int32), axis=1,
                                         keepdims=True)
    last = cp.shape[1] - 1
    return (
        t_run * cp[:, last:],
        colour(r_acc, F_RGB_R),
        colour(g_acc, F_RGB_G),
        colour(b_acc, F_RGB_B),
        count(a_ops, live_valid),
        count(b_ops, w > 0.0),
        kept,
    )


def _pixel_block(P: int) -> int:
    return PIXEL_BLOCK if P > PIXEL_BLOCK and P % PIXEL_BLOCK == 0 else P


def _raster_body(feat_ref, out_ref, *, ox, oy, p0, tile_px, chunk, n_chunks,
                 early_exit=True, mask_ref=None, tile_bit=None,
                 tile_capacity=None):
    """Rasterize pixels p0 .. p0 + PB of one T*T tile whose top-left pixel
    is (ox, oy) (int32 scalars; PB from the out block) and store their
    (OUT_ROWS, PB) block."""
    P = out_ref.shape[-1]
    lin = jax.lax.broadcasted_iota(jnp.int32, (P, 1), 0) + p0
    px = (lin % tile_px + ox).astype(jnp.float32) + 0.5
    py = (lin // tile_px + oy).astype(jnp.float32) + 0.5

    def body(i, carry):
        off = pl.multiple_of(i * chunk, chunk)

        def live_fn(c):
            fc = feat_ref[0, :, pl.ds(off, chunk)]
            mc = (mask_ref[0, :, pl.ds(off, chunk)]
                  if mask_ref is not None else None)
            return _blend_chunk(
                fc, px, py, c,
                early_exit=early_exit,
                mask_chunk=mc,
                tile_bit=tile_bit,
                tile_capacity=tile_capacity,
            )

        # A chunk past the end of the entry list (every entry invalid, so
        # opacity 0) contributes nothing: skip it. With early exit, also
        # skip it when every pixel is dead (block-granular early exit).
        live = jnp.max(feat_ref[0, F_VALID:F_VALID + 1, pl.ds(off, chunk)]) > 0.5
        if early_exit:
            live = live & (jnp.max(carry[0]) > T_EPS)
        return jax.lax.cond(live, live_fn, lambda c: c, carry)

    col_f = jnp.zeros((P, 1), jnp.float32)
    col_i = jnp.zeros((P, 1), jnp.int32)
    carry = (col_f + 1.0, col_f, col_f, col_f, col_i, col_i,
             jnp.zeros((1, 1), jnp.int32))
    t_run, r, g, b, a_ops, b_ops, _ = jax.lax.fori_loop(
        0, n_chunks, body, carry)
    # Columns -> rows: place the six per-pixel columns in lanes 0..5 of a
    # (P, 128) block and transpose it.
    lane = jax.lax.broadcasted_iota(jnp.int32, (P, LANE), 1)
    packed = jnp.zeros((P, LANE), jnp.float32)
    cols = (r, g, b, t_run, a_ops.astype(jnp.float32),
            b_ops.astype(jnp.float32))
    for j, col in enumerate(cols):
        packed = jnp.where(lane == j, col, packed)
    out_ref[...] = packed.T[:OUT_ROWS].reshape(out_ref.shape)


def _split(out, with_stats):
    """Kernel block (..., OUT_ROWS, P) -> image rows (..., 4, P) [+ counts
    (..., 2) int32]."""
    img = out[..., :4, :]
    if not with_stats:
        return img
    counts = jnp.sum(out[..., 4:6, :].astype(jnp.int32), axis=-1)
    return img, counts


def _check_chunk(K, chunk):
    if chunk % LANE or K % chunk:
        raise ValueError(
            f"chunk={chunk} must be a multiple of {LANE} lanes dividing "
            f"K={K}")


def raster_tile_kernel(
    feat: jnp.ndarray,          # (num_tiles, F, K)
    n_tiles_x: int,
    tile_px: int,
    chunk: int = LANE,
    interpret: bool | None = None,
    early_exit: bool = True,
    with_stats: bool = False,
):
    """Returns (num_tiles, 4, tile_px^2): rgb + final transmittance. Tile t
    has its top-left pixel at ((t % n_tiles_x) * T, (t // n_tiles_x) * T).

    With ``with_stats=True`` also returns (num_tiles, 2) int32
    (alpha_ops, blend_ops) per tile.
    """
    num_tiles, F, K = feat.shape
    assert F == NUM_FEATURES
    _check_chunk(K, chunk)
    P = tile_px * tile_px
    PB = _pixel_block(P)

    def kernel(feat_ref, out_ref):
        t = pl.program_id(0)
        _raster_body(
            feat_ref, out_ref,
            ox=(t % n_tiles_x) * tile_px,
            oy=(t // n_tiles_x) * tile_px,
            p0=pl.program_id(1) * PB,
            tile_px=tile_px,
            chunk=chunk,
            n_chunks=K // chunk,
            early_exit=early_exit,
        )

    interpret = resolve_interpret(interpret)
    call = pl.pallas_call(
        kernel,
        grid=(num_tiles, P // PB),
        in_specs=[pl.BlockSpec((1, F, K), lambda t, p: (t, 0, 0))],
        out_specs=pl.BlockSpec((1, OUT_ROWS, PB), lambda t, p: (t, 0, p)),
        out_shape=jax.ShapeDtypeStruct((num_tiles, OUT_ROWS, P), jnp.float32),
        interpret=interpret,
        name="gstg_raster_tile",
    )
    out = on_every_device(call, interpret)(feat)
    return _split(out, with_stats)


def raster_group_fused_kernel(
    feat: jnp.ndarray,          # (num_groups, F, K) group-sorted entries
    masks: jnp.ndarray,         # (num_groups, K) uint32 tile bitmasks
    n_groups_x: int,
    tile_px: int,
    gf: int,                    # tiles per group side
    chunk: int = LANE,
    interpret: bool | None = None,
    early_exit: bool = True,
    tile_capacity: int | None = None,
    with_stats: bool = False,
):
    """Fused GS-TG RM. Returns (num_groups, gf*gf, 4, tile_px^2). Member
    tile s of group g has its top-left pixel at the group origin
    ((g % n_groups_x) * G, (g // n_groups_x) * G), G = gf * T, plus
    ((s % gf) * T, (s // gf) * T).

    With ``with_stats=True`` also returns (num_groups, gf*gf, 2) int32
    (alpha_ops, blend_ops) per member tile.
    """
    num_groups, F, K = feat.shape
    assert F == NUM_FEATURES and masks.shape == (num_groups, K)
    _check_chunk(K, chunk)
    P = tile_px * tile_px
    PB = _pixel_block(P)
    tpg = gf * gf
    group_px = gf * tile_px
    masks = jax.lax.bitcast_convert_type(masks.astype(jnp.uint32), jnp.int32)

    def kernel(feat_ref, mask_ref, out_ref):
        g = pl.program_id(0)
        slot = pl.program_id(1)
        _raster_body(
            feat_ref, out_ref,
            ox=(g % n_groups_x) * group_px + (slot % gf) * tile_px,
            oy=(g // n_groups_x) * group_px + (slot // gf) * tile_px,
            p0=pl.program_id(2) * PB,
            tile_px=tile_px,
            chunk=chunk,
            n_chunks=K // chunk,
            early_exit=early_exit,
            mask_ref=mask_ref,
            tile_bit=slot,
            tile_capacity=tile_capacity,
        )

    interpret = resolve_interpret(interpret)
    call = pl.pallas_call(
        kernel,
        grid=(num_groups, tpg, P // PB),
        in_specs=[
            pl.BlockSpec((1, F, K), lambda g, s, p: (g, 0, 0)),
            pl.BlockSpec((1, 1, K), lambda g, s, p: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, OUT_ROWS, PB), lambda g, s, p: (g, s, 0, p)),
        out_shape=jax.ShapeDtypeStruct(
            (num_groups, tpg, OUT_ROWS, P), jnp.float32),
        interpret=interpret,
        name="gstg_raster_group",
    )
    out = on_every_device(call, interpret)(
        feat, masks.reshape(num_groups, 1, K))
    return _split(out, with_stats)
