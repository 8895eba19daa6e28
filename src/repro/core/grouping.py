"""Group/tile identification and static-shape binning (paper §IV-B).

TPU adaptation: GPU 3D-GS builds variable-length per-tile lists with atomics +
radix sort over duplicated (tileID||depth) keys. XLA needs static shapes, so we
enumerate a bounded grid of candidate bins per Gaussian (span x span window over
the bin grid, pre-filtered by the circumscribed-radius bbox exactly like
GSCore/FlashGS pre-filter with the AABB before running finer tests), flatten
to a global pair list, and bin with one stable sort keyed on (bin id, depth)
that carries the Gaussian index along, so the sort itself returns the binned
indices. Per-bin segments are then extracted with searchsorted into a
fixed-capacity table.

The SAME machinery runs at group granularity (GS-TG) and tile granularity
(per-tile baseline): the redundant-sorting reduction the paper measures is the
ratio of valid pair counts between the two.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.boundary import boundary_test
from repro.core.camera import Camera
from repro.core.projection import Projected
from repro.utils import cdiv, wide_count_dtype, wide_count_sum


@dataclasses.dataclass(frozen=True)
class GridSpec:
    """Static geometry of the tile/group decomposition.

    The image need not be a whole number of tiles: the grid covers it with
    ``cdiv`` tiles/groups per axis and the last row/column of tiles runs past
    the image edge (native resolutions such as 1959x1090). Every stage crops
    those pixels; boundary tests use the full tile rect, which only widens
    the conservative candidate set."""

    width: int
    height: int
    tile: int           # small tile side in pixels (e.g. 16)
    group: int          # group side in pixels (e.g. 64); must be k*tile
    span: int = 4       # candidate window (in bins) per Gaussian at group level

    def __post_init__(self):
        if self.group % self.tile != 0:
            raise ValueError("group size must be a multiple of tile size")
        if self.width <= 0 or self.height <= 0:
            raise ValueError("image dims must be positive")

    @property
    def gf(self) -> int:
        """Group factor: tiles per group side."""
        return self.group // self.tile

    @property
    def tiles_per_group(self) -> int:
        return self.gf * self.gf

    @property
    def n_tiles_x(self) -> int:
        return cdiv(self.width, self.tile)

    @property
    def n_tiles_y(self) -> int:
        return cdiv(self.height, self.tile)

    @property
    def n_groups_x(self) -> int:
        return cdiv(self.width, self.group)

    @property
    def n_groups_y(self) -> int:
        return cdiv(self.height, self.group)

    @property
    def num_tiles(self) -> int:
        return self.n_tiles_x * self.n_tiles_y

    @property
    def num_groups(self) -> int:
        return self.n_groups_x * self.n_groups_y

    def bins(self, level: str) -> Tuple[int, int, int]:
        """(n_bins_x, n_bins_y, bin_px) for 'group' or 'tile' level."""
        if level == "group":
            return self.n_groups_x, self.n_groups_y, self.group
        if level == "tile":
            return self.n_tiles_x, self.n_tiles_y, self.tile
        raise ValueError(level)

    def span_for(self, level: str) -> int:
        if level == "group":
            return self.span
        return self.span * self.gf


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PairSet:
    """Flattened (gaussian, bin) candidate pairs. All (P,) arrays."""

    bin_id: jnp.ndarray     # int32, == num_bins for invalid pairs (sorts last)
    gauss_idx: jnp.ndarray  # int32
    depth: jnp.ndarray      # float32, +inf for invalid
    valid: jnp.ndarray      # bool
    # -- counters (scalars) --
    n_candidate_tests: jnp.ndarray  # boundary tests (wide_count_dtype: can
                                    #   exceed int32 at tile level on big scenes)
    n_pairs: jnp.ndarray            # valid (gaussian, bin) pairs == sort keys
    n_span_overflow: jnp.ndarray    # bins lost to the static span window


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BinTable:
    """Fixed-capacity per-bin entry table (depth-sorted within each bin)."""

    gauss_idx: jnp.ndarray  # (B, K) int32 — index into the Projected arrays
    entry_valid: jnp.ndarray  # (B, K) bool
    lengths: jnp.ndarray    # (B,) int32 true segment length (pre-clamp)
    overflow: jnp.ndarray   # () int32 total entries dropped by capacity K

    @property
    def capacity(self) -> int:
        return self.gauss_idx.shape[1]

    @property
    def num_bins(self) -> int:
        return self.gauss_idx.shape[0]


def identify(
    proj: Projected,
    grid: GridSpec,
    level: str,
    method: str,
) -> PairSet:
    """Enumerate candidate (gaussian, bin) pairs and run the boundary test.

    This is the paper's 'tile identification' (level='tile') or 'group
    identification' (level='group') step.
    """
    n_bins_x, n_bins_y, bin_px = grid.bins(level)
    span = grid.span_for(level)
    num_bins = n_bins_x * n_bins_y

    mx, my = proj.mean2d[:, 0], proj.mean2d[:, 1]
    r = proj.radius
    # Circumscribed-radius pre-filter bbox (in bin coords), clipped to grid.
    bx0 = jnp.clip(jnp.floor((mx - r) / bin_px).astype(jnp.int32), 0, n_bins_x - 1)
    bx1 = jnp.clip(jnp.floor((mx + r) / bin_px).astype(jnp.int32), 0, n_bins_x - 1)
    by0 = jnp.clip(jnp.floor((my - r) / bin_px).astype(jnp.int32), 0, n_bins_y - 1)
    by1 = jnp.clip(jnp.floor((my + r) / bin_px).astype(jnp.int32), 0, n_bins_y - 1)

    # Candidate window laid out (span_x, span_y, N): the Gaussian axis is
    # the minor (lane) axis, so a TPU tiles these arrays densely; an
    # (N, span, span) layout would pad span to 128 lanes. Fields of shape
    # (N,) and (N, k)[..., i] broadcast against it directly.
    off = jnp.arange(span, dtype=jnp.int32)[:, None]
    cand_x = bx0[None, :] + off            # (span, N)
    cand_y = by0[None, :] + off
    in_bbox_x = cand_x <= bx1[None, :]
    in_bbox_y = cand_y <= by1[None, :]

    # (span_x, span_y, N)
    cx = cand_x[:, None, :]
    cy = cand_y[None, :, :]
    in_bbox = in_bbox_x[:, None, :] & in_bbox_y[None, :, :]
    in_bbox = in_bbox & proj.valid[None, None, :]

    rect = (
        (cx * bin_px).astype(jnp.float32),
        (cy * bin_px).astype(jnp.float32),
        ((cx + 1) * bin_px).astype(jnp.float32),
        ((cy + 1) * bin_px).astype(jnp.float32),
    )
    hit = in_bbox & boundary_test(method, proj, rect)

    bin_id = jnp.where(hit, cy * n_bins_x + cx, num_bins).astype(jnp.int32)
    depth = jnp.where(hit, proj.depth[None, None, :], jnp.inf)
    N, S = proj.mean2d.shape[0], span
    gauss_idx = jnp.broadcast_to(
        jnp.arange(N, dtype=jnp.int32)[None, None, :], (S, S, N)
    )
    # Pair order is Gaussian-major, then window x, then window y: the
    # insertion order the stable binning sort breaks depth ties by. The
    # barrier keeps XLA from hoisting the transpose into the window
    # computation, which would bring the padded layout back.
    bin_id, gauss_idx, depth, hit_b = jax.lax.optimization_barrier(
        (bin_id, gauss_idx, depth, hit))
    flat = lambda a: jnp.transpose(a, (2, 0, 1)).reshape(N * S * S)

    # Span-window overflow: bbox bins beyond the static window.
    full_w = jnp.where(proj.valid, bx1 - bx0 + 1, 0)
    full_h = jnp.where(proj.valid, by1 - by0 + 1, 0)
    lost = full_w * full_h - jnp.minimum(full_w, span) * jnp.minimum(full_h, span)

    return PairSet(
        bin_id=flat(bin_id),
        gauss_idx=flat(gauss_idx),
        depth=flat(depth).astype(jnp.float32),
        valid=flat(hit_b),
        n_candidate_tests=wide_count_sum(in_bbox),
        n_pairs=jnp.sum(hit.astype(jnp.int32)),
        n_span_overflow=jnp.sum(lost),
    )


def bin_pairs(pairs: PairSet, num_bins: int, capacity: int) -> BinTable:
    """Stable (bin, depth) sort + fixed-capacity segment extraction.

    One stable sort keyed on (bin_id, depth) carries gauss_idx as its
    payload, so it returns the binned Gaussian indices directly: no
    permutation is materialised or gathered through. Stability breaks
    (bin, depth) ties by pair-list position, which is the 3D-GS tie-break
    (insertion order == gaussian index); that is what makes the GS-TG
    per-tile subsequence *bitwise* identical to the per-tile baseline
    ordering.
    """
    # Ordering is non-differentiable by design (3D-GS treats it as constant);
    # stop_gradient also keeps sort JVP machinery out of the backward graph.
    sorted_bins, _, sorted_gauss = jax.lax.sort(
        (pairs.bin_id, jax.lax.stop_gradient(pairs.depth), pairs.gauss_idx),
        num_keys=2, is_stable=True)

    starts = jnp.searchsorted(sorted_bins, jnp.arange(num_bins, dtype=jnp.int32))
    ends = jnp.searchsorted(
        sorted_bins, jnp.arange(1, num_bins + 1, dtype=jnp.int32)
    )
    lengths = (ends - starts).astype(jnp.int32)

    k = jnp.arange(capacity, dtype=jnp.int32)
    idx = starts[:, None] + k[None, :]
    entry_valid = k[None, :] < jnp.minimum(lengths, capacity)[:, None]
    idx = jnp.clip(idx, 0, sorted_gauss.shape[0] - 1)
    gauss_idx = sorted_gauss[idx]
    gauss_idx = jnp.where(entry_valid, gauss_idx, 0)

    overflow = jnp.sum(jnp.maximum(lengths - capacity, 0))
    return BinTable(
        gauss_idx=gauss_idx,
        entry_valid=entry_valid,
        lengths=lengths,
        overflow=overflow,
    )


def sort_op_count(lengths: jnp.ndarray) -> jnp.ndarray:
    """Comparator-op model: sum_b L_b * ceil(log2 max(L_b, 2)).

    The n·log n model matches both the GPU radix/merge path and the paper's
    GSM comparator tree up to a constant, so *ratios* between per-tile and
    per-group sorting are preserved. Accumulated in ``wide_count_dtype`` —
    an int32 total wraps negative around ~80M sort keys (multi-million-
    Gaussian scenes at tile granularity).
    """
    L = lengths.astype(jnp.float32)
    logL = jnp.ceil(jnp.log2(jnp.maximum(L, 2.0)))
    return wide_count_sum(L * logL)


def merge_bin_tables(tables: BinTable, depth: jnp.ndarray) -> BinTable:
    """Merge D per-shard bin tables into the global depth-ordered table.

    ``tables`` is a shard-stacked BinTable (every field with a leading shard
    axis: gauss_idx/entry_valid ``(D, B, K)``, lengths ``(D, B)``) whose
    ``gauss_idx`` entries are already GLOBAL gaussian indices; ``depth`` is
    the per-entry sort key ``(D, B, K)``.

    Bitwise-identity invariant (DESIGN.md §10): provided the shards partition
    the gaussian axis contiguously in global order (sharding/scene.py layout)
    and the per-shard capacity is >= the merged capacity K, the result equals
    ``bin_pairs`` on the unsharded pair set, field for field:

      * each shard's per-bin segment is a subsequence of the global segment
        (stable per-shard sort preserves relative order, and within a shard
        the flattened pair order equals the global one);
      * concatenating shard-major and re-sorting by depth with a STABLE sort
        breaks depth ties by concatenation position = (shard, within-shard
        insertion) = global insertion order — exactly the 3D-GS tie-break the
        losslessness proof needs (§7);
      * even under capacity overflow the first K merged entries equal the
        global top-K: any entry in the global top-K has < K predecessors in
        its own shard, so per-shard clamping at K never drops it.

    Invalid slots carry key +inf and sort last; merged lengths are the exact
    (pre-clamp) per-bin totals, so overflow accounting matches the replicated
    path integer for integer.

    Downstream, the merged ``gauss_idx`` stays GLOBAL: feature-sharded
    consumers (DESIGN.md §12) decompose it back into ``(idx // Ns, idx %
    Ns)`` at each gather site (``core/projection.py::proj_take``) — the
    contiguous layout makes the decomposition a pure arithmetic view, which
    is why the merge needs no layout changes for feature sharding.

    Property-tested standalone in tests/test_grouping.py (hypothesis: depth
    ties, per-bin overflow, all-padding shards, D ∈ {1..4}) on top of the
    end-to-end render parity suite (tests/test_sharding.py).
    """
    D, B, K = tables.gauss_idx.shape
    key = jnp.where(tables.entry_valid, depth, jnp.inf)
    cat = lambda a: jnp.swapaxes(a, 0, 1).reshape(B, D * K)  # shard-major
    order = jnp.argsort(cat(key), axis=1, stable=True)[:, :K]
    merged_idx = jnp.take_along_axis(cat(tables.gauss_idx), order, axis=1)

    lengths = jnp.sum(tables.lengths, axis=0)  # (B,) exact pre-clamp totals
    k = jnp.arange(K, dtype=jnp.int32)
    entry_valid = k[None, :] < jnp.minimum(lengths, K)[:, None]
    overflow = jnp.sum(jnp.maximum(lengths - K, 0))
    return BinTable(
        gauss_idx=jnp.where(entry_valid, merged_idx, 0),
        entry_valid=entry_valid,
        lengths=lengths,
        overflow=overflow,
    )


def tile_rect_in_group(grid: GridSpec, group_ids: jnp.ndarray, tile_slot: jnp.ndarray):
    """Pixel rect of member tile ``tile_slot`` (0..gf^2-1) of each group."""
    gf = grid.gf
    gx = (group_ids % grid.n_groups_x).astype(jnp.float32)
    gy = (group_ids // grid.n_groups_x).astype(jnp.float32)
    tx = (tile_slot % gf).astype(jnp.float32)
    ty = (tile_slot // gf).astype(jnp.float32)
    x0 = gx * grid.group + tx * grid.tile
    y0 = gy * grid.group + ty * grid.tile
    return (x0, y0, x0 + grid.tile, y0 + grid.tile)


def group_tile_to_global_tile(grid: GridSpec, group_id, tile_slot):
    """Map (group, member-slot) -> global tile id in the tile grid."""
    gf = grid.gf
    gx = group_id % grid.n_groups_x
    gy = group_id // grid.n_groups_x
    tx = gx * gf + tile_slot % gf
    ty = gy * gf + tile_slot // gf
    return ty * grid.n_tiles_x + tx
