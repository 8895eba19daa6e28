import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.grouping import (
    GridSpec,
    PairSet,
    bin_pairs,
    identify,
    merge_bin_tables,
    sort_op_count,
)
from repro.core.projection import project
from repro.core import make_camera, random_scene
from repro.utils import wide_count_dtype, wide_count_sum

# Jitted stage wrappers for the full-scene tests (GridSpec is hashable):
# one compile per (shape, statics) instead of per-op eager tracing. The
# synthetic merge tests below stay eager — their many tiny shapes would
# each recompile.
identify_j = jax.jit(identify, static_argnames=("grid", "level", "method"))
bin_pairs_j = jax.jit(bin_pairs, static_argnames=("num_bins", "capacity"))


def _setup(seed=0, n=400, w=192, h=128):
    scene = random_scene(jax.random.key(seed), n, extent=3.0)
    cam = make_camera((0, 1.2, 5.0), (0, 0, 0), w, h)
    proj = project(scene, cam)
    grid = GridSpec(w, h, 16, 64, span=4)
    return proj, grid


def test_pairs_group_leq_tile():
    """The paper's core quantity: group-level sorting keys are a strict
    subset of tile-level ones (Table I / Fig 5)."""
    proj, grid = _setup()
    pt = identify_j(proj, grid, "tile", "ellipse")
    pg = identify_j(proj, grid, "group", "ellipse")
    assert int(pg.n_pairs) <= int(pt.n_pairs)
    assert int(pg.n_pairs) > 0
    # every tile hit implies its group hit => tile pairs >= group pairs and
    # per gaussian, #tiles >= #groups; globally strict for clustered scenes
    assert int(pt.n_pairs) > int(pg.n_pairs)


def test_no_overflow_small_scene():
    proj, grid = _setup()
    pg = identify_j(proj, grid, "group", "ellipse")
    assert int(pg.n_span_overflow) == 0
    table = bin_pairs_j(pg, grid.num_groups, 512)
    assert int(table.overflow) == 0


def test_bin_table_depth_sorted():
    proj, grid = _setup(1)
    pg = identify_j(proj, grid, "group", "ellipse")
    table = bin_pairs_j(pg, grid.num_groups, 512)
    depth = np.asarray(proj.depth)
    gidx = np.asarray(table.gauss_idx)
    valid = np.asarray(table.entry_valid)
    for g in range(table.num_bins):
        d = depth[gidx[g][valid[g]]]
        assert (np.diff(d) >= -1e-6).all(), f"group {g} not depth sorted"


def test_bin_lengths_match_pairs():
    proj, grid = _setup(2)
    pg = identify_j(proj, grid, "group", "ellipse")
    table = bin_pairs_j(pg, grid.num_groups, 512)
    assert int(jnp.sum(table.lengths)) == int(pg.n_pairs)


def test_sort_op_count_model():
    lengths = jnp.array([0, 1, 2, 8, 100])
    ops = int(sort_op_count(lengths))
    expected = 0 + 1 * 1 + 2 * 1 + 8 * 3 + 100 * 7
    assert ops == expected


def test_grid_spec_validation():
    import pytest

    # A native resolution that is not tile-divisible gets partial edge
    # tiles and groups (cdiv); the stages crop them.
    g = GridSpec(100, 100, 16, 64)
    assert (g.n_tiles_x, g.n_groups_x, g.num_tiles) == (7, 2, 49)
    with pytest.raises(ValueError):
        GridSpec(128, 128, 16, 40)  # group not multiple of tile
    with pytest.raises(ValueError):
        GridSpec(0, 128, 16, 64)    # empty image


# ---------------------------------------------------------------------------
# bin_pairs against a NumPy lexsort oracle
# ---------------------------------------------------------------------------


def _bin_oracle(bin_id, depth, gauss_idx, num_bins, capacity):
    """BinTable fields from np.lexsort over (bin id, depth, Gaussian index):
    the lexicographic order the binning sort must reproduce exactly."""
    order = np.lexsort((gauss_idx, depth, bin_id))
    sorted_bins, sorted_gauss = bin_id[order], gauss_idx[order]
    lengths = np.bincount(sorted_bins, minlength=num_bins + 1)[:num_bins]
    starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    k = np.arange(capacity)
    entry_valid = k[None, :] < np.minimum(lengths, capacity)[:, None]
    idx = np.minimum(starts[:, None] + k[None, :], bin_id.size - 1)
    return dict(
        gauss_idx=np.where(entry_valid, sorted_gauss[idx], 0),
        entry_valid=entry_valid,
        lengths=lengths.astype(np.int32),
        overflow=np.maximum(lengths - capacity, 0).sum(),
    )


def _oracle_pairs(rng, n_pairs, num_bins, depth_pool, invalid_share):
    """Pair arrays with depths drawn from ``depth_pool`` (heavy ties) and a
    shuffled Gaussian index, so a tie broken by anything but the Gaussian
    index (pair position, say) shows in gauss_idx. Invalid slots carry bin
    num_bins and +inf."""
    hit = rng.random(n_pairs) >= invalid_share
    bin_id = np.where(hit, rng.integers(0, num_bins, n_pairs), num_bins)
    depth = np.where(hit, rng.choice(np.asarray(depth_pool, np.float32),
                                     n_pairs), np.inf)
    return (bin_id.astype(np.int32), depth.astype(np.float32),
            rng.permutation(n_pairs).astype(np.int32), hit)


@pytest.mark.parametrize(
    "n_pairs,num_bins,capacity,depth_pool,invalid_share,lanes",
    [
        (2000, 7, 512, [1.0, 2.0, 3.0], 0.5, 1),      # heavy depth ties
        (1500, 5, 512, [-0.0, 0.0, 1.0], 0.3, 1),     # -0.0 ties with 0.0
        (800, 6, 512, [1.0], 1.0, 1),                 # every slot invalid
        (3000, 4, 16, [0.5, 2.0], 0.2, 1),            # bins past capacity
        (1200, 9, 32, [-0.0, 0.0, 4.0], 0.6, 2),      # vmapped batch of 2
    ],
    ids=["ties", "signed-zero", "all-invalid", "overflow", "vmapped"],
)
def test_bin_pairs_matches_lexsort_oracle(n_pairs, num_bins, capacity,
                                          depth_pool, invalid_share, lanes):
    """bin_pairs == a NumPy lexsort over (bin id, depth, Gaussian index),
    field for field: depth ties and -0.0/0.0 break by Gaussian index, +inf
    invalid slots never enter a bin, and lengths and overflow count a bin's
    entries past its capacity."""
    rng = np.random.default_rng(n_pairs + num_bins)
    cases = [_oracle_pairs(rng, n_pairs, num_bins, depth_pool, invalid_share)
             for _ in range(lanes)]
    stack = lambda i: jnp.asarray(np.stack([c[i] for c in cases]))
    zero = jnp.zeros((lanes,), jnp.int32)
    pairs = PairSet(bin_id=stack(0), depth=stack(1), gauss_idx=stack(2),
                    valid=stack(3), n_candidate_tests=zero, n_pairs=zero,
                    n_span_overflow=zero)
    tables = jax.jit(jax.vmap(
        lambda p: bin_pairs(p, num_bins, capacity)))(pairs)
    for lane, (bin_id, depth, gauss_idx, _) in enumerate(cases):
        want = _bin_oracle(bin_id, depth, gauss_idx, num_bins, capacity)
        for field, expected in want.items():
            got = np.asarray(getattr(tables, field))[lane]
            np.testing.assert_array_equal(got, expected, err_msg=field)


# ---------------------------------------------------------------------------
# Cross-shard merge stage (DESIGN.md §10)
# ---------------------------------------------------------------------------


def _synthetic_pairs(rng, n_gauss, span, num_bins):
    """Gaussian-major synthetic pair list with FORCED depth ties (depths drawn
    from 4 values) so the merge's insertion-order tie-break is exercised."""
    S = span * span
    bin_id = rng.integers(0, num_bins + 1, size=(n_gauss, S)).astype(np.int32)
    hit = bin_id < num_bins
    depth = rng.choice([1.0, 2.0, 3.0, 5.0], size=(n_gauss, S)).astype(np.float32)
    depth = np.where(hit, depth, np.inf)
    bin_id = np.where(hit, bin_id, num_bins).astype(np.int32)
    gauss = np.broadcast_to(
        np.arange(n_gauss, dtype=np.int32)[:, None], (n_gauss, S)
    )
    flat = lambda a: jnp.asarray(a.reshape(-1))
    zero = jnp.zeros((), jnp.int32)
    return PairSet(
        bin_id=flat(bin_id), gauss_idx=flat(gauss), depth=flat(depth),
        valid=flat(hit), n_candidate_tests=zero, n_pairs=zero,
        n_span_overflow=zero,
    )


def _shard_pairs(pairs, n_gauss, shards, span):
    """Slice the gaussian-major pair list into contiguous gaussian shards
    (what the sharded frontend's per-shard identify produces)."""
    S = span * span
    size = -(-n_gauss // shards)
    out = []
    for d in range(shards):
        lo, hi = d * size, min((d + 1) * size, n_gauss)
        sl = slice(lo * S, hi * S)
        out.append(
            dataclasses.replace(
                pairs,
                bin_id=pairs.bin_id[sl],
                gauss_idx=pairs.gauss_idx[sl] - lo,
                depth=pairs.depth[sl],
                valid=pairs.valid[sl],
            )
        )
    return out, size


def test_merge_bin_tables_bitwise_vs_global():
    """D per-shard tables + stable merge == binning the global pair set,
    field for field — including under depth ties (insertion-order tie-break)
    and per-bin capacity overflow (merged top-K == global top-K)."""
    rng = np.random.default_rng(0)
    n_gauss, span, num_bins = 60, 3, 7
    pairs = _synthetic_pairs(rng, n_gauss, span, num_bins)
    depth_by_gauss = rng.uniform(1.0, 9.0, size=n_gauss).astype(np.float32)
    # Per-gaussian depths (as projection produces): rebuild pair depths so the
    # merge's depth gather (a per-gaussian lookup) matches the pair keys.
    depth_flat = jnp.where(
        pairs.valid, jnp.asarray(depth_by_gauss)[pairs.gauss_idx], jnp.inf
    )
    # Quantize to force cross-gaussian ties.
    depth_flat = jnp.where(
        jnp.isfinite(depth_flat), jnp.round(depth_flat), jnp.inf
    )
    pairs = dataclasses.replace(pairs, depth=depth_flat)
    gauss_depth = jnp.round(jnp.asarray(depth_by_gauss))

    for capacity in (64, 6):   # no-overflow and overflow regimes
        for shards in (1, 2, 3):
            ref = bin_pairs(pairs, num_bins, capacity)
            shard_pairs, size = _shard_pairs(pairs, n_gauss, shards, span)
            tables = [bin_pairs(p, num_bins, capacity) for p in shard_pairs]
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *tables)
            offs = (jnp.arange(shards, dtype=jnp.int32) * size)[:, None, None]
            gidx = jnp.where(
                stacked.entry_valid, stacked.gauss_idx + offs, 0
            )
            pad_depth = jnp.concatenate(
                [gauss_depth,
                 jnp.full((shards * size - n_gauss,), jnp.inf, jnp.float32)]
            )
            depth = jnp.where(
                stacked.entry_valid, pad_depth[gidx], jnp.inf
            )
            merged = merge_bin_tables(
                dataclasses.replace(stacked, gauss_idx=gidx), depth
            )
            for field in ("gauss_idx", "entry_valid", "lengths", "overflow"):
                a = np.asarray(getattr(ref, field))
                b = np.asarray(getattr(merged, field))
                assert (a == b).all(), (capacity, shards, field)


# ---------------------------------------------------------------------------
# Wide op counters (int32-overflow regression, multi-million-Gaussian scenes)
# ---------------------------------------------------------------------------


def test_sort_op_count_no_int32_wraparound():
    """Synthetic lengths whose true op count exceeds 2**31: the old int32
    accumulator wrapped negative; the wide counter must stay positive and
    within fp rounding of the exact total."""
    lengths = jnp.full((64,), 10_000_000, jnp.int32)
    ops = float(sort_op_count(lengths))
    exact = 64 * 10_000_000 * 24          # ceil(log2 1e7) == 24
    assert ops > 2**31
    assert abs(ops - exact) / exact < 1e-5


def test_wide_count_sum_no_int32_wraparound():
    """The fifo_ops-style accumulation (sum of lengths x tiles_per_group)
    stays positive past 2**31."""
    lengths = jnp.full((2048,), 2**20, jnp.int32)
    total = float(wide_count_sum(lengths)) * 16
    assert total == float(2**31) * 16 > 2**31


def test_identify_counter_dtype_is_wide():
    proj, grid = _setup()
    pg = identify(proj, grid, "tile", "ellipse")
    assert pg.n_candidate_tests.dtype == wide_count_dtype()
    # small-regime exactness: the wide counter agrees with an int count
    exact = int(np.asarray(pg.valid).sum())
    assert int(pg.n_pairs) == exact
    assert int(pg.n_candidate_tests) >= exact


# ---------------------------------------------------------------------------
# merge_bin_tables property test (hypothesis): the merge invariant holds for
# ANY gaussian-major pair population — forced depth ties, per-bin capacity
# overflow, all-padding shards, D in {1..4} — not just the scenes the render
# parity suite happens to produce.
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # property tests degrade gracefully without hypothesis
    HAVE_HYPOTHESIS = False


def _merge_case(n_gauss, shards, capacity, num_bins, depth_levels,
                dead_tail, seed):
    """One merge-vs-global comparison on a synthetic pair population.

    Mirrors the canonical sharded layout (sharding/scene.py): the gaussian
    axis is padded to a multiple of D and every padding gaussian's pairs
    are invalid (culled rows still occupy pair slots) — so EVERY shard has
    the same size, and a shard can be entirely padding.
    """
    rng = np.random.default_rng(seed)
    span = 2
    size = -(-n_gauss // shards)
    n_pad = size * shards
    pairs = _synthetic_pairs(rng, n_pad, span, num_bins)
    # Per-gaussian depths from a tiny pool => heavy cross-gaussian ties, so
    # the stable tie-break (insertion order == global gaussian order) is the
    # only thing that can make the comparison pass.
    gauss_depth = np.full((n_pad,), np.inf, np.float32)
    gauss_depth[:n_gauss] = rng.choice(
        np.arange(1.0, depth_levels + 1.0, dtype=np.float32), size=n_gauss
    )
    gauss_depth = jnp.asarray(gauss_depth)
    # Cull padding rows; dead_tail additionally kills the whole LAST shard
    # (an all-padding shard must contribute nothing and not disturb the
    # tie-break).
    cut = (shards - 1) * size if dead_tail and shards > 1 else n_gauss
    alive = np.asarray(pairs.gauss_idx) < min(cut, n_gauss)
    valid = pairs.valid & jnp.asarray(alive)
    depth_flat = jnp.where(valid, gauss_depth[pairs.gauss_idx], jnp.inf)
    pairs = dataclasses.replace(
        pairs,
        depth=depth_flat,
        valid=valid,
        bin_id=jnp.where(valid, pairs.bin_id, num_bins).astype(jnp.int32),
    )

    ref = bin_pairs(pairs, num_bins, capacity)
    shard_pairs, size = _shard_pairs(pairs, n_pad, shards, span)
    tables = [bin_pairs(p, num_bins, capacity) for p in shard_pairs]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *tables)
    offs = (jnp.arange(shards, dtype=jnp.int32) * size)[:, None, None]
    gidx = jnp.where(stacked.entry_valid, stacked.gauss_idx + offs, 0)
    depth = jnp.where(stacked.entry_valid, gauss_depth[gidx], jnp.inf)
    merged = merge_bin_tables(
        dataclasses.replace(stacked, gauss_idx=gidx), depth
    )
    for field in ("gauss_idx", "entry_valid", "lengths", "overflow"):
        a = np.asarray(getattr(ref, field))
        b = np.asarray(getattr(merged, field))
        assert (a == b).all(), (
            f"{field} diverges (n={n_gauss}, D={shards}, K={capacity}, "
            f"bins={num_bins}, levels={depth_levels}, dead_tail={dead_tail})"
        )


if HAVE_HYPOTHESIS:

    @settings(max_examples=40, deadline=None)
    @given(
        n_gauss=st.integers(1, 24),
        shards=st.integers(1, 4),
        capacity=st.sampled_from([3, 8, 64]),   # overflow and no-overflow
        num_bins=st.integers(1, 6),
        depth_levels=st.integers(1, 3),          # 1 => EVERY depth ties
        dead_tail=st.booleans(),                 # all-padding last shard
        seed=st.integers(0, 2**20),
    )
    def test_merge_bin_tables_property(
        n_gauss, shards, capacity, num_bins, depth_levels, dead_tail, seed
    ):
        """merge_bin_tables == bin_pairs on the global pair set, field for
        field, for arbitrary pair populations — the standalone contract the
        render parity suite only exercises end-to-end."""
        _merge_case(
            n_gauss, shards, capacity, num_bins, depth_levels, dead_tail,
            seed,
        )

else:

    import pytest as _pytest

    @_pytest.mark.parametrize("shards", [1, 2, 3, 4])
    @_pytest.mark.parametrize(
        "n_gauss,capacity,depth_levels,dead_tail",
        [
            (1, 3, 1, False),     # single gaussian, everything ties
            (5, 3, 1, True),      # overflow + all-padding last shard
            (17, 8, 2, False),    # ragged shard sizes + ties
            (24, 64, 3, True),    # no overflow, dead tail
        ],
    )
    def test_merge_bin_tables_property(
        n_gauss, shards, capacity, depth_levels, dead_tail
    ):
        """Deterministic fallback sweep of the same merge property when
        hypothesis is unavailable (the property test proper randomizes the
        pair population; this pins the named edge cases)."""
        for seed in (0, 1):
            _merge_case(
                n_gauss, shards, capacity, 5, depth_levels, dead_tail, seed
            )
