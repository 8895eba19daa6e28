"""End-to-end rendering engine (paper Fig 1 vs Fig 9).

``render()`` is the single public entry point. It expresses the pipeline as
explicit stages (project -> identify -> bin/sort -> bitmask -> compact ->
rasterize; see core/stages.py and DESIGN.md §1) and dispatches every stage to
the backend selected by ``RenderConfig.backend``:

  * ``reference`` — pure-jnp XLA stages (differentiable oracle).
  * ``pallas``    — BGM + fused RM as Pallas kernels, same RenderStats.

Three modes share the substrate regardless of backend:

  * ``tile_baseline``  — conventional 3D-GS: identify + sort + rasterize at
    the small-tile level (paper Fig 1). Sorting keys = (gaussian, tile) pairs.
  * ``group_baseline`` — 'large tile' baseline: identify + sort + rasterize at
    the group level (what Fig 13 calls baseline 64x64).
  * ``gstg``           — the paper's method (Fig 9): group identification,
    group-wise sorting, per-entry tile bitmasks, FIFO compaction, small-tile
    rasterization. Sorting keys = (gaussian, group) pairs only.

Every mode returns the image plus RenderStats counters that drive the
benchmarks and the accelerator cost model.

``render_batch()`` renders a batch of cameras in ONE jit-compiled call (vmap
over the camera parameters); compiled renderers are cached by the static
(RenderConfig, camera-geometry) signature so repeated multi-view calls reuse
the executable (DESIGN.md §6).

Session-style rendering lives in ``repro.engine`` (DESIGN.md §11):
``engine.open(scene, cfg)`` commits the scene once and returns a handle with
``.render/.render_batch/.submit``; ``render_jit``/``render_image`` here are
deprecation shims over its module-default handle.

The GAUSSIAN axis is a sharding dimension too (DESIGN.md §10/§12): with
``cfg.scene_shards = D`` the frontend stages (project/identify/bin) run
per-shard on the canonical padded layout (sharding/scene.py) and a stable
merge stage rebuilds the global depth-ordered bin table bitwise-identically
to the replicated path. The projected features STAY in the per-shard layout
(``ShardedProjected``) all the way through bitmask/compact/rasterize: each
gather site decomposes the merged table's global indices into (shard,
local) and fetches from the owning shard (``cfg.feature_gather`` selects
the plain indexed gather or the owner-masked psum collective — both
bitwise-identical to the legacy flat concat), so per-camera activation
bytes scale 1/D alongside the persistent parameters. The engine handle
commits the strategy (engine/handle.py); ``serving/sharded.py`` lays the
shard axis over a 2-D (data=cameras, model=gaussians) mesh for scenes too
large to replicate. There the gstg Pallas backend is split too
(``model_bands``): each chip merges, bitmasks and rasterizes one band of
the groups, after an all-to-all of the per-shard bin tables.

Losslessness guarantees (tested in tests/test_pipeline_lossless.py):
  * BITWISE image equality gstg == tile_baseline whenever the bitmask method
    is at least as tight as the group method (ellipse bitmask under any group
    method; matched aabb+aabb) and no capacity overflow occurs — the per-tile
    entry tables are then identical arrays.
  * For the remaining method combos the CONTRIBUTING Gaussian sequences are
    still identical per tile (exact-set losslessness); images agree to fp
    reassociation of interleaved zero-alpha entries (<=1e-6), because every
    boundary method conservatively over-approximates the q<=9 support that
    rasterization enforces.
  * Across backends: identical integer counters and allclose images (the
    pallas RM chunks the group list rather than the compacted tile lists, so
    partial-sum association may differ by fp rounding; tests/test_engine.py).
"""
from __future__ import annotations

import dataclasses
import functools
import re
import warnings
from typing import Any, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.camera import Camera
from repro.core.gaussians import GaussianScene
from repro.core.grouping import BinTable, GridSpec, sort_op_count
from repro.core.projection import (
    FEATURE_GATHER_STRATEGIES,
    Projected,
    ShardedProjected,
    proj_valid_count,
)
from repro.core.stages import (
    Backend,
    PallasBackend,
    TimedBackend,
    get_backend,
    timed_stage_cache_clear,
    timed_stage_cache_info,
)
from repro.sharding.scene import SceneLike, ShardedScene, shard_scene
from repro.utils import cdiv, wide_count_dtype, wide_count_sum


@dataclasses.dataclass(frozen=True)
class RenderConfig:
    tile: int = 16
    group: int = 64
    mode: str = "gstg"                 # gstg | tile_baseline | group_baseline
    boundary_group: str = "ellipse"    # group-identification method (GS-TG)
    boundary_tile: str = "ellipse"     # tile identification / bitmask method
    group_capacity: int = 512          # K: entries per group segment
    tile_capacity: int = 256           # K_t: entries per tile segment
    span: int = 4                      # candidate window at group level (bins)
    chunk: int = 32                    # reference raster gaussian chunk
    early_exit: bool = True
    backend: str = "reference"         # stage implementation: reference | pallas
    scene_shards: int = 1              # D: gaussian-axis shards (DESIGN.md §10);
                                       #   part of the static jit/bucket signature
    feature_gather: str = "auto"       # projected-feature gather strategy when
                                       #   scene-sharded (DESIGN.md §12):
                                       #   auto (-> index) | index | psum | flat
    timing: bool = False               # timed-stage mode (DESIGN.md §14): run
                                       #   each stage as its own jit'd program
                                       #   with a block_until_ready fence and a
                                       #   stage/<name> span; bitwise-identical
                                       #   images, and part of the static
                                       #   signature so timed and untimed never
                                       #   share an executable


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RenderStats:
    """Operation counters for the paper's metrics + the cost model."""

    n_visible: jnp.ndarray           # gaussians surviving culling
    n_candidate_tests: jnp.ndarray   # identification boundary tests (wide)
    n_pairs_sort: jnp.ndarray        # sorting keys (the paper's redundancy axis)
    sort_ops: jnp.ndarray            # comparator-model ops sum L log L (wide)
    n_bit_tests: jnp.ndarray         # bitmask-generation tile tests (gstg only)
    fifo_ops: jnp.ndarray            # linear compaction ops (gstg only, wide)
    # 'wide' counters use utils.wide_count_dtype (int64 under x64, else f32):
    # they exceed int32 on multi-million-Gaussian scenes and must never wrap.
    alpha_ops: jnp.ndarray           # per-pixel alpha computations
    blend_ops: jnp.ndarray           # contributing blends
    tile_entries: jnp.ndarray        # total per-tile raster entries
    overflow: jnp.ndarray            # capacity-dropped entries (must be 0)
    span_overflow: jnp.ndarray       # candidate-window dropped bins (must be 0)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RenderResult:
    image: jnp.ndarray
    stats: RenderStats
    # Valid group-table entries of each band of a group-split backend
    # ((bands,) int32, band order); None where one backend drew every group.
    bands: Optional[jnp.ndarray] = None


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FrontendResult:
    """Everything the frontend program (project -> identify -> bin -> merge)
    hands the backend program (bitmask -> compact -> rasterize).

    A registered pytree so it crosses jit boundaries as-is: the engine
    handle compiles the two halves as SEPARATE programs (DESIGN.md §15) and
    a stream session parks these in its exact-reuse cache — feeding a cached
    FrontendResult to ``render_backend`` is bitwise-identical to the fused
    ``render`` because the backend consumes only ``proj``/``table`` and the
    frontend counters ride through untouched.
    """

    proj: Any                        # Projected | ShardedProjected
    table: Any                       # BinTable (group- or tile-level)
    n_visible: jnp.ndarray           # gaussians surviving culling
    n_candidate_tests: jnp.ndarray   # identification boundary tests (wide)
    n_pairs_sort: jnp.ndarray        # sorting keys produced by identify
    span_overflow: jnp.ndarray       # candidate-window dropped bins


# Every stage call of the fused program runs under ``jax.named_scope(
# "gstg/<stage>")``, so each op of the compiled program carries its stage in
# its ``op_name`` metadata and a device trace can be split by stage. Scopes
# change metadata only: the compiled program is otherwise the same.
STAGE_SCOPE = "gstg"
STAGES = ("project", "identify", "bin", "exchange", "merge", "bitmask",
          "compact", "raster")


def stage_scope(stage: str):
    """The named scope of one pipeline stage (one of ``STAGES``)."""
    return jax.named_scope(f"{STAGE_SCOPE}/{stage}")


def stage_of(op_name: str) -> Optional[str]:
    """The stage named in an op's ``op_name`` metadata, or None when the op
    ran outside every stage scope. A transformation wraps the scopes it
    traced through (``jit(one)/vmap(gstg/bin)/sort``)."""
    m = _STAGE_RE.search(op_name)
    return m.group(1) if m else None


_STAGE_RE = re.compile(
    rf"(?:^|[/(]){STAGE_SCOPE}/({'|'.join(STAGES)})(?=[/)]|$)")


def _grid(cam, cfg: RenderConfig) -> GridSpec:
    return GridSpec(
        width=cam.width,
        height=cam.height,
        tile=cfg.tile,
        group=cfg.group,
        span=cfg.span,
    )


def _scene_for_render(scene: SceneLike, cfg: RenderConfig) -> SceneLike:
    """Resolve the scene into the layout ``cfg.scene_shards`` asks for.

    A plain GaussianScene with ``scene_shards > 1`` is padded/sharded
    in-trace (sharding/scene.py canonical layout) — a real device placement
    only needs the caller to device_put a pre-sharded scene instead
    (serving/sharded.py). A ShardedScene is accepted at any D as long as it
    matches the config, including D == 1, which is how the sharded frontend
    is exercised degenerately (bitwise-identical to the replicated path).
    """
    if isinstance(scene, ShardedScene):
        if scene.num_shards != cfg.scene_shards:
            raise ValueError(
                f"scene has {scene.num_shards} shards but cfg.scene_shards="
                f"{cfg.scene_shards}; the shard count is part of the static "
                "signature and must agree"
            )
        return scene
    if cfg.scene_shards > 1:
        return shard_scene(scene, cfg.scene_shards)
    return scene


def resolve_feature_gather(cfg: RenderConfig) -> str:
    """Resolve ``cfg.feature_gather`` to a concrete strategy.

    ``'auto'`` resolves to ``'index'`` — the plain (shard, local) indexed
    gather, correct everywhere and optimal on one device or a logical-only
    shard axis. The engine handle commits ``'psum'`` instead when the scene
    is PHYSICALLY sharded over a mesh 'model' axis (engine/handle.py): the
    owner-masked collective form is what keeps per-camera features at N/D
    per device. ``'flat'`` is the legacy full-N concat, kept so benchmarks
    can A/B the memory/throughput tradeoff. All strategies are
    bitwise-identical (DESIGN.md §12); only memory/layout differ.
    """
    if cfg.feature_gather == "auto":
        return "index"
    if cfg.feature_gather not in FEATURE_GATHER_STRATEGIES:
        raise ValueError(
            f"unknown feature_gather {cfg.feature_gather!r}; expected "
            f"'auto' or one of {FEATURE_GATHER_STRATEGIES}"
        )
    return cfg.feature_gather


def _frontend(
    backend: Backend,
    scene: SceneLike,
    cam,
    grid: GridSpec,
    level: str,
    method: str,
    num_bins: int,
    capacity: int,
    feature_gather: str = "index",
):
    """Stages 1-3 (project / identify / bin) with the gaussian axis as a
    first-class sharding dimension.

    Replicated scene: the three stages run directly. ShardedScene: each
    stage runs per-shard (vmap over the leading shard axis D — laid over a
    mesh 'model' axis by the caller's input shardings), then the new merge
    stage combines the D fixed-capacity BinTables into the global
    depth-ordered table, bitwise-identical to the replicated path
    (core/grouping.py::merge_bin_tables, DESIGN.md §10). Downstream stages
    (bitmask/compact/rasterize) consume the merged table plus the projected
    features in the PER-SHARD layout (`ShardedProjected`): each gather site
    decomposes the table's global ``gauss_idx`` into (shard, local) and
    fetches from the owning shard (core/projection.py::proj_take,
    DESIGN.md §12) — the full padded-N flat feature concat only exists
    under the legacy ``feature_gather='flat'`` strategy.

    Returns ``(proj, table, (n_candidate_tests, n_pairs, n_span_overflow))``
    with ``proj`` a flat ``Projected`` (replicated scene or 'flat' strategy)
    or a ``ShardedProjected``, and the counters shard-summed —
    bitwise-equal to the replicated reduction whenever every partial fits
    the wide dtype's exact-integer range (always under x64; below 2**24 per
    counter under x64-off, which covers every parity test; above that the
    f32 counters are approximate-but-monotone on BOTH paths).
    """
    if isinstance(scene, GaussianScene):
        with stage_scope("project"):
            proj = backend.project(scene, cam)
        with stage_scope("identify"):
            pairs = backend.identify(proj, grid, level, method)
        with stage_scope("bin"):
            table = backend.bin(pairs, num_bins, capacity)
        return proj, table, (
            pairs.n_candidate_tests, pairs.n_pairs, pairs.n_span_overflow
        )

    D, shard_size = scene.num_shards, scene.shard_size
    if isinstance(backend, TimedBackend):
        # Timed mode: each vmapped stage is one fenced jit(vmap) program —
        # the per-shard calls below run inside the vmap trace, where fences
        # would no-op (core/stages.py::TimedBackend).
        with stage_scope("project"):
            proj_s = backend.project_shards(scene.shards, cam)
        with stage_scope("identify"):
            pairs_s = backend.identify_shards(proj_s, grid, level, method)
        with stage_scope("bin"):
            tables_s = backend.bin_shards(pairs_s, num_bins, capacity)
    else:
        with stage_scope("project"):
            proj_s = jax.vmap(lambda s: _project_in_chunks(backend, s, cam))(
                scene.shards)
        with stage_scope("identify"):
            pairs_s = jax.vmap(
                lambda p: backend.identify(p, grid, level, method)
            )(proj_s)
        with stage_scope("bin"):
            tables_s = jax.vmap(
                lambda p: backend.bin(p, num_bins, capacity)
            )(pairs_s)

    with stage_scope("merge"):
        # Shard-local -> global gaussian indices: the canonical layout is
        # gaussian-contiguous, so shard d starts at d * shard_size.
        offsets = (jnp.arange(D, dtype=jnp.int32) * shard_size)[:, None, None]
        gauss_idx = jnp.where(
            tables_s.entry_valid, tables_s.gauss_idx + offsets, 0
        )
        # Merge keys gathered SHARD-LOCALLY (each shard reads only its own
        # rows): bitwise-equal to the flat proj.depth[global_idx] gather
        # because flat[d * Ns + l] == proj_s.depth[d, l].
        depth = jnp.where(
            tables_s.entry_valid,
            jax.vmap(lambda p, t: p.depth[t.gauss_idx])(proj_s, tables_s),
            jnp.inf,
        )
        table = backend.merge(
            dataclasses.replace(tables_s, gauss_idx=gauss_idx), depth
        )
    if feature_gather == "flat":
        proj = jax.tree.map(
            lambda x: x.reshape(D * shard_size, *x.shape[2:]), proj_s
        )
    else:
        proj = ShardedProjected(shards=proj_s, gather=feature_gather)
    return proj, table, (
        jnp.sum(pairs_s.n_candidate_tests),
        jnp.sum(pairs_s.n_pairs),
        jnp.sum(pairs_s.n_span_overflow),
    )


def splits_backend(cfg: RenderConfig) -> bool:
    """Whether ``cfg`` renders with a backend that splits by group band on
    a 'model' axis (``model_bands``): gstg on the Pallas backend, with the
    features kept per shard (any gather but ``'flat'``)."""
    return (cfg.mode == "gstg"
            and isinstance(get_backend(cfg.backend), PallasBackend)
            and resolve_feature_gather(cfg) != "flat")


def model_bands(cfg: RenderConfig, backend: Backend, num_shards: int) -> int:
    """How many group bands a gstg render's backend is split into.

    The extent of the context mesh's 'model' axis when the scene's
    ``num_shards`` shards lie over it, one per chip (engine/handle.py
    commits that layout), the Pallas backend draws, and the features stay
    per shard (not ``'flat'``): each chip then merges, bitmasks, packs and
    rasterizes its own band of the groups (``_frontend_bands``,
    ``_backend_bands``; DESIGN.md §10). Otherwise 1: one backend draws
    every group, replicated on every chip of the mesh.
    """
    if (num_shards <= 1 or not isinstance(backend, PallasBackend)
            or not splits_backend(cfg)):
        return 1
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.manual_axes:
        return 1
    return num_shards if dict(mesh.shape).get("model") == num_shards else 1


# Most Gaussians of a scene shard projected at once. The projection's
# batched 3x3 products lie with 3x3 in the minor (4, 128) tile, 57x their
# bytes: 4.6 GB an array at a quarter of paper-size rubble.
PROJECT_ROWS = 1 << 20


def _project_in_chunks(backend: Backend, scene: GaussianScene,
                       cam) -> Projected:
    """``backend.project`` over at most ``PROJECT_ROWS`` Gaussians at a
    time (``lax.map``); each Gaussian's features are computed as in one
    pass."""
    n = scene.num_gaussians
    k = cdiv(n, PROJECT_ROWS)
    if k == 1:
        return backend.project(scene, cam)
    rows = cdiv(n, k)
    pad = k * rows - n
    chunks = jax.tree.map(
        lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        .reshape((k, rows) + x.shape[1:]), scene)
    proj = jax.lax.map(lambda s: backend.project(s, cam), chunks)
    return jax.tree.map(lambda x: x.reshape((k * rows,) + x.shape[2:])[:n],
                        proj)


def _frontend_bands(
    backend: Backend,
    scene: ShardedScene,
    cam,
    grid: GridSpec,
    level: str,
    method: str,
    capacity: int,
    bands: int,
    feature_gather: str,
):
    """``_frontend`` of a scene whose shards lie one per chip over the
    'model' axis, for a group-split backend.

    Each chip projects, identifies and bins its own shard into all G
    groups, as ``_frontend`` does. One all-to-all (the ``exchange`` stage)
    then hands chip d band d of every shard's table, shard-major: groups
    ``[d * Gb, (d + 1) * Gb)``, Gb = ceil(G / bands), the last band padded
    with empty groups. Each chip merges its band (``merge_bin_tables``, as
    it stands). The returned table is the merged table of ``_frontend``
    with its groups laid over 'model' and padded to ``bands * Gb`` rows;
    its ``lengths`` and ``overflow`` are whole and replicated.
    """
    from jax.sharding import PartitionSpec as P

    num_bins = grid.num_groups
    band = cdiv(num_bins, bands)
    pad = band * bands - num_bins
    shard_size = scene.shard_size

    def body(shards, R, t, fx, fy, cx, cy):
        # This chip's one shard, without the leading shard axis: a vmap
        # over it would give every gather of the frontend one more index.
        shard = jax.tree.map(lambda x: x[0], shards)
        c = Camera(R=R, t=t, fx=fx, fy=fy, cx=cx, cy=cy, width=cam.width,
                   height=cam.height, znear=cam.znear, zfar=cam.zfar)
        with stage_scope("project"):
            proj = _project_in_chunks(backend, shard, c)
        with stage_scope("identify"):
            pairs = backend.identify(proj, grid, level, method)
        with stage_scope("bin"):
            table = backend.bin(pairs, num_bins, capacity)
        with stage_scope("exchange"):
            # Global indices and merge keys, gathered on the shard's own
            # chip (as in _frontend), then this chip's band of every
            # shard's table, shard-major.
            valid = table.entry_valid
            offset = jax.lax.axis_index("model") * shard_size
            gauss_idx = jnp.where(valid, table.gauss_idx + offset, 0)
            depth = jnp.where(valid, proj.depth[table.gauss_idx], jnp.inf)

            def swap(a):
                widths = ((0, pad),) + ((0, 0),) * (a.ndim - 1)
                return jax.lax.all_to_all(jnp.pad(a, widths)[None], "model",
                                          1, 0, tiled=True)

            gauss_idx, depth, lengths = (
                swap(gauss_idx), swap(depth), swap(table.lengths))
        with stage_scope("merge"):
            # Each shard's entries are its first min(length, K) slots.
            k = jnp.arange(capacity, dtype=jnp.int32)
            valid = k < jnp.minimum(lengths, capacity)[..., None]
            merged = backend.merge(
                BinTable(gauss_idx=gauss_idx, entry_valid=valid,
                         lengths=lengths,
                         overflow=jnp.zeros((), jnp.int32)),
                depth)
            lengths = jax.lax.all_gather(merged.lengths, "model", tiled=True)
            overflow = jax.lax.psum(merged.overflow, "model")
        counts = (pairs.n_candidate_tests, pairs.n_pairs,
                  pairs.n_span_overflow)
        return (jax.tree.map(lambda x: x[None], proj), merged.gauss_idx,
                merged.entry_valid, lengths, overflow,
                tuple(x[None] for x in counts))

    model, repl = P("model"), P()
    proj_s, gauss_idx, valid, lengths, overflow, counts = jax.shard_map(
        body,
        in_specs=(model,) + (repl,) * 6,
        out_specs=(model, model, model, repl, repl, model),
        check_vma=False,
    )(scene.shards, cam.R, cam.t, jnp.asarray(cam.fx, jnp.float32),
      jnp.asarray(cam.fy, jnp.float32), jnp.asarray(cam.cx, jnp.float32),
      jnp.asarray(cam.cy, jnp.float32))
    table = BinTable(gauss_idx=gauss_idx, entry_valid=valid,
                     lengths=lengths, overflow=overflow)
    proj = ShardedProjected(shards=proj_s, gather=feature_gather)
    return proj, table, tuple(jnp.sum(c) for c in counts)


def _backend_bands(backend: PallasBackend, front: FrontendResult, grid,
                   cfg: RenderConfig, background, bands: int):
    """Stages 4-6 of gstg split by group band over the 'model' axis.

    Chip d takes band d of ``front.table`` (``_frontend_bands``) and every
    Gaussian's kernel fields, all-gathered from the chips that projected
    them (the ``exchange`` stage: ``kernels/layout.PACKED_FIELDS``, 60
    bytes a Gaussian). It bitmasks, counts, packs and rasterizes its own
    band: the kernels take the band's first group as ``group_offset``.
    The image is assembled from the bands. Returns the raster blocks'
    image, the (alpha, blend) op sums, the band-summed compaction and
    bitmask counters, and each band's valid entries.
    """
    from jax.sharding import PartitionSpec as P

    from repro.kernels.layout import PACKED_FIELDS
    from repro.kernels.ops import assemble_image

    table = front.table
    band = table.num_bins // bands

    def body(shards, gauss_idx, entry_valid, lengths):
        with stage_scope("exchange"):
            def gather(name):
                if name not in PACKED_FIELDS:
                    return None
                x = jax.lax.all_gather(getattr(shards, name), "model",
                                       tiled=True)
                return x.reshape((-1,) + x.shape[2:])

            flat = Projected(**{f.name: gather(f.name)
                                for f in dataclasses.fields(Projected)})
        gtable = BinTable(gauss_idx=gauss_idx, entry_valid=entry_valid,
                          lengths=lengths, overflow=jnp.zeros((), jnp.int32))
        first = jax.lax.axis_index("model") * band
        with stage_scope("bitmask"):
            masks = backend.bitmasks(flat, gtable, grid, cfg.boundary_tile,
                                     group_offset=first)
        with stage_scope("compact"):
            compacted = backend.compact(gtable, masks, grid,
                                        cfg.tile_capacity)
        with stage_scope("raster"):
            out, counts = backend.raster_blocks(
                flat, gtable, masks, grid, early_exit=cfg.early_exit,
                tile_capacity=cfg.tile_capacity, group_offset=first)
        sums = jax.lax.psum((masks.n_bit_tests, compacted.tile_entries,
                             compacted.overflow), "model")
        entries = jnp.sum(entry_valid.astype(jnp.int32))[None]
        return out, counts, sums, entries

    model = P("model")
    out, counts, sums, entries = jax.shard_map(
        body,
        in_specs=(model,) * 4,
        out_specs=(model, model, P(), model),
        check_vma=False,
    )(front.proj.shards, table.gauss_idx, table.entry_valid, table.lengths)
    G = grid.num_groups
    image = assemble_image(out[:G], grid, background)
    ops = (jnp.sum(counts[:G, :, 0]), jnp.sum(counts[:G, :, 1]))
    return image, ops, sums, entries


def render(
    scene: SceneLike,
    cam: Camera,
    cfg: RenderConfig,
    background: Optional[jnp.ndarray] = None,
) -> RenderResult:
    """Render one camera through the staged engine on ``cfg.backend``.

    ``scene`` is a plain (replicated) GaussianScene or a ShardedScene in the
    canonical gaussian-sharded layout; ``cfg.scene_shards`` selects the
    frontend and is part of every cache/bucket signature.
    """
    backend = get_backend(cfg.backend)
    scene = _scene_for_render(scene, cfg)
    if _timed_eligible(cfg, scene, cam, background):
        from repro.obs import get_tracer

        backend = TimedBackend(backend)
        tracer = get_tracer()
        t0 = tracer.clock()
        out = _render_mode(backend, scene, cam, cfg, background)
        # Umbrella span over the whole staged render; the per-stage spans
        # TimedBackend recorded nest under it on the same thread lane.
        tracer.complete(
            "stage/render", t0, tracer.clock(), category="stage",
            args={"mode": cfg.mode, "backend": cfg.backend}, force=True,
        )
        return out
    return _render_mode(backend, scene, cam, cfg, background)


def _timed_eligible(cfg: RenderConfig, scene, cam, background) -> bool:
    """Timed-stage mode applies only to CONCRETE inputs: under an outer
    trace (legacy jit(vmap) renderers, the jit'd autotune probe) fences
    would no-op and per-stage spans would record trace-time garbage, so
    traced calls stay on the plain backend — which is bitwise-identical."""
    return cfg.timing and not _has_tracers(
        (scene, cam.R, cam.fx, background)
    )


def _render_mode(backend, scene, cam, cfg, background) -> RenderResult:
    # The fused path IS the composition of the two halves (DESIGN.md §15):
    # same stage calls, same dataflow, so splitting the program at this
    # boundary (engine stream sessions jit each half separately) keeps
    # images bitwise-identical to the one-program render.
    front = _run_frontend(backend, scene, cam, cfg)
    return _run_backend(backend, front, cam, cfg, background)


def _frontend_spec(cfg: RenderConfig, grid: GridSpec) -> tuple:
    """The (level, method, num_bins, capacity) the mode's frontend runs at.

    gstg sorts once per GROUP with the group-identification method (the
    paper's redundancy win); tile_baseline sorts per tile; group_baseline
    sorts per group but with the tile method (Fig 13's 'large tile'
    baseline).
    """
    if cfg.mode == "gstg":
        return "group", cfg.boundary_group, grid.num_groups, cfg.group_capacity
    if cfg.mode == "tile_baseline":
        return "tile", cfg.boundary_tile, grid.num_tiles, cfg.tile_capacity
    if cfg.mode == "group_baseline":
        return "group", cfg.boundary_tile, grid.num_groups, cfg.group_capacity
    raise ValueError(f"unknown mode {cfg.mode!r}")


def _run_frontend(
    backend: Backend, scene, cam, cfg: RenderConfig
) -> FrontendResult:
    """Stages 1-3 (+ merge when scene-sharded) for any mode: ONE sort per
    bin at the mode's granularity. Per-shard + stable merge when sharded."""
    grid = _grid(cam, cfg)
    level, method, num_bins, capacity = _frontend_spec(cfg, grid)
    gather = resolve_feature_gather(cfg)
    bands = model_bands(cfg, backend, getattr(scene, "num_shards", 1))
    if bands > 1:
        proj, table, (n_tests, n_pairs, n_span) = _frontend_bands(
            backend, scene, cam, grid, level, method, capacity, bands,
            gather,
        )
    else:
        proj, table, (n_tests, n_pairs, n_span) = _frontend(
            backend, scene, cam, grid, level, method, num_bins, capacity,
            gather,
        )
    return FrontendResult(
        proj=proj,
        table=table,
        n_visible=proj_valid_count(proj),
        n_candidate_tests=n_tests,
        n_pairs_sort=n_pairs,
        span_overflow=n_span,
    )


def _run_backend(
    backend: Backend, front: FrontendResult, cam, cfg: RenderConfig, background
) -> RenderResult:
    """Stages 4-6 on a FrontendResult: bitmask/compact/rasterize for gstg,
    direct per-bin rasterization for the baselines."""
    grid = _grid(cam, cfg)
    proj, table = front.proj, front.table
    bands = model_bands(cfg, backend, getattr(proj, "num_shards", 1))

    if bands > 1:
        image, (alpha_ops, blend_ops), (n_bit_tests, tile_entries,
                                        tile_overflow), entries = (
            _backend_bands(backend, front, grid, cfg, background, bands))
        lengths = table.lengths[: grid.num_groups]
        stats = RenderStats(
            n_visible=front.n_visible,
            n_candidate_tests=front.n_candidate_tests,
            n_pairs_sort=front.n_pairs_sort,
            sort_ops=sort_op_count(lengths),
            n_bit_tests=n_bit_tests,
            fifo_ops=wide_count_sum(lengths) * grid.tiles_per_group,
            alpha_ops=alpha_ops,
            blend_ops=blend_ops,
            tile_entries=tile_entries,
            overflow=table.overflow + tile_overflow,
            span_overflow=front.span_overflow,
        )
        return RenderResult(image=image, stats=stats, bands=entries)

    if cfg.mode == "gstg":
        # 4) Bitmask generation (BGM): tile-granularity tests on group
        #    entries. On the ASIC this overlaps GSM; in XLA the two ops have
        #    no data dependence and schedule freely (table order does not
        #    affect masks: masks are per-entry — which is also why bitmasks
        #    need no cross-shard pass: they run on the already-merged table).
        with stage_scope("bitmask"):
            masks = backend.bitmasks(proj, table, grid, cfg.boundary_tile)
        # 5) RM FIFO: per-tile compaction by bitmask (linear, order-
        #    preserving). Materialized by the reference backend; virtual
        #    (in-register) for the fused pallas RM, which still reports the
        #    same length/overflow stats.
        with stage_scope("compact"):
            compacted = backend.compact(table, masks, grid, cfg.tile_capacity)
        # 6) Small-tile rasterization.
        with stage_scope("raster"):
            rast = backend.rasterize_groups(
                proj,
                table,
                masks,
                compacted,
                grid,
                background=background,
                chunk=cfg.chunk,
                early_exit=cfg.early_exit,
                tile_capacity=cfg.tile_capacity,
            )
        stats = RenderStats(
            n_visible=front.n_visible,
            n_candidate_tests=front.n_candidate_tests,
            n_pairs_sort=front.n_pairs_sort,
            sort_ops=sort_op_count(table.lengths),
            n_bit_tests=masks.n_bit_tests,
            fifo_ops=wide_count_sum(table.lengths) * grid.tiles_per_group,
            alpha_ops=rast.alpha_ops,
            blend_ops=rast.blend_ops,
            tile_entries=compacted.tile_entries,
            overflow=table.overflow + compacted.overflow,
            span_overflow=front.span_overflow,
        )
        return RenderResult(image=rast.image, stats=stats)

    if cfg.mode == "tile_baseline":
        raster_grid = grid
    else:
        # Rasterize at group granularity: treat groups as (large) tiles.
        raster_grid = GridSpec(
            width=grid.n_groups_x * grid.group,
            height=grid.n_groups_y * grid.group,
            tile=grid.group,
            group=grid.group,
            span=cfg.span,
        )
    with stage_scope("raster"):
        rast = backend.rasterize_tiles(
            proj,
            table,
            raster_grid,
            background=background,
            chunk=cfg.chunk,
            early_exit=cfg.early_exit,
        )
    image = rast.image[: cam.height, : cam.width]
    stats = RenderStats(
        n_visible=front.n_visible,
        n_candidate_tests=front.n_candidate_tests,
        n_pairs_sort=front.n_pairs_sort,
        sort_ops=sort_op_count(table.lengths),
        n_bit_tests=jnp.zeros((), jnp.int32),
        fifo_ops=jnp.zeros((), wide_count_dtype()),
        alpha_ops=rast.alpha_ops,
        blend_ops=rast.blend_ops,
        tile_entries=jnp.sum(table.lengths),
        overflow=table.overflow,
        span_overflow=front.span_overflow,
    )
    return RenderResult(image=image, stats=stats)


def render_frontend(
    scene: SceneLike, cam: Camera, cfg: RenderConfig
) -> FrontendResult:
    """The frontend HALF of :func:`render` as its own entry point.

    Runs project -> identify -> bin (-> merge when scene-sharded) and
    returns the :class:`FrontendResult` that :func:`render_backend` turns
    into pixels. The split is camera-pose-heavy but pixel-free: everything
    here depends on the pose, nothing on the background or the raster
    loop — which is what makes frontend results reusable across identical
    poses (engine/stream.py) and speculatively precomputable off the
    critical path (DESIGN.md §15).
    """
    backend = get_backend(cfg.backend)
    scene = _scene_for_render(scene, cfg)
    if _timed_eligible(cfg, scene, cam, None):
        backend = TimedBackend(backend)
    return _run_frontend(backend, scene, cam, cfg)


def render_backend(
    front: FrontendResult,
    cam: Camera,
    cfg: RenderConfig,
    background: Optional[jnp.ndarray] = None,
) -> RenderResult:
    """The backend HALF of :func:`render`: pixels from a FrontendResult.

    ``render_backend(render_frontend(scene, cam, cfg), cam, cfg, bg)`` is
    bitwise-identical to ``render(scene, cam, cfg, bg)`` — the fused path
    is literally this composition (tests/test_stream.py). Only the static
    geometry of ``cam`` is read (grid + crop); the pose was consumed by the
    frontend.
    """
    backend = get_backend(cfg.backend)
    if _timed_eligible(cfg, front, cam, background):
        backend = TimedBackend(backend)
    return _run_backend(backend, front, cam, cfg, background)


def frontend_stats(
    scene: SceneLike, cam: Camera, cfg: RenderConfig
) -> RenderStats:
    """Counters WITHOUT rasterization: the autotune phase-1 probe.

    Runs stages 1-5 (project / identify / bin, plus bitmask + compact for
    gstg) and returns a :class:`RenderStats` whose frontend counters are
    exactly what ``render()`` would report for the same config. The raster
    counters that would need the (expensive) stage 6 are replaced by the
    cost model's worst-case alpha estimate — ``tile_entries`` x pixels per
    bin, i.e. every surviving entry alpha-tested against every pixel of its
    bin, which is the no-early-exit upper bound and is MONOTONE across
    candidate configs (the property the phase-1 pruning needs;
    autotune/search.py). ``blend_ops`` is reported as 0 (the cost model
    never reads it). Traceable: jit it per candidate config.
    """
    backend = get_backend(cfg.backend)
    scene = _scene_for_render(scene, cfg)
    if _timed_eligible(cfg, scene, cam, None):
        backend = TimedBackend(backend)
    grid = _grid(cam, cfg)
    gather = resolve_feature_gather(cfg)

    if cfg.mode == "gstg":
        proj, gtable, (n_tests, n_pairs, n_span) = _frontend(
            backend, scene, cam, grid, "group", cfg.boundary_group,
            grid.num_groups, cfg.group_capacity, gather,
        )
        masks = backend.bitmasks(
            proj, gtable, grid, cfg.boundary_tile
        )
        compacted = backend.compact(gtable, masks, grid, cfg.tile_capacity)
        pixels_per_bin = cfg.tile * cfg.tile
        return RenderStats(
            n_visible=proj_valid_count(proj),
            n_candidate_tests=n_tests,
            n_pairs_sort=n_pairs,
            sort_ops=sort_op_count(gtable.lengths),
            n_bit_tests=masks.n_bit_tests,
            fifo_ops=wide_count_sum(gtable.lengths) * grid.tiles_per_group,
            alpha_ops=compacted.tile_entries * pixels_per_bin,
            blend_ops=jnp.zeros((), jnp.int32),
            tile_entries=compacted.tile_entries,
            overflow=gtable.overflow + compacted.overflow,
            span_overflow=n_span,
        )

    if cfg.mode == "tile_baseline":
        level, bins_xy, capacity, bin_px = (
            "tile", grid.num_tiles, cfg.tile_capacity, cfg.tile
        )
    elif cfg.mode == "group_baseline":
        level, bins_xy, capacity, bin_px = (
            "group", grid.num_groups, cfg.group_capacity, cfg.group
        )
    else:
        raise ValueError(f"unknown mode {cfg.mode!r}")
    proj, table, (n_tests, n_pairs, n_span) = _frontend(
        backend, scene, cam, grid, level, cfg.boundary_tile, bins_xy,
        capacity, gather,
    )
    tile_entries = jnp.sum(table.lengths)
    return RenderStats(
        n_visible=proj_valid_count(proj),
        n_candidate_tests=n_tests,
        n_pairs_sort=n_pairs,
        sort_ops=sort_op_count(table.lengths),
        n_bit_tests=jnp.zeros((), jnp.int32),
        fifo_ops=jnp.zeros((), wide_count_dtype()),
        alpha_ops=tile_entries * (bin_px * bin_px),
        blend_ops=jnp.zeros((), jnp.int32),
        tile_entries=tile_entries,
        overflow=table.overflow,
        span_overflow=n_span,
    )


def _has_tracers(tree) -> bool:
    """True when any leaf is a jax Tracer — the deprecation shims then stay
    on the eager ``render`` path (a handle cannot commit a traced scene)."""
    return any(isinstance(x, jax.core.Tracer) for x in jax.tree.leaves(tree))


def render_image(scene, cam, cfg, background=None) -> jnp.ndarray:
    """Deprecated: ``render(scene, cam, cfg).image`` for differentiable /
    in-trace use, or ``repro.engine.open(scene, cfg).render(cam).image`` for
    repeated rendering through a committed handle (DESIGN.md §11)."""
    warnings.warn(
        "render_image() is deprecated; use render(scene, cam, cfg).image "
        "(differentiable) or repro.engine.open(scene, cfg).render(cam).image",
        DeprecationWarning,
        stacklevel=2,
    )
    if _has_tracers(scene):
        return render(scene, cam, cfg, background).image
    from repro import engine

    return engine.default_renderer(scene, cfg).render(cam, background).image


# ---------------------------------------------------------------------------
# Batched multi-camera rendering (jit-compiled, cached by static signature)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CameraBatch:
    """A batch of cameras sharing static geometry (resolution, clip planes).

    Dynamic per-camera parameters (pose + intrinsics) are stacked arrays and
    become traced arguments of the cached renderer; width/height stay static
    so the GridSpec — and therefore the compiled program — is shared.
    """

    R: jnp.ndarray    # (B, 3, 3)
    t: jnp.ndarray    # (B, 3)
    fx: jnp.ndarray   # (B,)
    fy: jnp.ndarray   # (B,)
    cx: jnp.ndarray   # (B,)
    cy: jnp.ndarray   # (B,)
    width: int
    height: int
    znear: float = 0.2
    zfar: float = 1000.0

    @classmethod
    def from_cameras(cls, cams: Sequence[Camera]) -> "CameraBatch":
        if not cams:
            raise ValueError("empty camera batch")
        w, h = cams[0].width, cams[0].height
        zn, zf = cams[0].znear, cams[0].zfar
        for c in cams:
            if (c.width, c.height, c.znear, c.zfar) != (w, h, zn, zf):
                raise ValueError(
                    "all cameras in a batch must share width/height/znear/zfar"
                )
        stack = lambda f: jnp.asarray(np.stack([np.asarray(f(c)) for c in cams]))
        return cls(
            R=stack(lambda c: c.R),
            t=stack(lambda c: c.t),
            fx=stack(lambda c: np.float32(c.fx)),
            fy=stack(lambda c: np.float32(c.fy)),
            cx=stack(lambda c: np.float32(c.cx)),
            cy=stack(lambda c: np.float32(c.cy)),
            width=w,
            height=h,
            znear=zn,
            zfar=zf,
        )

    def __len__(self) -> int:
        return int(self.R.shape[0])


def batch_signature(cfg: RenderConfig, cam) -> tuple:
    """The full static jit signature for one (config, camera-geometry) pair.

    Accepts a ``Camera`` or a ``CameraBatch`` (anything with width/height/
    znear/zfar). Two renders hit the SAME cached executable iff their
    signatures are equal — this is the key the serving bucketer groups
    requests by (serving/bucketing.py) and the key of the lru caches below.
    """
    return (cfg, cam.width, cam.height, cam.znear, cam.zfar)


jax.tree_util.register_dataclass(
    CameraBatch,
    data_fields=["R", "t", "fx", "fy", "cx", "cy"],
    meta_fields=["width", "height", "znear", "zfar"],
)


def _render_with_traced_camera(cfg: RenderConfig, width, height, znear, zfar):
    """The shared closure both cached renderers jit: rebuild a Camera from
    traced pose/intrinsics around the static geometry and render."""

    def one(scene, R, t, fx, fy, cx, cy, background):
        cam = Camera(
            R=R, t=t, fx=fx, fy=fy, cx=cx, cy=cy,
            width=width, height=height, znear=znear, zfar=zfar,
        )
        return render(scene, cam, cfg, background)

    return one


def _frontend_with_traced_camera(cfg: RenderConfig, width, height, znear, zfar):
    """The frontend-program closure the engine handle jits (DESIGN.md §15):
    same traced-camera convention as ``_render_with_traced_camera`` minus
    the background (the frontend never reads it)."""

    def one(scene, R, t, fx, fy, cx, cy):
        cam = Camera(
            R=R, t=t, fx=fx, fy=fy, cx=cx, cy=cy,
            width=width, height=height, znear=znear, zfar=zfar,
        )
        return render_frontend(scene, cam, cfg)

    return one


def _backend_with_static_geometry(cfg: RenderConfig, width, height, znear, zfar):
    """The backend-program closure the engine handle jits (DESIGN.md §15).

    The backend reads only the STATIC camera geometry (grid + crop), so the
    closure bakes a placeholder pose in — the traced inputs are the
    FrontendResult pytree and the background.
    """
    geom_cam = Camera(
        R=np.eye(3, dtype=np.float32), t=np.zeros(3, np.float32),
        fx=np.float32(1.0), fy=np.float32(1.0),
        cx=np.float32(0.0), cy=np.float32(0.0),
        width=width, height=height, znear=znear, zfar=zfar,
    )

    def one(front, background):
        return render_backend(front, geom_cam, cfg, background)

    return one


@functools.lru_cache(maxsize=64)
def _batch_renderer(cfg: RenderConfig, width, height, znear, zfar):
    """Build + jit the vmapped renderer for one static signature.

    lru-cached by (RenderConfig, camera-geometry) — RenderConfig is a frozen
    (hashable, eq-by-value) dataclass, so equal configs share the executable
    even across distinct instances; stale entries age out of the bounded
    cache (the jit wrapper itself is dropped, releasing the executable).
    """
    one = _render_with_traced_camera(cfg, width, height, znear, zfar)
    return jax.jit(jax.vmap(one, in_axes=(None, 0, 0, 0, 0, 0, 0, None)))


# Auxiliary renderer-adjacent caches (name -> (info_fn, clear_fn)). Any
# module that builds a private cache on the render path (the sharded
# scene-layout cache in serving/sharded.py, every open engine handle's jit
# cache) MUST register it here so ``render_cache_clear``/
# ``render_cache_info`` stay the single source of truth — the serving
# cache-hit stats are deltas of render_cache_info and a cache outside this
# registry would make them lie.
_AUX_RENDER_CACHES: dict = {}


def register_render_cache(name: str, *, info, clear) -> None:
    """Register an auxiliary cache under ``name``. ``info()`` must return a
    dict with at least ``hits``/``misses`` ints (the cache_delta contract,
    serving/stats.py); ``clear()`` must drop every entry and reset both."""
    if name in ("single", "batch"):
        raise ValueError(f"cache name {name!r} is reserved")
    _AUX_RENDER_CACHES[name] = (info, clear)


def unregister_render_cache(name: str) -> None:
    """Remove an auxiliary cache from the registry (a closed engine handle
    must leave no trace in ``render_cache_info()``). Unknown names are a
    no-op so close() stays idempotent."""
    _AUX_RENDER_CACHES.pop(name, None)


def render_cache_clear() -> None:
    """Drop ALL cached compiled renderers and registered auxiliary caches."""
    _batch_renderer.cache_clear()
    for _, clear in _AUX_RENDER_CACHES.values():
        clear()


def _info_dict(info) -> dict:
    return {
        "hits": info.hits,
        "misses": info.misses,
        "currsize": info.currsize,
        "maxsize": info.maxsize,
    }


def render_cache_info() -> dict:
    """Statistics for EVERY renderer cache as plain dicts.

    ``{"batch": {hits, misses, currsize, maxsize}, **aux}`` where ``aux``
    covers each registered auxiliary cache (``"scene_layout"`` once
    serving/sharded.py is imported, one ``"engineN"`` entry per open handle)
    — used by tests/benchmarks to assert signature reuse, by
    ``launch/render.py --stats``, and by the serving stats
    (serving/stats.py) so the CLI and the server report cache hits in the
    same units.
    """
    out = {
        "batch": _info_dict(_batch_renderer.cache_info()),
    }
    for name, (info, _) in _AUX_RENDER_CACHES.items():
        out[name] = info()
    return out


# The timed-stage jit cache (core/stages.py::TimedBackend) is a render-path
# cache like any other: registering it keeps the serving cache-hit deltas
# truthful when `RenderConfig.timing` is on.
register_render_cache(
    "timed_stage", info=timed_stage_cache_info, clear=timed_stage_cache_clear
)


def _collect_render_caches(registry) -> None:
    """Metrics collector: publish every render-cache's hit/miss/size table
    as ``render_cache.<name>.<field>`` gauges at snapshot time (DESIGN.md
    §14). Gauges, not counters, because the totals are owned by the caches;
    the prefix is dropped first so caches that unregistered (closed engine
    handles) leave no stale series behind."""
    registry.drop("render_cache.")
    for kind, info in render_cache_info().items():
        for k, v in info.items():
            if isinstance(v, (int, float)):
                registry.gauge(f"render_cache.{kind}.{k}").set(v)


from repro.obs import get_registry as _obs_registry  # noqa: E402

_obs_registry().register_collector("render_caches", _collect_render_caches)


def _background_array(background) -> jnp.ndarray:
    if background is None:
        return jnp.zeros((3,), jnp.float32)
    return jnp.asarray(background, jnp.float32)


def render_jit(
    scene: SceneLike,
    cam: Camera,
    cfg: RenderConfig,
    background: Optional[jnp.ndarray] = None,
) -> RenderResult:
    """Deprecated: ``repro.engine.open(scene, cfg).render(cam)``.

    Delegates to the module-default handle for ``(scene, cfg)``
    (``repro.engine.default_renderer``), which keeps the legacy behavior —
    repeated calls with ANY camera of the same resolution reuse one compiled
    executable — while the handle owns the committed scene (DESIGN.md §11).
    """
    warnings.warn(
        "render_jit() is deprecated; open a handle with "
        "repro.engine.open(scene, cfg) and call .render(cam)",
        DeprecationWarning,
        stacklevel=2,
    )
    if _has_tracers(scene):
        return render(scene, cam, cfg, background)
    from repro import engine

    return engine.default_renderer(scene, cfg).render(cam, background)


def render_batch(
    scene: SceneLike,
    cams: Union[CameraBatch, Sequence[Camera]],
    cfg: RenderConfig,
    background: Optional[jnp.ndarray] = None,
) -> RenderResult:
    """Render B cameras in ONE jit call (image: (B, H, W, 3); stats: (B,)).

    The compiled fn is cached by the static (RenderConfig, geometry)
    signature, so multi-view serving amortizes compilation and dispatch
    across frames — the batching prerequisite named in the ROADMAP.
    """
    batch = cams if isinstance(cams, CameraBatch) else CameraBatch.from_cameras(cams)
    fn = _batch_renderer(*batch_signature(cfg, batch))
    return fn(
        scene,
        batch.R, batch.t, batch.fx, batch.fy, batch.cx, batch.cy,
        _background_array(background),
    )
