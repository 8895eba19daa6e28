"""Stage decomposition of the GS-TG rendering pipeline + backend dispatch.

The pipeline is expressed as six explicit stages (DESIGN.md §1):

    project -> identify -> bin/sort -> bitmask -> compact -> rasterize

``render()`` (core/pipeline.py) is the only public entry; a ``Backend``
supplies the stage implementations:

  * ``reference`` — pure-jnp XLA ops throughout (differentiable; the oracle
    every other backend is tested against).
  * ``pallas``    — BGM + fused RM run as Pallas TPU kernels (interpret mode
    off-TPU); identification and the group binning/sort stay on the XLA sort
    substrate (DESIGN.md §2: a stable lexicographic sort has no efficient
    Mosaic lowering, and stability is what the losslessness proof needs).

Both backends consume/produce the same dataclasses and emit the same
RenderStats counters, so they are interchangeable under ``render()`` and the
losslessness guarantees can be asserted across backends (tests/test_engine.py).

Every stage that consumes projected features (bitmask / rasterize) takes
``proj`` as a flat ``Projected`` OR a ``ShardedProjected`` kept in the
per-shard layout (DESIGN.md §12): the gathers route through
``core.projection.proj_take``, which decomposes the table's global gaussian
indices into (shard, local) and fetches from the owning shard —
bitwise-identical to the flat gather, so neither backend needs a sharded
fork of any stage.

The pallas 'compact' stage is *virtual*: the fused RM kernel applies the
bitmask filter in-register (paper Fig 10), so no per-tile table is ever
materialized — only the per-tile lengths/overflow counters are computed (a
cheap popcount) to keep the stats contract identical to the reference.
"""
from __future__ import annotations

import abc
import dataclasses
import threading
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.bitmask import GroupBitmasks, compact_tiles, generate_bitmasks
from repro.core.camera import Camera
from repro.core.gaussians import GaussianScene
from repro.core.grouping import (
    BinTable,
    GridSpec,
    PairSet,
    bin_pairs,
    identify,
    merge_bin_tables,
)
from repro.core.projection import Projected, project
from repro.core.raster import rasterize


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TileRaster:
    """Output of the rasterize stage over a tile-level work list."""

    image: jnp.ndarray       # (grid.height, grid.width, 3)
    alpha_ops: jnp.ndarray   # () int32
    blend_ops: jnp.ndarray   # () int32


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CompactedTiles:
    """Result of the compact stage (RM FIFO). ``table`` is only materialized
    by backends that need it (reference); the fused pallas RM consumes the
    group table + masks directly and leaves ``table`` as None."""

    tile_entries: jnp.ndarray   # () int32: sum of per-tile lengths (pre-clamp)
    overflow: jnp.ndarray       # () int32: entries dropped by tile_capacity
    table: Optional[BinTable] = None


def mask_tile_lengths(
    gtable: BinTable, masks: GroupBitmasks, grid: GridSpec
) -> jnp.ndarray:
    """(num_groups, tiles_per_group) per-member-tile entry counts — a popcount
    over the bitmask columns.

    Equals ``compact_tiles(...).lengths`` regrouped by (group, slot), without
    materializing the compacted table. Member tiles outside the image need no
    special-casing: both bitmask generators zero their mask bits already.
    """
    tpg = grid.tiles_per_group
    bits = (
        (masks.masks[:, :, None] >> jnp.arange(tpg, dtype=jnp.uint32)) & 1
    ).astype(jnp.int32)
    bits = bits * gtable.entry_valid[:, :, None].astype(jnp.int32)
    return jnp.sum(bits, axis=1)  # (G, tpg)


class Backend(abc.ABC):
    """Stage implementations behind ``render()``. Subclasses override the
    stages they accelerate; identification and binning default to the shared
    XLA substrate (stable sort => 3D-GS tie-break => losslessness)."""

    name: str = "abstract"

    # -- stage 1: preprocessing ------------------------------------------
    def project(self, scene: GaussianScene, cam: Camera) -> Projected:
        return project(scene, cam)

    # -- stage 2: group/tile identification ------------------------------
    def identify(
        self, proj: Projected, grid: GridSpec, level: str, method: str
    ) -> PairSet:
        return identify(proj, grid, level, method)

    # -- stage 3: binning + depth sort -----------------------------------
    def bin(self, pairs: PairSet, num_bins: int, capacity: int) -> BinTable:
        return bin_pairs(pairs, num_bins, capacity)

    # -- stage 3b: cross-shard merge (scene-sharded frontend) ------------
    def merge(self, tables: BinTable, depth: jnp.ndarray) -> BinTable:
        """Combine D per-shard bin tables (shard-stacked, gauss_idx already
        global) into the global depth-ordered table. Shared XLA substrate for
        every backend: the STABLE merge is what preserves the (depth,
        insertion-order) tie-break bitwise (core/grouping.py::
        merge_bin_tables, DESIGN.md §10) — a kernel backend may accelerate
        its own stages but must keep this merge order-exact."""
        return merge_bin_tables(tables, depth)

    # -- stage 4: bitmask generation (BGM) -------------------------------
    @abc.abstractmethod
    def bitmasks(
        self,
        proj: Projected,
        gtable: BinTable,
        grid: GridSpec,
        method: str,
    ) -> GroupBitmasks:
        """Per-entry tile bitmasks over the group table."""

    # -- stage 5: RM FIFO compaction -------------------------------------
    @abc.abstractmethod
    def compact(
        self,
        gtable: BinTable,
        masks: GroupBitmasks,
        grid: GridSpec,
        tile_capacity: int,
    ) -> CompactedTiles:
        ...

    # -- stage 6: rasterization ------------------------------------------
    @abc.abstractmethod
    def rasterize_tiles(
        self,
        proj: Projected,
        table: BinTable,
        grid: GridSpec,
        *,
        background: Optional[jnp.ndarray],
        chunk: int,
        early_exit: bool,
    ) -> TileRaster:
        """Rasterize a tile-level table (flat pipelines; reference gstg)."""

    @abc.abstractmethod
    def rasterize_groups(
        self,
        proj: Projected,
        gtable: BinTable,
        masks: GroupBitmasks,
        compacted: CompactedTiles,
        grid: GridSpec,
        *,
        background: Optional[jnp.ndarray],
        chunk: int,
        early_exit: bool,
        tile_capacity: int,
    ) -> TileRaster:
        """Rasterize the gstg work list (group table + per-entry bitmasks)."""


class ReferenceBackend(Backend):
    """Pure-jnp stages: the differentiable oracle (core/raster.py)."""

    name = "reference"

    def bitmasks(self, proj, gtable, grid, method):
        return generate_bitmasks(proj, gtable, grid, method)

    def compact(self, gtable, masks, grid, tile_capacity):
        table = compact_tiles(gtable, masks, grid, tile_capacity)
        return CompactedTiles(
            tile_entries=jnp.sum(table.lengths),
            overflow=table.overflow,
            table=table,
        )

    def rasterize_tiles(self, proj, table, grid, *, background, chunk, early_exit):
        rast = rasterize(
            proj, table, grid, background, chunk=chunk, early_exit=early_exit
        )
        return TileRaster(
            image=rast.image, alpha_ops=rast.alpha_ops, blend_ops=rast.blend_ops
        )

    def rasterize_groups(
        self, proj, gtable, masks, compacted, grid, *,
        background, chunk, early_exit, tile_capacity,
    ):
        return self.rasterize_tiles(
            proj, compacted.table, grid,
            background=background, chunk=chunk, early_exit=early_exit,
        )


class PallasBackend(Backend):
    """BGM + RM as Pallas kernels (interpret mode off-TPU), same counters.

    The fused RM never materializes per-tile tables; tile_capacity is honored
    in-register (entries past the capacity of a member tile's virtual FIFO are
    dropped, exactly like the reference compaction clamp), and alpha/blend op
    counters are accumulated inside the kernel. The kernels stream entries in
    their own 128-lane chunks (kernels/layout.LANE); the raster ``chunk``
    argument is the reference rasterizer's and is not read here.
    """

    name = "pallas"

    def __init__(self, interpret: Optional[bool] = None):
        self._interpret = interpret

    @property
    def interpret(self) -> Optional[bool]:
        return self._interpret

    def bitmasks(self, proj, gtable, grid, method, group_offset=0):
        """Row i of ``gtable`` is group ``group_offset + i`` (an int32
        scalar): a band of the image's groups under the group-split
        backend (core/pipeline.py), all of them from 0 otherwise."""
        from repro.kernels.bitmask_gen import bitmask_kernel
        from repro.kernels.layout import pack_features

        # Same padding rasterize_groups uses => the gather is an identical
        # expression there and XLA CSE merges the two under jit (the hot
        # paths — render_jit/render_batch — are jit'd; eager render() pays
        # the gather twice, acceptable for demos/tests).
        feat = pack_features(proj, gtable.gauss_idx, gtable.entry_valid)
        masks = bitmask_kernel(
            feat, grid, method=method, interpret=self._interpret,
            group_offset=group_offset,
        )
        # Kernel masks cover the padded K axis; crop to the table capacity.
        masks = masks[:, : gtable.capacity]
        n_tests = jnp.sum(gtable.entry_valid.astype(jnp.int32)) * grid.tiles_per_group
        return GroupBitmasks(masks=masks, n_bit_tests=n_tests)

    def compact(self, gtable, masks, grid, tile_capacity):
        lengths = mask_tile_lengths(gtable, masks, grid)
        return CompactedTiles(
            tile_entries=jnp.sum(lengths),
            overflow=jnp.sum(jnp.maximum(lengths - tile_capacity, 0)),
            table=None,
        )

    def rasterize_tiles(self, proj, table, grid, *, background, chunk, early_exit):
        from repro.kernels.layout import pack_features
        from repro.kernels.ops import assemble_image_tiles
        from repro.kernels.raster_tile import raster_tile_kernel

        feat = pack_features(proj, table.gauss_idx, table.entry_valid)
        out, counts = raster_tile_kernel(
            feat,
            grid.n_tiles_x,
            grid.tile,
            early_exit=early_exit,
            with_stats=True,
            interpret=self._interpret,
        )
        return TileRaster(
            image=assemble_image_tiles(out, grid, background),
            alpha_ops=jnp.sum(counts[:, 0]),
            blend_ops=jnp.sum(counts[:, 1]),
        )

    def raster_blocks(self, proj, gtable, masks, grid, *, early_exit,
                      tile_capacity, group_offset=0):
        """The fused RM's output per (group, member tile): rgb and final
        transmittance ``(G, tpg, 4, T*T)`` and (alpha_ops, blend_ops)
        ``(G, tpg, 2)``, for ``gtable``'s groups (a band of them from
        ``group_offset`` on, as in ``bitmasks``)."""
        from repro.kernels.layout import pack_features
        from repro.kernels.raster_tile import raster_group_fused_kernel

        feat = pack_features(proj, gtable.gauss_idx, gtable.entry_valid)
        K = feat.shape[-1]
        pad = K - masks.masks.shape[1]
        padded_masks = (
            jnp.pad(masks.masks, ((0, 0), (0, pad))) if pad else masks.masks
        )
        return raster_group_fused_kernel(
            feat,
            padded_masks,
            grid.n_groups_x,
            grid.tile,
            grid.gf,
            early_exit=early_exit,
            tile_capacity=tile_capacity,
            with_stats=True,
            interpret=self._interpret,
            group_offset=group_offset,
        )

    def rasterize_groups(
        self, proj, gtable, masks, compacted, grid, *,
        background, chunk, early_exit, tile_capacity,
    ):
        from repro.kernels.ops import assemble_image

        out, counts = self.raster_blocks(
            proj, gtable, masks, grid, early_exit=early_exit,
            tile_capacity=tile_capacity,
        )
        return TileRaster(
            image=assemble_image(out, grid, background),
            alpha_ops=jnp.sum(counts[:, :, 0]),
            blend_ops=jnp.sum(counts[:, :, 1]),
        )


# ---------------------------------------------------------------------------
# Timed-stage execution (observability, DESIGN.md §14)
# ---------------------------------------------------------------------------

# Per-stage jit cache for TimedBackend. Keyed by the Python statics of one
# stage invocation (backend name, stage, grid/method/capacity/...); jax.jit
# itself retraces per input shape/dtype, so the key needs nothing dynamic.
# Bounded FIFO; registered with the render-cache registry by core/pipeline.py
# (name "timed_stage") so cache stats stay truthful under timed serving.
_TIMED_FN_MAX = 128
_timed_lock = threading.Lock()
_timed_fns: Dict[tuple, object] = {}
_timed_stats = {"hits": 0, "misses": 0}


def _timed_fn(key: tuple, build):
    with _timed_lock:
        fn = _timed_fns.get(key)
        if fn is not None:
            _timed_stats["hits"] += 1
            return fn
        _timed_stats["misses"] += 1
    fn = jax.jit(build())
    with _timed_lock:
        while len(_timed_fns) >= _TIMED_FN_MAX:
            _timed_fns.pop(next(iter(_timed_fns)))
        _timed_fns.setdefault(key, fn)
        return _timed_fns[key]


def timed_stage_cache_info() -> dict:
    with _timed_lock:
        return {
            "hits": _timed_stats["hits"],
            "misses": _timed_stats["misses"],
            "currsize": len(_timed_fns),
            "maxsize": _TIMED_FN_MAX,
        }


def timed_stage_cache_clear() -> None:
    with _timed_lock:
        _timed_fns.clear()
        _timed_stats["hits"] = 0
        _timed_stats["misses"] = 0


class TimedBackend(Backend):
    """Per-stage timed execution of a wrapped backend (DESIGN.md §14).

    Each stage runs as its OWN jit'd program followed by a
    ``jax.block_until_ready`` fence, so the host interval around it is real
    per-stage device time — recorded as a ``stage/<name>`` span on the
    process tracer (``force=True``: ``RenderConfig.timing`` is the opt-in)
    and bracketed by ``jax.profiler.TraceAnnotation`` so host spans line up
    with device traces when the jax profiler is on.

    The per-stage-jit chain is BITWISE-identical to the whole-program jit on
    both backends (tests/test_obs.py): every stage boundary already carries
    concrete dtypes, and the eager glue between stages (index offsets,
    selects, gathers) is exact integer/select arithmetic. The first call per
    static signature pays per-stage compiles (``_timed_fn`` cache); callers
    that want clean numbers warm once, then measure (benchmarks/
    bench_stages.py, launch/render.py --stats).

    ``core.pipeline.render`` only installs this wrapper when inputs are
    concrete — under an outer trace (legacy jit(vmap) paths, the autotune
    probe) fences would no-op and spans would record trace-time garbage, so
    those paths stay on the plain backend.
    """

    def __init__(self, inner: Backend):
        self.inner = inner
        self.name = f"timed:{inner.name}"

    # -- span + fence around one stage program ---------------------------

    def _run(self, stage: str, key: tuple, build, *args):
        from repro.obs import get_tracer

        fn = _timed_fn((self.inner.name,) + key, build)
        tracer = get_tracer()
        t0 = tracer.clock()
        with jax.profiler.TraceAnnotation(f"stage/{stage}"):
            out = jax.block_until_ready(fn(*args))
        tracer.complete(
            f"stage/{stage}", t0, tracer.clock(), category="stage",
            args={"backend": self.inner.name}, force=True,
        )
        return out

    # -- camera split: static geometry vs dynamic pose/intrinsics --------

    @staticmethod
    def _cam_static(cam) -> tuple:
        return (int(cam.width), int(cam.height),
                float(cam.znear), float(cam.zfar))

    @staticmethod
    def _cam_dynamic(cam) -> tuple:
        return (
            jnp.asarray(cam.R), jnp.asarray(cam.t),
            jnp.asarray(cam.fx, jnp.float32), jnp.asarray(cam.fy, jnp.float32),
            jnp.asarray(cam.cx, jnp.float32), jnp.asarray(cam.cy, jnp.float32),
        )

    # -- stages ----------------------------------------------------------

    def project(self, scene, cam):
        inner = self.inner
        w, h, zn, zf = self._cam_static(cam)

        def build():
            def fn(scene, R, t, fx, fy, cx, cy):
                c = Camera(R=R, t=t, fx=fx, fy=fy, cx=cx, cy=cy,
                           width=w, height=h, znear=zn, zfar=zf)
                return inner.project(scene, c)
            return fn

        return self._run("project", ("project", w, h, zn, zf), build,
                         scene, *self._cam_dynamic(cam))

    def identify(self, proj, grid, level, method):
        inner = self.inner

        def build():
            return lambda p: inner.identify(p, grid, level, method)

        return self._run("identify", ("identify", grid, level, method),
                         build, proj)

    def bin(self, pairs, num_bins, capacity):
        inner = self.inner

        def build():
            return lambda p: inner.bin(p, num_bins, capacity)

        return self._run("bin", ("bin", num_bins, capacity), build, pairs)

    def merge(self, tables, depth):
        inner = self.inner

        def build():
            return lambda t, d: inner.merge(t, d)

        return self._run("merge", ("merge",), build, tables, depth)

    # Vmapped per-shard forms of stages 1-3 for the scene-sharded frontend
    # (core/pipeline.py::_frontend): each vmapped stage is ONE timed program,
    # fenced at the jit(vmap) level — inside the vmap trace the per-shard
    # calls are tracers and could not be fenced individually.

    def project_shards(self, shards, cam):
        inner = self.inner
        w, h, zn, zf = self._cam_static(cam)

        def build():
            def fn(shards, R, t, fx, fy, cx, cy):
                c = Camera(R=R, t=t, fx=fx, fy=fy, cx=cx, cy=cy,
                           width=w, height=h, znear=zn, zfar=zf)
                return jax.vmap(lambda s: inner.project(s, c))(shards)
            return fn

        return self._run("project", ("project_s", w, h, zn, zf), build,
                         shards, *self._cam_dynamic(cam))

    def identify_shards(self, proj_s, grid, level, method):
        inner = self.inner

        def build():
            return jax.vmap(lambda p: inner.identify(p, grid, level, method))

        return self._run("identify", ("identify_s", grid, level, method),
                         build, proj_s)

    def bin_shards(self, pairs_s, num_bins, capacity):
        inner = self.inner

        def build():
            return jax.vmap(lambda p: inner.bin(p, num_bins, capacity))

        return self._run("bin", ("bin_s", num_bins, capacity), build, pairs_s)

    def bitmasks(self, proj, gtable, grid, method):
        inner = self.inner

        def build():
            return lambda p, g: inner.bitmasks(p, g, grid, method)

        return self._run("bitmask", ("bitmask", grid, method),
                         build, proj, gtable)

    def compact(self, gtable, masks, grid, tile_capacity):
        inner = self.inner

        def build():
            return lambda g, m: inner.compact(g, m, grid, tile_capacity)

        return self._run("compact", ("compact", grid, tile_capacity),
                         build, gtable, masks)

    def rasterize_tiles(self, proj, table, grid, *,
                        background, chunk, early_exit):
        inner = self.inner
        has_bg = background is not None

        def build():
            if has_bg:
                return lambda p, t, bg: inner.rasterize_tiles(
                    p, t, grid, background=bg, chunk=chunk,
                    early_exit=early_exit)
            return lambda p, t: inner.rasterize_tiles(
                p, t, grid, background=None, chunk=chunk,
                early_exit=early_exit)

        args = (proj, table) + ((background,) if has_bg else ())
        return self._run(
            "rasterize", ("rast_tiles", grid, chunk, early_exit, has_bg),
            build, *args)

    def rasterize_groups(self, proj, gtable, masks, compacted, grid, *,
                         background, chunk, early_exit, tile_capacity):
        inner = self.inner
        has_bg = background is not None

        def build():
            if has_bg:
                return lambda p, g, m, c, bg: inner.rasterize_groups(
                    p, g, m, c, grid, background=bg, chunk=chunk,
                    early_exit=early_exit, tile_capacity=tile_capacity)
            return lambda p, g, m, c: inner.rasterize_groups(
                p, g, m, c, grid, background=None, chunk=chunk,
                early_exit=early_exit, tile_capacity=tile_capacity)

        args = (proj, gtable, masks, compacted)
        args += (background,) if has_bg else ()
        return self._run(
            "rasterize",
            ("rast_groups", grid, chunk, early_exit, tile_capacity, has_bg),
            build, *args)


_BACKENDS: Dict[str, Backend] = {}


def register_backend(name: str, backend: Backend) -> None:
    _BACKENDS[name] = backend


def get_backend(name: str) -> Backend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; available: {sorted(_BACKENDS)}"
        ) from None


register_backend("reference", ReferenceBackend())
register_backend("pallas", PallasBackend())
