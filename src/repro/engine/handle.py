"""Session-style rendering engine: commit a scene ONCE, render through a handle.

``open(scene, cfg)`` resolves everything the five legacy free entry points
(`render`, `render_jit`, `render_batch`, `render_batch_sharded`,
``RenderServer``) each re-derived per call — scene placement (replicated vs
the canonical :class:`~repro.sharding.scene.ShardedScene` layout), the 1-D or
2-D render mesh, and the jit-cache keys — and commits them into a
:class:`Renderer` handle (DESIGN.md §11):

  * the scene is staged on the HOST (``acquire_scene_layout`` when gaussian-
    sharded, so the full padded scene never allocates on one device) and
    ``device_put`` exactly once through a residency entry; every subsequent
    call reuses the device copy — unless a budgeted shared manager paged it
    out, in which case the next use pages it back in bitwise-identically
    from the host backing store (DESIGN.md §17);
  * the handle owns a per-handle jit cache, registered with the engine-wide
    ``register_render_cache`` registry so ``render_cache_info()`` /
    ``render_cache_clear()`` and the serving cache-hit stats keep covering it;
  * ``.render(cam)`` / ``.render_batch(cams, pad_to=...)`` are the synchronous
    entry points — bitwise-identical to the legacy ``render_jit`` /
    ``render_batch`` / ``render_batch_sharded`` paths (tests/
    test_engine_handle.py);
  * ``.submit(cam)`` returns a ``concurrent.futures.Future`` served by an
    internal queue -> bucketing-scheduler worker thread (the ROADMAP's
    "threaded front-end": batching becomes an implementation detail of the
    handle, and an asyncio caller just wraps the future);
  * ``.close()`` (or the context manager) drains the worker, unregisters and
    drops the jit cache, and releases the handle's refcounted residency
    entry and scene-layout reference — shared state (the host layout, the
    committed device copy) frees when the LAST handle over it closes, never
    under another open handle's feet.

The handle is intentionally a COMMIT of (scene, config): per-request knobs
that change the compiled program (mode, backend, capacities, scene_shards,
feature_gather) belong to a different handle — that is what makes the
jit-cache key within a handle collapse to the camera geometry alone. The
feature-sharded gathers (DESIGN.md §12) land exactly here as promised: the
commit resolves ``feature_gather='auto'`` to the owner-masked psum
collective when the mesh realizes a physical 'model' axis, and the budget
model counts the per-camera projected features at N/D accordingly;
multi-host serving remains the next commit-time decision to land.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import sys
import threading
import time
import weakref
from concurrent.futures import Future
from concurrent.futures import wait as _futures_wait
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.core.camera import Camera
from repro.core.gaussians import GaussianScene
from repro.core.pipeline import (
    CameraBatch,
    FrontendResult,
    RenderConfig,
    RenderResult,
    _background_array,
    _backend_with_static_geometry,
    _frontend_with_traced_camera,
    _render_with_traced_camera,
    register_render_cache,
    resolve_feature_gather,
    unregister_render_cache,
)
from repro.core.projection import projected_bytes_per_gaussian
from repro.launch.mesh import make_render_mesh, render_mesh_shards
from repro.obs import (
    emit_request_spans,
    get_registry,
    get_tracer,
    set_annotation_factory,
)
from repro.serving.bucketing import BucketingScheduler, padded_size
from repro.serving.queue import QueueClosed, RequestQueue
from repro.residency import ResidencyManager
from repro.serving.sharded import (
    acquire_scene_layout,
    pad_camera_batch,
    release_scene_layout,
)
from repro.sharding.policies import (
    camera_batch_pspec,
    data_extent,
    render_replicated_pspec,
    scene_shard_pspec,
)
from repro.sharding.scene import ShardedScene
from repro.utils import pytree_bytes

_HANDLE_SEQ = itertools.count()
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_FN_CACHE_MAX = 64          # per-handle compiled-renderer bound (mirrors the
                            # legacy global lru maxsize)


def _count_compile(event: str, duration_s: float, **_) -> None:
    if event == COMPILE_EVENT:
        registry = get_registry()
        registry.counter("engine.compiles_total").inc()
        registry.histogram("engine.compile_s").observe(duration_s)


def _count_cache_hit(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT:
        get_registry().counter("engine.compile_cache_hits_total").inc()


def _observe_jax() -> None:
    """Once per process, at import: live ``repro.obs`` spans open
    ``jax.profiler`` annotations, and every XLA compile feeds
    ``engine.compiles_total`` and ``engine.compile_s`` (a load from the
    persistent compilation cache counts as a compile, and also bumps
    ``engine.compile_cache_hits_total``)."""
    set_annotation_factory(jax.profiler.TraceAnnotation)
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    jax.monitoring.register_event_listener(_count_cache_hit)


_observe_jax()


@dataclasses.dataclass(frozen=True)
class _Submitted:
    """One queued ``submit()`` request: the camera plus its future.

    Shaped for the serving primitives: the ``RequestQueue`` stamps
    ``enqueue_time`` via ``dataclasses.replace`` and the
    ``BucketingScheduler`` groups by ``signature()`` — within one handle the
    config and scene are fixed, so the signature collapses to the camera
    geometry (one bucket per resolution).
    """

    camera: Any
    future: Future
    enqueue_time: Optional[float] = None
    request_id: str = ""
    # Lifecycle stamps (DESIGN.md §14): the dict OBJECT rides through the
    # queue's dataclasses.replace copies, so every phase writes into one
    # shared map; compare=False keeps it out of the generated eq.
    stamps: Dict[str, float] = dataclasses.field(
        default_factory=dict, compare=False, repr=False
    )

    def signature(self) -> tuple:
        c = self.camera
        return (c.width, c.height, c.znear, c.zfar)


def _timed_batch(one):
    """Batch renderer for timed-stage mode: loop lanes eagerly and stack.

    The vmapped jit batch and the per-lane jit are bitwise-identical
    (tests/test_engine_handle.py relies on the same property), so looping
    keeps pixels exact while letting TimedBackend fence every stage — a
    vmapped timed render would see only tracers.
    """

    def fn(scene, R, t, fx, fy, cx, cy, background):
        outs = [
            one(scene, R[i], t[i], fx[i], fy[i], cx[i], cy[i], background)
            for i in range(int(R.shape[0]))
        ]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *outs)

    return fn


def _vmap_cameras(one, mesh):
    """``one`` (scene, R, t, fx, fy, cx, cy, background) vmapped over a
    camera batch whose axis lies over ``mesh``'s data axes. Naming those
    axes lets a compiled Pallas kernel (kernels/ops.py::on_every_device) run
    on each device's own lanes instead of on the whole batch everywhere."""
    spmd = camera_batch_pspec(mesh)[0] if data_extent(mesh) > 1 else None
    return jax.vmap(one, in_axes=(None, 0, 0, 0, 0, 0, 0, None),
                    spmd_axis_name=spmd)


def _with_mesh(mesh, fn, *args):
    with jax.set_mesh(mesh):
        return fn(*args)


class Renderer:
    """A committed (scene, config) pair with render/serve entry points.

    Construct through :func:`open`. Not thread-safe for concurrent
    ``render``/``render_batch`` calls from multiple threads (device dispatch
    is serialized anyway); ``submit`` is the thread-safe entry — the bounded
    queue is the boundary, and the internal worker owns all device work for
    the futures path.
    """

    def __init__(
        self,
        scene: Union[GaussianScene, ShardedScene],
        cfg: RenderConfig,
        *,
        devices: Optional[int] = None,
        mesh: Optional[Mesh] = None,
        scene_shards: Union[str, int] = "auto",
        device_budget_mb: Optional[float] = None,
        max_batch: int = 8,
        max_wait: float = 0.05,
        queue_depth: int = 64,
        tile_params: Union[None, str, tuple] = None,
        autotune_opts: Optional[dict] = None,
        residency: Optional[ResidencyManager] = None,
        clock=time.monotonic,
    ):
        if devices is not None and mesh is not None:
            raise ValueError("pass devices or mesh, not both")
        # Tile-grouping params (DESIGN.md §13): an explicit (tile, group,
        # tile_capacity) triple commits immediately; 'auto' defers to the
        # autotune cache/search at FIRST render — the search needs a camera
        # resolution, which the handle only learns then. The committed cfg
        # is frozen from that point on; images are bitwise-identical to a
        # fixed-config open of the same params (same compiled program).
        self._autotune_opts = dict(autotune_opts or {})
        self._tune_pending = False
        self._tune_lock = threading.Lock()
        if tile_params == "auto":
            self._tune_pending = True
        elif tile_params is not None:
            try:
                t, g, c = (int(x) for x in tile_params)
            except (TypeError, ValueError):
                raise ValueError(
                    f"tile_params must be None, 'auto', or a (tile, group, "
                    f"tile_capacity) triple; got {tile_params!r}"
                ) from None
            cfg = dataclasses.replace(
                cfg, tile=t, group=g, tile_capacity=c,
                group_capacity=max(cfg.group_capacity, c),
            )
        shards = self._resolve_shards(scene, cfg, scene_shards)
        self._source = scene if isinstance(scene, GaussianScene) else None

        # The PHYSICAL shard count: what actually divides per-device bytes.
        # On an explicit mesh it is the mesh's 'model' extent (a mesh without
        # one leaves the shard axis logical — every device still holds the
        # whole scene); otherwise the render_mesh_shards policy over the
        # devices we are about to build the mesh from.
        if mesh is not None:
            n_dev = mesh.size
            phys = (
                shards
                if shards > 1 and dict(mesh.shape).get("model", 1) == shards
                else 1
            )
        else:
            n_dev = devices if devices is not None else len(jax.devices())
            phys = render_mesh_shards(n_dev, shards)
        # The effective per-device cap: an explicit device_budget_mb wins;
        # a shared residency manager's budget otherwise. The static check
        # below remains PER SCENE — a scene that cannot fit alone (even
        # after shard escalation) must still fail fast; the AGGREGATE
        # overflow across scenes is what the residency manager pages
        # against (DESIGN.md §17).
        budget_mb = device_budget_mb
        if budget_mb is None and residency is not None:
            budget_mb = residency.budget_mb
        if budget_mb is not None:
            # Per-device budget model (DESIGN.md §12): persistent scene
            # parameters at 1/phys PLUS the transient per-camera projected
            # features — N/phys ONLY under the resolved 'psum' strategy
            # over a physical 'model' axis (_feature_div: an explicit
            # 'index' gather may be all-gathered by GSPMD, so it counts
            # full N, as do replicated/logical-only/'flat' commits).
            scene_mb = pytree_bytes(scene) / 2**20
            # model(s, p) = per-device MB at shard count s realized p ways:
            # parameters at 1/p + per-camera features at N_pad(s)/fdiv.
            model = lambda s, p: (
                scene_mb / p
                + self._feature_mb(scene, s) / self._feature_div(cfg, s, p)
            )
            # Budget escalation only applies when the caller left BOTH the
            # layout and the mesh to us ('auto' shards, no explicit mesh —
            # an explicit mesh cannot grow a 'model' axis): pick the
            # smallest shard count the device count can realize that fits
            # the per-device cap (candidate counts are evaluated as a
            # PHYSICAL d-way commit: d divides both terms).
            if (
                scene_shards == "auto"
                and mesh is None
                and self._source is not None
                and model(shards, phys) > budget_mb
            ):
                for d in range(max(shards, 1), n_dev + 1):
                    if n_dev % d == 0 and model(d, d) <= budget_mb:
                        shards, phys = d, d
                        break
            if model(shards, phys) > budget_mb:
                layout = f"{phys}-way sharded" if phys > 1 else "replicated"
                fdiv = self._feature_div(cfg, shards, phys)
                raise ValueError(
                    f"scene needs {model(shards, phys):.2f} MB/device "
                    f"{layout} ({scene_mb / phys:.2f} MB parameters + "
                    f"{self._feature_mb(scene, shards) / fdiv:.2f} MB "
                    f"per-camera projected features at N/{fdiv}), over the "
                    f"{budget_mb} MB budget — raise scene_shards or "
                    f"the device count"
                )

        cfg_updates = {}
        if cfg.scene_shards != shards:
            cfg_updates["scene_shards"] = shards
        # The gather strategy is a commit-time decision (DESIGN.md §12):
        # 'auto' resolves to the owner-masked collective form when the
        # scene is PHYSICALLY sharded over a mesh 'model' axis — the form
        # whose per-device feature footprint is N/D — and to the plain
        # (shard, local) indexed gather otherwise. An explicit strategy in
        # cfg is respected (benchmarks A/B the legacy 'flat' concat).
        if shards > 1 and cfg.feature_gather == "auto":
            cfg_updates["feature_gather"] = "psum" if phys > 1 else "index"
        self._cfg = (
            dataclasses.replace(cfg, **cfg_updates) if cfg_updates else cfg
        )
        if mesh is None:
            mesh = make_render_mesh(devices, scene_shards=phys)
        model_extent = dict(mesh.shape).get("model", 1)
        if shards > 1 and model_extent not in (1, shards):
            raise ValueError(
                f"mesh model axis ({model_extent}) must match scene_shards="
                f"{shards} (or be absent for a logical-only shard axis)"
            )
        self._mesh = mesh

        # Stream-session registry BEFORE the commit: the residency entry's
        # dynamic-cost callback (frontend_cache_mb) may run during the
        # eager page-in below.
        self._worker_lock = threading.Lock()
        self._streams: List[Any] = []

        # Commit: host-staged layout when sharded (refcounted — the
        # layout survives until the LAST handle over it closes), then
        # registration with the residency manager (DESIGN.md §17). The
        # eager acquire below IS the one device_put the commit promises;
        # under a budgeted shared manager the scene may later page out and
        # back in bitwise-identically through the host backing store.
        staged = scene
        self._layout_ref = None
        if shards > 1 and isinstance(scene, GaussianScene):
            staged = acquire_scene_layout(scene, shards)
            self._layout_ref = (scene, shards)
        spec = (
            scene_shard_pspec(mesh)
            if isinstance(staged, ShardedScene)
            else render_replicated_pspec()
        )
        self._scene_mb_per_device = pytree_bytes(scene) / phys / 2**20
        self._feature_mb_per_device = self._feature_mb(scene, shards) / (
            self._feature_div(cfg, shards, phys)
        )
        # What the commit actually RUNS ('flat' for a replicated frontend,
        # even though cfg.feature_gather may still read 'auto').
        self._feature_gather = self._resolved_gather(cfg, shards, phys)
        self._phys_shards = phys
        self._residency = (
            residency if residency is not None
            else ResidencyManager(budget_mb=device_budget_mb)
        )
        self._res_entry = self._residency.register(
            (id(scene), shards, mesh),
            staged,
            NamedSharding(mesh, spec),
            self._scene_mb_per_device + self._feature_mb_per_device,
            label=f"scene@{id(scene):#x}/D{shards}",
        )
        # Dynamic cost: the stream sessions' frontend caches (the budget-
        # undercount fix) — weakref'd so the shared entry never pins the
        # handle. release() on the LAST close drops the entry and with it
        # every registered callback.
        self_ref = weakref.ref(self)

        def _dyn_cost(ref=self_ref):
            h = ref()
            return h.frontend_cache_mb() if h is not None else 0.0

        self._dyn_cost = _dyn_cost
        self._res_entry.cost_fns.append(_dyn_cost)
        # A handle dropped WITHOUT close() must still release its residency
        # reference, or the shared manager would pin the entry forever.
        self._res_finalizer = weakref.finalize(
            self, self._residency.release, self._res_entry
        )
        self._residency.acquire(self._res_entry)

        # Per-handle jit cache, visible through the engine-wide registry.
        # Registered through a weakref so the registry never pins the handle:
        # a Renderer dropped WITHOUT close() still gets collected (freeing
        # its executables and committed device scene), and the finalizer
        # removes the registry entry close() would have removed.
        self._fns: Dict[tuple, Any] = {}
        self._fn_stats = {"hits": 0, "misses": 0}
        self.cache_name = f"engine{next(_HANDLE_SEQ)}"
        self_ref = weakref.ref(self)

        def _info(ref=self_ref):
            h = ref()
            return h.cache_info() if h is not None else {
                "hits": 0, "misses": 0, "currsize": 0,
                "maxsize": _FN_CACHE_MAX,
            }

        def _clear(ref=self_ref):
            h = ref()
            if h is not None:
                h._cache_clear()

        register_render_cache(self.cache_name, info=_info, clear=_clear)
        weakref.finalize(self, unregister_render_cache, self.cache_name)

        # Futures front-end (worker started lazily on first submit()).
        self._clock = clock
        self._max_batch = max_batch
        self._queue = RequestQueue(queue_depth, clock=clock)
        self._scheduler = BucketingScheduler(max_batch, max_wait, clock=clock)
        self._worker: Optional[threading.Thread] = None
        self._flush_event = threading.Event()
        self._outstanding: List[Future] = []
        self._counters = {
            "submitted": 0, "completed": 0, "batches": 0, "padded_lanes": 0,
        }
        self._closed = False

    # -- committed-state introspection --------------------------------------

    @property
    def cfg(self) -> RenderConfig:
        return self._cfg

    @property
    def mesh(self) -> Mesh:
        return self._mesh

    @property
    def scene_shards(self) -> int:
        return self._cfg.scene_shards

    @property
    def _scene(self):
        """The device-resident committed scene, acquired through the
        residency manager on every use: a no-op LRU touch while resident,
        a bitwise-identical ``device_put`` of the host backing store after
        a page-out (DESIGN.md §17)."""
        entry = self._res_entry
        if entry is None:
            return None
        return self._residency.acquire(entry)

    @property
    def committed_scene(self):
        """The device-resident committed scene (paged in if needed).

        Handles opened through ONE residency manager on the same
        (scene, layout, mesh) share a single entry — and therefore one
        device copy (e.g. one per config in a server adds no scene HBM,
        serving/server.py::commit)."""
        self._check_open()
        return self._scene

    @property
    def resident(self) -> bool:
        """Whether the committed scene is device-resident RIGHT NOW (it may
        be paged out under a budgeted shared manager; any render pages it
        back in transparently)."""
        entry = self._res_entry
        return entry is not None and entry.resident

    def prefetch(self) -> bool:
        """Page the committed scene in ahead of a render — the serving
        tier's admission-time prefetch hook. True when a transfer actually
        happened; a resident scene is a no-op."""
        self._check_open()
        return self._residency.prefetch(self._res_entry)

    def frontend_cache_mb(self) -> float:
        """Device MB held by this handle's stream sessions' frontend caches
        (up to ``cache_frames`` FrontendResult pytrees per stream) — memory
        the static budget model cannot see; charged against the residency
        budget as the entry's dynamic cost."""
        with self._worker_lock:
            streams = list(self._streams)
        return sum(
            s.cache_bytes() for s in streams if not s.closed
        ) / 2**20

    @property
    def closed(self) -> bool:
        return self._closed

    @property
    def tile_params(self) -> Union[str, tuple]:
        """The committed (tile, group, tile_capacity) — or 'auto (pending)'
        while an 'auto' open is still waiting for its first camera."""
        if self._tune_pending:
            return "auto (pending)"
        return (self._cfg.tile, self._cfg.group, self._cfg.tile_capacity)

    def stats(self) -> dict:
        """Committed layout + per-handle cache and futures counters. Also
        publishes the committed-layout numbers as per-handle gauges in the
        metrics registry (DESIGN.md §14; dropped again by close())."""
        registry = get_registry()
        prefix = f"engine.{self.cache_name}."
        registry.gauge(prefix + "scene_mb_per_device").set(
            self._scene_mb_per_device)
        registry.gauge(prefix + "feature_mb_per_device").set(
            self._feature_mb_per_device)
        registry.gauge(prefix + "physical_shards").set(self._phys_shards)
        frontend_cache_mb = self.frontend_cache_mb()
        registry.gauge(prefix + "frontend_cache_mb").set(frontend_cache_mb)
        for k, v in self._counters.items():
            registry.gauge(prefix + k).set(v)
        return {
            "config": self._cfg,
            "tile_params": self.tile_params,
            "mesh": dict(self._mesh.shape),
            "scene_shards": self._cfg.scene_shards,
            "physical_shards": self._phys_shards,
            "scene_mb_per_device": self._scene_mb_per_device,
            "feature_mb_per_device": self._feature_mb_per_device,
            # The budget-undercount fix (DESIGN.md §17): live stream
            # frontend-cache memory, charged against the residency budget.
            "frontend_cache_mb": frontend_cache_mb,
            "resident": self.resident,
            "feature_gather": self._feature_gather,
            "cache": self.cache_info(),
            **self._counters,
        }

    def cache_info(self) -> dict:
        return {
            "hits": self._fn_stats["hits"],
            "misses": self._fn_stats["misses"],
            "currsize": len(self._fns),
            "maxsize": _FN_CACHE_MAX,
        }

    def _cache_clear(self) -> None:
        self._fns.clear()
        self._fn_stats["hits"] = 0
        self._fn_stats["misses"] = 0

    # -- budget model (DESIGN.md §12) ----------------------------------------

    @staticmethod
    def _feature_mb(scene, shards: int) -> float:
        """Per-camera projected-feature MB at the PADDED gaussian count
        (padding rows project too; they are culled, not skipped)."""
        if isinstance(scene, ShardedScene):
            n_pad = scene.padded_size
        else:
            n = scene.num_gaussians
            n_pad = -(-n // max(shards, 1)) * max(shards, 1)
        return n_pad * projected_bytes_per_gaussian() / 2**20

    @staticmethod
    def _resolved_gather(cfg: RenderConfig, shards: int, phys: int) -> str:
        """The gather strategy this commit would run (mirrors the 'auto'
        resolution applied to the committed cfg)."""
        if shards <= 1:
            return "flat"       # replicated frontend: features are flat-N
        if cfg.feature_gather == "auto":
            return "psum" if phys > 1 else "index"
        return resolve_feature_gather(cfg)

    @classmethod
    def _feature_div(cls, cfg: RenderConfig, shards: int, phys: int) -> int:
        """What divides the per-camera feature bytes per device: phys only
        when the owner-gather collective keeps them sharded over a PHYSICAL
        'model' axis; 1 for replicated scenes, logical-only shard axes, the
        plain indexed gather (GSPMD may gather the operand), and the legacy
        'flat' concat."""
        if phys > 1 and cls._resolved_gather(cfg, shards, phys) == "psum":
            return phys
        return 1

    # -- shard resolution ----------------------------------------------------

    @staticmethod
    def _resolve_shards(scene, cfg, scene_shards) -> int:
        requested = (
            cfg.scene_shards if scene_shards == "auto" else int(scene_shards)
        )
        if requested < 1:
            raise ValueError(f"scene_shards must be >= 1, got {requested}")
        if isinstance(scene, ShardedScene):
            if scene_shards != "auto" and requested != scene.num_shards:
                raise ValueError(
                    f"scene is pre-sharded {scene.num_shards} ways but "
                    f"scene_shards={requested} was requested"
                )
            return scene.num_shards
        return requested

    # -- per-handle jit cache ------------------------------------------------

    def _fn(self, kind: str, cam):
        """The compiled renderer for ``kind`` x this camera's geometry.

        The handle's config is committed, so the cache key is the geometry
        alone; the jit wrappers are per-handle (close() really releases the
        executables) and are built from the same traced-camera closure the
        legacy entry points jit — which is what makes the outputs bitwise
        match them.
        """
        key = (kind, cam.width, cam.height, cam.znear, cam.zfar)
        fn = self._fns.get(key)
        if fn is not None:
            self._fn_stats["hits"] += 1
            return fn
        self._fn_stats["misses"] += 1
        geom = (cam.width, cam.height, cam.znear, cam.zfar)
        if kind in ("frontend", "backend"):
            # The split programs (DESIGN.md §15): the frontend consumes the
            # traced pose, the backend consumes a FrontendResult pytree +
            # background — together bitwise-identical to the fused 'single'
            # program (tests/test_stream.py).
            one = (
                _frontend_with_traced_camera(self._cfg, *geom)
                if kind == "frontend"
                else _backend_with_static_geometry(self._cfg, *geom)
            )
            # Timed-stage mode runs the closure eagerly, same rationale as
            # below: only concrete inputs let TimedBackend fence stages.
            fn = one if self._cfg.timing else jax.jit(one)
        else:
            one = _render_with_traced_camera(self._cfg, *geom)
            if self._cfg.timing:
                # Timed-stage mode (DESIGN.md §14): the closure runs EAGERLY
                # so core.pipeline installs TimedBackend and fences each
                # stage's own jit'd program; under the usual outer jit every
                # input is a tracer and no stage could be timed. Bitwise-
                # identical pixels either way (tests/test_obs.py).
                fn = one if kind == "single" else _timed_batch(one)
            else:
                fn = (
                    jax.jit(one)
                    if kind == "single"
                    else jax.jit(_vmap_cameras(one, self._mesh))
                )
        if self._mesh.size > 1:
            # Trace and run under the handle's mesh: compiled Pallas kernels
            # find it there and run per device (kernels/ops.py::
            # on_every_device), since XLA cannot partition them.
            fn = functools.partial(_with_mesh, self._mesh, fn)
        while len(self._fns) >= _FN_CACHE_MAX:
            self._fns.pop(next(iter(self._fns)))
        self._fns[key] = fn
        return fn

    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError("Renderer is closed")

    # -- deferred tile-param autotune (DESIGN.md §13) -------------------------

    def _resolve_tile_params(self, cam) -> None:
        """Resolve a pending ``tile_params='auto'`` against this camera's
        resolution: consult the autotune cache (memory, then disk) and run
        the two-phase search on a miss, then commit the winner into the
        handle's config. Runs at most once per handle — before the first
        compiled renderer exists, so every subsequent geometry reuses the
        tuned knobs. Thread-safe (the submit() worker may race a direct
        render call here)."""
        if not self._tune_pending:
            return
        with self._tune_lock:
            if not self._tune_pending:
                return
            from repro.autotune import autotune as _autotune

            scene = self._source if self._source is not None else self._scene
            res = _autotune(
                scene, cam, self._cfg, mesh=self._mesh, **self._autotune_opts
            )
            self._cfg = dataclasses.replace(
                self._cfg,
                tile=res.tile,
                group=res.group,
                tile_capacity=res.tile_capacity,
                group_capacity=max(self._cfg.group_capacity,
                                   res.tile_capacity),
            )
            self._tune_pending = False

    # -- synchronous entry points -------------------------------------------

    def render(
        self, cam: Camera, background: Optional[jnp.ndarray] = None
    ) -> RenderResult:
        """Render one camera against the committed scene (jit-cached)."""
        self._check_open()
        self._resolve_tile_params(cam)
        fn = self._fn("single", cam)
        return fn(
            self._scene,
            jnp.asarray(cam.R), jnp.asarray(cam.t),
            jnp.float32(cam.fx), jnp.float32(cam.fy),
            jnp.float32(cam.cx), jnp.float32(cam.cy),
            _background_array(background),
        )

    def memory_analysis(self, cam: Camera):
        """The compiler's per-device memory plan (argument, output and temp
        bytes) of the single-camera program at ``cam``'s geometry. After a
        ``render(cam)`` this reads the program that ran; nothing compiles
        twice."""
        self._check_open()
        self._resolve_tile_params(cam)
        return self._compiled(
            self._fn("single", cam),
            self._scene,
            jnp.asarray(cam.R), jnp.asarray(cam.t),
            jnp.float32(cam.fx), jnp.float32(cam.fy),
            jnp.float32(cam.cx), jnp.float32(cam.cy),
            _background_array(None),
        ).memory_analysis()

    def program_text(
        self,
        cams: Union[CameraBatch, Sequence[Camera]],
        pad_to: Optional[int] = None,
    ) -> str:
        """The compiled HLO text of the batch program that ``render_batch(
        cams, pad_to=pad_to)`` runs. Each op's ``op_name`` metadata names
        its pipeline stage (``core.pipeline.stage_of``), so a device trace
        can be split by stage. After such a ``render_batch`` this reads the
        program that ran; nothing compiles twice."""
        self._check_open()
        fn, args, _ = self._batch_call(cams, pad_to, None)
        return self._compiled(fn, *args).as_text()

    def _compiled(self, fn, *args):
        under_mesh = isinstance(fn, functools.partial)
        if under_mesh:
            fn = fn.args[1]                       # unwrap _with_mesh
        if not hasattr(fn, "lower"):
            raise ValueError("timed-stage handles run eagerly: no program")
        with (jax.set_mesh(self._mesh) if under_mesh
              else contextlib.nullcontext()):
            return fn.lower(*args).compile()

    def render_frontend(self, cam: Camera) -> FrontendResult:
        """Run ONLY the frontend half (project -> identify -> bin -> merge)
        for one camera — the separately compiled program the stream sessions
        cache and speculate over (DESIGN.md §15). Feed the result to
        :meth:`render_backend` for pixels."""
        self._check_open()
        self._resolve_tile_params(cam)
        fn = self._fn("frontend", cam)
        return fn(
            self._scene,
            jnp.asarray(cam.R), jnp.asarray(cam.t),
            jnp.float32(cam.fx), jnp.float32(cam.fy),
            jnp.float32(cam.cx), jnp.float32(cam.cy),
        )

    def render_backend(
        self,
        front: FrontendResult,
        cam: Camera,
        background: Optional[jnp.ndarray] = None,
    ) -> RenderResult:
        """Run ONLY the backend half (bitmask -> compact -> rasterize) on a
        :class:`FrontendResult`. ``render_backend(render_frontend(cam), cam)``
        is bitwise-identical to ``render(cam)`` — only the static geometry
        of ``cam`` is read (it must match the frontend camera's)."""
        self._check_open()
        self._resolve_tile_params(cam)
        fn = self._fn("backend", cam)
        return fn(front, _background_array(background))

    def open_stream(
        self,
        *,
        cache_frames: int = 32,
        spec_depth: int = 2,
        speculate: bool = True,
    ):
        """Open a :class:`~repro.engine.stream.StreamRenderer` session over
        this handle (DESIGN.md §15): a bounded exact-reuse frontend cache
        (``cache_frames`` poses, LRU) plus a background speculation worker
        (``spec_depth`` pending predictions, drop-oldest; ``speculate=False``
        keeps reuse-only behavior). The stream registers its cache in the
        render-cache registry and is closed by :meth:`close`."""
        self._check_open()
        from repro.engine.stream import StreamRenderer

        stream = StreamRenderer(
            self, cache_frames=cache_frames, spec_depth=spec_depth,
            speculate=speculate,
        )
        with self._worker_lock:
            self._streams.append(stream)
        return stream

    def _forget_stream(self, stream) -> None:
        with self._worker_lock:
            if stream in self._streams:
                self._streams.remove(stream)

    def render_batch(
        self,
        cams: Union[CameraBatch, Sequence[Camera]],
        pad_to: Optional[int] = None,
        background: Optional[jnp.ndarray] = None,
    ) -> RenderResult:
        """Render B cameras in ONE jit call over the handle's mesh.

        The batch is padded to ``max(B, pad_to)`` rounded up to the mesh's
        DATA extent (serving loops pass their max batch so every dispatch of
        a geometry compiles one shape); exactly B images/stats come back.
        """
        self._check_open()
        fn, args, orig = self._batch_call(cams, pad_to, background)
        out = fn(*args)
        if len(args[1]) != orig:              # R, one row per padded lane
            out = jax.tree.map(lambda x: x[:orig], out)
        return out

    def _batch_call(self, cams, pad_to, background):
        """The compiled batch renderer for ``cams`` padded as
        ``render_batch`` pads them, its arguments, and the unpadded batch
        size."""
        batch = (
            cams if isinstance(cams, CameraBatch)
            else CameraBatch.from_cameras(cams)
        )
        if self._tune_pending:
            # The search probes through lane 0 — any lane would do, the
            # signature only reads the shared geometry.
            self._resolve_tile_params(Camera(
                R=np.asarray(batch.R[0]), t=np.asarray(batch.t[0]),
                fx=float(batch.fx[0]), fy=float(batch.fy[0]),
                cx=float(batch.cx[0]), cy=float(batch.cy[0]),
                width=batch.width, height=batch.height,
                znear=batch.znear, zfar=batch.zfar,
            ))
        orig = len(batch)
        lanes = data_extent(self._mesh)
        padded = pad_camera_batch(
            batch, padded_size(max(orig, pad_to or 0), lanes)
        )
        shard = NamedSharding(self._mesh, camera_batch_pspec(self._mesh))
        repl = NamedSharding(self._mesh, render_replicated_pspec())
        if self._cfg.timing:
            # Timed-stage mode loops lanes eagerly (_timed_batch); keep the
            # camera arrays uncommitted so the per-lane indexing stays a
            # local host slice instead of a cross-device gather.
            put_b = put_bg = lambda a: a
        else:
            put_b = lambda a: jax.device_put(a, shard)
            put_bg = lambda a: jax.device_put(a, repl)
        args = (
            self._scene,
            put_b(padded.R), put_b(padded.t),
            put_b(padded.fx), put_b(padded.fy),
            put_b(padded.cx), put_b(padded.cy),
            put_bg(_background_array(background)),
        )
        return self._fn("batch", padded), args, orig

    # -- futures front-end ---------------------------------------------------

    def submit(self, cam: Camera) -> Future:
        """Enqueue one camera; returns a Future of its ``RenderResult``.

        The result's leaves are HOST numpy arrays (the worker thread blocks
        on device completion before resolving futures). Requests batch with
        other submits of the same geometry up to the handle's
        ``max_batch``/``max_wait``; a full queue blocks the producer
        (bounded-queue backpressure). Thread-safe.
        """
        self._check_open()
        fut: Future = Future()
        self._ensure_worker()
        # Track BEFORE enqueueing: the worker may dispatch (and untrack) the
        # request the instant it lands in the queue.
        with self._worker_lock:
            self._counters["submitted"] += 1
            seq = self._counters["submitted"]
            self._outstanding.append(fut)
        get_registry().counter("engine.submitted_total").inc()
        try:
            self._queue.put(_Submitted(
                camera=cam, future=fut,
                request_id=f"{self.cache_name}#{seq}",
            ))
        except QueueClosed:
            with self._worker_lock:
                self._counters["submitted"] -= 1
                self._outstanding.remove(fut)
            raise RuntimeError("Renderer is closed") from None
        return fut

    def flush(self, timeout: Optional[float] = None) -> None:
        """Force-dispatch pending buckets and wait for outstanding futures."""
        self._flush_event.set()
        with self._worker_lock:
            futs = list(self._outstanding)
        _, not_done = _futures_wait(futs, timeout=timeout)
        if not_done:
            raise TimeoutError(f"flush timed out with {len(not_done)} pending")

    def _ensure_worker(self) -> None:
        with self._worker_lock:
            if self._worker is None or not self._worker.is_alive():
                self._worker = threading.Thread(
                    target=self._worker_loop,
                    name=f"{self.cache_name}-worker",
                    daemon=True,
                )
                self._worker.start()

    def _worker_loop(self) -> None:
        q, sched = self._queue, self._scheduler
        poll_s = max(min(sched.max_wait, 0.01), 0.001)
        try:
            while True:
                for req in q.get_batch(timeout=poll_s):
                    for bucket in sched.add(req):
                        self._dispatch_bucket(bucket)
                if self._flush_event.is_set():
                    self._flush_event.clear()
                    for req in q.drain():
                        for bucket in sched.add(req):
                            self._dispatch_bucket(bucket)
                    for bucket in sched.flush_all():
                        self._dispatch_bucket(bucket)
                for bucket in sched.poll():
                    self._dispatch_bucket(bucket)
                if q.closed and len(q) == 0:
                    for bucket in sched.flush_all():
                        self._dispatch_bucket(bucket)
                    return
        except BaseException as exc:      # noqa: BLE001 — futures must terminate
            # A crash OUTSIDE _dispatch_bucket's own handler (scheduler bug,
            # queue misuse) would otherwise strand every outstanding future
            # unresolved forever — and the gateway's failover accounting
            # depends on futures always terminating.
            self._fail_outstanding(exc)
            raise

    def _fail_outstanding(self, exc: BaseException) -> None:
        """Terminate every tracked future with ``exc`` (queue + scheduler
        pending are all in ``_outstanding``: submit tracks before enqueue)."""
        with self._worker_lock:
            futs, self._outstanding[:] = list(self._outstanding), []
        self._queue.drain()
        self._scheduler.flush_all()
        for fut in futs:
            if fut.set_running_or_notify_cancel():
                fut.set_exception(exc)

    def _dispatch_bucket(self, bucket) -> None:
        reqs = bucket.requests
        tracer = get_tracer()
        lanes = data_extent(self._mesh)
        padded = padded_size(max(len(reqs), self._max_batch), lanes)
        t0 = self._clock()
        try:
            with tracer.span(
                "engine/dispatch", category="engine",
                args={"handle": self.cache_name, "batch_size": len(reqs),
                      "padded": padded},
            ):
                with tracer.span("serve/launch", category="engine"):
                    out = self.render_batch(
                        [r.camera for r in reqs], pad_to=self._max_batch
                    )
                with tracer.span("serve/device_wait", category="engine"):
                    out = jax.block_until_ready(out)
                t_done = self._clock()
                with tracer.span("serve/fetch", category="engine"):
                    host = jax.tree.map(np.asarray, out)
            results = [
                jax.tree.map(lambda x, i=i: x[i], host)
                for i in range(len(reqs))
            ]
        except Exception as exc:                   # noqa: BLE001 — futures own it
            with self._worker_lock:
                for r in reqs:
                    self._outstanding.remove(r.future)
            for r in reqs:
                # A future cancelled between submit and dispatch must not
                # kill the worker (set_* on a cancelled Future raises).
                if r.future.set_running_or_notify_cancel():
                    r.future.set_exception(exc)
            return
        t1 = self._clock()
        with self._worker_lock:
            self._counters["batches"] += 1
            self._counters["completed"] += len(reqs)
            self._counters["padded_lanes"] += padded - len(reqs)
            for r in reqs:
                self._outstanding.remove(r.future)
        registry = get_registry()
        registry.counter("engine.batches_total").inc()
        registry.counter("engine.completed_total").inc(len(reqs))
        registry.counter("engine.padded_lanes_total").inc(padded - len(reqs))
        registry.histogram("engine.dispatch_s").observe(t1 - t0)
        for r, res in zip(reqs, results):
            st = getattr(r, "stamps", None)
            if st is not None:
                st["dispatch"] = t0
                st["device_done"] = t_done
                st["fetched"] = t1
            if r.future.set_running_or_notify_cancel():
                r.future.set_result(res)
            if st is not None:
                st["resolve"] = self._clock()
                emit_request_spans(tracer, r.request_id, st,
                                   args={"handle": self.cache_name})

    # -- lifecycle -----------------------------------------------------------

    def close(self) -> None:
        """Drain the worker, drop + unregister the jit cache, and release
        this handle's residency entry and scene-layout reference (the
        shared host layout and device copy free when the LAST handle over
        them closes). Idempotent; the handle is unusable afterwards."""
        if self._closed:
            return
        # Streams first: their speculation workers dispatch through this
        # handle's programs and their caches hold device arrays.
        with self._worker_lock:
            streams = list(self._streams)
        for stream in streams:
            stream.close()
        self._queue.close()                 # wakes the worker; drains pending
        worker = self._worker
        if worker is not None and worker.is_alive():
            worker.join()
        # A healthy worker resolved everything on the way out; if it died
        # earlier (or dispatch left stragglers) the remaining futures must
        # still terminate — callers blocked on .result() would otherwise
        # hang forever.
        self._fail_outstanding(RuntimeError(
            f"Renderer {self.cache_name} closed before the request resolved"
        ))
        self._closed = True
        self._worker = None
        unregister_render_cache(self.cache_name)
        # Per-handle gauges must not outlive the handle (same hygiene as the
        # render-cache registry entry); the aggregate engine.* counters stay.
        get_registry().drop(f"engine.{self.cache_name}.")
        self._cache_clear()
        # Residency release: refcounted, so a second handle (or server)
        # committed on the same (scene, layout, mesh) keeps its entry —
        # the shared-eviction fix: close() used to call
        # evict_scene_layouts(self._source) unconditionally, nuking
        # layouts other open handles still referenced.
        try:
            self._res_entry.cost_fns.remove(self._dyn_cost)
        except ValueError:
            pass
        if self._res_finalizer.detach():
            self._residency.release(self._res_entry)
        self._res_entry = None
        if self._layout_ref is not None:
            # Scoped to this handle's own (scene, D) layout reference; the
            # cached host layout drops only when the last reference goes.
            release_scene_layout(*self._layout_ref)
            self._layout_ref = None
        if self._source is not None:
            # Lifecycle fix for the autotune result cache: drop this
            # scene's in-memory entries (the persisted file keeps them, so
            # a re-open still skips the search). Lazy import — only a
            # process that autotuned has the cache registered/populated.
            if "repro.autotune.cache" in sys.modules:
                sys.modules["repro.autotune.cache"].evict_autotune_entries(
                    self._source
                )
        self._source = None

    def __enter__(self) -> "Renderer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __repr__(self) -> str:
        state = "closed" if self._closed else "open"
        return (
            f"<Renderer {self.cache_name} {state} mode={self._cfg.mode!r} "
            f"backend={self._cfg.backend!r} "
            f"scene_shards={self._cfg.scene_shards} "
            f"mesh={dict(self._mesh.shape)}>"
        )


def open(  # noqa: A001 — the module-level session verb is the API
    scene: Union[GaussianScene, ShardedScene],
    cfg: RenderConfig,
    *,
    devices: Optional[int] = None,
    mesh: Optional[Mesh] = None,
    scene_shards: Union[str, int] = "auto",
    device_budget_mb: Optional[float] = None,
    max_batch: int = 8,
    max_wait: float = 0.05,
    queue_depth: int = 64,
    tile_params: Union[None, str, tuple] = None,
    autotune_opts: Optional[dict] = None,
    residency: Optional[ResidencyManager] = None,
) -> Renderer:
    """Commit ``(scene, cfg)`` and return the :class:`Renderer` handle.

    * ``devices``/``mesh`` — where to commit: an explicit mesh, a local
      device count, or (default) every local device through
      ``make_render_mesh``.
    * ``scene_shards`` — ``'auto'`` takes the layout from ``cfg.scene_shards``
      (or the shard count of a pre-sharded scene); an int overrides it. The
      physical shard count follows the ``render_mesh_shards`` policy (logical
      shard axis when the device count cannot realize it).
    * ``device_budget_mb`` — per-device HBM cap counting the persistent
      scene parameters (1/D when physically sharded) PLUS the transient
      per-camera projected features — N/D under the feature-sharded psum
      gathers, full N otherwise (DESIGN.md §12). With
      ``scene_shards='auto'`` the handle escalates the shard count until
      the committed scene fits; otherwise an over-budget commit raises.
      The commit also resolves ``cfg.feature_gather='auto'``: 'psum' (the
      owner-masked collective) over a physical 'model' axis, 'index'
      otherwise.
    * ``max_batch``/``max_wait``/``queue_depth`` — the ``submit()`` futures
      front-end's batching knobs (same dials as the serving tier).
    * ``tile_params`` — ``None`` keeps the config's (tile, group,
      tile_capacity); an explicit triple overrides them at commit;
      ``'auto'`` consults the autotune cache (memory, then the persisted
      file) at FIRST render and runs the cost-model-guided search on a miss
      (DESIGN.md §13), committing the winner — images are bitwise-identical
      to a fixed-config open of the same resolved params.
      ``autotune_opts`` forwards search knobs (tiles/group_factors/
      capacities/top_k/warmup/reps/verify/persist) to
      :func:`repro.autotune.autotune`.
    * ``residency`` — a shared :class:`~repro.residency.ResidencyManager`
      (DESIGN.md §17): handles committed through one manager share device
      copies per (scene, layout, mesh) and page in/out against the
      manager's budget — many scenes serve from a device that fits only a
      few, bitwise-identically. Without it the handle gets a private
      manager (no paging unless ``device_budget_mb`` forces it; identical
      to the pre-residency semantics). When both are given,
      ``device_budget_mb`` still bounds THIS scene alone; the manager's
      budget drives aggregate paging.

    Use as a context manager (``with engine.open(...) as r:``) or call
    ``r.close()`` to release the committed state.
    """
    return Renderer(
        scene, cfg,
        devices=devices, mesh=mesh, scene_shards=scene_shards,
        device_budget_mb=device_budget_mb,
        max_batch=max_batch, max_wait=max_wait, queue_depth=queue_depth,
        tile_params=tile_params, autotune_opts=autotune_opts,
        residency=residency,
    )


# ---------------------------------------------------------------------------
# Module-default handles (the deprecation shims' delegate)
# ---------------------------------------------------------------------------

_DEFAULT_MAX = 32
_default_handles: Dict[tuple, Renderer] = {}


def default_renderer(
    scene: Union[GaussianScene, ShardedScene],
    cfg: RenderConfig,
    *,
    mesh: Optional[Mesh] = None,
) -> Renderer:
    """The module-default handle for ``(scene, cfg, mesh)``.

    Backs the deprecated free functions (``render_jit``/``render_image``/
    ``render_batch_sharded``): repeated legacy calls with the same scene and
    config reuse ONE committed handle — same executable-reuse behavior the
    old global lru caches provided for a fixed scene. Bounded FIFO; evicted
    handles are closed (which also evicts their scene layouts). Known
    tradeoff of per-handle caches: legacy callers LOOPING over many scenes
    under one config recompile per scene (the old global cache shared the
    executable); that is the migration pressure — new code should hold its
    own handle from :func:`open`.
    """
    key = (id(scene), cfg, mesh)
    handle = _default_handles.get(key)
    if handle is not None and not handle.closed:
        return handle
    handle = Renderer(scene, cfg, mesh=mesh)
    while len(_default_handles) >= _DEFAULT_MAX:
        _default_handles.pop(next(iter(_default_handles))).close()
    _default_handles[key] = handle
    # id() keys alone could alias a recycled object (a pre-sharded scene the
    # handle keeps no strong reference to could be collected and its id
    # reused): drop + close the entry when the source scene goes away.
    weakref.finalize(scene, _drop_default_handle, key)
    return handle


def _drop_default_handle(key) -> None:
    handle = _default_handles.pop(key, None)
    if handle is not None:
        handle.close()


def close_default_renderers() -> None:
    """Close and drop every module-default handle (test isolation hook)."""
    while _default_handles:
        _default_handles.pop(next(iter(_default_handles))).close()
