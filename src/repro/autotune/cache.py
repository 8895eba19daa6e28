"""Autotune result cache: tuned tile params per scene/resolution signature.

Two layers (DESIGN.md §13):

  * an in-process dict keyed by :func:`autotune_signature`, registered with
    the engine-wide render-cache registry (``core.pipeline
    .register_render_cache``) under ``"autotune"`` so ``render_cache_info()``
    / ``render_cache_clear()`` cover it and the serving cache-hit stats stay
    truthful;
  * a best-effort JSON file (``REPRO_AUTOTUNE_CACHE`` env override, default
    ``results/autotune_cache.json``) so a tuned config survives the process
    — a later ``engine.open(tile_params='auto')`` for the same signature
    reloads the winner instead of re-running the search.

The in-memory layer also tracks which SCENE OBJECT produced each entry so
``Renderer.close()`` can evict its handle's entries
(:func:`evict_autotune_entries` — the same lifecycle fix
``evict_scene_layouts`` applies to the scene-layout cache): a served scene
that is committed and closed repeatedly must not accrete per-scene state in
a process-wide dict. The disk layer is untouched by eviction — that is the
persistence the trajectory needs.

A signature deliberately hashes GEOMETRY, not parameter values: the tuned
trade-off depends on how many gaussians cover how many pixels, not on the
exact float contents, so a retrained checkpoint of the same scene reuses
the tune.
"""
from __future__ import annotations

import json
import os
import tempfile
import threading
from typing import Dict, Optional

import jax

from repro.core.pipeline import RenderConfig, register_render_cache
from repro.obs import get_registry
from repro.sharding.scene import ShardedScene

_ENV_PATH = "REPRO_AUTOTUNE_CACHE"
_DEFAULT_PATH = os.path.join("results", "autotune_cache.json")
# In-memory bound (the registry contract wants bounded caches with int
# maxsize — engine/handle.py, serving/sharded.py). FIFO on overflow; the
# disk layer keeps everything, so an evicted signature reloads, never
# re-searches.
_CACHE_MAX = 64

_lock = threading.RLock()
_cache: Dict[tuple, dict] = {}
_by_scene: Dict[int, set] = {}
_stats = {"hits": 0, "misses": 0}
_disk_loaded = False


def cache_path() -> str:
    return os.environ.get(_ENV_PATH) or _DEFAULT_PATH


def autotune_signature(scene, width: int, height: int, cfg: RenderConfig,
                       mesh=None) -> tuple:
    """The cache key: (scene geometry, resolution, backend, mesh layout,
    device).

    Scene geometry is the gaussian count (+ shard layout for a pre-sharded
    scene); the config contributes every knob that changes which candidate
    wins EXCEPT the three swept ones (tile/group/tile_capacity — the result,
    not the key — plus group_capacity, which the search derives from them).
    The device's platform and kind close the key: a winner timed on the CPU
    never steers a TPU run through the persisted file.
    """
    if isinstance(scene, ShardedScene):
        geom = ("sharded", scene.num_shards, scene.shard_size)
    else:
        geom = ("scene", int(scene.num_gaussians))
    mesh_shape = tuple(sorted(dict(mesh.shape).items())) if mesh is not None else ()
    device = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
    return (
        geom,
        int(width), int(height),
        cfg.backend, cfg.mode,
        cfg.boundary_group, cfg.boundary_tile,
        cfg.span, cfg.chunk, cfg.early_exit,
        cfg.scene_shards, cfg.feature_gather,
        mesh_shape,
        device.platform, device.device_kind,
    )


# -- disk layer (best-effort) -------------------------------------------------


def _load_disk() -> None:
    """Merge the persisted file into memory once per process (or after a
    clear). Missing/corrupt files are treated as empty — persistence is
    best-effort, never load-bearing for correctness."""
    global _disk_loaded
    if _disk_loaded:
        return
    _disk_loaded = True
    try:
        with open(cache_path()) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return
    for key, entry in doc.get("entries", {}).items():
        try:
            sig = eval(key, {"__builtins__": {}})  # repr'd tuple of literals
        except Exception:
            continue
        if isinstance(sig, tuple) and isinstance(entry, dict):
            _cache.setdefault(sig, dict(entry, source="disk"))


def _save_disk() -> None:
    """Rewrite the persisted file from the in-memory entries (atomic
    tmp+rename; failures are swallowed — a read-only checkout still tunes,
    it just re-tunes next process)."""
    path = cache_path()
    try:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        entries = {
            repr(sig): {k: v for k, v in e.items() if k != "source"}
            for sig, e in _cache.items()
        }
        fd, tmp = tempfile.mkstemp(dir=d or ".", suffix=".tmp")
        with os.fdopen(fd, "w") as f:
            json.dump({"schema": "repro.autotune_cache/v1", "entries": entries},
                      f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass


# -- in-memory layer ----------------------------------------------------------


def lookup(sig: tuple, scene=None) -> Optional[dict]:
    """The cached entry for ``sig`` (memory, then the persisted file), or
    None. Counts a hit/miss; a hit with ``scene`` given is re-attributed to
    that scene object for close()-time eviction."""
    with _lock:
        _load_disk()
        entry = _cache.get(sig)
        if entry is None:
            _stats["misses"] += 1
            get_registry().counter("autotune.cache_misses_total").inc()
            return None
        _stats["hits"] += 1
        get_registry().counter("autotune.cache_hits_total").inc()
        if scene is not None:
            _by_scene.setdefault(id(scene), set()).add(sig)
        return dict(entry)


def store(sig: tuple, entry: dict, scene=None, persist: bool = True) -> None:
    """Record a tuned result. ``entry`` must be JSON-serializable (the disk
    layer round-trips it); ``persist=False`` keeps it in-memory only."""
    with _lock:
        _load_disk()
        get_registry().counter("autotune.stores_total").inc()
        _cache[sig] = dict(entry)
        if scene is not None:
            _by_scene.setdefault(id(scene), set()).add(sig)
        if persist:
            _save_disk()
        while len(_cache) > _CACHE_MAX:   # FIFO; disk (above) keeps them all
            _cache.pop(next(iter(_cache)))


def evict_autotune_entries(scene) -> int:
    """Drop every IN-MEMORY entry attributed to ``scene`` (any signature).

    The ``Renderer.close()`` lifecycle hook, mirroring
    ``serving.sharded.evict_scene_layouts``: per-scene state must not
    outlive the handle that created it. The persisted file keeps the
    entries — a re-open reloads the tune from disk instead of re-searching.
    Returns the number of entries evicted."""
    global _disk_loaded
    with _lock:
        sigs = _by_scene.pop(id(scene), set())
        n = 0
        for sig in sigs:
            if _cache.pop(sig, None) is not None:
                n += 1
        if n:
            # The persisted file may still hold the evicted signatures; mark
            # it unmerged so the next lookup reloads instead of re-searching.
            _disk_loaded = False
        return n


def _info() -> dict:
    with _lock:
        return {
            "hits": _stats["hits"],
            "misses": _stats["misses"],
            "currsize": len(_cache),
            "maxsize": _CACHE_MAX,
        }


def _clear() -> None:
    global _disk_loaded
    with _lock:
        _cache.clear()
        _by_scene.clear()
        _stats["hits"] = 0
        _stats["misses"] = 0
        _disk_loaded = False   # next lookup reloads the persisted file


register_render_cache("autotune", info=_info, clear=_clear)
