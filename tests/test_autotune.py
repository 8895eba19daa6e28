"""Autotuned tile-grouping (DESIGN.md §13): signature/cache semantics, the
two-phase search, and the engine-handle 'auto' path.

The load-bearing guarantee: ``engine.open(..., tile_params='auto')`` renders
BITWISE-identically to a fixed-config open of the same resolved params —
the handle commits the tuned knobs before any compiled renderer exists, so
both handles run the identical program.
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro import engine
from repro.autotune import (
    DEFAULT_CAPACITIES,
    DEFAULT_GROUP_FACTORS,
    DEFAULT_TILES,
    Candidate,
    autotune,
    autotune_signature,
    candidate_grid,
    config_for,
    cost_phase,
    sweep,
)
from repro.autotune import cache as at_cache
from repro.core.pipeline import RenderConfig, render_cache_info

# A small grid keeps the e2e searches to a couple of stats passes + one
# measured candidate (~seconds, fast lane).
TINY_OPTS = dict(
    tiles=(16,), group_factors=(2, 4), capacities=(256,),
    top_k=1, warmup=1, reps=1,
)


@pytest.fixture(autouse=True)
def isolated_autotune_cache(tmp_path, monkeypatch):
    """Point the persisted layer at a per-test file and reset the in-memory
    layer on both sides, so tests neither see nor pollute a real cache."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    at_cache._clear()
    yield
    at_cache._clear()


def _cfg(**kw):
    kw.setdefault("mode", "gstg")
    kw.setdefault("span", 6)
    return RenderConfig(
        tile=16, group=64, group_capacity=256, tile_capacity=256, **kw
    )


# -- signature ----------------------------------------------------------------


def test_signature_excludes_swept_knobs(tiny_scene):
    a = autotune_signature(tiny_scene, 128, 128, _cfg())
    b = autotune_signature(
        tiny_scene, 128, 128,
        dataclasses.replace(
            _cfg(), tile=8, group=32, tile_capacity=512, group_capacity=512
        ),
    )
    assert a == b  # tile/group/capacities are the RESULT, not the key


def test_signature_keys_on_geometry_resolution_backend(tiny_scene,
                                                       small_scene):
    base = autotune_signature(tiny_scene, 128, 128, _cfg())
    assert autotune_signature(tiny_scene, 128, 96, _cfg()) != base
    assert autotune_signature(
        tiny_scene, 128, 128, _cfg(backend="pallas")
    ) != base
    assert autotune_signature(
        tiny_scene, 128, 128, _cfg(mode="tile_baseline")
    ) != base
    assert autotune_signature(small_scene, 128, 128, _cfg()) != base
    # the device closes the key: CPU winners never steer a chip run
    dev = jax.devices()[0]
    assert base[-2:] == (dev.platform, dev.device_kind)
    # same geometry, different parameter values -> SAME key (a retrained
    # checkpoint reuses the tune)
    clone = dataclasses.replace(
        tiny_scene, means3d=tiny_scene.means3d + 0.1
    )
    assert autotune_signature(clone, 128, 128, _cfg()) == base


# -- grid / config derivation -------------------------------------------------


def test_candidate_grid_is_legal_and_covers_the_floor():
    grid = candidate_grid()
    assert len(grid) == (
        len(DEFAULT_TILES) * len(DEFAULT_GROUP_FACTORS)
        * len(DEFAULT_CAPACITIES)
    )
    # >= 9 distinct (tile, group) points — the BENCH trajectory floor
    assert len({(c.tile, c.group) for c in grid}) >= 9
    for c in grid:
        assert c.group % c.tile == 0  # legal GridSpec

    cfg = config_for(_cfg(), Candidate(8, 64, 512))
    assert (cfg.tile, cfg.group, cfg.tile_capacity) == (8, 64, 512)
    assert cfg.group_capacity >= cfg.tile_capacity


# -- cache layers -------------------------------------------------------------


def test_cache_store_lookup_and_disk_round_trip(tiny_scene):
    sig = autotune_signature(tiny_scene, 128, 128, _cfg())
    assert at_cache.lookup(sig) is None
    at_cache.store(sig, {"tile": 16, "group": 32, "tile_capacity": 256,
                         "measured_ms": 1.5}, scene=tiny_scene)
    hit = at_cache.lookup(sig, scene=tiny_scene)
    assert hit["tile"] == 16 and hit["measured_ms"] == 1.5
    # survive a "process restart": clear memory, reload from the file
    at_cache._clear()
    hit = at_cache.lookup(sig)
    assert hit is not None and hit["source"] == "disk"
    assert hit["group"] == 32
    # the persisted file is valid schema'd JSON
    with open(at_cache.cache_path()) as f:
        doc = json.load(f)
    assert doc["schema"] == "repro.autotune_cache/v1"
    assert len(doc["entries"]) == 1


def test_eviction_drops_memory_keeps_disk(tiny_scene):
    sig = autotune_signature(tiny_scene, 128, 128, _cfg())
    at_cache.store(sig, {"tile": 16, "group": 64, "tile_capacity": 256},
                   scene=tiny_scene)
    assert at_cache.evict_autotune_entries(tiny_scene) == 1
    assert at_cache._info()["currsize"] == 0   # memory gone...
    at_cache._clear()
    assert at_cache.lookup(sig)["source"] == "disk"  # ...disk survives
    # registered with the engine-wide cache registry
    assert "autotune" in render_cache_info()


# -- the search ---------------------------------------------------------------


def test_cost_phase_counts_and_feasibility(tiny_scene, cam128):
    cands = candidate_grid(tiles=(16,), group_factors=(2, 4),
                           capacities=(8, 256))
    entries = cost_phase(tiny_scene, cam128, _cfg(), cands)
    assert len(entries) == len(cands)
    by_knobs = {(e["tile"], e["group"], e["tile_capacity"]): e
                for e in entries}
    # capacity 8 overflows a 200-gaussian scene at 128px -> infeasible;
    # capacity 256 does not
    assert not by_knobs[(16, 32, 8)]["feasible"]
    assert by_knobs[(16, 32, 256)]["feasible"]
    for e in entries:
        assert e["est_total_s"] > 0
        assert e["measured_ms"] is None  # phase 1 never times anything


def test_autotune_search_caches_and_rehits(tiny_scene, cam128):
    cfg = _cfg()
    res = autotune(tiny_scene, cam128, cfg, **TINY_OPTS)
    assert res.source == "search"
    assert res.measured_ms is not None and res.measured_ms > 0
    assert len(res.trajectory) == 2  # full grid recorded, pruned or not
    again = autotune(tiny_scene, cam128, cfg, **TINY_OPTS)
    assert again.source in ("cache", "disk")
    assert again.candidate == res.candidate


@pytest.mark.slow
def test_sweep_winner_is_measured_minimum(tiny_scene, cam128):
    res = sweep(tiny_scene, cam128, _cfg(),
                tiles=(16,), group_factors=(2, 4), capacities=(256,),
                warmup=1, reps=1)
    measured = [e for e in res.trajectory if e["measured_ms"] is not None]
    assert len(measured) == 2  # top_k=None measures EVERY feasible point
    assert res.measured_ms <= min(e["measured_ms"] for e in measured)
    # a sweep must not have written the cache (benchmarks re-measure)
    assert at_cache._info()["currsize"] == 0


# -- the engine-handle 'auto' path --------------------------------------------


def test_open_auto_bitwise_matches_fixed(tiny_scene, cam128):
    cfg = _cfg()
    with engine.open(tiny_scene, cfg, tile_params="auto",
                     autotune_opts=TINY_OPTS) as ra:
        assert ra.tile_params == "auto (pending)"
        img_a = np.asarray(ra.render(cam128).image)
        tuned = ra.tile_params
        assert isinstance(tuned, tuple)
        assert ra.stats()["tile_params"] == tuned
    with engine.open(tiny_scene, cfg, tile_params=tuned) as rf:
        img_f = np.asarray(rf.render(cam128).image)
    assert (img_a == img_f).all()   # acceptance criterion 4: BITWISE


@pytest.mark.slow
def test_open_auto_bitwise_matches_fixed_pallas(tiny_scene, cam128):
    cfg = _cfg(backend="pallas")
    with engine.open(tiny_scene, cfg, tile_params="auto",
                     autotune_opts=TINY_OPTS) as ra:
        img_a = np.asarray(ra.render(cam128).image)
        tuned = ra.tile_params
    with engine.open(tiny_scene, cfg, tile_params=tuned) as rf:
        img_f = np.asarray(rf.render(cam128).image)
    assert (img_a == img_f).all()


def test_open_explicit_triple_and_validation(tiny_scene, cam128):
    cfg = _cfg()
    with engine.open(tiny_scene, cfg, tile_params=(16, 32, 512)) as r:
        assert r.tile_params == (16, 32, 512)
        assert r.stats()["config"].group == 32
        r.render(cam128)
    with pytest.raises(ValueError):
        engine.open(tiny_scene, cfg, tile_params=(16, 32))
    with pytest.raises(ValueError):
        engine.open(tiny_scene, cfg, tile_params="fastest")


@pytest.mark.slow
def test_close_evicts_autotune_entries_disk_survives(tiny_scene, cam128):
    cfg = _cfg()
    with engine.open(tiny_scene, cfg, tile_params="auto",
                     autotune_opts=TINY_OPTS) as r:
        r.render(cam128)
        assert at_cache._info()["currsize"] == 1
    assert at_cache._info()["currsize"] == 0   # close() evicted (memory)
    # a re-open skips the search: the persisted file answers the lookup
    with engine.open(tiny_scene, cfg, tile_params="auto",
                     autotune_opts=TINY_OPTS) as r2:
        r2.render(cam128)
        assert isinstance(r2.tile_params, tuple)
    info = render_cache_info()["autotune"]
    assert info["hits"] >= 1


@pytest.mark.slow
def test_render_server_autotune_path(tiny_scene, cam128):
    """RenderServer(autotune=True): the first dispatch tunes, the handle
    serves the committed triple afterwards."""
    from repro.serving.queue import RenderRequest
    from repro.serving.server import RenderServer

    cfg = _cfg()
    with RenderServer({"s": tiny_scene}, autotune=True,
                      autotune_opts=TINY_OPTS,
                      max_batch=2, max_wait=0.01) as srv:
        for i in range(2):
            assert srv.submit(RenderRequest(i, "s", cam128, cfg))
        srv.drain()
        assert len(srv.results) == 2
        assert isinstance(srv.commit("s", cfg).tile_params, tuple)


@pytest.mark.slow
def test_auto_render_batch_resolves_from_lane0(tiny_scene, cam128):
    cfg = _cfg()
    with engine.open(tiny_scene, cfg, tile_params="auto",
                     autotune_opts=TINY_OPTS) as r:
        out = r.render_batch([cam128, cam128], pad_to=2)
        assert isinstance(r.tile_params, tuple)
        assert np.asarray(out.image).shape[0] == 2
