"""The session-style engine handle (repro.engine, DESIGN.md §11).

Covers the handle-semantics contract of the API redesign:
  * bitwise parity of Renderer.render / .render_batch / .submit against the
    legacy free entry points for every mode x backend x shard count;
  * per-handle jit caches: hits across repeated calls, registration with the
    render-cache registry, and close() leaving the registry empty;
  * the layout-cache lifecycle fix (close() evicts every layout of the
    handle's scene, at any shard count);
  * deprecation shims emitting exactly one DeprecationWarning per call and
    the console-script entry points resolving to importable callables.
"""
from __future__ import annotations

import dataclasses
import re
import warnings
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from repro import engine
from repro.core import make_camera, orbit_cameras
from repro.core.pipeline import (
    RenderConfig,
    render_batch,
    render_cache_clear,
    render_cache_info,
)

REPO = Path(__file__).resolve().parents[1]

INT_COUNTERS = (
    "n_visible", "n_candidate_tests", "n_pairs_sort", "sort_ops",
    "n_bit_tests", "fifo_ops", "alpha_ops", "blend_ops", "tile_entries",
    "overflow", "span_overflow",
)


def _assert_bitwise(a, b, what):
    assert (np.asarray(a.image) == np.asarray(b.image)).all(), (
        f"{what}: image diverges"
    )
    for name in INT_COUNTERS:
        va = np.asarray(getattr(a.stats, name))
        vb = np.asarray(getattr(b.stats, name))
        assert (va == vb).all(), f"{what}: counter {name} diverges"


def _legacy(scene, cam, cams, cfg):
    """The deprecated free-function outputs the handle must match bitwise."""
    from repro.core.pipeline import render_jit
    from repro.serving.sharded import render_batch_sharded

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        single = render_jit(scene, cam, cfg)
        batch = render_batch_sharded(scene, cams, cfg, pad_to=len(cams))
    return single, batch


# Fast lane: the gstg-reference pair (the paper's mode, both shard counts).
# The other modes and the pallas interpret runs ride the slow lane — the
# fast lane still pins those paths through tests/test_sharding.py (engine-
# level parity, all modes) and tests/test_golden.py (both backends).
PARITY_CASES = [
    pytest.param(
        mode, backend, shards,
        marks=(
            [] if (backend, mode) == ("reference", "gstg")
            else [pytest.mark.slow]
        ),
        id=f"{mode}-{backend}-D{shards}",
    )
    for mode in ("gstg", "tile_baseline", "group_baseline")
    for backend in ("reference", "pallas")
    for shards in (1, 2)
]


@pytest.mark.parametrize("mode,backend,shards", PARITY_CASES)
def test_handle_bitwise_parity_vs_legacy(tiny_scene, mode, backend, shards):
    """Renderer.render / .render_batch / .submit are bitwise-identical to
    the legacy render_jit / render_batch(_sharded) paths for every mode x
    backend x D — the acceptance contract of the handle redesign."""
    cfg = RenderConfig(
        tile=16, group=64, group_capacity=256, tile_capacity=256,
        mode=mode, backend=backend, scene_shards=shards,
    )
    cams = orbit_cameras(2, 4.5, 64, 64)
    legacy_single, legacy_batch = _legacy(tiny_scene, cams[0], cams, cfg)

    with engine.open(tiny_scene, cfg, max_batch=2, max_wait=30.0) as r:
        _assert_bitwise(r.render(cams[0]), legacy_single, "render vs render_jit")
        out_b = r.render_batch(cams, pad_to=2)
        _assert_bitwise(out_b, legacy_batch, "render_batch vs legacy sharded")
        if shards == 1:
            plain = render_batch(tiny_scene, cams, cfg)
            assert (np.asarray(out_b.image) == np.asarray(plain.image)).all()

        # submit(): max_batch=2 fills one bucket -> ONE dispatch through the
        # same padded shape as the render_batch above (a cache hit, not a
        # recompile), so the futures must come back bitwise-identical.
        before = r.cache_info()
        futs = [r.submit(c) for c in cams]
        results = [f.result(timeout=600) for f in futs]
        for i, res in enumerate(results):
            assert (res.image == np.asarray(out_b.image[i])).all(), (
                f"submit result {i} diverges from render_batch"
            )
        after = r.cache_info()
        assert after["misses"] == before["misses"], "submit recompiled"
    engine.close_default_renderers()


def test_handle_cache_hits_across_calls(tiny_scene, base_cfg):
    """Repeated handle calls reuse the per-handle compiled renderers: one
    miss per (kind, geometry), hits afterwards — including across distinct
    cameras of the same resolution."""
    cam_a = make_camera((0.0, 1.0, 4.5), (0, 0, 0), 64, 64)
    cam_b = make_camera((1.5, 0.8, 4.0), (0, 0, 0), 64, 64)
    with engine.open(tiny_scene, base_cfg) as r:
        r.render(cam_a)
        assert r.cache_info()["misses"] == 1
        r.render(cam_b)                        # same geometry: hit
        assert r.cache_info() == {
            "hits": 1, "misses": 1, "currsize": 1, "maxsize": 64,
        }
        r.render_batch([cam_a, cam_b])         # batch kind: new miss
        r.render_batch([cam_b, cam_a])
        info = r.cache_info()
        assert (info["hits"], info["misses"], info["currsize"]) == (2, 2, 2)
        # the handle cache is visible through the engine-wide registry
        assert render_cache_info()[r.cache_name] == info


def test_memory_analysis_reads_the_rendered_program(tiny_scene, base_cfg):
    """The memory plan comes from the handle's own single-camera program:
    no new cache entry, and its arguments hold the committed scene."""
    from repro.utils import pytree_bytes

    cam = make_camera((0.0, 1.0, 4.5), (0, 0, 0), 64, 64)
    with engine.open(tiny_scene, base_cfg) as r:
        r.render(cam)
        plan = r.memory_analysis(cam)
        assert r.cache_info()["misses"] == 1
        assert plan.argument_size_in_bytes >= pytree_bytes(r.committed_scene)
        assert plan.output_size_in_bytes > 0


def test_handle_close_empties_registry(tiny_scene, base_cfg):
    """close() unregisters the handle cache, drops its executables, and
    evicts the handle's scene layouts — render_cache_info() shows an empty
    registry afterwards."""
    render_cache_clear()
    engine.close_default_renderers()
    cfg = dataclasses.replace(base_cfg, scene_shards=2)
    cam = make_camera((0.0, 1.0, 4.5), (0, 0, 0), 64, 64)

    r = engine.open(tiny_scene, cfg)
    r.render(cam)
    name = r.cache_name
    info = render_cache_info()
    assert info[name]["currsize"] == 1
    assert info["scene_layout"]["currsize"] == 1   # host-staged layout

    r.close()
    info = render_cache_info()
    assert name not in info, "closed handle left its cache registered"
    assert sum(k["currsize"] for k in info.values()) == 0, (
        f"registry not empty after close: {info}"
    )
    with pytest.raises(RuntimeError, match="closed"):
        r.render(cam)
    with pytest.raises(RuntimeError, match="closed"):
        r.submit(cam)
    r.close()                                       # idempotent


def test_close_releases_only_own_layout(tiny_scene, base_cfg):
    """close() releases exactly this handle's own (scene, D) layout
    reference. Other layouts of the scene are NOT nuked implicitly any
    more (the shared-eviction fix) — they stay until explicit
    evict_scene_layouts()/capacity eviction/scene GC."""
    from repro.serving.sharded import evict_scene_layouts, shard_scene_cached

    render_cache_clear()
    r = engine.open(tiny_scene, base_cfg, scene_shards=2)
    shard_scene_cached(tiny_scene, 3)   # a second, UNREFERENCED layout
    assert render_cache_info()["scene_layout"]["currsize"] == 2
    r.close()
    # The handle's own (scene, 2) entry is gone; the unreferenced bare
    # layout survives until explicitly evicted.
    assert render_cache_info()["scene_layout"]["currsize"] == 1
    assert evict_scene_layouts(tiny_scene) == 1
    assert render_cache_info()["scene_layout"]["currsize"] == 0


def test_close_keeps_layout_shared_with_other_open_handle(
    tiny_scene, base_cfg
):
    """Regression (two handles, one scene): closing one handle must not
    evict the host layout the OTHER open handle still references —
    close() used to call evict_scene_layouts(scene) unconditionally,
    nuking every layout of the scene."""
    render_cache_clear()
    cam = make_camera((0.0, 1.0, 4.5), (0, 0, 0), 64, 64)
    a = engine.open(tiny_scene, base_cfg, scene_shards=2)
    b = engine.open(tiny_scene, base_cfg, scene_shards=2)
    assert render_cache_info()["scene_layout"]["currsize"] == 1
    a.close()
    assert render_cache_info()["scene_layout"]["currsize"] == 1, (
        "closing one handle evicted a layout another open handle references"
    )
    b.render(cam)                       # the survivor still renders
    b.close()
    assert render_cache_info()["scene_layout"]["currsize"] == 0


def test_open_accepts_presharded_scene(tiny_scene, base_cfg):
    """A host-staged ShardedScene commits as-is (its shard count wins) and
    renders bitwise-identically to the replicated handle."""
    from repro.sharding.scene import shard_scene_host

    cams = orbit_cameras(2, 4.5, 64, 64)
    staged = shard_scene_host(tiny_scene, 2)
    with engine.open(staged, base_cfg) as sharded, \
            engine.open(tiny_scene, base_cfg) as repl:
        assert sharded.scene_shards == 2
        a = sharded.render_batch(cams)
        b = repl.render_batch(cams)
        assert (np.asarray(a.image) == np.asarray(b.image)).all()
    with pytest.raises(ValueError, match="pre-sharded"):
        engine.open(shard_scene_host(tiny_scene, 2), base_cfg, scene_shards=3)


def test_open_enforces_device_budget(tiny_scene, base_cfg):
    """An over-budget commit refuses loudly (the simulated HBM cap moved
    into the handle); a generous budget commits fine and reports the
    per-device footprint."""
    with pytest.raises(ValueError, match="budget"):
        engine.open(tiny_scene, base_cfg, device_budget_mb=1e-6)
    with engine.open(tiny_scene, base_cfg, device_budget_mb=64.0) as r:
        assert 0 < r.stats()["scene_mb_per_device"] <= 64.0


def test_budget_counts_logical_shards_as_replicated(tiny_scene, base_cfg):
    """A shard axis the mesh cannot realize (no 'model' axis) leaves the
    full scene on every device, so the budget must count it as replicated —
    a half-size budget that only a PHYSICAL 2-way shard could meet refuses."""
    from repro.launch.mesh import make_render_mesh
    from repro.sharding.scene import shard_scene_host
    from repro.utils import pytree_bytes

    half_mb = pytree_bytes(tiny_scene) / 2 / 2**20
    mesh = make_render_mesh(1)                     # 1-D ('data',): no 'model'
    with pytest.raises(ValueError, match="replicated"):
        engine.open(
            tiny_scene, base_cfg, mesh=mesh, scene_shards=2,
            device_budget_mb=half_mb * 1.2,
        )
    # The budget applies to pre-sharded scenes too (their layout is fixed:
    # no escalation, just enforcement).
    with pytest.raises(ValueError, match="budget"):
        engine.open(
            shard_scene_host(tiny_scene, 2), base_cfg, mesh=mesh,
            device_budget_mb=1e-6,
        )


def test_submit_failure_resolves_future_exception(tiny_scene, base_cfg):
    """A request the dispatch cannot render resolves ITS future with the
    exception instead of killing the worker for everyone behind it."""
    bad_cam = SimpleNamespace(
        width=64, height=64, znear=0.2, zfar=1000.0,
        R=np.zeros((2, 2), np.float32), t=np.zeros((3,), np.float32),
        fx=60.0, fy=60.0, cx=32.0, cy=32.0,
    )
    good_cam = make_camera((0.0, 1.0, 4.5), (0, 0, 0), 64, 64)
    with engine.open(tiny_scene, base_cfg, max_batch=1, max_wait=0.0) as r:
        bad = r.submit(bad_cam)
        with pytest.raises(Exception):
            bad.result(timeout=600)
        good = r.submit(good_cam)          # worker survived the bad request
        expect = r.render(good_cam)
        # The futures worker runs the batched program, render() the single
        # one: two compiled programs agree to float32 fusion rounding (one
        # ulp of a projected mean moves a pixel by up to ~1e-5).
        np.testing.assert_allclose(
            good.result(timeout=600).image, np.asarray(expect.image),
            rtol=0, atol=1e-5)


def test_cancelled_future_does_not_kill_worker(tiny_scene, base_cfg):
    """Cancelling a pending submit() must not crash the worker or lose the
    other requests sharing its bucket — cancelled futures are skipped at
    resolve time (Future.set_* on a cancelled future raises)."""
    cams = orbit_cameras(3, 4.5, 64, 64)
    with engine.open(tiny_scene, base_cfg, max_batch=3, max_wait=30.0) as r:
        futs = [r.submit(c) for c in cams[:2]]
        cancelled = futs[0].cancel()     # still PENDING in the scheduler
        r.submit(cams[2])                # fills the bucket -> dispatch
        sibling = futs[1].result(timeout=600)
        assert cancelled and futs[0].cancelled()
        expect = r.render(cams[1])
        assert (sibling.image == np.asarray(expect.image)).all()
        # worker survived: a fresh submit still completes. flush() forces
        # the partial bucket out instead of waiting max_wait (30s) out.
        fut = r.submit(cams[0])
        r.flush(timeout=600)
        assert fut.result(timeout=60) is not None


def test_worker_crash_fails_outstanding_futures(tiny_scene, base_cfg):
    """A crash OUTSIDE the dispatch handler (scheduler bug) must terminate
    every outstanding future with the exception — callers blocked on
    .result() (and the gateway's failover accounting above them) depend on
    futures always terminating."""
    cam = make_camera((0.0, 1.0, 4.5), (0, 0, 0), 64, 64)
    with engine.open(tiny_scene, base_cfg, max_batch=2, max_wait=30.0) as r:
        def bad_add(req):
            raise RuntimeError("scheduler exploded")

        r._scheduler.add = bad_add
        fut = r.submit(cam)
        with pytest.raises(RuntimeError, match="scheduler exploded"):
            fut.result(timeout=600)


def test_close_fails_futures_the_worker_never_resolved(tiny_scene, base_cfg):
    """close() on a handle whose worker never got to a pending submit must
    fail that future, not strand it PENDING forever."""
    r = engine.open(tiny_scene, base_cfg, max_batch=2, max_wait=30.0)
    r._ensure_worker = lambda: None        # a worker that never runs
    cam = make_camera((0.0, 1.0, 4.5), (0, 0, 0), 64, 64)
    fut = r.submit(cam)
    r.close()
    with pytest.raises(RuntimeError, match="closed before the request"):
        fut.result(timeout=60)


def test_dropped_handle_is_not_pinned_by_registry(tiny_scene, base_cfg):
    """A handle dropped WITHOUT close() must still be collectable (the
    registry holds only weak references) and its registry entry must
    disappear — the leak-safety net behind the close() contract."""
    import gc

    r = engine.open(tiny_scene, base_cfg)
    name = r.cache_name
    ref = __import__("weakref").ref(r)
    assert name in render_cache_info()
    del r
    gc.collect()
    assert ref() is None, "registry pinned a dropped handle"
    assert name not in render_cache_info()


@pytest.mark.filterwarnings("always::DeprecationWarning")
def test_deprecated_shims_warn_exactly_once_per_call(tiny_scene, base_cfg):
    """Each legacy free function emits exactly ONE DeprecationWarning per
    call (no cascades through the handle they delegate to) and returns the
    handle-backed result.

    Explicitly whitelisted from the suite-wide ``error::DeprecationWarning``
    filter for repro.* (pyproject.toml): this test MUST observe the shim
    warnings as warnings to count them."""
    from repro.core.pipeline import render_image, render_jit
    from repro.serving.sharded import render_batch_sharded

    cam = make_camera((0.0, 1.0, 4.5), (0, 0, 0), 64, 64)
    calls = [
        lambda: render_jit(tiny_scene, cam, base_cfg),
        lambda: render_image(tiny_scene, cam, base_cfg),
        lambda: render_batch_sharded(tiny_scene, [cam], base_cfg),
    ]
    for call in calls:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            call()
        deps = [w for w in caught if issubclass(w.category, DeprecationWarning)]
        assert len(deps) == 1, (
            f"{call}: expected exactly 1 DeprecationWarning, got "
            f"{[str(w.message) for w in deps]}"
        )
    engine.close_default_renderers()


def test_internal_shim_callers_error_under_suite_filters(tiny_scene, base_cfg):
    """The pyproject ``error::DeprecationWarning:repro`` contract: a shim
    call ATTRIBUTED to a repro.* module (an internal caller — the shims warn
    with stacklevel=2) raises under the suite's warning filters, so internal
    code can never silently regress onto the deprecated entry points. The
    simulated caller lives in a module named ``repro._filter_selftest``;
    test-module callers (like every other test here) only warn."""
    import textwrap
    import types

    mod = types.ModuleType("repro._filter_selftest")
    exec(
        compile(
            textwrap.dedent(
                """
                def call(scene, cam, cfg):
                    from repro.core.pipeline import render_jit
                    return render_jit(scene, cam, cfg)
                """
            ),
            "repro/_filter_selftest.py",
            "exec",
        ),
        mod.__dict__,
    )
    cam = make_camera((0.0, 1.0, 4.5), (0, 0, 0), 64, 64)
    with pytest.raises(DeprecationWarning, match="render_jit"):
        mod.call(tiny_scene, cam, base_cfg)
    engine.close_default_renderers()


@pytest.mark.filterwarnings("always::DeprecationWarning")
def test_shims_share_one_default_handle(tiny_scene, base_cfg):
    """Repeated legacy calls with one (scene, cfg) ride ONE module-default
    handle — the legacy executable-reuse behavior, now handle-owned.
    Whitelisted from the repro.* DeprecationWarning error filter like the
    once-per-call test above."""
    from repro.core.pipeline import render_jit

    engine.close_default_renderers()
    cam = make_camera((0.0, 1.0, 4.5), (0, 0, 0), 64, 64)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        render_jit(tiny_scene, cam, base_cfg)
        handle = engine.default_renderer(tiny_scene, base_cfg)
        before = handle.cache_info()
        render_jit(tiny_scene, cam, base_cfg)
        assert engine.default_renderer(tiny_scene, base_cfg) is handle
    after = handle.cache_info()
    assert after["hits"] == before["hits"] + 1
    assert after["misses"] == before["misses"]
    engine.close_default_renderers()
    assert handle.closed


def test_console_script_entry_points_import():
    """pyproject's [project.scripts] targets must import and be callable —
    the console-script smoke (the package is used from PYTHONPATH here, so
    the metadata is parsed straight from pyproject.toml)."""
    import importlib

    text = (REPO / "pyproject.toml").read_text()
    block = re.search(r"\[project\.scripts\](.*?)(?:\n\[|\Z)", text, re.S)
    assert block, "pyproject.toml lost its [project.scripts] table"
    entries = dict(
        re.findall(r'^([\w-]+)\s*=\s*"([^"]+)"', block.group(1), re.M)
    )
    assert set(entries) == {"repro-render", "repro-serve", "repro-gateway"}
    for name, target in entries.items():
        module, _, attr = target.partition(":")
        fn = getattr(importlib.import_module(module), attr)
        assert callable(fn), f"{name} -> {target} is not callable"
