"""Observability layer (DESIGN.md §14): tracer + Chrome export, metrics
registry, timed per-stage rendering, and the serving lifecycle spans.

The tracer/metrics unit tests run pure Python (the obs package must not pull
jax — enforced by a subprocess guard, same pattern as the serving layer).
The timed-render tests assert the ONE property the whole layer hangs off:
``RenderConfig(timing=True)`` (per-stage jit + fences) renders
BITWISE-identical images to the default whole-program jit, on both backends.
"""
from __future__ import annotations

import contextlib
import json
import subprocess
import sys
import threading
from pathlib import Path
from types import SimpleNamespace

import pytest

from repro.obs import (
    REQUEST_PHASES,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Tracer,
    emit_request_spans,
    percentile,
    trace_span,
    validate_chrome_trace,
)
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

SRC = str(Path(__file__).resolve().parents[1] / "src")


# ---------------------------------------------------------------------------
# pure layer: imports
# ---------------------------------------------------------------------------


def test_obs_imports_without_jax():
    """repro.obs must not pull jax: the serving admission layer and the
    pure-Python stats surfaces import it, and they run anywhere."""
    code = (
        "import sys; import repro.obs; "
        "import repro.obs.trace, repro.obs.metrics; "
        "assert 'jax' not in sys.modules, 'obs layer imported jax'"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin"},
        capture_output=True,
        text=True,
    )
    assert proc.returncode == 0, proc.stderr


# ---------------------------------------------------------------------------
# pure layer: tracer
# ---------------------------------------------------------------------------


def _manual_clock(start=0.0):
    state = {"t": start}

    def clock():
        return state["t"]

    def advance(dt):
        state["t"] += dt

    clock.advance = advance
    return clock


def test_tracer_records_and_exports_chrome():
    clock = _manual_clock()
    tr = Tracer(clock=clock, enabled=True)
    with tr.span("outer", category="test", args={"k": 1}):
        clock.advance(0.010)
        with tr.span("inner", category="test"):
            clock.advance(0.005)
        clock.advance(0.001)
    evs = tr.events()
    assert [e.name for e in evs] == ["inner", "outer"]   # completion order
    assert evs[1].duration_s == pytest.approx(0.016)
    doc = tr.chrome_trace()
    assert doc["schema"] == obs_trace.SCHEMA
    assert doc["dropped"] == 0
    assert validate_chrome_trace(doc) == []
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"outer", "inner"}
    outer = next(e for e in xs if e["name"] == "outer")
    assert outer["dur"] == pytest.approx(16000.0)        # us
    assert outer["args"] == {"k": 1}
    # metadata names the process and the recording thread
    mnames = {e["name"] for e in doc["traceEvents"] if e["ph"] == "M"}
    assert {"process_name", "thread_name"} <= mnames


def test_tracer_ring_is_bounded():
    clock = _manual_clock()
    tr = Tracer(capacity=4, clock=clock, enabled=True)
    for i in range(10):
        tr.complete(f"s{i}", 0.0, 1.0)
    assert len(tr.events()) == 4
    assert tr.dropped == 6
    assert [e.name for e in tr.events()] == ["s6", "s7", "s8", "s9"]
    tr.clear()
    assert tr.events() == [] and tr.dropped == 0


def test_tracer_disabled_records_nothing_unless_forced():
    tr = Tracer(clock=_manual_clock(), enabled=False)
    with tr.span("ambient"):
        pass
    tr.complete("plain", 0.0, 1.0)
    assert tr.events() == []
    tr.complete("forced", 0.0, 1.0, force=True)   # the timed-stage opt-in
    assert [e.name for e in tr.events()] == ["forced"]


def test_spans_are_live_profiler_annotations_anchored_on_the_clock():
    """Every span opens the installed annotation with its start on the
    tracer's clock as ``mono``, recorded in the ring or not."""
    opened = []

    @contextlib.contextmanager
    def annotation(name, **args):
        opened.append((name, args))
        yield

    prev = obs_trace.set_annotation_factory(annotation)
    try:
        clock = _manual_clock(5.0)
        for enabled in (False, True):
            tr = Tracer(clock=clock, enabled=enabled)
            with tr.span("serve/dispatch"):
                clock.advance(1.0)
                with tr.span("serve/fetch"):
                    clock.advance(0.5)
        assert opened == [("serve/dispatch", {"mono": 5.0}),
                          ("serve/fetch", {"mono": 6.0}),
                          ("serve/dispatch", {"mono": 6.5}),
                          ("serve/fetch", {"mono": 7.5})]
        assert [e.name for e in tr.events()] == ["serve/fetch",
                                                 "serve/dispatch"]
        assert tr.events()[1].t0 == 6.5
    finally:
        obs_trace.set_annotation_factory(prev)


def test_trace_span_decorator_resolves_tracer_at_call_time():
    from repro.obs import get_tracer, set_tracer

    @trace_span("decorated", category="test")
    def f(x):
        return x + 1

    prev = set_tracer(Tracer(clock=_manual_clock(), enabled=True))
    try:
        assert f(1) == 2
        assert [e.name for e in get_tracer().events()] == ["decorated"]
    finally:
        set_tracer(prev)


def test_tracer_thread_lanes():
    """Spans from different threads land on different tids (no false
    nesting violations across real concurrency)."""
    tr = Tracer(enabled=True)   # real clock: threads overlap in time
    barrier = threading.Barrier(4)   # all alive at once => distinct idents

    def work():
        barrier.wait(timeout=10)
        with tr.span("t-span"):
            pass

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    with tr.span("main-span"):
        pass
    assert len({e.tid for e in tr.events()}) == 5
    assert validate_chrome_trace(tr.chrome_trace()) == []


def test_validate_chrome_trace_catches_bad_documents():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": "nope"}) != []
    assert validate_chrome_trace(
        {"traceEvents": [{"ph": "X", "pid": 1, "tid": 1, "ts": 0.0}]}
    )  # missing name + dur
    # partial overlap on one lane is the nesting violation
    bad = {
        "traceEvents": [
            {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 10.0},
            {"name": "b", "ph": "X", "pid": 1, "tid": 1, "ts": 5.0, "dur": 10.0},
        ]
    }
    assert any("partially overlaps" in e for e in validate_chrome_trace(bad))
    # same spans on DIFFERENT lanes are fine
    ok = {
        "traceEvents": [
            {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 10.0},
            {"name": "b", "ph": "X", "pid": 1, "tid": 2, "ts": 5.0, "dur": 10.0},
        ]
    }
    assert validate_chrome_trace(ok) == []


def test_emit_request_spans_tiles_the_lifecycle():
    tr = Tracer(clock=_manual_clock(), enabled=True)
    stamps = {"enqueue": 1.0, "batch_form": 1.2, "dispatch": 1.5,
              "device_done": 2.5, "fetched": 2.55, "resolve": 2.6}
    emit_request_spans(tr, 7, stamps, args={"scene_id": "train"})
    by_name = {e.name: e for e in tr.events()}
    assert set(by_name) == {"request"} | {n for _, _, n in REQUEST_PHASES}
    assert by_name["request"].duration_s == pytest.approx(1.6)
    assert by_name["request/device"].duration_s == pytest.approx(1.0)
    assert by_name["request/fetch"].duration_s == pytest.approx(0.05)
    assert by_name["request"].args["request_id"] == 7
    # all on one synthetic lane, nested under the enclosing request span
    assert len({e.tid for e in tr.events()}) == 1
    assert validate_chrome_trace(tr.chrome_trace()) == []
    # missing stamps skip their phase; disabled tracer records nothing
    tr.clear()
    emit_request_spans(tr, 8, {"dispatch": 1.0, "device_done": 2.0})
    assert [e.name for e in tr.events()] == ["request/device"]
    tr2 = Tracer(clock=_manual_clock(), enabled=False)
    emit_request_spans(tr2, 9, stamps)
    assert tr2.events() == []


# ---------------------------------------------------------------------------
# pure layer: metrics
# ---------------------------------------------------------------------------


def test_counter_and_gauge():
    c = Counter()
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = Gauge()
    g.set(2.5)
    g.set(1.5)
    assert g.value == 1.5


def test_histogram_exact_below_cap():
    h = Histogram(cap=100)
    h.observe_many(float(i) for i in range(1, 11))
    assert h.count == 10 and h.sum == 55.0
    assert (h.min, h.max) == (1.0, 10.0)
    assert not h.sampled
    assert h.percentile(50) == pytest.approx(5.5)
    snap = h.snapshot()
    assert snap["mean"] == pytest.approx(5.5)
    assert snap["reservoir"] == 10 and not snap["sampled"]


def test_histogram_reservoir_bounds_memory():
    h = Histogram(cap=64, seed=0)
    for i in range(10_000):
        h.observe(float(i))
    assert len(h.values()) == 64          # bounded
    assert h.count == 10_000              # exact count survives sampling
    assert h.sum == pytest.approx(sum(range(10_000)))
    assert (h.min, h.max) == (0.0, 9999.0)
    assert h.sampled and h.snapshot()["sampled"]
    # deterministic seed: same stream -> same reservoir
    h2 = Histogram(cap=64, seed=0)
    for i in range(10_000):
        h2.observe(float(i))
    assert h.values() == h2.values()


def test_percentile_contracts_differ_on_empty():
    """obs.percentile -> 0.0 (JSON-plain snapshots); serving keeps nan so
    the render_serve CI exit check fails an empty run."""
    from repro.serving.stats import percentile as serving_percentile

    assert percentile([], 99) == 0.0
    assert serving_percentile([], 99) != serving_percentile([], 99)   # nan
    assert percentile([1.0, 2.0, 3.0], 50) == serving_percentile(
        [1.0, 2.0, 3.0], 50)


def test_registry_instruments_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("a.total").inc(3)
    reg.gauge("b.level").set(0.5)
    reg.histogram("c.lat").observe_many([0.1, 0.2])
    with pytest.raises(TypeError):
        reg.gauge("a.total")             # kind mismatch
    assert reg.counter("a.total").value == 3   # get-or-create returns same
    snap = reg.snapshot()
    assert snap["schema"] == obs_metrics.SCHEMA
    assert snap["counters"] == {"a.total": 3}
    assert snap["gauges"] == {"b.level": 0.5}
    assert snap["histograms"]["c.lat"]["count"] == 2
    json.dumps(snap)                      # JSON-plain throughout
    assert reg.drop("b.") == 1
    assert "b.level" not in reg.snapshot()["gauges"]


def test_registry_collectors_run_at_snapshot():
    reg = MetricsRegistry()
    state = {"v": 1.0}
    reg.register_collector("t", lambda r: r.gauge("scraped.v").set(state["v"]))
    assert reg.snapshot()["gauges"]["scraped.v"] == 1.0
    state["v"] = 2.0
    assert reg.snapshot()["gauges"]["scraped.v"] == 2.0
    reg.unregister_collector("t")
    state["v"] = 3.0
    assert reg.snapshot()["gauges"]["scraped.v"] == 2.0   # stale, not rerun


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("serving.requests_total").inc(2)
    reg.histogram("serving.latency_s").observe(0.5)
    text = reg.prometheus_text()
    assert "# TYPE serving_requests_total counter" in text
    assert "serving_requests_total 2" in text
    assert 'serving_latency_s{quantile="0.99"} 0.5' in text
    assert "serving_latency_s_count 1" in text


# ---------------------------------------------------------------------------
# jax layer: timed per-stage rendering (the bitwise guarantee)
# ---------------------------------------------------------------------------


@pytest.fixture()
def fresh_tracer():
    from repro.obs import set_tracer

    prev = set_tracer(Tracer(enabled=True))
    try:
        yield
    finally:
        set_tracer(prev)


def _render_pair(scene, cam, cfg):
    import dataclasses

    import numpy as np

    from repro import engine

    with engine.open(scene, cfg) as r:
        plain = np.asarray(r.render(cam).image)
    with engine.open(scene, dataclasses.replace(cfg, timing=True)) as r:
        timed = np.asarray(r.render(cam).image)
    return plain, timed


def test_timed_render_bitwise_reference(small_scene, cam128, base_cfg,
                                        fresh_tracer):
    import dataclasses

    from repro.obs import get_tracer

    plain, timed = _render_pair(
        small_scene, cam128, dataclasses.replace(base_cfg, backend="reference")
    )
    assert (plain == timed).all()
    names = {e.name for e in get_tracer().events() if e.category == "stage"}
    assert {"stage/project", "stage/identify", "stage/bin", "stage/bitmask",
            "stage/compact", "stage/rasterize", "stage/render"} <= names
    assert validate_chrome_trace(get_tracer().chrome_trace()) == []


@pytest.mark.slow
def test_timed_render_bitwise_pallas(small_scene, cam128, base_cfg,
                                     fresh_tracer):
    import dataclasses

    plain, timed = _render_pair(
        small_scene, cam128, dataclasses.replace(base_cfg, backend="pallas")
    )
    assert (plain == timed).all()


def test_timed_render_bitwise_sharded(small_scene, cam128, base_cfg,
                                      fresh_tracer):
    """Sharded frontend under timing: the per-stage jit(vmap) programs (incl.
    the merge stage) must match the whole-program sharded render bitwise."""
    import dataclasses

    from repro.obs import get_tracer

    plain, timed = _render_pair(
        small_scene, cam128, dataclasses.replace(base_cfg, scene_shards=2)
    )
    assert (plain == timed).all()
    names = {e.name for e in get_tracer().events() if e.category == "stage"}
    assert "stage/merge" in names


def test_timed_batch_bitwise(small_scene, base_cfg, fresh_tracer):
    """Timed batch path (per-lane loop + stack) == jit(vmap) batch path."""
    import dataclasses

    import numpy as np

    from repro import engine
    from repro.core import orbit_cameras

    cams = orbit_cameras(3, 4.5, 128, 128)
    with engine.open(small_scene, base_cfg) as r:
        plain = np.asarray(r.render_batch(cams).image)
    with engine.open(
        small_scene, dataclasses.replace(base_cfg, timing=True)
    ) as r:
        timed = np.asarray(r.render_batch(cams).image)
    assert (plain == timed).all()


def test_timed_stage_cache_registered():
    from repro.core.pipeline import render_cache_info

    assert "timed_stage" in render_cache_info()


# ---------------------------------------------------------------------------
# jax layer: serving lifecycle spans + metrics end to end
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_server_emits_lifecycle_spans_and_metrics(small_scene, base_cfg):
    """One small serve: every completed request gets a nested lifecycle on
    its own lane, serve/dispatch spans match batches, and the serving.*
    counters in a fresh registry agree with the stats summary."""
    import numpy as np

    from repro.core import orbit_cameras
    from repro.obs import get_tracer, set_tracer
    from repro.serving.queue import RenderRequest
    from repro.serving.server import RenderServer
    from repro.serving.stats import ServingStats

    reg = MetricsRegistry()
    prev = set_tracer(Tracer(enabled=True))
    try:
        server = RenderServer(
            {"s": small_scene}, max_batch=2, max_wait=0.01
        )
        server.stats = ServingStats(registry=reg)
        cams = orbit_cameras(4, 4.5, 96, 96)
        load = [
            (0.0, RenderRequest(i, "s", cams[i], base_cfg))
            for i in range(4)
        ]
        results = server.run(load, realtime=False)
        summary = server.stats.summary()
        server.close()

        assert len(results) == 4
        tracer = get_tracer()
        evs = tracer.events()
        req_spans = [e for e in evs if e.name == "request"]
        assert len(req_spans) == 4
        assert len({e.tid for e in req_spans}) == 4       # one lane each
        dispatches = [e for e in evs if e.name == "serve/dispatch"]
        assert len(dispatches) == summary["batches"]
        assert validate_chrome_trace(tracer.chrome_trace()) == []

        snap = reg.snapshot()
        assert snap["counters"]["serving.requests_total"] == 4
        assert snap["counters"]["serving.batches_total"] == summary["batches"]
        assert snap["histograms"]["serving.latency_s"]["count"] == 4
        # request/device span duration matches the recorded render walltime
        # order of magnitude (both bracket the same device work)
        dev = [e for e in evs if e.name == "request/device"]
        assert all(e.duration_s > 0 for e in dev)
        for img in (np.asarray(r.image) for r in results.values()):
            assert img.shape == (96, 96, 3)
    finally:
        set_tracer(prev)


def test_engine_submit_emits_request_spans(small_scene, base_cfg):
    """The engine futures path stamps + emits the same lifecycle spans."""
    from repro import engine
    from repro.core import make_camera
    from repro.obs import get_tracer, set_tracer

    prev = set_tracer(Tracer(enabled=True))
    try:
        cam = make_camera((0.0, 1.0, 4.5), (0, 0, 0), 96, 96)
        with engine.open(small_scene, base_cfg) as r:
            futs = [r.submit(cam) for _ in range(3)]
            for f in futs:
                f.result(timeout=120)
        evs = get_tracer().events()
        req = [e for e in evs if e.name == "request"]
        assert len(req) == 3
        ids = {e.args["request_id"] for e in req}
        assert len(ids) == 3
        assert all("#" in rid for rid in ids)
        assert any(e.name == "engine/dispatch" for e in evs)
        assert validate_chrome_trace(get_tracer().chrome_trace()) == []
    finally:
        set_tracer(prev)


def _serve_two_batches(scene, cfg, registry=None):
    from repro.core import orbit_cameras
    from repro.serving.queue import RenderRequest
    from repro.serving.server import RenderServer
    from repro.serving.stats import ServingStats

    server = RenderServer({"s": scene}, max_batch=2, max_wait=0.0)
    if registry is not None:
        server.stats = ServingStats(registry=registry)
    cams = orbit_cameras(4, 4.5, 64, 48)
    reqs = [RenderRequest(i, "s", cams[i], cfg) for i in range(4)]
    for batch in (reqs[:2], reqs[2:]):
        for r in batch:
            server.submit(r)
        server.drain()
    server.close()
    return reqs


def test_server_dispatch_splits_into_live_phases(small_scene, base_cfg):
    """Each dispatch records nested serve/launch, serve/device_wait and
    serve/fetch spans; each request a device_done stamp before the copy
    and a fetched stamp after it; and the registry the per-request and
    per-dispatch readings, in dispatch order."""
    from repro.obs import get_tracer, set_tracer

    reg = MetricsRegistry()
    prev = set_tracer(Tracer(enabled=True))
    try:
        reqs = _serve_two_batches(small_scene, base_cfg, reg)
        evs = get_tracer().events()
        assert validate_chrome_trace(get_tracer().chrome_trace()) == []
    finally:
        set_tracer(prev)
    outer = [e for e in evs if e.name == "serve/dispatch"]
    assert len(outer) == 2
    for phase in ("serve/launch", "serve/device_wait", "serve/fetch"):
        spans = [e for e in evs if e.name == phase]
        assert len(spans) == 2
        assert all(o.t0 <= s.t0 and s.t1 <= o.t1
                   for s, o in zip(spans, outer))
    for r in reqs:
        st = r.stamps
        assert (st["enqueue"] <= st["dispatch"] <= st["device_done"]
                <= st["fetched"] <= st["resolve"])
    assert {e.name for e in evs if e.category == "request"} == \
        {"request"} | {n for _, _, n in REQUEST_PHASES}
    snap = reg.snapshot()["histograms"]
    assert snap["serving.queue_wait_s"]["count"] == 4
    assert snap["serving.fetch_s"]["count"] == 4
    assert reg.get("serving.batch_size").values() == [2.0, 2.0]
    waits = reg.get("serving.queue_wait_s").values()
    assert waits[-1] == pytest.approx(
        reqs[3].stamps["dispatch"] - reqs[3].stamps["enqueue"])
    fetch = reg.get("serving.fetch_s").values()
    assert fetch[-1] == pytest.approx(
        reqs[3].stamps["fetched"] - reqs[3].stamps["device_done"])
    # A fresh shape compiles in the first dispatch; the second is cached.
    compiles = reg.get("serving.dispatch_compiles").values()
    assert compiles[0] >= 1 and compiles[1] == 0
    assert reg.get("serving.dispatch_compile_s").values()[1] == 0.0
    assert reg.get("no.such.metric") is None


def test_compile_counter_counts_new_shapes_only():
    import jax
    import jax.numpy as jnp

    import repro.engine  # noqa: F401 — installs the compile listeners
    from repro.obs import get_registry

    reg = get_registry()
    f = jax.jit(lambda x: jnp.cumsum(x) * 3.0)

    def compiles():
        return reg.counter("engine.compiles_total").value

    a, b, c = (jax.block_until_ready(jnp.full(n, 2.0))
               for n in (37, 37, 41))
    n0, s0 = compiles(), reg.histogram("engine.compile_s").sum
    f(a).block_until_ready()
    assert compiles() == n0 + 1
    assert reg.histogram("engine.compile_s").sum > s0
    f(b).block_until_ready()
    assert compiles() == n0 + 1
    f(c).block_until_ready()
    assert compiles() == n0 + 2


def test_live_spans_reach_the_profiler_trace_with_anchors(small_scene,
                                                          base_cfg,
                                                          tmp_path):
    """With the profiler on, the server's live spans are host events of the
    trace carrying their ``mono`` start: one offset maps them all from the
    tracer's clock to the trace's."""
    import warnings

    import jax
    from jax.profiler import ProfileData

    _serve_two_batches(small_scene, base_cfg)          # compile outside
    with jax.profiler.trace(str(tmp_path)):
        _serve_two_batches(small_scene, base_cfg)
    (path,) = tmp_path.glob("**/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    found = {}
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve/"):
                    with warnings.catch_warnings():
                        warnings.simplefilter("ignore", DeprecationWarning)
                        stats = dict(e.stats)
                    found.setdefault(e.name, []).append(
                        e.start_ns * 1e-9 - float(stats["mono"]))
    assert set(found) == {"serve/dispatch", "serve/launch",
                          "serve/device_wait", "serve/fetch"}
    assert all(len(v) == 2 for v in found.values())
    offsets = [o for v in found.values() for o in v]
    assert max(offsets) - min(offsets) < 1e-3
