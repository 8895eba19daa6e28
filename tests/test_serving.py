"""Serving subsystem: queue/bucketing (pure Python), sharded dispatch, and
the end-to-end server loop (DESIGN.md §9).

The queue/bucketing/stats tests run the scheduling layer with stub cameras
and injected clocks — no jax, no devices, deterministic time — because that
layer is pure by design (enforced by test_pure_layer_imports_without_jax).
"""
from __future__ import annotations

import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from repro.serving.bucketing import (
    BucketingScheduler,
    pad_indices,
    pad_indices_to,
    padded_size,
)
from repro.serving.queue import QueueFull, RenderRequest, RequestQueue
from repro.serving.stats import ServingStats, cache_delta, percentile

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _cam(w=128, h=128):
    return SimpleNamespace(width=w, height=h, znear=0.2, zfar=1000.0)


def _req(i, w=128, h=128, cfg="cfg-a", scene="scene-a"):
    return RenderRequest(i, scene, _cam(w, h), cfg)


# ---------------------------------------------------------------------------
# pure layer: queue
# ---------------------------------------------------------------------------


def test_pure_layer_imports_without_jax():
    """queue/bucketing/stats must not pull jax (admission layer runs
    anywhere; importing repro.serving must not init devices)."""
    code = (
        "import sys; import repro.serving; "
        "import repro.serving.queue, repro.serving.bucketing, "
        "repro.serving.stats; "
        "assert 'jax' not in sys.modules, 'pure serving layer imported jax'"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin"},
        capture_output=True,
        text=True,
    )
    assert proc.returncode == 0, proc.stderr


def test_queue_fifo_depth_and_backpressure():
    q = RequestQueue(maxsize=2, clock=lambda: 0.0)
    q.put_nowait(_req(0))
    q.put_nowait(_req(1))
    with pytest.raises(QueueFull):
        q.put_nowait(_req(2))
    assert not q.try_put(_req(2))
    assert not q.put(_req(2), timeout=0.0)       # bounded put times out
    assert (q.accepted, q.rejected) == (2, 3)
    assert [r.request_id for r in q.drain()] == [0, 1]   # FIFO
    assert len(q) == 0 and q.try_put(_req(3))            # space freed


def test_queue_rejections_count_into_registry():
    """Backpressure is a first-class metrics signal: every failed put
    attempt increments queue.rejected_total alongside the local field
    (delta-based — the registry is process-global across tests)."""
    from repro.obs import get_registry

    before = get_registry().counter("queue.rejected_total").value
    q = RequestQueue(maxsize=1, clock=lambda: 0.0)
    q.put_nowait(_req(0))
    with pytest.raises(QueueFull):
        q.put_nowait(_req(1))
    assert not q.try_put(_req(1))
    assert not q.put(_req(1), timeout=0.0)
    assert q.rejected == 3
    assert get_registry().counter("queue.rejected_total").value == before + 3


def test_queue_enqueue_time_stamped():
    q = RequestQueue(maxsize=4, clock=lambda: 42.0)
    q.put_nowait(_req(0))
    (r,) = q.drain()
    assert r.enqueue_time == 42.0


def test_queue_get_batch_bounds_and_timeout():
    q = RequestQueue(maxsize=8, clock=lambda: 0.0)
    for i in range(5):
        q.put_nowait(_req(i))
    got = q.get_batch(max_n=3)
    assert [r.request_id for r in got] == [0, 1, 2]
    assert q.get_batch() and q.get_batch(timeout=0.0) == []


# ---------------------------------------------------------------------------
# pure layer: bucketing scheduler
# ---------------------------------------------------------------------------


def test_bucketing_flush_on_max_batch():
    sched = BucketingScheduler(max_batch=3, max_wait=10.0, clock=lambda: 0.0)
    assert sched.add(_req(0)) == []
    assert sched.add(_req(1)) == []
    (bucket,) = sched.add(_req(2))               # third request fills it
    assert [r.request_id for r in bucket.requests] == [0, 1, 2]
    assert sched.pending == 0                    # flushed buckets leave


def test_bucketing_flush_on_max_wait():
    sched = BucketingScheduler(max_batch=100, max_wait=0.05)
    sched.add(_req(0), now=1.0)
    sched.add(_req(1), now=1.03)
    assert sched.poll(now=1.04) == []            # oldest only 40ms old
    (bucket,) = sched.poll(now=1.05)             # 50ms: due
    assert len(bucket) == 2 and bucket.age(1.05) == pytest.approx(0.05)
    assert sched.poll(now=9.9) == []             # nothing left


def test_bucketing_signature_isolation():
    """Requests mix only within one executable signature: resolution, cfg,
    and scene each split buckets."""
    sched = BucketingScheduler(max_batch=2, max_wait=10.0, clock=lambda: 0.0)
    sched.add(_req(0, w=128))
    sched.add(_req(1, w=256))                    # other resolution
    sched.add(_req(2, cfg="cfg-b"))              # other config
    sched.add(_req(3, scene="scene-b"))          # other scene
    assert sched.pending == 4                    # four singleton buckets
    (bucket,) = sched.add(_req(4, w=256))        # completes the 256 bucket
    assert {r.request_id for r in bucket.requests} == {1, 4}
    buckets = sched.flush_all()
    assert sorted(len(b) for b in buckets) == [1, 1, 1]
    assert sched.pending == 0


def test_padding_round_trip():
    assert padded_size(1, 4) == 4
    assert padded_size(4, 4) == 4
    assert padded_size(5, 4) == 8
    assert padded_size(7, 1) == 7
    for n, m in [(1, 1), (3, 2), (5, 4), (8, 8), (9, 8)]:
        idx = pad_indices(n, m)
        assert len(idx) == padded_size(n, m) and len(idx) % m == 0
        assert idx[:n] == list(range(n))         # slicing off the pad is exact
        assert all(i == n - 1 for i in idx[n:])  # pad replicates the last lane
    # The absolute-target variant (the fixed-dispatch-shape policy the
    # server's pad_to uses) obeys the same round trip.
    for n, target in [(1, 4), (3, 3), (3, 8)]:
        idx = pad_indices_to(n, target)
        assert len(idx) == target and idx[:n] == list(range(n))
        assert all(i == n - 1 for i in idx[n:])
    with pytest.raises(ValueError):
        padded_size(0, 4)
    with pytest.raises(ValueError):
        pad_indices_to(5, 3)


# ---------------------------------------------------------------------------
# pure layer: stats
# ---------------------------------------------------------------------------


def test_stats_percentiles_and_aggregation():
    assert percentile([], 50) != percentile([], 50)      # nan
    assert percentile([5.0], 99) == 5.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 50) == pytest.approx(2.5)

    stats = ServingStats()
    stats.record_dispatch(("sig-a",), 3, 4, 0.1, [0.01, 0.02, 0.03])
    stats.record_dispatch(("sig-a",), 2, 2, 0.1, [0.02, 0.04])
    stats.record_dispatch(("sig-b",), 1, 1, 0.1, [0.05])
    stats.wall_s = 0.5
    s = stats.summary()
    assert s["completed"] == 6 and s["batches"] == 3 and s["padded"] == 1
    assert s["fps"] == pytest.approx(12.0)
    assert stats.bucket(("sig-a",)).mean_batch == pytest.approx(2.5)
    assert s["p99_ms"] <= 50.0 + 1e-6
    assert "sig-a" in stats.format()


def test_stats_cache_delta():
    before = {"single": dict(hits=1, misses=2), "batch": dict(hits=0, misses=1)}
    after = {"single": dict(hits=1, misses=2), "batch": dict(hits=3, misses=2)}
    assert cache_delta(before, after) == {"hits": 3, "misses": 1}


@pytest.mark.parametrize("entries,skew", [
    ([10, 10, 10, 10], 1.0),          # an even split
    ([40, 0, 0, 0], 4.0),             # every entry on one chip
    ([30, 10, 20, 20], 1.5),
    ([0, 0, 0, 0], 1.0),              # a frame with no entries
])
def test_stats_publish_band_skew_per_request(entries, skew):
    """A dispatch a group-split backend drew publishes, per request, its
    largest band's entries over the mean band's; one that carries no band
    entries publishes nothing."""
    from repro.obs import MetricsRegistry
    from repro.serving.stats import band_skew

    assert band_skew(entries) == skew
    reg = MetricsRegistry()
    stats = ServingStats(registry=reg)
    stats.record_dispatch(("sig",), 1, 1, 0.01, [0.01])
    assert reg.get("serving.band_skew") is None
    stats.record_dispatch(("sig",), 2, 2, 0.01, [0.01, 0.02],
                          band_entries=[entries, [5, 5, 5, 5]])
    assert reg.get("serving.band_skew").values() == [skew, 1.0]


def test_stats_latency_memory_is_bounded():
    """A long-lived server must not grow one float per request: latencies
    live in reservoir histograms (DESIGN.md §14) — bounded storage, exact
    counts, percentiles from a uniform sample once past the cap."""
    from repro.obs import MetricsRegistry
    from repro.serving.stats import LATENCY_RESERVOIR

    stats = ServingStats(registry=MetricsRegistry())
    n = LATENCY_RESERVOIR + 500
    for i in range(n // 10):
        stats.record_dispatch(("sig",), 10, 10, 0.01,
                              [0.01 * (j + 1) for j in range(10)])
    b = stats.bucket(("sig",))
    assert b.requests == (n // 10) * 10
    assert b.latency.count == b.requests          # exact count survives
    assert len(b.latencies_s) == LATENCY_RESERVOIR   # bounded storage
    assert len(stats.all_latencies()) == LATENCY_RESERVOIR
    assert b.latency.sampled
    d = b.to_dict()
    assert d["latency_count"] == b.requests and d["latency_sampled"]
    # percentiles still come out of the sampled window
    assert 0.01 <= d["p99_ms"] / 1e3 <= 0.1


def test_stats_record_dispatch_thread_safe():
    """Dispatch folds race in production (driver loop + futures worker);
    every counter must survive N threads folding concurrently."""
    import threading

    from repro.obs import MetricsRegistry

    reg = MetricsRegistry()
    stats = ServingStats(registry=reg)
    threads, per_thread, batch = 8, 50, 4
    barrier = threading.Barrier(threads)

    def fold(k):
        barrier.wait(timeout=10)
        for _ in range(per_thread):
            stats.record_dispatch((f"sig-{k % 2}",), batch, batch + 1,
                                  0.001, [0.01] * batch)
            stats.count_rejected()
            stats.count_deadline_miss()

    ts = [threading.Thread(target=fold, args=(k,)) for k in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    total = threads * per_thread
    s = stats.summary()
    assert s["completed"] == total * batch
    assert s["batches"] == total
    assert s["padded"] == total
    assert s["rejected"] == total
    assert s["deadline_misses"] == total
    assert stats.latency.count == total * batch
    snap = reg.snapshot()
    assert snap["counters"]["serving.requests_total"] == total * batch
    assert snap["counters"]["serving.batches_total"] == total
    assert snap["counters"]["serving.rejected_total"] == total
    assert snap["histograms"]["serving.latency_s"]["count"] == total * batch


# ---------------------------------------------------------------------------
# jax layer: sharded dispatch + server loop
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serving_cfg():
    from repro.core.pipeline import RenderConfig

    return RenderConfig(
        tile=16, group=64, group_capacity=256, tile_capacity=256
    )


def test_sharded_one_device_bitwise(small_scene, serving_cfg):
    """render_batch_sharded over a 1-device mesh IS render_batch."""
    import numpy as np

    from repro.core import orbit_cameras
    from repro.core.pipeline import render_batch
    from repro.launch.mesh import make_render_mesh
    from repro.serving.sharded import render_batch_sharded

    cams = orbit_cameras(3, 4.5, 128, 128)
    plain = render_batch(small_scene, cams, serving_cfg)
    shard = render_batch_sharded(
        small_scene, cams, serving_cfg, mesh=make_render_mesh(1)
    )
    assert (np.asarray(shard.image) == np.asarray(plain.image)).all()
    for name in vars(plain.stats):
        a = np.asarray(getattr(plain.stats, name))
        b = np.asarray(getattr(shard.stats, name))
        assert (a == b).all(), f"sharded stats counter {name} diverges"


def test_pad_camera_batch_mask_correct(small_scene, serving_cfg):
    """Rendering the padded batch and slicing the pad off reproduces the
    unpadded render exactly — padding only appends replicated lanes."""
    import numpy as np

    from repro.core import orbit_cameras
    from repro.core.pipeline import CameraBatch, render_batch
    from repro.serving.sharded import pad_camera_batch

    batch = CameraBatch.from_cameras(orbit_cameras(3, 4.5, 128, 128))
    padded = pad_camera_batch(batch, 4)
    assert len(padded) == 4 and len(pad_camera_batch(batch, 3)) == 3
    out_pad = render_batch(small_scene, padded, serving_cfg)
    out = render_batch(small_scene, batch, serving_cfg)
    assert (np.asarray(out_pad.image[:3]) == np.asarray(out.image)).all()
    assert (np.asarray(out_pad.image[3]) == np.asarray(out.image[2])).all()


def test_server_end_to_end(tiny_scene, serving_cfg):
    """Mixed resolutions through queue -> bucket -> dispatch: every request
    completes with the image render() produces, buckets never mix
    signatures, cache counters see the executable reuse."""
    import numpy as np

    from conftest import jit_render

    from repro.core import make_camera
    from repro.serving.queue import RenderRequest
    from repro.serving.server import RenderServer

    resolutions = [(96, 64), (64, 64)]
    reqs = []
    for i in range(9):
        w, h = resolutions[i % 2]
        cam = make_camera((1.5 - 0.2 * i, 1.0, 4.0), (0, 0, 0), w, h)
        reqs.append(RenderRequest(i, "scene", cam, serving_cfg))

    server = RenderServer(
        {"scene": tiny_scene}, max_batch=3, max_wait=0.0, queue_depth=16
    )
    results = server.run([(0.0, r) for r in reqs], realtime=False)

    assert sorted(results) == list(range(9))
    assert server.stats.rejected == 0
    for r in reqs:
        got = results[r.request_id]
        assert got.signature == r.signature()
        # jit'd oracle (conftest session cache): the dispatch path is jit
        # too, and the 1e-6 tolerance absorbs batched-vs-single fusion.
        expect = jit_render(tiny_scene, r.camera, serving_cfg)
        np.testing.assert_allclose(
            got.image, np.asarray(expect.image), atol=1e-6, rtol=1e-6
        )
    s = server.stats.summary()
    assert s["completed"] == 9
    assert len(server.stats.buckets) == 2        # one bucket per signature
    assert s["cache_hits"] > 0                   # repeated signatures reused
    assert np.isfinite(s["p99_ms"]) and s["fps"] > 0


def test_render_cache_covers_scene_layout(tiny_scene):
    """render_cache_clear()/render_cache_info() must cover ALL renderer
    caches, including the sharded scene-LAYOUT cache serving/sharded.py
    keeps — otherwise the server's cache-hit stats (deltas of
    render_cache_info) would lie about sharded dispatches."""
    from repro.core.pipeline import render_cache_clear, render_cache_info
    from repro.serving.sharded import shard_scene_cached

    render_cache_clear()
    info = render_cache_info()
    assert "scene_layout" in info
    assert (info["scene_layout"]["hits"], info["scene_layout"]["misses"]) == (0, 0)

    a = shard_scene_cached(tiny_scene, 2)
    b = shard_scene_cached(tiny_scene, 2)    # hit: same scene, same layout
    shard_scene_cached(tiny_scene, 4)        # miss: different shard count
    assert a is b
    info = render_cache_info()["scene_layout"]
    assert info["hits"] == 1 and info["misses"] == 2 and info["currsize"] == 2

    render_cache_clear()                     # must drop the layout cache too
    info = render_cache_info()["scene_layout"]
    assert (info["hits"], info["misses"], info["currsize"]) == (0, 0, 0)


def test_server_scene_sharded_end_to_end(tiny_scene, serving_cfg):
    """Scene-sharded requests through the full queue -> bucket -> dispatch
    path: bitwise-identical to the replicated batched render, and the
    replicated/sharded layouts of one scene never share a bucket."""
    import dataclasses

    import numpy as np

    from repro.core import make_camera
    from repro.core.pipeline import render_batch
    from repro.launch.mesh import make_render_mesh
    from repro.serving.queue import RenderRequest
    from repro.serving.server import RenderServer

    cfg_sh = dataclasses.replace(serving_cfg, scene_shards=2)
    cams = [
        make_camera((1.5 - 0.3 * i, 1.0, 4.0), (0, 0, 0), 64, 64)
        for i in range(4)
    ]
    reqs = [
        RenderRequest(i, "scene", cam, cfg_sh if i % 2 else serving_cfg)
        for i, cam in enumerate(cams)
    ]
    server = RenderServer(
        {"scene": tiny_scene}, mesh=make_render_mesh(1),
        max_batch=2, max_wait=0.0, queue_depth=16, scene_shards=2,
    )
    results = server.run([(0.0, r) for r in reqs], realtime=False)
    assert sorted(results) == [0, 1, 2, 3]
    assert len(server.stats.buckets) == 2    # replicated vs sharded split
    for r in reqs:
        expect = render_batch(tiny_scene, [r.camera], serving_cfg)
        assert (
            results[r.request_id].image == np.asarray(expect.image[0])
        ).all(), f"request {r.request_id} diverges from replicated batch"


def test_server_shares_committed_scene_across_configs(tiny_scene, serving_cfg):
    """Two configs over one scene open two handles (different compiled
    programs) but ONE committed device scene: the second handle commits on
    the first's device copy, so per-scene HBM does not scale with the
    config count."""
    import dataclasses

    from repro.serving.server import RenderServer

    with RenderServer({"scene": tiny_scene}) as server:
        a = server.commit("scene", serving_cfg)
        b = server.commit(
            "scene", dataclasses.replace(serving_cfg, mode="tile_baseline")
        )
        assert a is not b
        assert a.committed_scene.means3d is b.committed_scene.means3d


def test_server_backpressure_and_unknown_scene(tiny_scene, serving_cfg):
    from repro.core import make_camera
    from repro.serving.queue import RenderRequest
    from repro.serving.server import RenderServer

    cam = make_camera((0, 1, 4), (0, 0, 0), 64, 64)
    server = RenderServer({"scene": tiny_scene}, queue_depth=1)
    assert server.submit(RenderRequest(0, "scene", cam, serving_cfg))
    assert not server.submit(RenderRequest(1, "scene", cam, serving_cfg))
    assert server.stats.rejected == 1
    with pytest.raises(KeyError):
        server.submit(RenderRequest(2, "nope", cam, serving_cfg))


def test_server_rejects_unservable_scene_shards(tiny_scene, serving_cfg):
    """A request whose cfg.scene_shards neither is 1 nor matches the server
    must be screened at ADMISSION (submit raises; run skips + rejects) —
    letting it reach the dispatch would kill the loop for every queued
    request behind it."""
    import dataclasses

    from repro.core import make_camera
    from repro.serving.queue import RenderRequest
    from repro.serving.server import RenderServer

    cam = make_camera((0, 1, 4), (0, 0, 0), 64, 64)
    bad_cfg = dataclasses.replace(serving_cfg, scene_shards=4)
    server = RenderServer({"scene": tiny_scene}, scene_shards=2)
    with pytest.raises(ValueError, match="scene_shards"):
        server.submit(RenderRequest(0, "scene", cam, bad_cfg))
    # run(): the bad request is rejected, the good one still completes.
    load = [
        (0.0, RenderRequest(1, "scene", cam, bad_cfg)),
        (0.0, RenderRequest(2, "scene", cam, serving_cfg)),
    ]
    results = server.run(load, realtime=False)
    assert sorted(results) == [2]
    assert server.stats.rejected == 1


def test_render_batch_sharded_default_mesh_logical_fallback(
    tiny_scene, serving_cfg
):
    """mesh=None with a shard count that does not divide the device count
    must fall back to the logical shard axis (the docstring's single-device
    contract), not crash in make_render_mesh."""
    import dataclasses

    import numpy as np

    from repro.core import orbit_cameras
    from repro.core.pipeline import render_batch
    from repro.serving.sharded import render_batch_sharded

    cams = orbit_cameras(2, 4.5, 64, 64)
    cfg = dataclasses.replace(serving_cfg, scene_shards=3)
    out = render_batch_sharded(tiny_scene, cams, cfg)   # 3 shards, 1 device
    rep = render_batch(tiny_scene, cams, serving_cfg)
    assert (np.asarray(out.image) == np.asarray(rep.image)).all()


@pytest.mark.slow
def test_render_serve_cli_multi_device(tmp_path):
    """The CLI end-to-end on 2 virtual host devices (fresh process so the
    XLA flag lands before jax init): all requests complete, a Chrome trace
    (DESIGN.md §14) is written with the stats summary riding along, and the
    metrics snapshot agrees with it."""
    import json

    from repro.obs import validate_chrome_trace

    trace = tmp_path / "trace.json"
    metrics = tmp_path / "metrics.json"
    proc = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.render_serve",
            "--requests", "6", "--rate", "500", "--devices", "2",
            "--gaussians", "400", "--resolutions", "64x64",
            "--scenes", "train", "--max-batch", "3", "--max-wait", "0.02",
            "--no-realtime", "--trace-json", str(trace),
            "--metrics-json", str(metrics),
        ],
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path)},
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(trace.read_text())
    assert validate_chrome_trace(doc) == []
    data = doc["summary"]   # the pre-§14 stats document rides along here
    assert data["completed"] == 6 and data["devices"] == 2
    assert len(data["requests"]) == 6
    # 2 batches of 3 on 2 devices -> each padded to 4: 2 wasted lanes total
    assert data["padded"] == 2
    # request-lifecycle spans: one `request` span per completed request
    reqs = [e for e in doc["traceEvents"]
            if e.get("ph") == "X" and e.get("name") == "request"]
    assert len(reqs) == 6
    snap = json.loads(metrics.read_text())
    assert snap["schema"] == "repro.metrics/v1"
    assert snap["counters"]["serving.requests_total"] == 6
    assert snap["histograms"]["serving.latency_s"]["count"] == 6


def test_server_close_vs_commit_race_leaves_registry_empty(
    tiny_scene, serving_cfg
):
    """close() racing commit() must never leak a handle: the server lock
    orders them — a commit that wins the lock opens a handle close() then
    tears down; one that loses raises RuntimeError. Either way the handle
    registry is empty after close and every handle handed out is closed."""
    import threading

    from repro.serving.server import RenderServer

    for _attempt in range(3):
        server = RenderServer({"scene": tiny_scene})
        handles, barrier = [], threading.Barrier(3)

        def committer():
            barrier.wait()
            try:
                handles.append(server.commit("scene", serving_cfg))
            except RuntimeError:
                pass                     # lost the race: commit after close

        threads = [threading.Thread(target=committer) for _ in range(2)]
        for t in threads:
            t.start()
        barrier.wait()
        server.close()
        for t in threads:
            t.join()
        assert server._renderers == {}, "close() left a handle registered"
        assert all(h.closed for h in handles), "a raced commit leaked"
        with pytest.raises(RuntimeError, match="closed"):
            server.commit("scene", serving_cfg)
