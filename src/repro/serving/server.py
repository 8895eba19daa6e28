"""The render server: a thin driver loop over shared engine handles.

Single driver loop, three stages (DESIGN.md §9/§11):

  submit() --> RequestQueue --> BucketingScheduler --> Renderer.render_batch
   (bounded, backpressure)      (one bucket per jit       (ONE committed handle
                                 signature; max-batch /    per (scene, config);
                                 max-wait flush)           fixed dispatch shape)

Scene placement, mesh layout, and the compiled-renderer caches all live in
the ``repro.engine.Renderer`` handles the server opens lazily per
(scene id, config) — the server itself only schedules: it drains the queue
into signature buckets and hands each bucket to the right handle. The loop
is synchronous and single-threaded on the dispatch side — device work is
serialized anyway, and keeping scheduling single-threaded makes the latency
accounting exact. Producers may submit from other threads (the queue is the
thread-safe boundary) or inline via ``run(load)`` which replays a timed
load (e.g. ``poisson_arrivals``) in real time. (A per-scene futures
front-end without the multi-scene admission layer is just
``Renderer.submit`` — the server adds scenes, admission screening, and
serving stats on top.)

Every completed request yields a ``RequestResult`` with the rendered image
(host numpy), its end-to-end latency, and the bucket it rode in;
``RenderServer.stats`` aggregates per-bucket latency/throughput/cache-hit
counters (serving/stats.py).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, Iterable, List, Mapping, Optional, Tuple

import numpy as np

from repro.core.gaussians import GaussianScene
from repro.core.pipeline import CameraBatch, render_cache_info
from repro.obs import emit_request_spans, get_registry, get_tracer
from repro.residency import ResidencyManager
from repro.serving.bucketing import Bucket, BucketingScheduler, padded_size
from repro.serving.queue import RenderRequest, RequestQueue
from repro.serving.stats import ServingStats


@dataclasses.dataclass
class RequestResult:
    request_id: int
    image: np.ndarray            # (H, W, 3) host copy
    latency_s: float             # completion - enqueue (queue + batch + render)
    batch_size: int              # how many requests shared the dispatch
    signature: tuple
    deadline_missed: bool = False


class RenderServer:
    """Serves render requests against a registry of scenes.

    ``mesh=None`` shards each dispatch over all local devices (built lazily
    on first dispatch so constructing a server never touches device state);
    ``scene_shards = D > 1`` builds the 2-D (data, model) render mesh and
    the handles commit scenes gaussian-sharded over 'model' (DESIGN.md §10).
    Requests choose their own layout via ``cfg.scene_shards`` — it is part
    of the bucket signature, so replicated and sharded dispatches of the
    same scene never mix in a batch; a request's shard count must be 1 or
    match the server's mesh. ``device_budget_mb`` seeds the server's
    :class:`~repro.residency.ResidencyManager` (DESIGN.md §17): scenes
    that fit individually but not together page in/out LRU against the
    budget (bitwise-invisibly) instead of refusing to commit — only a
    scene too big to fit even alone still fails fast; ``prefetch=False``
    disables the admission-time page-in. ``autotune=True`` opens every handle
    with ``tile_params='auto'`` (DESIGN.md §13): the first dispatch of each
    (scene, config) pays a tuning sweep — or hits the persisted autotune
    cache — and serves the tuned tiling from then on (``autotune_opts`` is
    forwarded to ``repro.autotune.autotune``). Close the server (or use it
    as a context manager) to close its handles.
    """

    def __init__(
        self,
        scenes: Mapping[str, GaussianScene],
        *,
        mesh=None,
        max_batch: int = 8,
        max_wait: float = 0.05,
        queue_depth: int = 64,
        scene_shards: int = 1,
        device_budget_mb: Optional[float] = None,
        autotune: bool = False,
        autotune_opts: Optional[dict] = None,
        stream_cache_frames: int = 32,
        spec_depth: int = 2,
        speculate: bool = True,
        prefetch: bool = True,
        clock=time.monotonic,
    ):
        self.scenes = dict(scenes)
        self._mesh = mesh
        self.scene_shards = scene_shards
        self.device_budget_mb = device_budget_mb
        self.autotune = autotune
        self.autotune_opts = autotune_opts
        self.stream_cache_frames = stream_cache_frames
        self.spec_depth = spec_depth
        self.speculate = speculate
        self.prefetch = prefetch
        self._clock = clock
        self.queue = RequestQueue(queue_depth, clock=clock)
        self.scheduler = BucketingScheduler(max_batch, max_wait, clock=clock)
        self.stats = ServingStats()
        self.results: Dict[int, RequestResult] = {}
        # ONE residency manager for every handle this server opens
        # (DESIGN.md §17): device copies dedupe per (scene, layout, mesh)
        # — the committed-scene sharing across configs — and, under a
        # device_budget_mb, an over-budget commit evicts cold scenes
        # instead of failing fast (a single scene that cannot fit even
        # alone still raises from engine.open).
        self.residency = ResidencyManager(
            budget_mb=device_budget_mb, name="server"
        )
        # The server lock: commit()/stream_for()/close() all mutate the
        # handle registry — without it, commit() could hand out a handle
        # while close() tears the map down, leaking its jit cache and
        # scene layouts. Reentrant: stream_for -> commit nests.
        self._lock = threading.RLock()
        self._server_closed = False
        self._renderers: Dict[Tuple[str, object], object] = {}
        # Stream sessions (DESIGN.md §15): one StreamRenderer per
        # (scene, cfg, stream_id), opened lazily on the stream's first
        # frame over the shared committed handle; the handle's close()
        # closes its streams.
        self._streams: Dict[Tuple[str, object, str], object] = {}

    @property
    def mesh(self):
        if self._mesh is None:
            import jax

            from repro.launch.mesh import make_render_mesh, render_mesh_shards

            # Logical shard axis when D does not divide the device count
            # (single-device tests still serve sharded layouts correctly —
            # they just do not save per-device memory).
            self._mesh = make_render_mesh(
                scene_shards=render_mesh_shards(
                    len(jax.devices()), self.scene_shards
                )
            )
        return self._mesh

    # -- admission ----------------------------------------------------------

    def _layout_ok(self, req: RenderRequest) -> bool:
        """A request's gaussian layout must be replicated (1) or match the
        server's configured shard count — a mismatched layout would raise
        inside the dispatch and kill the loop for everyone behind it, so it
        is screened at admission (pure Python, no device touch)."""
        return getattr(req.cfg, "scene_shards", 1) in (1, self.scene_shards)

    def submit(self, req: RenderRequest) -> bool:
        """Non-blocking admission; False = backpressure (queue at depth).
        Raises KeyError for an unknown scene and ValueError for a scene-shard
        layout the server's mesh cannot serve (caller bugs, not load)."""
        if req.scene_id not in self.scenes:
            raise KeyError(f"unknown scene {req.scene_id!r}")
        if not self._layout_ok(req):
            raise ValueError(
                f"request {req.request_id} wants scene_shards="
                f"{getattr(req.cfg, 'scene_shards', 1)} but this server "
                f"serves 1 or {self.scene_shards}"
            )
        ok = self.queue.try_put(req)
        if not ok:
            self.stats.count_rejected()
        elif self.prefetch:
            self._prefetch(req)
        return ok

    def _prefetch(self, req: RenderRequest) -> None:
        """Admission-time prefetch (DESIGN.md §17): if the admitted
        request's scene is already committed but paged out, page it back
        in NOW so the dispatch that follows finds it resident. Only
        already-committed handles are touched — admission stays cheap and
        raise-free (a first-time scene pays its commit at dispatch, as
        before)."""
        with self._lock:
            handle = self._renderers.get((req.scene_id, req.cfg))
            if handle is None or handle.closed or handle.resident:
                return
            try:
                handle.prefetch()
            except Exception:       # noqa: BLE001 — prefetch is advisory;
                pass                # the dispatch path surfaces real errors

    # -- committed handles --------------------------------------------------

    @property
    def committed_scene_ids(self) -> frozenset:
        """Scenes with at least one committed handle — the gateway tier's
        scene-affinity signal (route to the worker already holding the
        scene on device before paying a commit elsewhere)."""
        with self._lock:
            return frozenset(sid for sid, _cfg in self._renderers)

    @property
    def resident_scene_ids(self) -> frozenset:
        """Committed scenes whose device copy is resident RIGHT NOW (not
        paged out by the residency manager) — the gateway tier's
        residency-aware placement signal: a resident worker serves the
        request without paying a page-in."""
        with self._lock:
            return frozenset(
                sid for (sid, _cfg), h in self._renderers.items()
                if not h.closed and h.resident
            )

    def commit(self, scene_id: str, cfg):
        """The shared engine handle for ``(scene_id, cfg)``, opened on first
        use. Public so drivers can pre-commit scenes before taking load — a
        scene too big to fit the budget even ALONE still fails fast here
        (``engine.open`` raises); scenes that fit individually but not
        together page in and out through the server's residency manager
        instead of failing (DESIGN.md §17).

        Handles are per (scene, config) — the compiled programs differ —
        but the committed DEVICE scene is shared per (scene, layout): the
        residency manager dedupes entries, so two configs over one scene
        cost one scene copy, not two. Raises RuntimeError after close()."""
        with self._lock:
            if self._server_closed:
                raise RuntimeError("RenderServer is closed")
            key = (scene_id, cfg)
            handle = self._renderers.get(key)
            if handle is None:
                from repro import engine

                handle = engine.open(
                    self.scenes[scene_id], cfg,
                    mesh=self.mesh,
                    residency=self.residency,
                    tile_params="auto" if self.autotune else None,
                    autotune_opts=self.autotune_opts,
                )
                self._renderers[key] = handle
            return handle

    def stream_for(self, req: RenderRequest):
        """The stream session serving ``req``'s (scene, cfg, stream_id),
        opened on first use over the shared committed handle."""
        with self._lock:
            key = (req.scene_id, req.cfg, req.stream_id)
            stream = self._streams.get(key)
            if stream is None or stream.closed:
                handle = self.commit(req.scene_id, req.cfg)
                stream = handle.open_stream(
                    cache_frames=self.stream_cache_frames,
                    spec_depth=self.spec_depth,
                    speculate=self.speculate,
                )
                self._streams[key] = stream
            return stream

    def stream_stats(self) -> Dict[str, dict]:
        """Per-stream session counters keyed by registry cache name."""
        return {
            s.name: s.stats()
            for s in self._streams.values() if not s.closed
        }

    # -- scheduling / dispatch ----------------------------------------------

    def _pump_queue(self, now: Optional[float] = None) -> int:
        """Drain the queue into buckets, dispatching any bucket that fills
        to max_batch (partial buckets keep waiting)."""
        n = 0
        for req in self.queue.drain():
            for bucket in self.scheduler.add(req, now):
                self._dispatch(bucket)
                n += 1
        return n

    def step(self, now: Optional[float] = None) -> int:
        """One scheduler turn: pump the queue, then dispatch buckets past
        max_wait. Returns the number of dispatches."""
        n = self._pump_queue(now)
        for bucket in self.scheduler.poll(now):
            self._dispatch(bucket)
            n += 1
        return n

    def drain(self) -> None:
        """Flush everything pending (shutdown path): remaining queue items
        are bucketed and every bucket dispatches regardless of age."""
        while len(self.queue) or self.scheduler.pending:
            self._pump_queue()
            for bucket in self.scheduler.flush_all():
                self._dispatch(bucket)

    def _dispatch(self, bucket: Bucket) -> None:
        reqs = bucket.requests
        if getattr(reqs[0], "stream_id", None) is not None:
            self._dispatch_stream(bucket)
            return
        handle = self.commit(reqs[0].scene_id, reqs[0].cfg)
        batch = CameraBatch.from_cameras([r.camera for r in reqs])
        # Fixed dispatch shape: every bucket of a signature pads to
        # max_batch (rounded to the camera-lane count — the mesh's DATA
        # extent), so ragged max_wait flushes reuse the ONE compiled program
        # instead of tracing a new shape (DESIGN.md §9 invariant).
        from repro.sharding.policies import data_extent

        shape = padded_size(self.scheduler.max_batch, data_extent(self.mesh))

        tracer = get_tracer()
        mark = self._mark()
        with tracer.span("serve/dispatch", category="serving",
                         args={"batch_size": len(reqs), "padded": shape,
                               "signature": repr(bucket.signature)}):
            with tracer.span("serve/launch", category="serving"):
                image = handle.render_batch(batch, pad_to=shape).image
            with tracer.span("serve/device_wait", category="serving"):
                image.block_until_ready()
            t_done = self._clock()
            with tracer.span("serve/fetch", category="serving"):
                images = np.asarray(image)
        self._complete(bucket, images, mark, t_done, padded=shape,
                       span_args={"scene_id": reqs[0].scene_id})

    def _dispatch_stream(self, bucket: Bucket) -> None:
        """Dispatch a stream bucket: frames run IN ORDER through the
        stream's session (exact-reuse cache + speculation), one frame per
        device dispatch — the signature guarantees every request here
        belongs to one stream, and queue FIFO + in-order bucket appends
        preserved the frame order. Output is bitwise-identical to the
        stateless batch path (the session reuses frontends only on exact
        pose-key hits; tests/test_stream.py)."""
        reqs = bucket.requests
        stream = self.stream_for(reqs[0])

        tracer = get_tracer()
        mark = self._mark()
        with tracer.span("serve/dispatch", category="serving",
                         args={"batch_size": len(reqs), "padded": len(reqs),
                               "stream": stream.name,
                               "signature": repr(bucket.signature)}):
            with tracer.span("serve/launch", category="serving"):
                frames = [stream.render(r.camera).image for r in reqs]
            with tracer.span("serve/device_wait", category="serving"):
                for image in frames:
                    image.block_until_ready()
            t_done = self._clock()
            with tracer.span("serve/fetch", category="serving"):
                images = [np.asarray(image) for image in frames]
        # Per-frame dispatch: no pad lanes.
        self._complete(bucket, images, mark, t_done, padded=len(reqs),
                       span_args={"scene_id": reqs[0].scene_id,
                                  "stream": stream.name})

    def _mark(self) -> tuple:
        """What a dispatch's accounting diffs across it: the render-cache
        table, the process's compile count and seconds, and the launch
        time."""
        reg = get_registry()
        return (render_cache_info(), reg.counter("engine.compiles_total").value,
                reg.histogram("engine.compile_s").sum, self._clock())

    def _complete(self, bucket: Bucket, images, mark: tuple, t_done: float,
                  *, padded: int, span_args: dict) -> None:
        """Fold a finished dispatch into the stats, results and request
        stamps: launched at ``mark``, device work done at ``t_done``, images
        on the host now."""
        reqs = bucket.requests
        before, compiles, compile_s, t0 = mark
        t1 = self._clock()
        after = render_cache_info()
        reg = get_registry()
        latencies = [t1 - r.enqueue_time for r in reqs]
        self.stats.record_dispatch(
            bucket.signature,
            batch_size=len(reqs),
            padded_size=padded,
            render_s=t1 - t0,
            latencies_s=latencies,
            cache_before=before,
            cache_after=after,
            queue_waits_s=[t0 - r.enqueue_time for r in reqs],
            fetch_s=t1 - t_done,
            compiles=reg.counter("engine.compiles_total").value - compiles,
            compile_s=reg.histogram("engine.compile_s").sum - compile_s,
        )
        tracer = get_tracer()
        for req, img, lat in zip(reqs, images, latencies):
            missed = req.deadline is not None and t1 > req.deadline
            if missed:
                self.stats.count_deadline_miss()
            self.results[req.request_id] = RequestResult(
                request_id=req.request_id,
                image=img,
                latency_s=lat,
                batch_size=len(reqs),
                signature=bucket.signature,
                deadline_missed=missed,
            )
            stamps = getattr(req, "stamps", None)
            if stamps is not None:
                stamps["dispatch"] = t0
                stamps["device_done"] = t_done
                stamps["fetched"] = t1
                stamps["resolve"] = self._clock()
                emit_request_spans(tracer, req.request_id, stamps,
                                   args=span_args)

    # -- lifecycle -----------------------------------------------------------

    def close(self) -> None:
        """Close every committed handle (releasing their jit caches, scene
        layouts, and residency entries — each handle also closes its
        stream sessions). TERMINAL: a later ``commit()`` raises
        RuntimeError — the server lock makes close-vs-commit a clean
        ordering instead of a race that could hand out a handle the
        teardown never closes (leaked jit cache + layouts). Idempotent."""
        with self._lock:
            self._server_closed = True
            while self._renderers:
                self._renderers.pop(next(iter(self._renderers))).close()
            self._streams.clear()

    def __enter__(self) -> "RenderServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- timed replay --------------------------------------------------------

    def run(
        self,
        load: Iterable[Tuple[float, RenderRequest]],
        realtime: bool = True,
    ) -> Dict[int, RequestResult]:
        """Serve a timed load of ``(arrival_offset_s, request)`` pairs.

        ``realtime=True`` sleeps the inter-arrival gaps (servicing due
        buckets while waiting) so max-wait flushes behave as in production —
        it requires the default wall clock (an injected fake clock never
        advances through ``time.sleep`` and would spin forever; fakes are
        for the scheduler unit tests). ``realtime=False`` enqueues the whole
        backlog and drains it (closed-loop throughput mode: buckets fill to
        max_batch regardless of max_wait — what bench_serving measures).
        Unknown-scene and unservable-layout (scene_shards mismatch) requests
        in a load are counted as rejections and skipped rather than killing
        the requests behind them. Returns the results map; ``stats.wall_s``
        is stamped on exit.
        """
        t_start = self._clock()
        for offset, req in load:
            if req.scene_id not in self.scenes or not self._layout_ok(req):
                self.stats.count_rejected()
                continue
            if realtime:
                while self._clock() - t_start < offset:
                    self.step()
                    gap = offset - (self._clock() - t_start)
                    if gap > 0:
                        time.sleep(min(gap, max(self.scheduler.max_wait, 1e-3) / 4))
            if not self.queue.try_put(req):
                # Backpressure inline: service the backlog, then retry once;
                # a second failure is a real rejection.
                self._pump_queue()
                if not self.queue.try_put(req):
                    self.stats.count_rejected()
                elif self.prefetch:
                    self._prefetch(req)
            elif self.prefetch:
                self._prefetch(req)
            if realtime:
                self.step()
        self.drain()
        self.stats.wall_s = self._clock() - t_start
        return self.results


def poisson_arrivals(
    n: int, rate_hz: float, seed: int = 0
) -> List[float]:
    """n arrival offsets with exponential inter-arrival gaps (Poisson
    process at ``rate_hz``) — the synthetic open-loop load for the CLI and
    the serving benchmark."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_hz, size=n)
    return np.cumsum(gaps).tolist()
