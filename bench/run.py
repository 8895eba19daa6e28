#!/usr/bin/env python3
"""Runs one benchmark cell on the chip and prints its result line.

    python3 bench/run.py --workload train.backlog --seed 7 --seconds 10 --trace 0

From the root of a checkout. The cell (``BENCHMARK.json``'s ``workloads``)
names a configuration and a traffic mix; ``bench/spec.py`` finds their
files. One run:

1. set-up: the scene is drawn on the device from ``--seed``, a
   ``RenderServer`` is opened on it, and one dispatch of the cell's batch
   shape warms the compiled program (JAX's persistent compilation cache
   lives in ``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR``
   says otherwise). ``setup_s`` runs from process start to the first
   request of the window;
2. the window: the mix's kind drives the server for ``--seconds``, plus
   the frames still in flight (at most one batch). With ``--trace 1`` the
   window runs under the JAX profiler and the per-layer metrics are read
   from that trace by ``bench/metrics/<name>.py``;
3. the check: a seeded sample of the window's frames against
   ``bench/reference.py``, after the program's state is freed
   (``bench/check.py``). With ``--trace 1`` the reference also counts
   those frames' work, which the shares of peak and roofline read
   (``bench/work.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``busy_s`` and
``window_s`` too with ``--trace 1``), ``breakdown`` with ``--trace 1``, and
last ``check``, each compared number beside its limit; the same numbers end
standard error. Without a TPU, or with fewer chips than the cell asks for,
it exits 3 and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import shutil
import statistics
import sys
import time
from pathlib import Path
from types import SimpleNamespace

_T_IMPORT = time.monotonic()
ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402

from bench import check, reference, tracing  # noqa: E402
from bench.scene import make_scene  # noqa: E402
from bench.session import Session  # noqa: E402
from bench.spec import BENCH, load_cell, load_json  # noqa: E402
from bench.work import frame_work  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench" / "trace"
NO_CHIP = 3
# The TPU runtime logs to /tmp/tpu_logs unless told otherwise; keep a run's
# files inside its checkout.
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench" / "tpu_logs"))


def process_age() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.monotonic() - _T_IMPORT


def quantile(values, q):
    """The ``q``-quantile of ``values`` by linear interpolation."""
    return float(np.quantile(np.asarray(values, np.float64), q))


def enable_cache(jax):
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip():
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def look_for_chips(jax, chips):
    devs = jax.devices()
    if devs[0].platform == "cpu":
        return "JAX found no accelerator"
    if len(devs) < chips:
        return f"the cell needs {chips} chips and JAX found {len(devs)}"
    return None


def _peak_bytes(devs):
    """Peak device memory on the fullest chip: the peak of the buffers in
    use plus the peak of the memory the runtime reserved for compiled
    programs' temporaries, which a TPU runtime counts apart
    (``peak_bytes_reserved``) and leaves out of ``peak_bytes_in_use``."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(stats["peak_bytes_in_use"]
                         + stats.get("peak_bytes_reserved", 0))
    return int(max(peaks)) if peaks else None


def _program(cell):
    """The program's objects for this cell: scene wrapper, config, server."""
    from repro.core.camera import Camera
    from repro.core.gaussians import GaussianScene
    from repro.core.pipeline import RenderConfig
    from repro.serving.queue import RenderRequest
    from repro.serving.server import RenderServer

    cfg = RenderConfig(**cell.config["render"])

    def make_camera(p):
        return Camera(R=p.R, t=p.t, fx=p.fx, fy=p.fy, cx=p.cx, cy=p.cy,
                      width=p.width, height=p.height, znear=p.znear,
                      zfar=p.zfar)

    def open_server(arrays):
        mix = cell.traffic
        return RenderServer(
            {cell.config["name"]: GaussianScene(**arrays)},
            max_batch=int(mix["max_batch"]), max_wait=float(mix["max_wait"]))

    return cfg, make_camera, RenderRequest, open_server


def orbit_of(cell):
    o = dict(cell.config["orbit"])
    o["radius"] = float(cell.traffic["orbit_extents"]) * float(
        cell.config["extent"])
    return o


def _work(conf, counts):
    """Operations and bytes per frame (``bench/work.py``) from the mean of the
    sampled frames' reference counts; None where no frame was compared."""
    counts = [c for c in counts if c is not None]
    if not counts:
        return None
    mean = {k: float(np.mean([c[k] for c in counts])) for k in counts[0]}
    return frame_work(mean, conf["width"], conf["height"])


def _per_layer(cell, summary, frames, fps, chips, device_kind, work):
    peaks = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in peaks:
        raise KeyError(f"bench/peaks.json has no peaks for {device_kind!r}")
    ctx = SimpleNamespace(summary=summary, frames=frames, frames_per_s=fps,
                          chips=chips, work=work,
                          peak=peaks[device_kind], config=cell.config)
    out = {}
    for m in cell.per_layer:
        value = cell.reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


@dataclasses.dataclass
class Served:
    """What one served window left: its frames, clocks and trace."""

    attempted: int
    done: list            # window frames that came back, in request order
    t0: float             # first submit of the window
    t1: float             # last frame back
    setup_s: float
    memory_peak: int | None
    summary: object       # tracing.Summary with trace, else None
    arrays: dict          # the scene, as the benchmark drew it

    @property
    def frames_per_s(self) -> float:
        return len(self.done) / (self.t1 - self.t0) if self.t1 > self.t0 \
            else 0.0

    @property
    def latencies_ms(self) -> list:
        return [1e3 * (f.done_t - f.submit_t) for f in self.done]

    def sample(self) -> list:
        return [f for f in self.done if f.image is not None]


def serve(cell, seed, seconds, *, trace=False, hooks=None) -> Served:
    """Set-up and window of one run; the program's state is freed on
    return, and the frames of the seeded sample keep their images."""
    import jax

    conf, mix = cell.config, cell.traffic
    devs = jax.devices()[:int(cell.entry["chips"])]
    cfg, make_camera, make_request, open_server = _program(cell)

    # -- set-up ------------------------------------------------------------
    arrays = make_scene(seed, conf)
    jax.block_until_ready(arrays)
    server = open_server(arrays)
    if hooks and "server" in hooks:
        server = hooks["server"](server)
    n_check = int(conf["correct"]["frames"])
    sample_rng = np.random.default_rng([seed, 2])
    reservoir, seen = [], [0]

    def keep(frame):
        # Reservoir sample of the window's frames, drawn from the seed.
        seen[0] += 1
        if len(reservoir) < n_check:
            reservoir.append(frame)
            return True
        j = int(sample_rng.integers(seen[0]))
        if j >= n_check:
            return False
        reservoir[j].image = None
        reservoir[j] = frame
        return True

    annotate = jax.profiler.TraceAnnotation
    session = Session(server, conf["name"], cfg, orbit_of(cell),
                      conf["width"], conf["height"], make_camera,
                      make_request, annotate, keep=keep)
    poses = cell.kind.angles(mix, np.random.default_rng([seed, 1]))
    cell.kind.warm(session, mix, poses)
    session.warming = False
    setup_s = process_age()

    # -- the window --------------------------------------------------------
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        profiler = jax.profiler.trace(str(TRACE_DIR))
    else:
        profiler = contextlib.nullcontext()
    with profiler:
        with annotate(tracing.WINDOW_SPAN):
            cell.kind.drive(session, mix, seconds, poses)
    window = session.window()
    done = [f for f in window if f.done_t is not None]
    t0 = min(f.submit_t for f in window)
    t1 = max((f.done_t for f in done), default=t0)
    memory_peak = _peak_bytes(devs)
    summary = None
    if trace:
        summary = tracing.summarize(tracing.load(str(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR.parent, ignore_errors=True)
    server.close()
    del server, session
    gc.collect()
    return Served(len(window), done, t0, t1, setup_s, memory_peak, summary,
                  arrays)


def references(served, capacity, passes=6, counts=False):
    """Reference images of the sampled frames, on the device, one by one;
    ``capacity`` is the configuration's pair-list size for the reference.
    Returns the images and, with ``counts``, each frame's work counts
    (``reference.render``), else Nones."""
    import jax

    images, work = [], []
    with jax.profiler.TraceAnnotation("bench/check"):
        for f in served.sample():
            img, cnt = reference.render(served.arrays, f.pose, passes=passes,
                                        capacity=capacity,
                                        with_counts=counts)
            images.append(np.asarray(img))
            work.append(cnt)
    return images, work


def run(argv=None, *, require_chip=True, bench=None, hooks=None):
    """One run; returns the exit code. ``require_chip=False`` and ``bench``
    (a parsed benchmark) let the tests rehearse a run on the CPU, and
    ``hooks`` may wrap the server to plant a fault under the timed path."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload, bench)
    chips = int(cell.entry["chips"])

    import jax

    enable_cache(jax)
    if require_chip:
        why = look_for_chips(jax, chips)
        if why:
            print(f"bench: {why}; no result", file=sys.stderr)
            return NO_CHIP
    conf = cell.config
    dev = jax.devices()[0]
    served = serve(cell, args.seed, args.seconds, trace=bool(args.trace),
                   hooks=hooks)
    fps, lat_ms = served.frames_per_s, served.latencies_ms

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": served.memory_peak}
    result = {"correct": False, "attempted": served.attempted,
              "failed": served.attempted - len(served.done)}

    # -- the check, on freed program state ---------------------------------
    # A traced run also takes the sampled frames' work counts from the
    # reference, for the shares of peak and roofline.
    t = time.monotonic()
    images, counts = references(served, conf["correct"]["pair_capacity"],
                                counts=bool(args.trace))
    ok, numbers = check.judge(served.sample(), images,
                              conf["correct"]["limits"],
                              (conf["height"], conf["width"], 3),
                              result["failed"])
    check_s = time.monotonic() - t

    if args.trace:
        summary = served.summary
        metrics = _per_layer(cell, summary, len(served.done), fps, chips,
                             dev.device_kind, _work(conf, counts))
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.top_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps],
        }
    else:
        values = {
            "frames_per_s": fps,
            "frame_p50_ms": quantile(lat_ms, 0.50) if lat_ms else None,
            "frame_p95_ms": quantile(lat_ms, 0.95) if lat_ms else None,
            "setup_s": served.setup_s,
        }
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end
                   if values.get(m["name"]) is not None}
    result.update(correct=ok, metrics=metrics, device=device)
    result["window"] = {
        "frames": len(served.done), "seconds": served.t1 - served.t0,
        "latency_ms_median": statistics.median(lat_ms) if lat_ms else None,
        "check_s": check_s,
        "work_counts": [c for c in counts if c is not None] or None}
    result["check"] = numbers
    for line in check.report_lines(numbers):
        print(line, file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(run())
