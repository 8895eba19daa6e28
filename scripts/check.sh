#!/usr/bin/env bash
# Single CI gate: tier-1 tests (fast lane + slow remainder) + smoke renders.
#
#   scripts/check.sh          # fast lane, then the slow remainder = full tier-1
#   scripts/check.sh --fast   # fast lane only (-m "not slow", target < 5 min)
#
# The compiled-Pallas lane has no flag here: tests/test_tpu_compile.py
# compiles the kernels for a described v5e chip inside tier-1, and on a TPU
# host `python chip_smoke.py` renders through them (DESIGN.md §13).
#
# The fast lane is the quick signal: golden-image checksums (both backends),
# every non-slow parity/unit suite, with per-test timings reported so creep
# is visible. The slow remainder (-m slow) holds the pallas-interpret
# heavyweights and the subprocess/virtual-device suites; running it second
# keeps the default invocation equal to the full tier-1 set without running
# anything twice.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== tier-1 tests: fast lane (-m 'not slow') =="
python -m pytest -x -q -m "not slow" --durations=15

if [[ "${1:-}" != "--fast" ]]; then
    echo "== tier-1 tests: slow remainder (-m slow) =="
    python -m pytest -x -q -m "slow" --durations=15
fi

# module runs (benchmarks/, repro.*) need both roots on the path; pytest gets
# them from pyproject's pythonpath, plain `python -m` does not.
export PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}"

# Engine-handle smokes (DESIGN.md §11): both drivers run on the committed
# Renderer handle (engine.open), so these exercise commit -> per-handle jit
# cache -> render on each backend end to end.
SMOKE="--scene train --gaussians 1200 --width 256 --height 192 --capacity 256"
echo "== engine-handle smoke render: reference backend =="
python -m repro.launch.render $SMOKE --backend reference --stats
echo "== engine-handle smoke render: pallas backend =="
python -m repro.launch.render $SMOKE --backend pallas --stats

# Serving smoke: a small synthetic load through queue -> bucketing -> the
# server's shared handles; render_serve exits non-zero unless every request
# completes and p99 latency is finite.
echo "== smoke serve: reference backend =="
python -m repro.launch.render_serve --backend reference \
    --requests 8 --rate 200 --gaussians 600 --scenes train \
    --resolutions 96x96,128x96 --max-batch 4 --max-wait 0.05

# Scene-sharded handle smoke: 2 virtual host devices, gaussian axis over the
# mesh 'model' axis (DESIGN.md §10) with the FEATURE-SHARDED gathers the
# handle commits for a physical 'model' axis (feature_gather=psum, DESIGN.md
# §12). The handle-enforced --device-budget-mb now counts the per-camera
# projected features too: 0.04 MB admits the sharded layout (params + N/2
# features ~ 0.033 MB/device) but would REFUSE the replicated one (~0.065
# MB), so passing proves the full per-device footprint halves.
# --parity-check re-renders every request on a replicated handle and
# requires BITWISE-identical images (exit non-zero otherwise).
echo "== smoke serve: feature-sharded handle (2 virtual devices, bitwise parity) =="
python -m repro.launch.render_serve --backend reference --devices 2 \
    --scene-shards 2 --parity-check --device-budget-mb 0.04 \
    --requests 6 --rate 200 --gaussians 500 --scenes train \
    --resolutions 96x96 --max-batch 2 --max-wait 0.05 --no-realtime

# Autotune smoke (DESIGN.md §13): a 2x2 (group x capacity) grid at the
# default tile on a tiny scene through the full sweep -> BENCH emission
# path. Validates the schema-versioned document AND asserts the tuned
# config renders BITWISE-identical to the default config (group/capacity
# are the lossless axes; the smoke pins the tile so the guarantee is exact).
# Exits non-zero on any failure; writes under results/ so the committed
# BENCH_autotune_<host>.json trajectory is never clobbered by CI.
echo "== autotune smoke: 2x2 sweep, schema + bitwise tuned-vs-default =="
python benchmarks/bench_autotune.py --smoke

# Traced serving smoke (DESIGN.md §14): the same 2-virtual-device serve with
# REPRO_TRACE=1 (fenced per-stage device spans) writing a Chrome trace +
# metrics snapshot, then cross-validated — span nesting, >= 7 distinct stage
# span names, and request/batch counts agreeing across trace, metrics
# registry, and stats summary. Exits non-zero on any drift.
echo "== traced smoke serve: chrome trace + metrics registry cross-check =="
REPRO_TRACE=1 python -m repro.launch.render_serve --backend reference \
    --devices 2 --requests 6 --rate 200 --gaussians 500 --scenes train \
    --resolutions 96x96 --max-batch 2 --max-wait 0.05 --no-realtime \
    --trace-json results/trace_smoke.json \
    --metrics-json results/metrics_smoke.json
python scripts/validate_trace.py \
    results/trace_smoke.json results/metrics_smoke.json

# Stream smoke (DESIGN.md §15): 2 interactive camera streams on the
# 2-virtual-device server, frames lapping a 16-pose orbit so the exact-reuse
# frontend cache and the speculation worker both engage. --parity-check
# exits non-zero on ANY frame that is not BITWISE-identical to the stateless
# path (the verify-or-discard invariant), and validate_trace.py cross-checks
# the spec/* span counts against the stream/spec metrics counters.
echo "== stream smoke serve: exact-reuse + speculation, bitwise parity =="
REPRO_TRACE=1 python -m repro.launch.render_serve --backend reference \
    --devices 2 --scene-shards 2 --streams 2 --stream-frames 20 \
    --spec-depth 2 \
    --rate 200 --gaussians 500 --scenes train --resolutions 96x96 \
    --max-batch 4 --max-wait 0.05 --no-realtime --parity-check \
    --trace-json results/trace_stream_smoke.json \
    --metrics-json results/metrics_stream_smoke.json
python scripts/validate_trace.py \
    results/trace_stream_smoke.json results/metrics_stream_smoke.json

# Residency smoke (DESIGN.md §17): 3 scenes on the 2-virtual-device server
# under a budget that holds only ONE of them — commits succeed anyway
# (over-budget commits evict cold scenes instead of failing fast), the
# round-robin load thrashes the LRU, and --parity-check exits non-zero on
# ANY image that is not BITWISE-identical to the replicated unbudgeted
# path (paging must be invisible in the pixels). validate_trace.py
# (residency mode) cross-checks the residency/page_in|page_out span
# counts against the residency.* counters.
echo "== residency smoke serve: 3 scenes in a 1-scene budget, bitwise parity =="
REPRO_TRACE=1 python -m repro.launch.render_serve --backend reference \
    --devices 2 --requests 12 --rate 200 --gaussians 500 \
    --scenes train,truck,drjohnson --resolutions 96x96 \
    --max-batch 2 --max-wait 0.05 --no-realtime --parity-check \
    --device-budget-mb 0.1 \
    --trace-json results/trace_residency_smoke.json \
    --metrics-json results/metrics_residency_smoke.json
python scripts/validate_trace.py \
    results/trace_residency_smoke.json results/metrics_residency_smoke.json

# Measured per-stage bench smoke (DESIGN.md §14): tiny scene through the
# timing=True engine path -> BENCH_stages schema validation.
echo "== bench_stages smoke: measured per-stage spans, schema valid =="
python benchmarks/bench_stages.py --smoke

# Gateway chaos smoke (DESIGN.md §16): 2 subprocess workers (2 virtual
# devices EACH, in their own jax runtimes) behind the gateway; w0 is
# SIGKILLed after 3 completions mid-load. render_gateway exits non-zero
# unless 100% of requests complete with finite p99, zero failures, and at
# least one failover; validate_trace.py (gateway mode) then cross-checks
# the gateway/route|retry|failover span counts against the gateway.*
# counters and the embedded summary.
echo "== gateway chaos smoke: 2 workers, induced kill, failover cross-check =="
python -m repro.launch.render_gateway --workers 2 --devices-per-worker 2 \
    --requests 16 --rate 200 --gaussians 400 --scenes train,truck \
    --resolutions 96x96 --max-batch 4 --kill-worker auto --kill-after 3 \
    --no-realtime \
    --trace-json results/trace_gateway_smoke.json \
    --metrics-json results/metrics_gateway_smoke.json
python scripts/validate_trace.py \
    results/trace_gateway_smoke.json results/metrics_gateway_smoke.json

echo "check.sh: OK"
