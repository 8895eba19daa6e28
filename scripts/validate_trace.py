#!/usr/bin/env python
"""CI validator for the traced serving smoke (scripts/check.sh).

  PYTHONPATH=src python scripts/validate_trace.py TRACE.json METRICS.json

Cross-checks the three observability surfaces one ``repro.launch
.render_serve --trace-json --metrics-json`` run emits (DESIGN.md §14):

  * the Chrome trace itself: ``repro.trace/v1`` schema, well-formed events,
    per-(pid, tid) span nesting (``repro.obs.validate_chrome_trace``);
  * stage coverage: with REPRO_TRACE=1 the timed renders must record >= 7
    distinct ``cat == "stage"`` span names (project/identify/bin/bitmask/
    compact/rasterize + the enclosing render; merge rides along when the
    scene is gaussian-sharded; the group-split backend's ``exchange`` is a
    scope of the fused program, which timed renders do not run);
  * trace <-> metrics <-> summary consistency: completed requests and
    dispatched batches must agree between the request/serve spans, the
    ``serving.*`` counters + latency histogram, and the stats summary
    embedded under the trace's ``"summary"`` key; every dispatch holds its
    nested ``serve/launch``, ``serve/device_wait`` and ``serve/fetch``
    spans, and every request its ``request/device`` and ``request/fetch``
    phases;
  * band split (DESIGN.md §10): ``serving.band_skew``, one per request a
    group-split backend drew, is at least 1 and counts no more requests
    than completed;
  * residency paging (DESIGN.md §17): ``residency/page_in|page_out``
    span counts must equal the ``residency.page_ins_total|page_outs_total``
    counters (span + counter are recorded in the same critical section),
    in both serve and gateway modes.

Exits non-zero listing every drift — the point is that a broken stamp,
a lost span, or a double-counted metric fails CI instead of silently
skewing the next perf investigation.
"""
from __future__ import annotations

import json
import sys

from repro.obs import validate_chrome_trace

MIN_STAGE_NAMES = 7
# The live phases nested under every serve/dispatch (and engine/dispatch).
DISPATCH_PHASES = ("serve/launch", "serve/device_wait", "serve/fetch")


def validate_residency(xs: list, counters: dict) -> list:
    """Residency-mode checks (DESIGN.md §17): every scene page-in/-out
    records its ``residency/*`` span and bumps its ``residency.*`` counter
    in the same critical section, so the two surfaces must agree exactly.
    Enforced whenever the run paged at all (any residency counter or span
    present) — which includes every serve run, since commits page scenes
    in even with no budget set."""
    errs = []
    if "residency.page_ins_total" not in counters and not any(
        e.get("cat") == "residency" for e in xs
    ):
        return errs
    for name, counter in (
        ("residency/page_in", "residency.page_ins_total"),
        ("residency/page_out", "residency.page_outs_total"),
    ):
        n_span = sum(1 for e in xs if e.get("name") == name)
        n_counter = counters.get(counter, 0)
        if n_span != n_counter:
            errs.append(
                f"{name} spans = {n_span} but counters[{counter!r}] = "
                f"{n_counter} — a page transition lost its span or "
                f"double-counted")
    evictions = counters.get("residency.evictions_total", 0)
    page_outs = counters.get("residency.page_outs_total", 0)
    if evictions > page_outs:
        errs.append(
            f"counters['residency.evictions_total'] = {evictions} exceeds "
            f"page_outs = {page_outs} — an eviction that never paged out")
    return errs


def validate_gateway(trace_doc: dict, metrics_doc: dict) -> list:
    """Gateway-mode checks (``repro.launch.render_gateway --trace-json``):
    the rendering happens inside worker subprocesses, so there are no
    stage/serving spans in the parent trace — instead the ``gateway/*``
    span family must match the ``gateway.*`` counters and the embedded
    summary one-to-one (route spans == routed, retry spans == retries,
    failover spans == failovers, request spans == completed)."""
    errs = list(validate_chrome_trace(trace_doc))
    xs = [e for e in trace_doc.get("traceEvents", [])
          if isinstance(e, dict) and e.get("ph") == "X"]
    summary = trace_doc.get("summary", {})
    if metrics_doc.get("schema") != "repro.metrics/v1":
        errs.append(f"metrics schema != 'repro.metrics/v1': "
                    f"{metrics_doc.get('schema')!r}")
    counters = metrics_doc.get("counters", {})

    spans = {}
    for e in xs:
        if e.get("cat") == "gateway":
            spans[e["name"]] = spans.get(e["name"], 0) + 1
    for name, counter, key in (
        ("gateway/route", "gateway.routed_total", "routed"),
        ("gateway/retry", "gateway.retries_total", "retries"),
        ("gateway/failover", "gateway.failovers_total", "failovers"),
    ):
        n_span = spans.get(name, 0)
        n_counter = counters.get(counter, 0)
        n_summary = summary.get(key)
        if not (n_span == n_counter == n_summary):
            errs.append(
                f"{name} spans = {n_span}, counters[{counter!r}] = "
                f"{n_counter}, summary.{key} = {n_summary} — must agree")

    req_ids = {e["args"]["request_id"] for e in xs
               if e.get("cat") == "request" and e.get("name") == "request"}
    completed = summary.get("completed")
    done_counter = counters.get("gateway.completed_total")
    for label, got in (
        ("request spans in trace", len(req_ids)),
        ("counters['gateway.completed_total']", done_counter),
    ):
        if got != completed:
            errs.append(f"{label} = {got} but summary.completed = {completed}")

    # An induced kill must leave a consistent failure record: a failover
    # implies a worker-death counter and at least one retry span.
    if summary.get("failovers", 0) > 0:
        if counters.get("gateway.worker_deaths_total", 0) < 1:
            errs.append("summary.failovers > 0 but "
                        "counters['gateway.worker_deaths_total'] < 1")
        if spans.get("gateway/retry", 0) < 1:
            errs.append("summary.failovers > 0 but no gateway/retry spans")
    # Inproc fleets page in the parent process (subprocess workers page in
    # their own registries — both sides absent here, trivially consistent).
    errs.extend(validate_residency(xs, counters))
    return errs


def validate(trace_doc: dict, metrics_doc: dict) -> list:
    if trace_doc.get("summary", {}).get("gateway"):
        return validate_gateway(trace_doc, metrics_doc)
    errs = list(validate_chrome_trace(trace_doc))

    xs = [e for e in trace_doc.get("traceEvents", [])
          if isinstance(e, dict) and e.get("ph") == "X"]
    stage_names = {e["name"] for e in xs if e.get("cat") == "stage"}
    if len(stage_names) < MIN_STAGE_NAMES:
        errs.append(
            f"only {len(stage_names)} distinct stage span names "
            f"{sorted(stage_names)}; need >= {MIN_STAGE_NAMES} "
            f"(was the run traced with REPRO_TRACE=1?)")

    summary = trace_doc.get("summary")
    if not isinstance(summary, dict):
        errs.append("trace is missing the embedded 'summary' object")
        summary = {}

    if metrics_doc.get("schema") != "repro.metrics/v1":
        errs.append(f"metrics schema != 'repro.metrics/v1': "
                    f"{metrics_doc.get('schema')!r}")
    counters = metrics_doc.get("counters", {})
    hists = metrics_doc.get("histograms", {})

    # Completed requests: request spans == serving.requests_total ==
    # summary.completed == latency histogram count.
    req_ids = {e["args"]["request_id"] for e in xs
               if e.get("cat") == "request" and e.get("name") == "request"}
    completed = summary.get("completed")
    req_counter = counters.get("serving.requests_total")
    lat_count = hists.get("serving.latency_s", {}).get("count")
    for label, got in (
        ("request spans in trace", len(req_ids)),
        ("counters['serving.requests_total']", req_counter),
        ("latency histogram count", lat_count),
    ):
        if got != completed:
            errs.append(f"{label} = {got} but summary.completed = {completed}")

    # Dispatched batches: serve/dispatch spans == serving.batches_total ==
    # summary.batches.
    dispatches = sum(1 for e in xs if e.get("name") == "serve/dispatch")
    batches = summary.get("batches")
    batch_counter = counters.get("serving.batches_total")
    for label, got in (
        ("serve/dispatch spans in trace", dispatches),
        ("counters['serving.batches_total']", batch_counter),
    ):
        if got != batches:
            errs.append(f"{label} = {got} but summary.batches = {batches}")

    # Each dispatch (server or engine handle) splits into its three live
    # phases, each nested in it on the dispatching thread's lane.
    outer = [e for e in xs
             if e.get("name") in ("serve/dispatch", "engine/dispatch")]
    for phase in DISPATCH_PHASES:
        inside = sum(
            1 for e in xs if e.get("name") == phase and any(
                o["tid"] == e["tid"] and o["ts"] <= e["ts"]
                and e["ts"] + e["dur"] <= o["ts"] + o["dur"] + 0.01
                for o in outer))
        if inside != len(outer):
            errs.append(
                f"{phase} spans nested in a dispatch = {inside} but "
                f"dispatches = {len(outer)}")

    # Stream/speculation consistency (DESIGN.md §15): every stream frame
    # records exactly one spec/verify span (the exact-reuse cache decision),
    # so the span count must equal the stream hit+miss counter totals; every
    # speculative frontend records one spec/run span matching spec.runs_total.
    # Only enforced when the run actually served streams — stateless smokes
    # carry no stream counters or spec spans.
    stream_frames = counters.get("stream.frames_total")
    if stream_frames is not None or any(e.get("cat") == "spec" for e in xs):
        hits = counters.get("stream.hits_total", 0)
        misses = counters.get("stream.misses_total", 0)
        verifies = sum(1 for e in xs if e.get("name") == "spec/verify")
        if verifies != hits + misses:
            errs.append(
                f"spec/verify spans = {verifies} but stream hit+miss "
                f"counters total {hits + misses} "
                f"(hits={hits}, misses={misses})")
        if stream_frames != hits + misses:
            errs.append(
                f"counters['stream.frames_total'] = {stream_frames} but "
                f"hit+miss counters total {hits + misses}")
        spec_runs = counters.get("spec.runs_total", 0)
        run_spans = sum(1 for e in xs if e.get("name") == "spec/run")
        if run_spans != spec_runs:
            errs.append(
                f"spec/run spans = {run_spans} but "
                f"counters['spec.runs_total'] = {spec_runs}")

    # Every request span must carry its device phase — a request that
    # completed without a dispatch/device_done stamp pair means a lifecycle
    # stamp went missing.
    # The same for the image copy to the host (device_done/fetched).
    for phase in ("request/device", "request/fetch"):
        ids = {e["args"]["request_id"] for e in xs
               if e.get("cat") == "request" and e.get("name") == phase}
        missing = req_ids - ids
        if missing:
            errs.append(f"{len(missing)} request(s) have no {phase} span: "
                        f"{sorted(missing)[:5]}")

    # Band split (DESIGN.md §10): each request a group-split backend drew
    # publishes one serving.band_skew, its largest band's entries over the
    # mean band's: never below 1, and for no more requests than completed.
    skew = hists.get("serving.band_skew")
    if skew and skew.get("count"):
        if skew["count"] > (completed or 0):
            errs.append(f"serving.band_skew count = {skew['count']} but "
                        f"summary.completed = {completed}")
        if skew.get("min", 1.0) < 1.0:
            errs.append(f"serving.band_skew min = {skew['min']} < 1")

    errs.extend(validate_residency(xs, counters))
    return errs


def main(argv) -> int:
    if len(argv) != 3:
        print(__doc__.strip().splitlines()[2].strip())
        return 2
    with open(argv[1]) as f:
        trace_doc = json.load(f)
    with open(argv[2]) as f:
        metrics_doc = json.load(f)
    errs = validate(trace_doc, metrics_doc)
    if errs:
        for e in errs:
            print(f"validate_trace: DRIFT: {e}")
        print(f"validate_trace: FAILED ({len(errs)} problems)")
        return 1
    n_events = len(trace_doc.get("traceEvents", []))
    summary = trace_doc.get("summary", {})
    tail = (f"failovers={summary.get('failovers')}" if summary.get("gateway")
            else f"batches={summary.get('batches')}")
    print(f"validate_trace: OK ({n_events} events, "
          f"{trace_doc.get('dropped', 0)} dropped, "
          f"completed={summary.get('completed')}, {tail})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
