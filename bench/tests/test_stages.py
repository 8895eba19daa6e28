"""What the program's own records and stage scopes give the benchmark:
the serving and engine readers over a rehearsed window, and the stage
split and idle spans of a recorded CPU trace (``bench/stages.py``)."""
import types

import pytest

from bench import run, stages
from bench.spec import load_cell

READERS = ("queue_wait_ms", "fetch_ms", "compiles_in_window",
           "setup_compile_s")


@pytest.fixture
def fresh_registry():
    from repro.obs import get_registry

    get_registry().reset()
    yield get_registry()
    get_registry().reset()


def _read(cell, name, frames):
    ctx = types.SimpleNamespace(frames=frames)
    return cell.reader(name).read(ctx)


def test_readers_take_the_window_from_the_program_records(tiny_bench,
                                                          fresh_registry):
    cell = load_cell("tiny.backlog", tiny_bench)
    served = run.serve(cell, 2**31 + 77, 1.0)
    frames = len(served.done)
    assert frames >= 2
    got = {name: _read(cell, name, frames) for name in READERS}
    assert got["queue_wait_ms"] >= 0.0
    assert got["fetch_ms"] > 0.0
    assert got["compiles_in_window"] == 0.0
    assert got["setup_compile_s"] > 0.0
    # A window that does not end on a dispatch boundary is not read.
    assert _read(cell, "compiles_in_window", frames + 1) is None


def test_readers_give_nothing_without_the_program_records(tiny_bench,
                                                          fresh_registry):
    cell = load_cell("tiny.backlog", tiny_bench)
    for name in READERS:
        assert _read(cell, name, 4) is None


def test_a_cpu_trace_splits_by_stage_and_labels_idle_time(tiny_bench):
    """The served program's ops fall in their stage scopes, every sort in
    ``bin``; the stage times add up to the busy classes; idle time falls
    in the benchmark's and the program's spans; the live spans anchor."""
    cell = load_cell("tiny.backlog", tiny_bench)
    with stages.keeping(cell) as (kept, hooks):
        served = run.serve(cell, 5, 1.0, trace=True, hooks=hooks)
    frames = len(served.done)
    out = stages.split(kept, frames)
    ms = out["stage_ms"]
    for stage in ("project", "identify", "bin", "bitmask", "compact",
                  "raster"):
        assert ms[stage] > 0.0, stage
    assert ms["merge"] == 0.0
    classes = served.summary.class_s
    total_ms = 1e3 * sum(classes.values()) / frames
    assert sum(ms.values()) == pytest.approx(total_ms, rel=1e-6)
    assert out["class_by_stage"]["bin"]["sort"] == pytest.approx(
        classes["sort"])
    idle = out["idle_by_span"]
    assert idle and all(name == "none" or name.startswith(("bench/",
                                                           "serve/"))
                        for name in idle)
    assert out["anchors"]["spans"] >= 4 * frames // 2
    assert out["anchors"]["offset_spread_s"] < 1e-3


def test_program_stages_follow_fusions_into_their_computations():
    text = "\n".join([
        "HloModule jit_one, is_scheduled=true",
        "",
        "%fused_computation (param_0: f32[4]) -> f32[4] {",
        "  %param_0 = f32[4]{0} parameter(0)",
        '  ROOT %mul.1 = f32[4]{0} multiply(%param_0, %param_0), '
        'metadata={op_name="jit(one)/vmap(gstg/raster)/mul"}',
        "}",
        "",
        "ENTRY %main (x: f32[4]) -> f32[4] {",
        '  %x = f32[4]{0} parameter(0), metadata={op_name="x"}',
        '  %sort.0 = f32[4]{0} sort(%x), dimensions={0}, '
        'metadata={op_name="jit(one)/vmap(gstg/bin)/sort"}',
        "  ROOT %fusion = f32[4]{0} fusion(%sort.0), kind=kLoop, "
        "calls=%fused_computation",
        "}",
    ])
    got = stages.program_stages(text)
    assert got["sort.0"] == "bin"
    assert got["fusion"] == "raster"
    assert got["x"] is None
    assert stages.instruction("%fusion = f32[4]{0} fusion(%sort.0)") \
        == "fusion"
    assert stages.instruction("fusion") == "fusion"
