"""The reduction from a profiler trace to busy time, idle share and the
sort / kernel / other split."""
import jax
import jax.numpy as jnp
import pytest

from bench import tracing
from bench.tracing import Op


TPU_KERNEL = ('%vmap__.3 = f32[2,558,16,8,256]{4,3,2,1,0:T(8,128)} '
              'custom-call(f32[2,558,16,24576]{3,2,1,0:T(8,128)} '
              '%concatenate.25), custom_call_target="tpu_custom_call"')
TPU_SORT = ('%sort.0 = (f32[2,36936000]{1,0:T(2,128)}, s32[2,36936000]{1,0:'
            'T(2,128)}) sort(f32[2,36936000]{1,0:T(2,128)} %bitcast.260), '
            'dimensions={1}, is_stable=true, to_apply=%region_4.32')
TPU_FUSION = ('%fusion.13 = f32[27426816,2]{0,1:T(2,128)} fusion(f32[2,'
              '1026000,2]{1,2,0:T(2,128)S(1)} %custom-call.40, s32[27426816]'
              '{0:T(1024)} %get-tuple-element.174), kind=kCustom')
TPU_WHILE = ('%while.31 = (u32[]{:T(128)}, s32[73872000]{0:T(1024)}) '
             'while((u32[]{:T(128)}, s32[73872000]{0:T(1024)}) %tuple.5)')


def test_classify_by_the_hlo_opcode():
    assert tracing.opcode(TPU_SORT) == "sort"
    assert tracing.opcode(TPU_KERNEL) == "custom-call"
    assert tracing.classify(TPU_KERNEL) == "kernel"
    assert tracing.classify(TPU_SORT) == "sort"
    # A fusion that reads a custom call's result is not a kernel.
    assert tracing.classify(TPU_FUSION) == "other"
    assert tracing.classify(TPU_WHILE) == "container"
    assert tracing.classify("sort.3") == "sort"
    assert tracing.classify("wrapped_sine") == "other"


def test_reduce_counts_the_union_of_busy_intervals_inside_the_window():
    spans = [("bench/window", 1.0, 11.0), ("bench/step", 1.0, 6.0),
             ("bench/drain", 6.5, 11.0)]
    ops = {"/device:TPU:0": [
        Op("sort.1", 0.5, 2.0, "sort"),       # clipped to 1.0 .. 2.0
        Op("fusion.1", 1.5, 3.0, "other"),    # overlaps the sort
        Op("kernel", 4.0, 5.0, "kernel"),
        Op("fusion.2", 7.0, 8.0, "other"),
        Op("fusion.3", 12.0, 13.0, "other"),  # outside the window
        Op("while.1", 7.0, 8.0, "container"),  # holds fusion.2
    ]}
    s = tracing.reduce(ops, spans)
    assert s.window_s == pytest.approx(10.0)
    assert s.busy_s == pytest.approx(1.0 + 1.0 + 1.0 + 1.0)   # 1-3, 4-5, 7-8
    assert s.class_s == pytest.approx({"sort": 1.0, "other": 2.5,
                                       "kernel": 1.0})
    # Gaps: 3-4 and 5-7 in the step, 8-11 in the drain.
    assert s.idle_gaps[0] == ("bench/drain", pytest.approx(3.0))
    assert [g for g, _ in s.idle_gaps] == ["bench/drain", "bench/step",
                                           "bench/step"]
    assert sum(d for _, d in s.idle_gaps) == pytest.approx(6.0)
    assert s.top_ops[0][0] == "fusion.1"


def test_reduce_averages_busy_time_over_devices():
    spans = [("bench/window", 0.0, 4.0)]
    ops = {"/device:TPU:0": [Op("a", 0.0, 4.0, "other")],
           "/device:TPU:1": [Op("a", 0.0, 2.0, "other")]}
    s = tracing.reduce(ops, spans)
    assert s.devices == 2
    assert s.busy_s == pytest.approx(3.0)


def test_reduce_needs_a_window_and_device_work():
    with pytest.raises(ValueError):
        tracing.reduce({"d": [Op("a", 0, 1, "other")]}, [])
    with pytest.raises(ValueError):
        tracing.reduce({}, [("bench/window", 0.0, 1.0)])


def test_a_trace_recorded_on_the_cpu(tmp_path):
    """XLA:CPU ops carry an ``hlo_op`` stat on host threads; the window
    span comes from the benchmark's own TraceAnnotation."""
    f = jax.jit(lambda x: jnp.sort(x) * 2.0 + jnp.sin(x).sum())
    x = jnp.arange(1 << 15, dtype=jnp.float32)[::-1]
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench/step"):
                    f(x).block_until_ready()
    s = tracing.summarize(tracing.load(str(tmp_path)))
    assert s.devices == 1
    assert 0.0 < s.busy_s <= s.window_s
    assert s.class_s["sort"] > 0.0
    assert s.class_s["other"] > 0.0
    assert s.class_s["kernel"] == 0.0
    assert any(name.startswith("sort") for name, _ in s.top_ops)
    labels = {g for g, _ in s.idle_gaps}
    assert labels <= {"bench/step", "none"}
