"""CPU rehearsals of whole runs at the test-only tiny configuration: the
chip look, the result line, the lower-precision control, and faults
planted under the timed path that ``correct`` has to catch."""
import json

import numpy as np
import pytest

from bench import control, run


def _run(capsys, tiny_bench, workload, seed, *, hooks=None, trace=0,
         require_chip=False):
    rc = run.run(["--workload", workload, "--seed", str(seed), "--seconds",
                  "1.5", "--trace", str(trace)], require_chip=require_chip,
                 bench=tiny_bench, hooks=hooks)
    out, err = capsys.readouterr()
    return rc, out, err


def test_without_a_chip_a_run_exits_and_prints_no_result(capsys,
                                                         tiny_bench):
    rc, out, err = _run(capsys, tiny_bench, "tiny.backlog", 1,
                        require_chip=True)
    assert rc == run.NO_CHIP
    assert out == ""
    assert "no accelerator" in err


@pytest.mark.parametrize("workload", ["tiny.backlog", "tiny.viewer"])
def test_a_rehearsed_run_prints_the_result_line(capsys, tiny_bench,
                                                workload):
    rc, out, err = _run(capsys, tiny_bench, workload, 2**31 + 12345)
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "check"
    assert line["correct"] is True, line["check"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    m = line["metrics"]
    assert m["frames_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert ("frame_p95_ms" in m) == (workload == "tiny.viewer")
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert err.strip().splitlines()[-1].startswith("check requests_failed")


def test_a_traced_run_on_the_cpu_fails_rather_than_report(capsys,
                                                          tiny_bench):
    with pytest.raises(KeyError, match="no peaks for 'cpu'"):
        _run(capsys, tiny_bench, "tiny.backlog", 3, trace=1)


def test_the_control_fails_where_the_program_passes(tiny_bench, capsys):
    assert control.main(["--workload", "tiny.backlog", "--seeds", "4,5",
                         "--seconds", "0.5"], require_chip=False,
                        bench=tiny_bench) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    limits = json.loads(open("bench/tests/data/tiny.json").read())[
        "correct"]["limits"]
    assert all(last["program_max"][k] <= v for k, v in limits.items())
    assert any(last["control_min"][k] > v for k, v in limits.items())
    assert (last["program_correct"], last["control_correct"]) == (2, 0)


def _after_dispatch(server, alter):
    orig = server._dispatch

    def dispatch(bucket):
        orig(bucket)
        alter([server.results[r.request_id] for r in bucket.requests])

    server._dispatch = dispatch
    return server


def _tile_altered(results):
    for r in results:
        r.image = np.array(r.image)
        r.image[16:32, 16:32] = 0.0


def _lanes_swapped(results):
    if len(results) > 1:
        results[0].image, results[1].image = results[1].image, \
            results[0].image


class _Stale:
    """Each answer is the previous request's frame."""

    def __init__(self):
        self.last = None

    def __call__(self, results):
        for r in results:
            img = r.image
            if self.last is not None:
                r.image = self.last
            self.last = img


@pytest.mark.parametrize("fault, workload", [
    ("tile_altered", "tiny.backlog"),
    ("lanes_swapped", "tiny.backlog"),
    ("stale_frame", "tiny.viewer"),
    ("entries_dropped", "tiny.backlog"),
])
def test_a_planted_fault_makes_correct_false(capsys, tiny_bench, fault,
                                             workload, monkeypatch):
    alter = {"tile_altered": _tile_altered, "lanes_swapped": _lanes_swapped,
             "stale_frame": _Stale()}.get(fault)
    hooks = None
    if alter is not None:
        hooks = {"server": lambda s: _after_dispatch(s, alter)}
    else:
        # The lossless guarantee broken: per-tile lists cut short.
        from bench import spec

        load = spec.load_json

        def small_tables(path):
            conf = load(path)
            if conf.get("name") == "tiny":
                conf["render"]["tile_capacity"] = 64
            return conf

        monkeypatch.setattr(spec, "load_json", small_tables)
    rc, out, err = _run(capsys, tiny_bench, workload, 6, hooks=hooks)
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is False, (fault, line["check"])


def test_memory_peak_counts_what_is_reserved_for_programs():
    class Device:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    devs = [Device({"peak_bytes_in_use": 3, "peak_bytes_reserved": 10}),
            Device({"peak_bytes_in_use": 5})]
    assert run._peak_bytes(devs) == 13
    assert run._peak_bytes([Device(None)]) is None


def test_table_sizing_reads_lists_within_the_capacities(tiny_bench, capsys):
    from bench import size_tables

    assert size_tables.main(["--workload", "tiny.backlog", "--seeds", "1",
                             "--frames", "1"], bench=tiny_bench) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert 0 < last["longest_tile_list"] <= last["longest_group_list"]
    assert last["longest_group_list"] <= last["group_capacity"]
    assert last["tile_lists_exact"] and last["span_overflow"] == 0
