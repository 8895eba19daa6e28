"""BENCHMARK.json against its contract, and discovery of configurations,
mixes, kinds and metric readers by name."""
import copy
import json
import re

import numpy as np
import pytest

from bench.spec import BENCH, ROOT, load_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection",
               "head", "expansion", "per_tok")


def test_top_level_keys_and_sizes(bench_json):
    assert set(bench_json) == {"command", "paths", "run_seconds", "configs",
                               "workloads", "end_to_end", "per_layer"}
    assert bench_json["command"] == ["python3", "bench/run.py"]
    assert bench_json["paths"] == ["bench"]
    assert 1 <= bench_json["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_names_units_and_entries(bench_json):
    names = []
    for c in bench_json["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k)
            assert not k.endswith(("_dim", "_rank"))
            assert not any(w in k for w in WIDTH_WORDS)
        names.append(c["name"])
    used = {w["config"] for w in bench_json["workloads"]}
    assert used == set(names)
    for w in bench_json["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names.append(w["name"])
    for m in bench_json["end_to_end"] + bench_json["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.append(m["name"])
    for n in names:
        assert NAME.match(n), n
    assert len(names) == len(set(names))


def test_bounds(bench_json):
    e2e = {m["name"]: m for m in bench_json["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_cell_reports_setup_another_metric_and_a_layer(bench_json):
    for w in bench_json["workloads"]:
        cell = load_cell(w["name"], bench_json)
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        moved = {m["moves"] for m in cell.per_layer}
        assert moved <= e2e


@pytest.mark.parametrize("kind", ["configs", "traffic", "metrics"])
def test_every_file_is_found_by_name(bench_json, kind):
    if kind == "configs":
        for c in bench_json["configs"]:
            conf = json.loads((ROOT / c["file"]).read_text())
            assert conf["name"] == c["name"]
            assert set(conf["reduced"]) == set(c["reduced"])
            for k in conf["reduced"]:
                assert conf["published"][k] != conf[k]
    elif kind == "traffic":
        for w in bench_json["workloads"]:
            cell = load_cell(w["name"], bench_json)
            for fn in ("angles", "warm", "drive"):
                assert callable(getattr(cell.kind, fn))
    else:
        for m in bench_json["per_layer"]:
            assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
            cell = load_cell(bench_json["workloads"][0]["name"], bench_json)
            assert callable(cell.reader(m["name"]).read)


def test_a_new_cell_needs_only_files_and_entries(tiny_bench):
    cell = load_cell("tiny.viewer", tiny_bench)
    assert cell.config["name"] == "tiny"
    assert cell.traffic["path"] == "drag"
    assert {m["name"] for m in cell.end_to_end} >= {"frames_per_s",
                                                    "setup_s"}


def _scene_shards_within_chips(bench):
    for w in bench["workloads"]:
        cell = load_cell(w["name"], bench)
        shards = cell.config["render"].get("scene_shards", 1)
        assert shards in (1, w["chips"]), w["name"]


def test_scene_shards_are_one_or_the_cells_chips(bench_json, tiny_bench):
    _scene_shards_within_chips(bench_json)
    _scene_shards_within_chips(tiny_bench)
    bad = copy.deepcopy(tiny_bench)
    for w in bad["workloads"]:
        if w["name"] == "tiny_sharded.backlog":
            w["chips"] = 1
    with pytest.raises(AssertionError, match="tiny_sharded.backlog"):
        _scene_shards_within_chips(bad)


def test_an_sh3_sharded_cell_needs_only_files_and_entries(tiny_bench):
    """An SH-3, 4-chip, Gaussian-sharded configuration loads by name, its
    scene and reference are drawn on the CPU, and the 3-pass control
    differs from the reference at SH degree 3."""
    from bench import reference
    from bench.poses import orbit_pose
    from bench.run import orbit_of
    from bench.scene import make_scene

    cell = load_cell("tiny_sh3.backlog", tiny_bench)
    conf = cell.config
    assert (conf["sh_degree"], cell.entry["chips"]) == (3, 4)
    assert conf["render"]["scene_shards"] == 4
    scene = make_scene(2**31 + 5, conf)
    assert scene["sh"].shape == (conf["gaussians"], 16, 3)
    pose = orbit_pose(1.0, orbit_of(cell), conf["width"], conf["height"])
    img, counts = reference.render(
        scene, pose, capacity=conf["correct"]["pair_capacity"],
        with_counts=True)
    img = np.asarray(img)
    assert img.shape == (conf["height"], conf["width"], 3)
    assert np.isfinite(img).all() and img.max() > 0.0
    assert counts["sh_coeffs"] == 16 and counts["visible"] > 0
    low, _ = reference.render(
        scene, pose, capacity=conf["correct"]["pair_capacity"], passes=3)
    assert not np.array_equal(np.asarray(low), img)


def test_readers_return_nothing_without_work_counts():
    from types import SimpleNamespace

    from bench.tracing import Summary

    s = Summary(window=(0.0, 10.0), devices=1, busy_s=9.0,
                class_s={"kernel": 0.0, "sort": 4.0, "other": 5.0},
                top_ops=[], idle_gaps=[])
    ctx = SimpleNamespace(summary=s, frames=2, frames_per_s=0.2, chips=1,
                          work=None, peak={"ops_per_s": 1.0,
                                           "bytes_per_s": 1.0}, config={})
    cell_readers = {p.stem: p for p in (BENCH / "metrics").glob("*.py")}
    from bench.spec import load_module

    got = {n: load_module(p).read(ctx) for n, p in cell_readers.items()}
    assert got["device_idle_pct"] == pytest.approx(10.0)
    assert got["sort_ms"] == pytest.approx(2000.0)
    assert got["xla_other_ms"] == pytest.approx(2500.0)
    assert got["kernels_ms"] is None
    assert got["kernels_roofline_pct"] is None
    assert got["frame_mfu_pct"] is None
