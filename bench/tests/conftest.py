"""The harness tests run on the CPU, with Pallas kernels interpreted."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import copy  # noqa: E402

import pytest  # noqa: E402

TINY_CELLS = {"tiny.backlog": ("backlog_b2", "train.backlog"),
              "tiny.viewer": ("viewer", "train.viewer")}


@pytest.fixture(scope="session")
def bench_json():
    from bench.spec import load_json

    return load_json(ROOT / "BENCHMARK.json")


@pytest.fixture
def tiny_bench(bench_json):
    """BENCHMARK.json with the test-only tiny configuration (128x96, 3000
    Gaussians) and two tiny cells that report what the train cells of the
    same mix report. Not cells of the benchmark: only these tests use
    them."""
    b = copy.deepcopy(bench_json)
    b["configs"].append({"name": "tiny", "source": "test only",
                         "file": "bench/tests/data/tiny.json",
                         "reduced": [], "why": "test only"})
    for name, (traffic, _) in TINY_CELLS.items():
        b["workloads"].append({"name": name, "config": "tiny",
                               "traffic": traffic, "chips": 1,
                               "why": "test only"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + [
                name for name, (_, like) in TINY_CELLS.items()
                if like in m["workloads"]]
    return b
