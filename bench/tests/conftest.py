"""The harness tests run on the CPU, with Pallas kernels interpreted."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import pytest  # noqa: E402

from bench.tests.cells import with_test_cells  # noqa: E402


@pytest.fixture(scope="session")
def bench_json():
    from bench.spec import load_json

    return load_json(ROOT / "BENCHMARK.json")


@pytest.fixture
def tiny_bench(bench_json):
    """BENCHMARK.json with the test-only configurations and cells of
    ``bench/tests/cells.py``, added as files and entries alone."""
    return with_test_cells(bench_json)
