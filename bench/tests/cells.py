"""Test-only configurations and cells, added to a parsed BENCHMARK.json as
files and entries alone, the way a later change adds a cell. Not cells of
the benchmark: only the harness tests use them.

- ``tiny`` (128x96, 3000 Gaussians, SH degree 1): ``tiny.backlog`` and
  ``tiny.viewer`` report what ``train.backlog`` and ``train.viewer``
  report;
- ``tiny_sharded``: ``tiny`` with ``render.scene_shards`` 4, served in
  ``tiny_sharded.backlog`` on 4 chips;
- ``tiny_sh3``: ``tiny`` at SH degree 3 with ``render.scene_shards`` 4,
  in ``tiny_sh3.backlog`` on 4 chips; the program serves SH degree 1 at
  most, so only its scene and reference are drawn.
"""
import copy

TEST_CONFIGS = ("tiny", "tiny_sharded", "tiny_sh3")

# cell: (configuration, traffic, chips, the benchmark cell it reports like)
TEST_CELLS = {
    "tiny.backlog": ("tiny", "backlog_b2", 1, "train.backlog"),
    "tiny.viewer": ("tiny", "viewer", 1, "train.viewer"),
    "tiny_sharded.backlog": ("tiny_sharded", "backlog_b1", 4,
                             "rubble_eighth.backlog"),
    "tiny_sh3.backlog": ("tiny_sh3", "backlog_b1", 4,
                         "rubble_eighth.backlog"),
}


def with_test_cells(bench: dict) -> dict:
    """A copy of ``bench`` with the test configurations and cells."""
    b = copy.deepcopy(bench)
    for name in TEST_CONFIGS:
        b["configs"].append({"name": name, "source": "test only",
                             "file": f"bench/tests/data/{name}.json",
                             "reduced": [], "why": "test only"})
    for name, (config, traffic, chips, _) in TEST_CELLS.items():
        b["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": chips,
                               "why": "test only"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + [
                name for name, (*_, like) in TEST_CELLS.items()
                if like in m["workloads"]]
    return b
