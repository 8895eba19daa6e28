"""Finds what a cell is made of, by the names in ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix:

- the configuration's file is the ``file`` of its ``configs`` entry;
- the mix is ``bench/traffic/<traffic>.json``, and the module that drives it
  is ``bench/traffic/kinds/<kind>.py``, where ``kind`` is a key of the mix;
- each per-layer metric ``<name>`` is read by ``bench/metrics/<name>.py``.

So a later change adds a configuration, a mix, a kind or a metric as a file
of its own and an entry in ``BENCHMARK.json``, and edits no file here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no module at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict              # the workloads entry
    config: dict             # the configuration file
    traffic: dict            # the mix file
    kind: ModuleType         # bench/traffic/kinds/<kind>.py
    end_to_end: List[dict]   # end-to-end metrics this cell reports
    per_layer: List[dict]    # per-layer metrics this cell reports
    run_seconds: int

    def reader(self, metric: str) -> ModuleType:
        return load_module(BENCH / "metrics" / f"{metric}.py")


def load_cell(workload: str, bench: dict | None = None,
              root: Path = ROOT) -> Cell:
    """The cell ``workload`` of ``bench`` (by default the parsed
    ``BENCHMARK.json`` under ``root``)."""
    if bench is None:
        bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"there are {sorted(cells)}")
    entry = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[entry["config"]]["file"])
    traffic = load_json(
        root / "bench" / "traffic" / f"{entry['traffic']}.json")
    kind = load_module(root / "bench" / "traffic" / "kinds"
                       / f"{traffic['kind']}.py")
    return Cell(
        name=workload, entry=entry, config=config, traffic=traffic, kind=kind,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        run_seconds=int(bench["run_seconds"]),
    )
