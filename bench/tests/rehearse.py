"""Rehearses cells of the test benchmark (``bench/tests/cells.py``) on the
CPU, and prints one JSON object: for each cell, the run's exit code, its
``correct`` and ``check``, the server's mesh, and the layout of each
handle it committed. Run it with four virtual devices to rehearse a
Gaussian-sharded cell on a physical shard axis:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 bench/tests/rehearse.py tiny.backlog tiny_sharded.backlog
"""
import contextlib
import io
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import run  # noqa: E402
from bench.spec import load_json  # noqa: E402
from bench.tests.cells import with_test_cells  # noqa: E402


def _keep(kept):
    def hook(server):
        kept["mesh"] = {"devices": int(server.mesh.devices.size),
                        "shape": dict(server.mesh.shape)}
        commit = server.commit

        def committing(scene_id, cfg):
            handle = commit(scene_id, cfg)
            kept.setdefault("handles", []).append({
                "scene_shards": handle.scene_shards,
                "model": dict(handle.mesh.shape).get("model", 1),
                "feature_gather": handle.cfg.feature_gather})
            return handle

        server.commit = committing
        return server

    return hook


def rehearse(workload, seed, bench, seconds=1.5) -> dict:
    """One rehearsed run of ``workload`` and what its server committed."""
    kept, out = {}, io.StringIO()
    with contextlib.redirect_stdout(out), \
            contextlib.redirect_stderr(io.StringIO()):
        rc = run.run(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", "0"],
                     require_chip=False, bench=bench,
                     hooks={"server": _keep(kept)})
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    return {"rc": rc, "correct": line["correct"], "check": line["check"],
            "device_count": line["device"]["count"], **kept}


def main(argv):
    bench = with_test_cells(load_json(ROOT / "BENCHMARK.json"))
    print(json.dumps({w: rehearse(w, 2**31 + 4321, bench) for w in argv}))


if __name__ == "__main__":
    main(sys.argv[1:])
