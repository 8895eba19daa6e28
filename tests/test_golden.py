"""Golden-image regression suite (tests/golden/, DESIGN.md §12 pinning).

The cross-path parity suites (test_sharding / test_engine) prove every path
agrees with the replicated reference WITHIN one checkout — a numerics
regression that moves all paths together would sail through them. These
tests pin the rendered output itself ACROSS PRs: three tiny deterministic
scenes are committed with their rendered images (scene arrays stored, not
seeds, so a jax.random change cannot move the pin) and sha256 checksums.

Covered per fixture: both backends (each against its OWN golden — they
agree only to fp reassociation in some configs, DESIGN.md §6) x scene
shards D in {1, 2} (D=2 runs the feature-sharded gathers, so losslessness
of the sharded path is pinned across PRs too — not just cross-path within
one PR).

If a render intentionally changes numerics, regenerate with
``PYTHONPATH=src python tests/golden/generate.py`` and review the image
diff in the PR.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

GOLDEN = Path(__file__).resolve().parent / "golden"
FIXTURES = ("mini_gstg", "aabb_lossless", "tile_base")
BACKENDS = ("reference", "pallas")

# The generator module is the single source of truth for HOW a golden is
# rendered (the jit'd traced-camera closure the engine handle compiles);
# the test must render through the identical path.
_spec = importlib.util.spec_from_file_location(
    "golden_generate", GOLDEN / "generate.py"
)
golden_generate = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(golden_generate)


# The generator's hash IS the pin definition — reuse it so the two files
# can never hash differently.
_sha256 = golden_generate._sha256


@pytest.fixture(scope="module")
def checksums():
    with open(GOLDEN / "checksums.json") as f:
        return json.load(f)


def _load(name):
    data = np.load(GOLDEN / f"{name}.npz")
    from repro.core import GaussianScene, make_camera

    scene = GaussianScene(
        **{
            f.name: data[f"scene_{f.name}"]
            for f in dataclasses.fields(GaussianScene)
        }
    )
    cam_kw = json.loads(bytes(data["camera_json"]).decode())
    cam_kw["eye"] = tuple(cam_kw.pop("eye"))
    cam_kw["target"] = tuple(cam_kw.pop("target"))
    cfg_kw = json.loads(bytes(data["config_json"]).decode())
    return data, scene, make_camera(**cam_kw), cfg_kw


@pytest.mark.parametrize("name", FIXTURES)
def test_fixture_integrity(name, checksums):
    """Every stored array hashes to its committed checksum — accidental
    fixture regeneration (or a corrupted npz) fails loudly and separately
    from a real numerics regression."""
    data = np.load(GOLDEN / f"{name}.npz")
    assert set(data.files) == set(checksums[name]), (
        f"{name}: fixture/checksum key sets diverge"
    )
    for key in data.files:
        assert _sha256(data[key]) == checksums[name][key], (
            f"{name}/{key}: stored array does not match checksums.json — "
            "was the fixture regenerated without updating the other file?"
        )


GOLDEN_CASES = [
    pytest.param(name, backend, shards, id=f"{name}-{backend}-D{shards}")
    for name in FIXTURES
    for backend in BACKENDS
    for shards in (1, 2)
]


# D=2 compiles a different program (per-shard frontend + merge, sharded
# gathers) and XLA fuses its float32 projection differently. One ulp of a
# projected mean at ~100 px is ~7.6e-6 px, which moves an alpha (and so a
# pixel) by up to ~1e-5; 1.2e-7 is seen here. A real numerics change moves
# pixels by orders of magnitude more.
SHARDED_ATOL = 1e-5


@pytest.mark.parametrize("name,backend,shards", GOLDEN_CASES)
def test_golden_image(name, backend, shards, checksums):
    """The committed golden image, per backend, at D in {1, 2}. D=1 runs
    the generator's own program and must reproduce it bitwise; D=2
    exercises the per-shard frontend + merge + the feature-sharded gathers
    and must land within SHARDED_ATOL with the SAME integer counters."""
    from repro.core.pipeline import RenderConfig

    data, scene, cam, cfg_kw = _load(name)
    cfg = RenderConfig(backend=backend, scene_shards=shards, **cfg_kw)
    out = golden_generate.render_one_jit(scene, cam, cfg)
    img = np.asarray(out.image)
    golden = data[f"image_{backend}"]
    assert img.shape == golden.shape and img.dtype == golden.dtype
    assert int(np.asarray(out.stats.overflow)) == 0
    if shards > 1:
        base = golden_generate.render_one_jit(
            scene, cam, RenderConfig(backend=backend, **cfg_kw))
        for field in vars(base.stats):
            assert np.array_equal(np.asarray(getattr(out.stats, field)),
                                  np.asarray(getattr(base.stats, field))), (
                f"{name}/{backend}/D{shards}: counter {field} differs")
        diff = np.abs(img - golden)
        assert diff.max() <= SHARDED_ATOL, (
            f"{name}/{backend}/D{shards}: max abs diff {diff.max():.3e} "
            f"over {(diff > 0).sum()} channels exceeds {SHARDED_ATOL:.1e}")
        return
    if not (img == golden).all():
        diff = np.abs(img - golden)
        pytest.fail(
            f"{name}/{backend}/D{shards}: image diverges from golden "
            f"(max abs diff {diff.max():.3e} over "
            f"{(diff > 0).sum()} channels); if intentional, regenerate via "
            "tests/golden/generate.py and review the diff"
        )
    assert _sha256(img) == checksums[name][f"image_{backend}"]
