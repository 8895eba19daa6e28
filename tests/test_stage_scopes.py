"""Stage scopes in the fused program (core/pipeline.py): every stage call
runs under ``jax.named_scope("gstg/<stage>")``, which changes op metadata
and nothing else — the compiled served program and its pixels are the same
with the scopes as without them."""
import contextlib
import re

import numpy as np
import pytest

from repro import engine
from repro.core import orbit_cameras
from repro.core import pipeline
from repro.core.pipeline import STAGES, RenderConfig, stage_of


def _served(scene, cfg, batch):
    cams = orbit_cameras(batch, 4.5, 64, 48)
    with engine.open(scene, cfg) as r:
        image = np.asarray(r.render_batch(cams, pad_to=batch).image)
        return image, r.program_text(cams, pad_to=batch)


VARIANTS = {
    "reference": (dict(backend="reference"), 1),
    "pallas_interpret": (dict(backend="pallas"), 1),
    "sharded": (dict(backend="reference", scene_shards=2), 1),
    "batched": (dict(backend="reference"), 3),
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_scopes_change_metadata_only(small_scene, base_cfg, monkeypatch,
                                     strip_metadata, variant):
    overrides, batch = VARIANTS[variant]
    cfg = RenderConfig(**{**base_cfg.__dict__, **overrides})
    image, text = _served(small_scene, cfg, batch)
    monkeypatch.setattr(pipeline, "stage_scope",
                        lambda stage: contextlib.nullcontext())
    bare_image, bare_text = _served(small_scene, cfg, batch)
    assert "gstg/" in text and "gstg/" not in bare_text
    assert strip_metadata(text) == strip_metadata(bare_text)
    np.testing.assert_array_equal(image, bare_image)


@pytest.mark.parametrize("shards", [1, 2])
def test_every_stage_scope_is_in_the_served_program(small_scene, base_cfg,
                                                    shards):
    cfg = RenderConfig(**{**base_cfg.__dict__, "backend": "pallas",
                          "scene_shards": shards})
    _, text = _served(small_scene, cfg, 2)
    found = {stage_of(n) for n in re.findall(r'op_name="([^"]*)"', text)}
    want = set(STAGES) if shards > 1 else set(STAGES) - {"merge"}
    assert found - {None} == want


def test_stage_of_reads_the_scope_through_transformations():
    assert stage_of("jit(one)/vmap(gstg/bin)/sort") == "bin"
    assert stage_of("jit(one)/vmap(gstg/raster)/vmap()/while/body/mul") \
        == "raster"
    assert stage_of("gstg/merge/lt") == "merge"
    assert stage_of("jit(one)/reduce_sum") is None
    assert stage_of("jit(one)/gstg/binning/sort") is None


def test_kernels_carry_their_names(small_scene, base_cfg):
    cfg = RenderConfig(**{**base_cfg.__dict__, "backend": "pallas"})
    _, text = _served(small_scene, cfg, 1)
    for name in ("gstg_bitmask", "gstg_raster_group"):
        assert name in text
