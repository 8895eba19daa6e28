"""The engine's Pallas kernels compile for a TPU v5e chip.

Each kernel is compiled ahead of time for a described (not attached) v5e
chip at the widths the train scene is served at on the chip (chip_smoke.py):
558 groups of 64x64 px at 1959x1090, 16 features, the served table
capacities and the 128-lane chunk. Interpret mode cannot see what Mosaic
refuses (block shapes that break the (8, 128) tiling, unaligned slices,
missing lowerings, VMEM overflow); this compile does, with no chip.

The topology is described inside a module fixture — never at import — so
that under pytest-xdist only the worker that runs this file loads the TPU
compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.gs_scenes import PAPER_SCENES, SERVE_SPECS
from repro.core.grouping import GridSpec
from repro.kernels.layout import LANE, NUM_FEATURES


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    # A described-chip compile can be written to the persistent cache but
    # never read back without the chip: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2[0])


@pytest.fixture(scope="module")
def train():
    spec, serve = PAPER_SCENES["train"], SERVE_SPECS["train"]
    grid = GridSpec(spec.width, spec.height, 16, 64, span=serve.span)
    assert grid.num_groups == 558
    return grid, serve


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_bitmask_kernel_compiles(one_chip, train):
    from repro.kernels.bitmask_gen import bitmask_kernel

    grid, serve = train
    feat = jax.ShapeDtypeStruct(
        (grid.num_groups, NUM_FEATURES, serve.group_capacity), jnp.float32,
        sharding=one_chip)
    _compile(lambda f: bitmask_kernel(f, grid, interpret=False), feat)


def test_fused_raster_kernel_compiles(one_chip, train):
    from repro.kernels.raster_tile import raster_group_fused_kernel

    grid, serve = train
    G, K = grid.num_groups, serve.group_capacity
    feat = jax.ShapeDtypeStruct((G, NUM_FEATURES, K), jnp.float32,
                                sharding=one_chip)
    masks = jax.ShapeDtypeStruct((G, K), jnp.uint32, sharding=one_chip)
    _compile(
        lambda f, m: raster_group_fused_kernel(
            f, m, grid.n_groups_x, grid.tile, grid.gf, chunk=LANE,
            interpret=False, tile_capacity=serve.tile_capacity,
            with_stats=True),
        feat, masks)


def test_tile_raster_kernel_compiles(one_chip, train):
    from repro.kernels.raster_tile import raster_tile_kernel

    grid, serve = train
    feat = jax.ShapeDtypeStruct(
        (grid.num_tiles, NUM_FEATURES, serve.tile_capacity), jnp.float32,
        sharding=one_chip)
    _compile(
        lambda f: raster_tile_kernel(
            f, grid.n_tiles_x, grid.tile, chunk=LANE, interpret=False,
            with_stats=True),
        feat)


def test_data_parallel_batch_runs_kernels_on_own_lanes(v5e_2x2, monkeypatch):
    """A camera batch over a (data=4, model=1) mesh: each chip's kernels
    take only its own lane. Without the data axes named in the batch vmap
    every chip all-gathers the batch and rasterizes all four lanes."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.gaussians import random_scene
    from repro.core.pipeline import RenderConfig, _render_with_traced_camera
    from repro.engine.handle import _vmap_cameras

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    mesh = Mesh(np.array(v5e_2x2).reshape(4, 1), ("data", "model"))
    cfg = RenderConfig(mode="gstg", backend="pallas", group_capacity=128,
                       tile_capacity=128, span=4)
    one = _render_with_traced_camera(cfg, 192, 128, 0.01, 100.0)  # 6 groups
    rep = NamedSharding(mesh, P())
    lanes = NamedSharding(mesh, P("data"))

    def spec(shape, sharding):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    scene = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=rep),
        random_scene(jax.random.key(0), 500))
    args = ([scene, spec((4, 3, 3), lanes), spec((4, 3), lanes)]
            + [spec((4,), lanes)] * 4 + [spec((3,), rep)])
    with jax.set_mesh(mesh):
        text = jax.jit(_vmap_cameras(one, mesh)).lower(*args).compile(
        ).as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 2                     # bitmask + fused raster
    assert "all-gather" not in text
    # Each kernel's result holds one lane: (G=6, ...), no batch axis.
    for line in kernels:
        shape = line.split("= ", 1)[1].split("{", 1)[0]
        assert shape.split("[", 1)[1].startswith("6,"), shape


def test_stage_scopes_change_only_metadata_on_the_chip(one_chip, monkeypatch,
                                                       strip_metadata):
    """Compiled for the chip, the served batch program is the same with its
    stage scopes as without them, once metadata and what the kernels' names
    set are left out; each kernel runs in its own stage's scope under its
    own name, and the program's one sort is ``bin``'s: keys (bin id, depth)
    with the Gaussian index carried as a third operand, so no permutation
    is built to gather through."""
    import contextlib
    import re

    from repro.core import pipeline
    from repro.core.gaussians import random_scene
    from repro.core.pipeline import RenderConfig, stage_of

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    cfg = RenderConfig(mode="gstg", backend="pallas", group_capacity=128,
                       tile_capacity=128, span=4)
    scene = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        random_scene(jax.random.key(0), 500))

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def compiled_text():
        one = pipeline._render_with_traced_camera(cfg, 192, 128, 0.01,
                                                  100.0)
        batch = jax.vmap(one, in_axes=(None, 0, 0, 0, 0, 0, 0, None))
        return jax.jit(batch).lower(
            scene, spec(2, 3, 3), spec(2, 3), spec(2), spec(2), spec(2),
            spec(2), spec(3)).compile().as_text()

    text = compiled_text()
    monkeypatch.setattr(pipeline, "stage_scope",
                        lambda stage: contextlib.nullcontext())
    assert strip_metadata(text) == strip_metadata(compiled_text())

    ops, sort_operands = {}, []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = .*? (sort|custom-call)\(",
                     line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        if m and (m.group(2) == "sort" or "tpu_custom_call" in line):
            ops[m.group(1)] = stage_of(op_name.group(1))
        if m and m.group(2) == "sort":
            types = line.split("= (", 1)[1].split(") sort(", 1)[0]
            args = line[m.end():].split(")", 1)[0]
            sort_operands.append((
                re.findall(r"(?:^|, )([a-z]\w*)\[", types),
                re.findall(r"%([a-z_\-]+)", args)[3:]))
    kernels = {name.split(".")[0]: stage for name, stage in ops.items()
               if not name.startswith("sort")}
    assert kernels == {"gstg_bitmask": "bitmask",
                       "gstg_raster_group": "raster"}
    sorts = [stage for name, stage in ops.items() if name.startswith("sort")]
    assert sorts == ["bin"]
    # The program's three operands (bin id, depth, Gaussian index), then
    # the iota the chip's compiler appends to make the sort stable.
    assert sort_operands == [(["s32", "f32", "s32", "s32"], ["iota"])]
