"""Distribution transparency: sharded == single-device, for BOTH axes.

Part 1 (LM): sharded train step == single-device step.
Part 2 (render engine): the gaussian-sharded scene pipeline (DESIGN.md §10)
is bitwise-identical — image AND integer counters — to the replicated path,
for every mode, both backends, 1/2/3 logical shards in-process and 2/4
virtual host devices in subprocesses (so the XLA host-platform flag never
leaks into the main test process; smoke tests must see 1 device).
"""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import dataclasses
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_smoke_config
from repro.models import build_param_spec, loss_fn
from repro.models.spec import init_from_spec
from repro.sharding.policies import make_constrain

cfg = get_smoke_config("granite-3-2b")
cfg = dataclasses.replace(cfg, mlp_sharding="ff", d_ff=128, shard_vocab=True, vocab=512)
params = init_from_spec(build_param_spec(cfg), jax.random.key(0))
batch = {"tokens": jnp.ones((8, 32), jnp.int32),
         "labels": jnp.tile(jnp.arange(32, dtype=jnp.int32)[None], (8, 1))}

# single device
l1 = float(loss_fn(cfg, params, batch, lambda x, a: x)[0])

# 2x4 mesh
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(2, 4)
constrain = make_constrain(cfg, mesh)
with mesh:
    l2 = float(jax.jit(lambda p, b: loss_fn(cfg, p, b, constrain)[0])(params, batch))

print(json.dumps({"single": l1, "sharded": l2}))
"""


@pytest.mark.slow
def test_sharded_loss_matches_single_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(res["single"] - res["sharded"]) < 5e-3, res


def test_param_rules_divisibility_checks():
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.sharding.policies import param_rules

    mesh = make_host_mesh(1, 1)
    # all production configs must build rules against the 16-wide model axis;
    # emulate by checking the declared dims directly
    for name in ("qwen1.5-110b", "kimi-k2-1t-a32b", "jamba-1.5-large-398b"):
        cfg = get_config(name)
        assert cfg.n_heads % 16 == 0
        if cfg.n_experts:
            assert cfg.n_experts % 16 == 0
    rules = param_rules(get_config("qwen1.5-110b"), mesh)
    assert rules["heads"] == "model"


def test_elastic_then_restore_shapes(tmp_path):
    """Checkpoint saved under one mesh restores under another (reshard)."""
    import jax, jax.numpy as jnp, numpy as np
    from repro.checkpoint import CheckpointManager

    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    mgr = CheckpointManager(str(tmp_path), async_write=False)
    mgr.save(1, tree)
    leaves, _ = mgr.restore()  # host arrays; device_put under new mesh is a
    assert (np.asarray(leaves[0]) == np.asarray(tree["w"])).all()


# ===========================================================================
# Scene sharding: gaussian-axis parity (DESIGN.md §10)
# ===========================================================================


CAM_POS = (0.0, 1.0, 4.0)


def _cfg(**kw):
    from repro.core.pipeline import RenderConfig

    base = dict(tile=16, group=64, group_capacity=256, tile_capacity=256)
    base.update(kw)
    return RenderConfig(**base)


_REP_CACHE = {}


def _replicated(scene, mode):
    """Module-shared replicated reference render per mode (the jit'd
    production path every sharded/strategy variant is compared against —
    rendering it once keeps the parity matrix inside the fast lane)."""
    key = (id(scene), mode)
    if key not in _REP_CACHE:
        from conftest import jit_render

        from repro.core import make_camera

        cam = make_camera(CAM_POS, (0, 0, 0), 128, 128)
        _REP_CACHE[key] = jit_render(scene, cam, _cfg(mode=mode))
    return _REP_CACHE[key]


def _assert_same_result(a, b, ctx=""):
    assert (np.asarray(a.image) == np.asarray(b.image)).all(), (
        f"image diverges {ctx}"
    )
    for name in vars(a.stats):
        va, vb = np.asarray(getattr(a.stats, name)), np.asarray(
            getattr(b.stats, name)
        )
        assert (va == vb).all(), f"counter {name} diverges {ctx}: {va} != {vb}"


def test_shard_scene_canonical_layout(tiny_scene):
    """Pad/shard/flatten round trip: contiguous layout, bitwise real rows,
    cull-guaranteed padding rows."""
    import jax
    from repro.core.projection import project
    from repro.core import make_camera
    from repro.sharding.scene import scene_flat, shard_scene, unshard_scene

    n = tiny_scene.num_gaussians          # 200
    sharded = shard_scene(tiny_scene, 3)  # ragged: 200 -> 3 x 67
    assert sharded.num_shards == 3 and sharded.shard_size == 67
    assert sharded.num_real == n and sharded.padded_size == 201

    flat = scene_flat(sharded)
    for f in dataclasses.fields(tiny_scene):
        a = np.asarray(getattr(tiny_scene, f.name))
        b = np.asarray(getattr(flat, f.name))
        assert (a == b[:n]).all(), f.name

    # padding rows are culled by projection (alpha < 1/255)
    cam = make_camera(CAM_POS, (0, 0, 0), 128, 128)
    proj = project(flat, cam)
    assert not np.asarray(proj.valid)[n:].any()

    back = unshard_scene(sharded)
    assert back.num_gaussians == n
    with pytest.raises(ValueError):
        shard_scene(tiny_scene, 0)

    # The host-side staging path (serving) builds the IDENTICAL layout.
    from repro.sharding.scene import shard_scene_host

    hosted = shard_scene_host(tiny_scene, 3)
    assert hosted.num_real == sharded.num_real
    for f in dataclasses.fields(tiny_scene):
        a = np.asarray(getattr(sharded.shards, f.name))
        b = getattr(hosted.shards, f.name)
        assert isinstance(b, np.ndarray) and (a == b).all(), f.name


@pytest.mark.parametrize("mode", ["gstg", "tile_baseline", "group_baseline"])
@pytest.mark.parametrize("shards", [1, 2, 3])
def test_scene_sharded_render_parity(tiny_scene, jit_render_fn, mode, shards):
    """The tentpole invariant: the sharded engine is bitwise-identical
    (image + every integer counter) to the replicated path, for every mode,
    including the degenerate 1-shard layout and ragged padding (200 % 3).
    Since DESIGN.md §12 this runs WITH feature-sharded gathers on (the
    default 'auto' strategy resolves to the (shard, local) indexed gather):
    the projected features stay per-shard through bitmask/compact/raster.
    Both sides run the jit'd production closure (the eager oracle differs
    from ANY jit path by ~1 ulp of fusion rounding, sharded or not)."""
    from repro.core import make_camera
    from repro.sharding.scene import shard_scene

    cam = make_camera(CAM_POS, (0, 0, 0), 128, 128)
    rep = _replicated(tiny_scene, mode)
    # Pass the canonical layout explicitly — exercises the ShardedScene entry
    # (the serving path) rather than the in-trace shard.
    sh = jit_render_fn(
        shard_scene(tiny_scene, shards), cam,
        _cfg(mode=mode, scene_shards=shards),
    )
    _assert_same_result(rep, sh, f"(mode={mode}, shards={shards})")


@pytest.mark.parametrize(
    "mode,gather",
    [
        ("gstg", "index"),
        ("gstg", "psum"),
        ("gstg", "flat"),
        ("tile_baseline", "psum"),
        ("group_baseline", "psum"),
        ("group_baseline", "flat"),
    ],
)
def test_feature_gather_strategy_parity(tiny_scene, jit_render_fn, mode, gather):
    """Every feature-gather strategy (DESIGN.md §12) lands on the SAME bits
    as the replicated path: the plain (shard, local) indexed gather, the
    owner-masked psum collective (whose cross-shard sum runs on raw bit
    patterns — the partition-friendly form), and the legacy flat concat.
    Gathers commute with concatenation; this is the test of that claim.
    ('index' is the default strategy, so the full mode matrix above already
    covers it; the explicit combos here pin psum/flat on every mode.)"""
    from repro.core import make_camera

    cam = make_camera(CAM_POS, (0, 0, 0), 128, 128)
    rep = _replicated(tiny_scene, mode)
    sh = jit_render_fn(
        tiny_scene, cam,
        _cfg(mode=mode, scene_shards=3, feature_gather=gather),
    )
    _assert_same_result(rep, sh, f"(mode={mode}, gather={gather})")


def test_feature_gather_unknown_strategy_raises(tiny_scene):
    from repro.core import make_camera
    from repro.core.pipeline import render

    cam = make_camera(CAM_POS, (0, 0, 0), 128, 128)
    with pytest.raises(ValueError, match="feature_gather"):
        render(
            tiny_scene, cam,
            _cfg(scene_shards=2, feature_gather="bogus"),
        )


def test_sharded_proj_take_matches_flat_gather(tiny_scene):
    """proj_take unit contract: on a ShardedProjected, both strategies
    reproduce the flat gather bit for bit, for every Projected field —
    including NaN-free specials like signed zeros (the psum path sums raw
    bits, so exactly-one-owner == owner's bits verbatim)."""
    import dataclasses as dc

    import jax
    import jax.numpy as jnp

    from repro.core import make_camera
    from repro.core.projection import (
        ShardedProjected,
        proj_take,
        proj_valid_count,
        project,
    )
    from repro.sharding.scene import shard_scene

    cam = make_camera(CAM_POS, (0, 0, 0), 128, 128)
    sharded = shard_scene(tiny_scene, 3)
    proj_s = jax.vmap(lambda s: project(s, cam))(sharded.shards)
    flat = jax.tree.map(
        lambda x: x.reshape(x.shape[0] * x.shape[1], *x.shape[2:]), proj_s
    )
    rng = np.random.default_rng(0)
    idx = jnp.asarray(
        rng.integers(0, sharded.padded_size, size=(7, 13)).astype(np.int32)
    )
    for gather in ("index", "psum"):
        sp = ShardedProjected(shards=proj_s, gather=gather)
        for f in dc.fields(flat):
            want = np.asarray(getattr(flat, f.name)[idx])
            got = np.asarray(proj_take(sp, f.name, idx))
            assert want.dtype == got.dtype and (
                want.view(np.uint8) == got.view(np.uint8)
            ).all(), f"{gather}/{f.name} diverges from flat gather"
        assert int(proj_valid_count(sp)) == int(proj_valid_count(flat))


def test_feature_budget_model_scales_inverse_d(tiny_scene):
    """The --device-budget-mb model (engine/handle.py): per-camera projected
    feature bytes divide by D exactly when the commit runs the psum gathers
    over a PHYSICAL 'model' axis; logical shard axes and the legacy 'flat'
    strategy count full N (asserted without devices — the model is pure
    arithmetic; the virtual-device suite asserts the committed stats)."""
    import dataclasses as dc

    from repro.core.pipeline import RenderConfig
    from repro.core.projection import projected_bytes_per_gaussian
    from repro.engine import Renderer

    cfg = RenderConfig(scene_shards=4)
    full = Renderer._feature_mb(tiny_scene, 4)
    n_pad = -(-tiny_scene.num_gaussians // 4) * 4
    assert full == n_pad * projected_bytes_per_gaussian() / 2**20
    # physical 4-way shard + auto (-> psum): 1/D
    assert Renderer._feature_div(cfg, 4, 4) == 4
    # logical-only shard axis: full N per device
    assert Renderer._feature_div(cfg, 4, 1) == 1
    # legacy flat concat: full N even when physically sharded
    flat_cfg = dc.replace(cfg, feature_gather="flat")
    assert Renderer._feature_div(flat_cfg, 4, 4) == 1
    # replicated scene: no sharded features at all
    assert Renderer._feature_div(RenderConfig(), 1, 1) == 1


@pytest.mark.parametrize("degree,floats", [(0, 14), (1, 23), (2, 38),
                                           (3, 59)])
def test_budget_model_counts_the_scene_from_its_sh_coefficients(degree,
                                                                 floats):
    """The handle's parameter term is the committed scene's bytes: 3 + 3 +
    4 + 1 + 3 (d + 1)^2 floats a Gaussian, 59 at SH degree 3, for the
    generator's (d + 1)^2 coefficients, replicated and in the padded
    sharded layout."""
    import jax

    from repro import engine
    from repro.core import random_scene
    from repro.core.pipeline import RenderConfig
    from repro.sharding.scene import shard_scene
    from repro.utils import pytree_bytes

    scene = random_scene(jax.random.key(degree), 301, sh_degree=degree)
    assert scene.sh.shape == (301, (degree + 1) ** 2, 3)
    assert pytree_bytes(scene) == 301 * 4 * floats
    assert pytree_bytes(shard_scene(scene, 4)) == 304 * 4 * floats  # 4 x 76
    r = engine.open(scene, RenderConfig(), device_budget_mb=1024.0)
    try:
        assert r.stats()["scene_mb_per_device"] == 301 * 4 * floats / 2**20
    finally:
        r.close()


def test_budget_model_refuses_sh_degrees_past_3():
    import jax

    from repro.core import random_scene

    with pytest.raises(ValueError, match="sh_degree"):
        random_scene(jax.random.key(0), 10, sh_degree=4)


def test_budget_model_counts_the_band_split_feature_gather(tiny_scene):
    """Under the group-split backend (gstg on Pallas over a physical
    'model' axis) each chip holds its shard's projected features and every
    Gaussian's 15 packed kernel fields; other backends hold no such copy."""
    from repro.core.pipeline import RenderConfig
    from repro.core.projection import projected_bytes_per_gaussian
    from repro.engine import Renderer
    from repro.kernels.layout import packed_bytes_per_gaussian

    assert packed_bytes_per_gaussian() == 60
    n_pad = -(-tiny_scene.num_gaussians // 4) * 4
    split = RenderConfig(backend="pallas", scene_shards=4)
    got = Renderer._device_feature_mb(split, tiny_scene, 4, 4)
    want = n_pad * (projected_bytes_per_gaussian() / 4 + 60) / 2**20
    assert abs(got - want) < 1e-12
    # Logical shards, the reference backend and 'flat' split nothing.
    per_shard = n_pad * projected_bytes_per_gaussian() / 2**20
    assert Renderer._device_feature_mb(split, tiny_scene, 4, 1) == per_shard
    reference = RenderConfig(backend="reference", scene_shards=4)
    assert Renderer._device_feature_mb(
        reference, tiny_scene, 4, 4) == per_shard / 4
    flat = RenderConfig(backend="pallas", scene_shards=4,
                        feature_gather="flat")
    assert Renderer._device_feature_mb(flat, tiny_scene, 4, 4) == per_shard


@pytest.mark.parametrize("bg,bt", [("aabb", "aabb"), ("obb", "ellipse")])
def test_scene_sharded_lossless_combos(tiny_scene, jit_render_fn, bg, bt):
    """Sharding composes with the §7 losslessness combos: gstg sharded ==
    gstg replicated (bitwise) == tile_baseline (bitwise, lossless combo) —
    all through the jit'd production closure (the §7 combos hold under jit
    because the per-tile entry TABLES are identical arrays, so the blended
    programs see the same inputs; the eager-oracle combos are
    tests/test_pipeline_lossless.py)."""
    from repro.core import make_camera

    cam = make_camera(CAM_POS, (0, 0, 0), 128, 128)
    cfg = _cfg(mode="gstg", boundary_group=bg, boundary_tile=bt)
    rep = jit_render_fn(tiny_scene, cam, cfg)
    sh = jit_render_fn(
        tiny_scene, cam, dataclasses.replace(cfg, scene_shards=2)
    )
    _assert_same_result(rep, sh, f"({bg},{bt})")
    base = jit_render_fn(
        tiny_scene, cam, _cfg(mode="tile_baseline", boundary_tile=bt)
    )
    assert (np.asarray(sh.image) == np.asarray(base.image)).all()


def test_scene_shards_config_mismatch_raises(tiny_scene):
    from repro.core import make_camera
    from repro.core.pipeline import render
    from repro.sharding.scene import shard_scene

    cam = make_camera(CAM_POS, (0, 0, 0), 128, 128)
    with pytest.raises(ValueError, match="scene_shards"):
        render(shard_scene(tiny_scene, 2), cam, _cfg(scene_shards=3))


def test_scene_sharded_batch_ragged_cameras(tiny_scene):
    """Gaussian sharding x ragged camera padding: a B=3 batch through
    render_batch_sharded with pad_to=4 and scene_shards=2 equals the
    replicated render_batch row for row (both axes' padding is sliced)."""
    from repro.core import orbit_cameras
    from repro.core.pipeline import render_batch
    from repro.launch.mesh import make_render_mesh
    from repro.serving.sharded import render_batch_sharded

    cams = orbit_cameras(3, 4.5, 128, 128)
    cfg = _cfg()
    rep = render_batch(tiny_scene, cams, cfg)
    sh = render_batch_sharded(
        tiny_scene, cams, cfg, mesh=make_render_mesh(1), pad_to=4,
        scene_shards=2,
    )
    _assert_same_result(rep, sh, "(batch ragged)")


_PALLAS_REP = {}


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["gstg", "tile_baseline", "group_baseline"])
@pytest.mark.parametrize("shards", [2, 3])
def test_scene_sharded_pallas_parity(tiny_scene, jit_render_fn, mode, shards):
    """Both backends honor the sharded frontend WITH feature-sharded
    gathers: pallas sharded == pallas replicated bitwise for every mode x
    D (the kernels' feature packer gathers straight from the owning shards
    — kernels/layout.py::pack_features via proj_take). Completes the
    acceptance matrix: all modes x backends x D in {1, 2, 3} (D=1 pallas
    rides tests/test_engine_handle.py and tests/test_golden.py)."""
    from repro.core import make_camera

    cam = make_camera(CAM_POS, (0, 0, 0), 64, 64)
    cfg = _cfg(
        mode=mode, backend="pallas", group_capacity=128, tile_capacity=128
    )
    if mode not in _PALLAS_REP:
        _PALLAS_REP[mode] = jit_render_fn(tiny_scene, cam, cfg)
    rep = _PALLAS_REP[mode]
    sh = jit_render_fn(
        tiny_scene, cam, dataclasses.replace(cfg, scene_shards=shards)
    )
    _assert_same_result(rep, sh, f"(pallas, mode={mode}, D={shards})")
    # The psum collective form too (what a physical mesh commits).
    sh_psum = jit_render_fn(
        tiny_scene, cam,
        dataclasses.replace(cfg, scene_shards=shards, feature_gather="psum"),
    )
    _assert_same_result(
        rep, sh_psum, f"(pallas psum, mode={mode}, D={shards})"
    )


_DEVICE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(devices)d"
import dataclasses, json, warnings
import jax, numpy as np

from repro import engine
from repro.core import orbit_cameras, random_scene
from repro.core.pipeline import RenderConfig, render_batch
from repro.launch.mesh import make_render_mesh
from repro.serving.sharded import render_batch_sharded

scene = random_scene(jax.random.key(3), 300, extent=3.0)
cams = orbit_cameras(3, 4.5, 96, 96)   # ragged over the data axis
failures = []
for mode, backend in %(combos)s:
    cfg = RenderConfig(mode=mode, backend=backend, group_capacity=256,
                       tile_capacity=256, span=6)
    rep = render_batch(scene, cams, cfg)
    mesh = make_render_mesh(%(devices)d, scene_shards=%(shards)d)
    sh = render_batch_sharded(scene, cams, cfg, mesh=mesh,
                              scene_shards=%(shards)d)
    # Two different programs (replicated render_batch vs the sharded mesh
    # program) fuse the float32 projection differently: one ulp of a
    # projected mean at ~100 px (~7.6e-6 px) moves a pixel by up to ~1e-5.
    # Counters stay exact.
    diff = np.abs(np.asarray(rep.image) - np.asarray(sh.image)).max()
    if not diff <= 1e-5:
        failures.append((mode, backend, "image", float(diff)))
    for name in vars(rep.stats):
        if not (np.asarray(getattr(rep.stats, name))
                == np.asarray(getattr(sh.stats, name))).all():
            failures.append((mode, backend, name))

# Commit-time gather decision (DESIGN.md S12): a PHYSICAL 'model' axis must
# commit the psum collective, with the budget model's per-camera feature
# term at N/D per device; and the per-shard features must actually lay over
# 'model' (the feature_shard_pspec layout).
mesh = make_render_mesh(%(devices)d, scene_shards=%(shards)d)
cfg = RenderConfig(group_capacity=256, tile_capacity=256, span=6,
                   scene_shards=%(shards)d)
h = engine.open(scene, cfg, mesh=mesh)
hs = h.stats()
if hs["feature_gather"] != "psum":
    failures.append(("commit", "feature_gather", hs["feature_gather"]))
from repro.core.projection import projected_bytes_per_gaussian
n_pad = -(-scene.num_gaussians // %(shards)d) * %(shards)d
want_mb = n_pad * projected_bytes_per_gaussian() / 2**20 / %(shards)d
if abs(hs["feature_mb_per_device"] - want_mb) > 1e-9:
    failures.append(("commit", "feature_mb", hs["feature_mb_per_device"]))
h.close()

# Budget-driven auto escalation under the full (params + features) model: a
# budget only a physical %(shards)d-way commit can meet must escalate a
# scene_shards=1 'auto' open() to %(shards)d with psum gathers.
from repro.utils import pytree_bytes
full_mb = pytree_bytes(scene) / 2**20 + n_pad * projected_bytes_per_gaussian() / 2**20
h = engine.open(
    scene,
    RenderConfig(group_capacity=256, tile_capacity=256, span=6),
    devices=%(devices)d,
    device_budget_mb=full_mb / %(shards)d * 1.2,
)
hs = h.stats()
if hs["physical_shards"] < 2 or hs["feature_gather"] != "psum":
    failures.append(("escalation", hs["physical_shards"], hs["feature_gather"]))
h.close()

from jax.sharding import NamedSharding
from repro.core.projection import project
from repro.sharding.policies import feature_shard_pspec, scene_shard_pspec
from repro.sharding.scene import shard_scene_host
staged = jax.device_put(
    shard_scene_host(scene, %(shards)d),
    NamedSharding(mesh, scene_shard_pspec(mesh)),
)
proj_s = jax.jit(
    lambda s: jax.vmap(lambda x: project(x, cams[0]))(s.shards),
    out_shardings=NamedSharding(mesh, feature_shard_pspec(mesh)),
)(staged)
spec = proj_s.depth.sharding.spec
if tuple(spec)[:1] != ("model",):
    failures.append(("pspec", "feature_shard", str(spec)))
print(json.dumps({"failures": failures}))
"""


@pytest.mark.slow
@pytest.mark.parametrize("devices,shards", [(2, 2), (4, 4), (4, 2)])
def test_scene_sharded_virtual_devices(devices, shards):
    """Physically sharded over 2/4 virtual host devices (2-D (data, model)
    mesh, subprocess so the XLA flag stays contained): image within 1e-5
    (float32 fusion) + identical counters vs the replicated path, gstg and tile_baseline —
    pallas included on the 2-device mesh (interpret mode is slow)."""
    combos = [("gstg", "reference"), ("tile_baseline", "reference")]
    if devices == 2:
        combos.append(("gstg", "pallas"))
    script = _DEVICE_SCRIPT % {
        "devices": devices, "shards": shards, "combos": repr(combos),
    }
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["failures"] == [], res


# ---------------------------------------------------------------------------
# Group-split backend on a physical 'model' axis (DESIGN.md §10)
# ---------------------------------------------------------------------------

_BAND_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, re
import jax, jax.numpy as jnp, numpy as np

from repro import engine
from repro.core import orbit_cameras, pipeline, random_scene
from repro.core.gaussians import GaussianScene
from repro.core.pipeline import RenderConfig, stage_of
from repro.launch.mesh import make_render_mesh
from repro.obs import get_registry
from repro.serving.queue import RenderRequest
from repro.serving.server import RenderServer
from repro.serving.stats import band_skew
from repro.sharding.scene import PAD_OPACITY, ShardedScene, shard_scene

case = %(case)r
# Project each chip's shard in chunks, as paper-size shards are.
pipeline.PROJECT_ROWS = 32
if case == "sh3":
    scene = random_scene(jax.random.key(3), 300, extent=3.0, sh_degree=3)
elif case == "depth_ties":
    # Gaussians i, i + 60, i + 120 and i + 180 (one in each shard) share
    # everything but their colour, so every bin they cover holds four
    # entries of one depth from four shards, in global index order.
    base = random_scene(jax.random.key(4), 240, extent=3.0, sh_degree=3)
    same = lambda x: jnp.tile(x[:60], (4,) + (1,) * (x.ndim - 1))
    scene = GaussianScene(
        means3d=same(base.means3d), log_scales=same(base.log_scales),
        quats=same(base.quats), opacity=same(base.opacity), sh=base.sh)
else:
    # Three shards of Gaussians and a fourth of padding rows alone.
    real = shard_scene(
        random_scene(jax.random.key(5), 225, extent=3.0, sh_degree=3), 3)
    pad = jax.tree.map(lambda x: jnp.zeros((1,) + x.shape[1:], x.dtype),
                       real.shards)
    pad.opacity = jnp.full_like(pad.opacity, PAD_OPACITY)
    scene = ShardedScene(
        shards=jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                            real.shards, pad), num_real=225)

cams = orbit_cameras(2, 4.5, 160, 96)   # 3 x 2 = 6 groups, bands of 2
cfg = RenderConfig(mode="gstg", backend="pallas", group_capacity=256,
                   tile_capacity=256, span=6, scene_shards=4)
mesh = make_render_mesh(4, scene_shards=4)
split_bands = pipeline.model_bands


def served(split):
    pipeline.model_bands = split_bands if split else (lambda *a: 1)
    with engine.open(scene, cfg, mesh=mesh) as h:
        out = h.render_batch(cams, pad_to=2)
        text = h.program_text(cams, pad_to=2)
    return jax.tree.map(np.asarray, out), text


split, text = served(True)
replicated, _ = served(False)
pipeline.model_bands = split_bands
failures = []
if not (split.image == replicated.image).all():
    failures.append(("image", float(np.abs(split.image
                                           - replicated.image).max())))
for name in vars(replicated.stats):
    a, b = getattr(split.stats, name), getattr(replicated.stats, name)
    if not (a == b).all():
        failures.append((name, a.tolist(), b.tolist()))
if split.stats.overflow.any() or split.stats.span_overflow.any():
    failures.append(("lossless", split.stats.overflow.tolist()))
if replicated.bands is not None or split.bands.shape != (2, 4):
    failures.append(("bands", None if split.bands is None
                     else split.bands.shape))
elif not (split.bands.sum(axis=1) * 16 == split.stats.n_bit_tests).all():
    failures.append(("band entries", split.bands.tolist()))
stages = {stage_of(n) for n in re.findall(r'op_name="([^"]*)"', text)}
if "exchange" not in stages or "all-to-all" not in text:
    failures.append(("exchange", sorted(s for s in stages if s)))

# The server publishes each request's band skew.
with RenderServer({"s": scene}, mesh=mesh, scene_shards=4,
                  max_batch=2, max_wait=0.0) as server:
    for i, cam in enumerate(cams):
        server.submit(RenderRequest(i, "s", cam, cfg))
    server.drain()
skews = get_registry().get("serving.band_skew").values()
want = [band_skew(row) for row in split.bands]
if not np.allclose(skews, want) or min(skews) < 1.0:
    failures.append(("band_skew", skews, want))
print(json.dumps({"failures": failures,
                  "bands": split.bands.tolist()}))
"""


@pytest.mark.slow
@pytest.mark.parametrize("case", ["sh3", "depth_ties", "padding_shard"])
def test_group_split_backend_is_bitwise_on_four_devices(case):
    """Gaussian-sharded over four virtual devices (a (data=1, model=4)
    mesh), the backend split by group band equals the backend replicated on
    every device bitwise: image and every counter, at SH degree 3, with 6
    groups in bands of 2 (the last band padded), depth ties across shards,
    and a shard of padding alone. The program exchanges tables under
    ``gstg/exchange``, returns each band's entries, and the server
    publishes their skew."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _BAND_SCRIPT % {"case": case}],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["failures"] == [], res
    if case == "padding_shard":
        assert all(row[3] >= 0 for row in res["bands"])
