import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import GaussianScene, make_camera, random_scene
from repro.core.pipeline import RenderConfig

# Session-wide compiled-renderer cache for parity-style tests: jitting the
# whole render (the same traced-camera closure the engine handle compiles)
# costs ~1.4s per (config, geometry) vs ~8s for a first EAGER render()
# (which traces/compiles its internal scans piecemeal) — the single biggest
# lever of the `-m "not slow"` fast lane. Tests that specifically assert
# the eager differentiable oracle keep calling render() directly.
_JIT_RENDER_FNS = {}


def jit_render(scene, cam, cfg, background=None):
    from repro.core.pipeline import (
        _background_array,
        _render_with_traced_camera,
    )

    key = (cfg, cam.width, cam.height, cam.znear, cam.zfar)
    fn = _JIT_RENDER_FNS.get(key)
    if fn is None:
        fn = jax.jit(
            _render_with_traced_camera(
                cfg, cam.width, cam.height, cam.znear, cam.zfar
            )
        )
        _JIT_RENDER_FNS[key] = fn
    return fn(
        scene,
        jnp.asarray(cam.R), jnp.asarray(cam.t),
        jnp.float32(cam.fx), jnp.float32(cam.fy),
        jnp.float32(cam.cx), jnp.float32(cam.cy),
        _background_array(background),
    )


@pytest.fixture(scope="session")
def jit_render_fn():
    return jit_render


_DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                 "StackFrames")


def _strip_metadata(hlo_text: str) -> str:
    lines, skipping = [], False
    for line in hlo_text.splitlines():
        if line in _DEBUG_TABLES:
            skipping = True
            continue
        if skipping and line.startswith(("ENTRY", "%", "HloModule")):
            skipping = False
        if not skipping:
            lines.append(line)
    text = re.sub(r", metadata=\{[^{}]*\}", "", "\n".join(lines))
    text = re.sub(r'"body":"[^"]*"', '"body":"<kernel>"', text)
    kernels = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    for i, name in enumerate(dict.fromkeys(kernels)):
        text = re.sub(rf"%{re.escape(name)}(?![\w.\-])", f"%kernel_{i}",
                      text)
    return text


@pytest.fixture(scope="session")
def strip_metadata():
    """A compiled program's text without what names and scopes change: op
    metadata and the debug tables it points into, and each Pallas kernel's
    instruction name and serialized body (which carries the kernel's name
    and its ops' source locations)."""
    return _strip_metadata


@pytest.fixture(scope="session")
def small_scene():
    return random_scene(jax.random.key(0), 800, extent=3.0)


@pytest.fixture(scope="session")
def tiny_scene():
    return random_scene(jax.random.key(1), 200, extent=2.5)


@pytest.fixture(scope="session")
def cam128():
    return make_camera((0.0, 1.0, 4.5), (0, 0, 0), 128, 128)


@pytest.fixture(scope="session")
def cam256():
    return make_camera((0.0, 1.2, 5.0), (0, 0, 0), 256, 192)


@pytest.fixture()
def base_cfg():
    return RenderConfig(
        tile=16,
        group=64,
        group_capacity=256,
        tile_capacity=256,
    )
