"""Where programs and caches land on a chip host: the persistent compile
cache directory, physical shard layouts, and one process per chip."""
import jax
import pytest

from repro.launch.mesh import make_render_mesh, render_mesh_shards
from repro.utils import compile_cache


@pytest.fixture()
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_honours_env(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no other directory.
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    from pathlib import Path

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    root = Path(__file__).resolve().parents[1]
    want = str(root / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # a fixed path: the same on every call, in every process
    assert compile_cache.enable_compile_cache() == want


def test_render_mesh_shards_logical_only_on_cpu(monkeypatch):
    assert render_mesh_shards(4, 2) == 2
    assert render_mesh_shards(1, 1) == 1
    assert render_mesh_shards(1, 2) == 1          # logical shard axis
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert render_mesh_shards(4, 4) == 4
    with pytest.raises(ValueError, match="does not divide"):
        render_mesh_shards(1, 2)


def test_render_mesh_takes_explicit_devices():
    devs = jax.devices()[:1]
    mesh = make_render_mesh(devs)
    assert list(mesh.devices.flat) == devs
    assert dict(mesh.shape) == {"data": 1}


def test_gateway_refuses_subprocess_workers_on_chip(monkeypatch, capsys):
    from repro.launch import render_gateway

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert render_gateway.main(["--workers", "2", "--no-warmup"]) == 2
    assert "--inproc" in capsys.readouterr().err
