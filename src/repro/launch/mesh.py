"""Production mesh builders.

Functions, not module-level constants, so importing never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

import jax


_AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    """v5e-256 pod mesh: (data=16, model=16); multi-pod adds a pure-DP 'pod'
    axis across the DCI: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(_AUTO,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over the locally available devices (tests/examples)."""
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=(_AUTO,) * 2
    )


def render_mesh_shards(n_devices: int, scene_shards: int) -> int:
    """The physical shard count a render mesh over ``n_devices`` can realize:
    ``scene_shards`` when it divides the device count. Otherwise the shard
    axis stays logical (1: correct results, no per-device memory saving) on
    the CPU, and on an accelerator the request fails: a chip run that asks
    for physical shards never silently gets a replicated layout. THE single
    shard policy: serving/sharded.py, serving/server.py,
    launch/render_serve.py and the benchmarks all route through it."""
    if scene_shards <= 1 or n_devices % scene_shards == 0:
        return max(scene_shards, 1)
    if jax.default_backend() != "cpu":
        raise ValueError(
            f"scene_shards={scene_shards} does not divide the "
            f"{n_devices} {jax.default_backend()} devices; a physical "
            f"shard layout needs a divisor of the device count"
        )
    return 1


def make_render_mesh(devices=None, scene_shards: int = 1):
    """Render-serving mesh (serving/sharded.py).

    ``scene_shards == 1``: the classic 1-D ('data',) pure-DP mesh — rendering
    is embarrassingly parallel over the camera axis. ``scene_shards = D > 1``:
    a 2-D ('data', 'model') mesh laying cameras over 'data' and the gaussian
    shard axis of a ShardedScene over 'model' (DESIGN.md §10) — each device
    holds one camera slice x one scene shard, which is what lets a scene
    larger than a single device's replicated budget render at all.

    ``devices=None`` takes every local device (the single-host serving
    deployment); an int takes a prefix (tests pin 1); a sequence of devices
    is used as given (one in-process replica per chip)."""
    if devices is None:
        devs = jax.devices()
    elif isinstance(devices, int):
        devs = jax.devices()[:max(devices, 0)]
    else:
        devs = list(devices)
    n = len(devs)
    if n == 0:
        raise ValueError(f"render mesh needs >= 1 device, got {devices!r}")
    if scene_shards <= 1:
        shape, axes = (n,), ("data",)
    else:
        if n % scene_shards:
            raise ValueError(
                f"scene_shards={scene_shards} must divide the device count {n}"
            )
        shape, axes = (n // scene_shards, scene_shards), ("data", "model")
    return jax.make_mesh(shape, axes, devices=devs,
                         axis_types=(_AUTO,) * len(axes))
