"""Thin public wrappers + layout glue for the GS-TG Pallas kernels.

On the CPU the kernels execute via Pallas interpret mode; on a TPU the same
code lowers to Mosaic. There is NO standalone kernel-path renderer here: the
Pallas kernels are stage implementations of the unified engine — select them
with ``RenderConfig(backend="pallas")`` and go through
``repro.core.pipeline.render`` (see core/stages.PallasBackend).
Identification and group binning stay on the XLA sort substrate (DESIGN.md
§2); this module hosts the interpret/compile decision, the multi-device
wrapper and the layout helpers those stages share.
"""
from __future__ import annotations

import os
import warnings

import jax
import jax.numpy as jnp

# Pallas lowers natively (Mosaic) on the TPU; everywhere else the kernels
# run through the interpreter.
_ACCEL_PLATFORMS = ("tpu",)


def default_interpret() -> bool:
    """Should Pallas kernels run in interpret mode?

    Resolution order (DESIGN.md §13, the real-hardware lane):
      1. ``REPRO_PALLAS_INTERPRET`` env var — ``0/false/off/no`` forces
         compiled kernels, anything else non-blank forces the interpreter
         (debugging on a TPU host). Forcing the interpreter on a TPU warns:
         a chip run never drops to the interpreter silently.
      2. Platform auto-detect: compile on TPU, interpret elsewhere (the CPU
         has no Mosaic lowering).

    Per-call ``interpret=`` arguments on the kernel wrappers and
    ``PallasBackend(interpret=...)`` still override both.
    """
    on_accel = jax.default_backend() in _ACCEL_PLATFORMS
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip()
    if not env:
        return not on_accel
    interpret = env.lower() not in ("0", "false", "off", "no")
    if interpret and on_accel:
        warnings.warn(
            f"REPRO_PALLAS_INTERPRET={env!r}: Pallas kernels run in the "
            f"interpreter on {jax.default_backend()}, not compiled",
            RuntimeWarning, stacklevel=2,
        )
    return interpret


def resolve_interpret(interpret) -> bool:
    """A kernel wrapper's ``interpret`` argument: None defers to
    :func:`default_interpret`."""
    return default_interpret() if interpret is None else bool(interpret)


def on_every_device(kernel, interpret: bool):
    """``kernel`` made runnable under the multi-device mesh in context.

    XLA cannot partition a compiled Mosaic kernel. Under a mesh of more than
    one device (``jax.set_mesh``; the engine handle sets its own) the kernel
    therefore runs through ``shard_map`` on every device. Inside a camera
    batch vmapped with ``spmd_axis_name`` (the handle's batch program names
    the mesh's data axes) each device takes its own lanes; every other
    operand axis is replicated.

    On a 'model' axis the kernels do not run replicated: the group-split
    backend (``core/pipeline.py``, DESIGN.md §10) calls them inside its own
    ``shard_map`` over every mesh axis, each chip on its band of the
    image's groups (the kernels' ``group_offset``). There every axis is
    manual already and the kernel is returned unchanged, as it is when
    interpreted (plain XLA).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if (interpret or mesh.empty or mesh.size == 1
            or mesh.are_all_axes_manual):
        return kernel
    from jax.sharding import PartitionSpec

    return jax.shard_map(kernel, in_specs=PartitionSpec(),
                         out_specs=PartitionSpec(), check_vma=False)


def sort_groups_bitonic(depth_keys, payload_idx, interpret=None):
    """GSM path: per-group depth sort via the bitonic kernel.

    depth_keys: (G, K) float32 with +inf at invalid slots.
    payload_idx: (G, K) int32. Returns (keys, idx) sorted ascending.

    Note: the engine's binning uses the XLA *stable* sort (the tie-break the
    losslessness proof needs); the bitonic kernel is the ASIC GSM model and
    is validated standalone (tests/test_kernels_sort.py, DESIGN.md §2).
    """
    from repro.kernels.bitonic_sort import bitonic_sort_kernel

    payload_f = payload_idx.astype(jnp.float32)  # indices < 2^24: exact in f32
    k, v = bitonic_sort_kernel(depth_keys, payload_f, interpret=interpret)
    return k, v.astype(jnp.int32)


def assemble_image(out, grid, background=None):
    """(G, tpg, 4, P) kernel output -> (H, W, 3) image."""
    if background is None:
        background = jnp.zeros((3,), jnp.float32)
    G, tpg, _, P = out.shape
    gf = grid.gf
    T = grid.tile
    rgb = out[:, :, :3, :] + out[:, :, 3:4, :] * background[None, None, :, None]
    # (gy, gx, ty, tx, c, py, px)
    rgb = rgb.reshape(grid.n_groups_y, grid.n_groups_x, gf, gf, 3, T, T)
    rgb = rgb.transpose(0, 2, 5, 1, 3, 6, 4)
    img = rgb.reshape(
        grid.n_groups_y * gf * T, grid.n_groups_x * gf * T, 3
    )
    return img[: grid.height, : grid.width]


def assemble_image_tiles(out, grid, background=None):
    """(num_tiles, 4, P) raster_tile_kernel output -> (H, W, 3)."""
    if background is None:
        background = jnp.zeros((3,), jnp.float32)
    T = grid.tile
    rgb = out[:, :3, :] + out[:, 3:4, :] * background[None, :, None]
    rgb = rgb.reshape(grid.n_tiles_y, grid.n_tiles_x, 3, T, T)
    rgb = rgb.transpose(0, 3, 1, 4, 2)
    img = rgb.reshape(grid.n_tiles_y * T, grid.n_tiles_x * T, 3)
    return img[: grid.height, : grid.width]
