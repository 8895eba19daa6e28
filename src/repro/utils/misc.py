"""Small shared utilities (no jax device state at import time)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Float32 matmuls on every platform: the TPU's default precision rounds
# float32 operands to bfloat16. The render path's geometry and colour sums
# pass this explicitly.
HIGHEST = jax.lax.Precision.HIGHEST


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def wide_count_dtype():
    """Dtype for op counters that can exceed int32 on multi-million-Gaussian
    scenes (RenderStats sort_ops / fifo_ops / n_candidate_tests): int64 when
    x64 is enabled, float32 otherwise. float32 is exact for counts below
    2**24 (every parity test regime) and stays positive/monotone above —
    int32 silently wraps negative, which is the bug this guards against."""
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.float32


def wide_count_sum(values: jnp.ndarray) -> jnp.ndarray:
    """Overflow-safe sum for counter accumulation: accumulates in the widest
    available float (f64 under x64, else f32) and casts to
    ``wide_count_dtype``. Integer-exact whenever the total fits the
    accumulator mantissa; never wraps."""
    acc = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    return jnp.sum(values.astype(acc)).astype(wide_count_dtype())


def pytree_count(tree) -> int:
    """Total number of elements across all leaves."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def pytree_bytes(tree) -> int:
    """Total bytes across all leaves (uses declared dtypes)."""
    return sum(
        int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree_util.tree_leaves(tree)
    )
