from repro.utils.misc import (
    HIGHEST,
    cdiv,
    pytree_bytes,
    pytree_count,
    round_up,
    wide_count_dtype,
    wide_count_sum,
)

__all__ = [
    "HIGHEST",
    "cdiv",
    "round_up",
    "pytree_bytes",
    "pytree_count",
    "wide_count_dtype",
    "wide_count_sum",
]
