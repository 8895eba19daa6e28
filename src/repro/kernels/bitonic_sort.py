"""Pallas TPU kernel for the Group-wise Sorting Module (GSM, paper Fig 10).

TPU adaptation (DESIGN.md §2): the ASIC's 16-comparator *quicksort* unit
relies on data-dependent pivots, which do not map to the VPU. A bitonic
network is the branch-free equivalent: log^2(K) compare-exchange stages,
each fully vectorized across lanes. Compare-exchange partners at distance d
are materialized by a reshape to (K/2d, 2, d) and a min/max swap along the
middle axis — no gathers, pure layout ops, which is what the TPU wants.

Sorts (key, payload) pairs ascending by key within each group segment.
Invalid entries must carry key=+inf so they sink to the end.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ops import resolve_interpret


def _compare_exchange(keys, vals, dist: int, asc_mask):
    """One stage: partner = index XOR dist, ascending where asc_mask."""
    K = keys.shape[0]
    kr = keys.reshape(K // (2 * dist), 2, dist)
    vr = vals.reshape(K // (2 * dist), 2, dist)
    am = asc_mask.reshape(K // (2 * dist), 2, dist)[:, 0, :]  # same for pair

    lo_k, hi_k = kr[:, 0, :], kr[:, 1, :]
    lo_v, hi_v = vr[:, 0, :], vr[:, 1, :]
    swap = jnp.where(am, lo_k > hi_k, lo_k < hi_k)
    new_lo_k = jnp.where(swap, hi_k, lo_k)
    new_hi_k = jnp.where(swap, lo_k, hi_k)
    new_lo_v = jnp.where(swap, hi_v, lo_v)
    new_hi_v = jnp.where(swap, lo_v, hi_v)
    keys = jnp.stack([new_lo_k, new_hi_k], axis=1).reshape(K)
    vals = jnp.stack([new_lo_v, new_hi_v], axis=1).reshape(K)
    return keys, vals


def _bitonic_network(keys, vals, K: int):
    # iota computed in-kernel (constants cannot be captured by pallas).
    idx = jax.lax.iota(jnp.int32, K)
    for k in [2 ** p for p in range(1, K.bit_length())]:
        if k > K:
            break
        asc = (idx & k) == 0  # ascending blocks of size k
        for j in [k >> s for s in range(1, k.bit_length())]:
            if j < 1:
                break
            # Partner distance j: reshape trick needs contiguous pairs, which
            # XOR-at-distance-j provides when flattened as (K/2j, 2, j).
            keys, vals = _compare_exchange(keys, vals, j, asc)
    return keys, vals


def bitonic_sort_kernel(
    keys: jnp.ndarray,   # (num_groups, K) float32, +inf padding
    payload: jnp.ndarray,  # (num_groups, K) float32 (bit-cast your ints)
    interpret: bool | None = None,
):
    """Returns (sorted_keys, permuted_payload), both (num_groups, K)."""
    num_groups, K = keys.shape
    if K & (K - 1):
        raise ValueError("bitonic sort requires power-of-two capacity")

    def kernel(k_ref, v_ref, ko_ref, vo_ref):
        k = k_ref[0]
        v = v_ref[0]
        k, v = _bitonic_network(k, v, K)
        ko_ref[0] = k
        vo_ref[0] = v

    return pl.pallas_call(
        kernel,
        grid=(num_groups,),
        in_specs=[
            pl.BlockSpec((1, K), lambda g: (g, 0)),
            pl.BlockSpec((1, K), lambda g: (g, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, K), lambda g: (g, 0)),
            pl.BlockSpec((1, K), lambda g: (g, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_groups, K), jnp.float32),
            jax.ShapeDtypeStruct((num_groups, K), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(keys, payload)
