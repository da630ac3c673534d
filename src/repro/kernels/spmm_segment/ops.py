"""Jitted SpMM wrapper: sorts edges by destination (kernel contract) and
exposes the degree-normalized variant used by GCN-style layers."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .ref import spmm_segment_ref
from .spmm_segment import spmm_segment_pallas


def spmm_segment(x: jax.Array, src: jax.Array, dst: jax.Array,
                 weights: jax.Array | None, num_out: int,
                 *, use_pallas: bool = False) -> jax.Array:
    e = src.shape[0]
    if weights is None:
        weights = jnp.ones((e,), x.dtype)
    if not use_pallas:
        return spmm_segment_ref(x, src, dst, weights, num_out)
    order = jnp.argsort(dst, stable=True)
    return spmm_segment_pallas(x, src[order], dst[order], weights[order],
                               num_out)


def gcn_norm_spmm(x: jax.Array, src: jax.Array, dst: jax.Array,
                  num_nodes: int, *, use_pallas: bool = False) -> jax.Array:
    """Symmetric-normalized aggregation: out = D^{-1/2} A D^{-1/2} x."""
    ones = jnp.ones((src.shape[0],), x.dtype)
    deg = jax.ops.segment_sum(ones, dst, num_segments=num_nodes) + \
        jax.ops.segment_sum(ones, src, num_segments=num_nodes)
    deg = jnp.maximum(deg * 0.5, 1.0)
    inv = jax.lax.rsqrt(deg)
    w = inv[src] * inv[dst]
    return spmm_segment(x, src, dst, w, num_nodes, use_pallas=use_pallas)
