"""Jitted EmbeddingBag wrapper: normalizes ragged input to the kernel
contract (sorted segments, no empty bags) and exposes fixed-hotness and
per-field conveniences used by the recsys/GNN models."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .embedding_bag import embedding_bag_pallas
from .ref import embedding_bag_ref


def embedding_bag(table: jax.Array, indices: jax.Array,
                  segment_ids: jax.Array, num_bags: int,
                  weights: jax.Array | None = None,
                  *, combiner: str = "sum", use_pallas: bool = False
                  ) -> jax.Array:
    """General ragged bag lookup.  ``segment_ids`` need not be sorted and
    bags may be empty; normalization happens here, not in the kernel."""
    if use_pallas:
        order = jnp.argsort(segment_ids, stable=True)
        idx_s = indices[order]
        seg_s = segment_ids[order]
        w_s = None if weights is None else weights[order]
        # guarantee every bag visited: append one sentinel index per bag
        r = table.shape[0]
        pad_idx = jnp.full((num_bags,), r, jnp.int32)
        pad_seg = jnp.arange(num_bags, dtype=jnp.int32)
        idx2 = jnp.concatenate([idx_s, pad_idx])
        seg2 = jnp.concatenate([seg_s, pad_seg])
        order2 = jnp.argsort(seg2, stable=True)
        w2 = None if w_s is None else jnp.concatenate(
            [w_s, jnp.zeros((num_bags,), table.dtype)])[order2]
        out = embedding_bag_pallas(table, idx2[order2], seg2[order2],
                                   num_bags, w2)
    else:
        out = embedding_bag_ref(table, indices, segment_ids, num_bags,
                                weights)
    if combiner == "mean":
        sizes = jax.ops.segment_sum(
            (indices < table.shape[0]).astype(table.dtype), segment_ids,
            num_segments=num_bags)
        out = out / jnp.maximum(sizes, 1.0)[:, None]
    return out


def fixed_hot_lookup(table: jax.Array, ids: jax.Array,
                     *, use_pallas: bool = False) -> jax.Array:
    """(B, K) ids -> (B, K, D): the DeepFM per-field lookup (hotness 1 per
    field, fields stacked).  Pure gather — the degenerate bag."""
    b, k = ids.shape
    flat = ids.reshape(-1)
    if use_pallas:
        from repro.kernels.late_gather import late_gather_pallas
        rows = late_gather_pallas(table, flat)
    else:
        rows = jnp.take(table, jnp.minimum(flat, table.shape[0] - 1), axis=0)
        rows = jnp.where((flat < table.shape[0])[:, None], rows, 0.0)
    return rows.reshape(b, k, table.shape[1])
