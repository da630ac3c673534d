"""Jitted public wrapper for the Materialize gather.

``materialize`` fuses multiple columns into a single wide gather (one DMA
stream per position instead of one per column — the columnar analogue of a
heap-page read, but only for rows that survived the recursion).
"""
from __future__ import annotations

from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from .late_gather import late_gather_pallas
from .ref import late_gather_ref


def late_gather(table: jax.Array, positions: jax.Array,
                *, use_pallas: bool = False) -> jax.Array:
    if use_pallas:
        return late_gather_pallas(table, positions)
    return late_gather_ref(table, positions)


def materialize(columns: Dict[str, jax.Array], positions: jax.Array,
                names: Sequence[str], *, use_pallas: bool = False
                ) -> Dict[str, jax.Array]:
    """Gather ``names`` columns at ``positions`` via ONE fused wide gather."""
    parts, slices, off = [], {}, 0
    dtype = jnp.float32
    for n in names:
        col = columns[n]
        c2 = col[:, None] if col.ndim == 1 else col
        parts.append(c2.astype(dtype))
        slices[n] = (off, off + c2.shape[1], col.ndim == 1, col.dtype)
        off += c2.shape[1]
    fused = jnp.concatenate(parts, axis=1)
    g = late_gather(fused, positions, use_pallas=use_pallas)
    out = {}
    for n, (a, b, was_1d, dt) in slices.items():
        v = g[:, a:b].astype(dt)
        out[n] = v[:, 0] if was_1d else v
    return out
