"""Jitted frontier-expansion wrapper with the engine's contract.

``frontier_expand_fused(csr, targets, valid, capacity)`` is drop-in for
:func:`repro.core.csr.expand_frontier` (same signature is accepted by
``precursive_bfs(expand_fn=...)``): the rank inversion runs as the Pallas
``expand_index`` kernel, the perm gather as one XLA gather.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.csr import CSRIndex, csr_degrees

from .frontier_expand import expand_index_pallas


def frontier_expand_fused(csr: CSRIndex, targets: jax.Array,
                          valid: jax.Array, capacity: int
                          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    deg = csr_degrees(csr, targets, valid)
    ends = jnp.cumsum(deg, dtype=jnp.int32)
    total = ends[-1]
    v = jnp.clip(targets, 0, csr.num_vertices - 1)
    estart = jnp.where(deg > 0, csr.indptr[v], 0)

    gidx = expand_index_pallas(ends, estart, deg, csr.num_edges,
                               capacity=capacity)
    # sentinel slots (>= num_edges) keep the engine's sentinel value
    epos = jnp.take(csr.perm, gidx, mode="fill", fill_value=csr.num_edges)
    return epos.astype(jnp.int32), jnp.minimum(total, capacity), \
        total > capacity
