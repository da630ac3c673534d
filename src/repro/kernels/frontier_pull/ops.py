"""Jitted pull-step wrapper with the engine's contract.

``frontier_pull_fused(rcsr, join_src, join_dst, frontier, visited)`` is
drop-in for the ``expand_fn=`` slot of
:class:`repro.core.operators.PullStep`: the in-neighbor / owning-vertex
columns come off the reverse CSR's permutation (cheap positional
gathers), and the frontier/visited MEMBERSHIP test — the gather-heavy
heart of the bottom-up step — runs as the Pallas ``pull_contrib`` kernel.
The per-vertex segment-OR stays in XLA (scatter-max is native there).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.csr import CSRIndex

from .frontier_pull import pull_contrib_pallas


def frontier_pull_fused(rcsr: CSRIndex, join_src: jax.Array,
                        join_dst: jax.Array, frontier: jax.Array,
                        visited: jax.Array) -> jax.Array:
    nv = frontier.shape[0]
    perm = rcsr.perm
    if perm.shape[0] == 0:
        return jnp.zeros((nv,), bool)
    nbr = jnp.clip(join_src[perm], 0, nv - 1)
    vtx = jnp.clip(join_dst[perm], 0, nv - 1)
    contrib = pull_contrib_pallas(nbr, vtx, frontier, visited,
                                  nv).astype(bool)
    nxt = jnp.zeros((nv,), bool).at[vtx].max(contrib, mode="drop")
    return nxt & ~visited
