"""Pallas TPU kernel: the bottom-up (pull) membership test of
direction-optimizing BFS.

One pull level asks, for every reverse-adjacency entry ``q`` (a join edge
grouped by its DESTINATION vertex), whether the entry's in-neighbor is in
the frontier bitmap while its owning vertex is still unvisited:

    contrib[q] = frontier[nbr[q]] & ~visited[vtx[q]]

The two (V,)-bitmap gathers are the whole kernel.  A dynamic VMEM gather
does not lower on the TPU, so both bitmaps are bit-packed (8 vertices per
byte, 128 bytes per row: a 2^20-vertex bitmap is ``(1024, 128)`` bytes,
512 KiB as f32) and held wholly in VMEM, transposed to ``(128, rows)``.
An entry's lookup is then one MXU product with the one-hot of its row
(``bytes^T @ onehot(row)``, exact: one nonzero term per column, and bytes
<= 255 are exact even in a bf16 pass), a 128-way select of its lane and a
shift for its bit.  The segment-OR per vertex
(``nxt = any(contrib over the vertex's reverse slice)``) stays outside in
XLA, where a scatter-max is native.

Output entries are int32 0/1 (Pallas-friendly); the wrapper casts back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mode import resolve_interpret

_LANES = 128                  # bytes per packed bitmap row
_ROW_ALIGN = 128              # packed rows are padded to a multiple of this


def _lookup(bytes_t: jax.Array, idx: jax.Array) -> jax.Array:
    """Bit ``idx`` (a ``(1, block_e)`` int32 row) of a packed bitmap held
    as ``(128, rows)`` f32 bytes -> ``(1, block_e)`` int32 0/1."""
    byte = idx >> 3
    rows = bytes_t.shape[1]
    onehot = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) == (byte >> 7),
        1.0, 0.0)                                           # (rows, block_e)
    g = jnp.dot(bytes_t, onehot,
                preferred_element_type=jnp.float32)         # (128, block_e)
    lane = jax.lax.broadcasted_iota(jnp.int32, (_LANES, 1), 0)
    val = jnp.sum(jnp.where(lane == (byte & (_LANES - 1)), g, 0.0),
                  axis=0, keepdims=True).astype(jnp.int32)
    return (val >> (idx & 7)) & 1


def _pull_contrib_kernel(nbr_ref, vtx_ref, frontier_ref, visited_ref,
                         out_ref):
    hit = _lookup(frontier_ref[...], nbr_ref[...])
    seen = _lookup(visited_ref[...], vtx_ref[...])
    out_ref[...] = hit & (1 - seen)


def _pack(bits: jax.Array, rows: int, fill: int) -> jax.Array:
    """(V,) bool -> (128, rows) f32 transposed byte matrix: byte ``b`` of
    the bitmap sits at ``[b % 128, b // 128]``."""
    nbits = rows * _LANES * 8
    b = jnp.pad(bits.astype(jnp.int32), (0, nbits - bits.shape[0]),
                constant_values=fill)
    packed = jnp.sum(b.reshape(-1, 8) << jnp.arange(8, dtype=jnp.int32),
                     axis=1)
    return packed.reshape(rows, _LANES).T.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("num_vertices", "block_e",
                                             "interpret"))
def pull_contrib_pallas(nbr: jax.Array, vtx: jax.Array,
                        frontier: jax.Array, visited: jax.Array,
                        num_vertices: int, *, block_e: int = 512,
                        interpret: bool | None = None) -> jax.Array:
    """(E,) int32 contribution mask: entry q contributes iff
    ``frontier[nbr[q]] & ~visited[vtx[q]]``.  ``nbr``/``vtx`` must be
    pre-clipped to [0, num_vertices)."""
    e = nbr.shape[0]
    rows = -(-num_vertices // (_LANES * 8))
    rows += (-rows) % _ROW_ALIGN
    # padded vertices: frontier=0 / visited=1, so they never contribute
    f_t = _pack(frontier, rows, 0)
    v_t = _pack(visited, rows, 1)

    pad_e = (-e) % block_e
    ep = e + pad_e
    nbr_p = jnp.pad(nbr.astype(jnp.int32), (0, pad_e))[None, :]
    vtx_p = jnp.pad(vtx.astype(jnp.int32), (0, pad_e))[None, :]

    out = pl.pallas_call(
        _pull_contrib_kernel,
        grid=(ep // block_e,),
        in_specs=[pl.BlockSpec((1, block_e), lambda eb: (0, eb)),
                  pl.BlockSpec((1, block_e), lambda eb: (0, eb)),
                  pl.BlockSpec((_LANES, rows), lambda eb: (0, 0)),
                  pl.BlockSpec((_LANES, rows), lambda eb: (0, 0))],
        out_specs=pl.BlockSpec((1, block_e), lambda eb: (0, eb)),
        out_shape=jax.ShapeDtypeStruct((1, ep), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(nbr_p, vtx_p, f_t, v_t)
    return out[0, :e]
