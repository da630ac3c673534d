"""Pallas TPU kernel: positional row gather (the Materialize operator).

The paper's late materialization ends every positional plan with ONE gather
of the output columns at the surviving positions.  On TPU the gather is
expressed with a scalar-prefetched position vector driving the input
BlockSpec ``index_map``s: only the tiles holding reached rows are DMA'd
from HBM — rows that were never reached are never touched, which is the
whole point.

Blocking: a TPU block's last two dims must be multiples of the
(sublane, 128) tile or equal to the array's, so a one-row block is
illegal.  Each grid step therefore gathers ``S`` positions (``S`` = the
dtype's sublane tile: 8 rows of 32-bit, 16 of 16-bit) through ``S`` views
of the same table, each fetching the ``(S, W)`` row tile that holds one
position, selects that row in VMEM and writes one ``(S, W)`` output tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import resolve_interpret


def sublanes(dtype) -> int:
    """Rows in one TPU sublane tile of ``dtype`` (8 for 32-bit)."""
    return max(8, 32 // jnp.dtype(dtype).itemsize)


def select_row(tile: jax.Array, k) -> jax.Array:
    """Row ``k`` (traced) of a VMEM row tile as a ``(1, W)`` value —
    static row slices and selects only, which every TPU generation
    lowers."""
    row = tile[0:1, :]
    for r in range(1, tile.shape[0]):
        row = jnp.where(k == r, tile[r:r + 1, :], row)
    return row


def _kernel(pos_ref, *refs, num_rows: int, tile_rows: int):
    tab_refs, out_ref = refs[:-1], refs[-1]
    s = len(tab_refs)
    i = pl.program_id(0)
    for k in range(s):
        p = pos_ref[i * s + k]
        row = select_row(tab_refs[k][...], p % tile_rows)
        out_ref[k:k + 1, :] = jnp.where(p < num_rows, row,
                                        jnp.zeros((), row.dtype))


@functools.partial(jax.jit, static_argnames=("interpret",))
def late_gather_pallas(table: jax.Array, positions: jax.Array,
                       *, interpret: bool | None = None) -> jax.Array:
    """(R, W) table, (P,) int32 positions -> (P, W) gathered rows; a
    position >= R gathers a zero row."""
    r, w = table.shape
    p = positions.shape[0]
    s = sublanes(table.dtype)
    tile_rows = min(s, r)
    pad_p = (-p) % s
    pos = jnp.pad(positions.astype(jnp.int32), (0, pad_p),
                  constant_values=r)

    def tile_of(k):
        return lambda i, pos_ref: (
            jnp.clip(pos_ref[i * s + k], 0, r - 1) // tile_rows, 0)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=((p + pad_p) // s,),
        in_specs=[pl.BlockSpec((tile_rows, w), tile_of(k))
                  for k in range(s)],
        out_specs=pl.BlockSpec((s, w), lambda i, pos_ref: (i, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, num_rows=r, tile_rows=tile_rows),
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((p + pad_p, w), table.dtype),
        interpret=resolve_interpret(interpret),
    )(pos, *([table] * s))
    return out[:p]
