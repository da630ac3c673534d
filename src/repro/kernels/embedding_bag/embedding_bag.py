"""Pallas TPU kernel: EmbeddingBag — ragged gather + segment reduce.

JAX has no native ``nn.EmbeddingBag``; this kernel IS the framework's one
(required for the recsys architecture, reused by GNN mean-aggregation).

It is the paper's positional discipline on the embedding path: categorical
ids are *positions* into a huge table; only hit rows cross HBM->VMEM.  The
scalar-prefetched ``indices`` drive the table BlockSpec (one row DMA per
grid step) and the scalar-prefetched ``segment_ids`` drive the *output*
BlockSpec, so consecutive grid steps of the same bag accumulate in the VMEM
output block without round-tripping to HBM.

Contract (enforced/arranged by ops.py):
  * ``segment_ids`` non-decreasing (bags contiguous) — gives consecutive
    output-block revisits, the only accumulation pattern TPU Pallas allows;
  * every bag non-empty (ops pads empty bags with a sentinel index >= R,
    which gathers a zero row);
  * weights are an ordinary VMEM operand blocked (1,) per grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import resolve_interpret


def _kernel(idx_ref, seg_ref, tab_ref, w_ref, out_ref, *, num_rows: int):
    i = pl.program_id(0)
    first = (i == 0) | (seg_ref[i] != seg_ref[jnp.maximum(i - 1, 0)])
    valid = idx_ref[i] < num_rows
    row = tab_ref[...] * w_ref[0]
    row = jnp.where(valid, row, jnp.zeros((), row.dtype))

    @pl.when(first)
    def _init():
        out_ref[...] = row

    @pl.when(jnp.logical_not(first))
    def _acc():
        out_ref[...] += row


@functools.partial(jax.jit, static_argnames=("num_bags", "interpret"))
def embedding_bag_pallas(table: jax.Array, indices: jax.Array,
                         segment_ids: jax.Array, num_bags: int,
                         weights: jax.Array | None = None,
                         *, interpret: bool | None = None) -> jax.Array:
    r, d = table.shape
    i_n = indices.shape[0]
    if weights is None:
        weights = jnp.ones((i_n,), table.dtype)
    pad_d = (-d) % 128
    if pad_d:
        table = jnp.pad(table, ((0, 0), (0, pad_d)))
    dp = d + pad_d

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(i_n,),
        in_specs=[
            pl.BlockSpec((1, dp),
                         lambda i, idx_ref, seg_ref:
                         (jnp.minimum(idx_ref[i], r - 1), 0)),
            pl.BlockSpec((1,), lambda i, idx_ref, seg_ref: (i,)),
        ],
        out_specs=pl.BlockSpec((1, dp),
                               lambda i, idx_ref, seg_ref:
                               (jnp.minimum(seg_ref[i], num_bags - 1), 0)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, num_rows=r),
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((num_bags, dp), table.dtype),
        interpret=resolve_interpret(interpret),
    )(indices, segment_ids, table, weights)
    return out[:, :d]
