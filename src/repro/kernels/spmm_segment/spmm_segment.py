"""Pallas TPU kernel: fused gather x scale -> segment-sum (message passing).

For every edge, gather the source node's feature row, scale by the edge
weight, and reduce into the destination node.  JAX-native code
materializes the (E, D) message matrix in HBM; this kernel keeps each
message in VMEM only.  The weighted bitmap engine runs it as the (sum, x)
semiring's dense combine.

Edge order contract (arranged by ops.py): edges sorted by destination, so
revisits of an output block are consecutive grid steps — the TPU Pallas
accumulation pattern.  Grid is (feature_blocks, edges) with the edge axis
minor, so for a fixed feature block the edge sweep accumulates in VMEM and
each output tile is written back once per feature block.

Blocking: a one-row block is not a legal TPU block, so rows move as
sublane tiles (8 rows of 32-bit).  Each step fetches the tile holding the
edge's source row, selects the row in VMEM, and adds it into the row of
the destination's output tile; the edge weights ride in SMEM with the
scalar-prefetched indices.  SMEM holds 1 MiB, so the edges are swept in
chunks of ``_EDGE_CHUNK``: each chunk's call adds into the running sums
of the previous one (an aliased input/output), and a tile first touched
in a chunk starts from those sums.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.late_gather.late_gather import select_row, sublanes
from repro.kernels.mode import resolve_interpret

_EDGE_CHUNK = 1 << 15    # 3 x 128 KiB of scalar-prefetched indices/weights


def _kernel(src_ref, seg_ref, w_ref, x_ref, acc_ref, out_ref, *,
            num_nodes: int, num_out: int, x_rows: int):
    i = pl.program_id(1)                      # edge index (minor axis)
    s_rows = out_ref.shape[0]
    seg = jnp.minimum(seg_ref[i], num_out - 1)
    prev = jnp.minimum(seg_ref[jnp.maximum(i - 1, 0)], num_out - 1)
    first = (i == 0) | (seg // s_rows != prev // s_rows)
    src = src_ref[i]
    row = select_row(x_ref[...], jnp.minimum(src, num_nodes - 1) % x_rows)
    row = jnp.where(src < num_nodes, (row * w_ref[i]).astype(row.dtype),
                    jnp.zeros((), row.dtype))
    dst_row = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    contrib = jnp.where(dst_row == seg % s_rows, row,
                        jnp.zeros((), row.dtype))

    @pl.when(first)
    def _init():
        out_ref[...] = acc_ref[...]

    out_ref[...] += contrib


@functools.partial(jax.jit, static_argnames=("num_out", "block_d",
                                             "interpret"))
def spmm_segment_pallas(x: jax.Array, src: jax.Array, seg: jax.Array,
                        weights: jax.Array, num_out: int,
                        *, block_d: int = 128, interpret: bool | None = None
                        ) -> jax.Array:
    n, d = x.shape
    e = src.shape[0]
    bd = min(block_d, max(d, 1))
    pad_d = (-d) % bd
    if pad_d:
        x = jnp.pad(x, ((0, 0), (0, pad_d)))
    dp = d + pad_d
    s = sublanes(x.dtype)
    x_rows = min(s, n)
    out_rows = min(s, num_out)
    chunk = min(_EDGE_CHUNK, max(e, 1))
    nchunk = -(-e // chunk)
    pad_e = nchunk * chunk - e
    # padded edges: an out-of-range source (contributes zero) on the last
    # destination (keeps the destination order)
    src = jnp.pad(src.astype(jnp.int32), (0, pad_e), constant_values=n)
    seg = jnp.pad(seg.astype(jnp.int32), (0, pad_e),
                  constant_values=num_out - 1)
    weights = jnp.pad(weights.astype(jnp.float32), (0, pad_e))

    tile = lambda j, i, src_ref, seg_ref, w_ref: (       # noqa: E731
        jnp.minimum(seg_ref[i], num_out - 1) // out_rows, j)
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(dp // bd, chunk),                   # edges minor: consecutive
        in_specs=[                                # same-destination revisits
            pl.BlockSpec((x_rows, bd),
                         lambda j, i, src_ref, seg_ref, w_ref:
                         (jnp.minimum(src_ref[i], n - 1) // x_rows, j)),
            pl.BlockSpec((out_rows, bd), tile),
        ],
        out_specs=pl.BlockSpec((out_rows, bd), tile),
    )
    call = pl.pallas_call(
        functools.partial(_kernel, num_nodes=n, num_out=num_out,
                          x_rows=x_rows),
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((num_out, dp), x.dtype),
        input_output_aliases={4: 0},
        interpret=resolve_interpret(interpret),
    )

    def sweep(c, acc):
        part = lambda a: jax.lax.dynamic_slice_in_dim(   # noqa: E731
            a, c * chunk, chunk)
        return call(part(src), part(seg), part(weights), x, acc)

    out = jax.lax.fori_loop(0, nchunk, sweep,
                            jnp.zeros((num_out, dp), x.dtype))
    return out[:, :d]
