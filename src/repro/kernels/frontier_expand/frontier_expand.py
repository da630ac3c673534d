"""Pallas TPU kernel: CSR frontier expansion (positions -> positions).

One BFS level of the paper's PRecursive operator: every frontier target
vertex emits the contiguous CSR range of its out-edges.  The kernel is the
rank inversion (``expand_index``): for each output slot ``j`` find which
frontier slot produced it (``srcslot = #{ends <= j}``) and the edge offset
within that vertex's CSR range.  The frontier-sized rows live wholly in
VMEM and are walked in lane-aligned chunks sliced from the refs.  ``ends``
is sorted, so a block of output slots only needs the chunks holding the
frontier slots that produced it: the wrapper finds that chunk range per
block (one ``searchsorted``, scalar-prefetched), every chunk below it
counts in full, and the work is O(F + capacity) chunks instead of
O(F * capacity).  Both steps run on the MXU with no dynamic VMEM gather:

* the count is ``ones @ [ends <= j]^T`` over a ``(slots, chunk)`` compare
  matrix (0/1 entries, sums exact in f32);
* the select is ``bytes @ onehot(srcslot)``, where ``bytes`` holds the
  CSR range start and the slot's first output index split into 8-bit
  planes, so every product is exact even in a bf16 MXU pass.

The perm gather that turns CSR slots into edge positions is plain XLA
(``ops.frontier_expand_fused``).  Output slots beyond the level's total
carry the sentinel ``num_edges`` (gathers mask them to zero downstream,
per the engine convention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import resolve_interpret

_CHUNK = 512     # frontier slots per compare step (a multiple of 128 lanes)
_PLANES = 4      # 8-bit planes per int32 value


def _expand_index_kernel(lo_ref, hi_ref, ends_ref, planes_ref, out_ref, *,
                         block_c: int, frontier: int, num_edges: int):
    jb = pl.program_id(0)
    lo, hi = lo_ref[jb], hi_ref[jb]        # this block's chunk range
    j0 = jb * block_c
    j_col = j0 + jax.lax.broadcasted_iota(jnp.int32, (block_c, 1), 0)
    j_row = j0 + jax.lax.broadcasted_iota(jnp.int32, (1, block_c), 1)
    ones = jnp.ones((8, _CHUNK), jnp.float32)

    def count_body(c, cnt):
        c0 = pl.multiple_of(c * _CHUNK, _CHUNK)
        ends_c = ends_ref[:, pl.ds(c0, _CHUNK)]                 # (1, CHUNK)
        le = jnp.where(ends_c <= j_col, 1.0, 0.0)
        return cnt + jax.lax.dot_general(
            ones, le, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                 # (8, block_c)

    below = jnp.full((8, block_c), lo * _CHUNK, jnp.int32).astype(jnp.float32)
    cnt = jax.lax.fori_loop(lo, hi, count_body, below)
    srcslot = cnt[0:1, :].astype(jnp.int32)                     # (1, block_c)

    def sel_body(c, acc):
        c0 = pl.multiple_of(c * _CHUNK, _CHUNK)
        planes = planes_ref[:, pl.ds(c0, _CHUNK)]
        f = c0 + jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, 1), 0)
        onehot = jnp.where(f == srcslot, 1.0, 0.0)
        return acc + jnp.dot(planes, onehot,
                             preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(lo, hi, sel_body,
                            jnp.zeros((2 * _PLANES, block_c), jnp.float32))
    b = acc.astype(jnp.int32)

    def value(base):
        v = b[base:base + 1, :]
        for k in range(1, _PLANES):
            v = v | (b[base + k:base + k + 1, :] << (8 * k))
        return v

    gidx = value(0) + (j_row - value(_PLANES))
    out_ref[...] = jnp.where(srcslot < frontier, gidx, num_edges)


def _byte_planes(x: jax.Array) -> jax.Array:
    """(F,) non-negative int32 -> (_PLANES, F) f32 8-bit planes."""
    return jnp.stack([(x >> (8 * k)) & 0xFF for k in range(_PLANES)]
                     ).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("num_edges", "capacity",
                                             "block_c", "interpret"))
def expand_index_pallas(ends: jax.Array, estart: jax.Array, deg: jax.Array,
                        num_edges: int, *, capacity: int, block_c: int = 256,
                        interpret: bool | None = None) -> jax.Array:
    """(F,) cumulative ends / CSR starts / degrees -> (capacity,)
    positions *into perm* (gidx), sentinel-padded."""
    f = ends.shape[0]
    pad_f = (-f) % _CHUNK
    big = jnp.iinfo(jnp.int32).max
    ends_p = jnp.pad(ends.astype(jnp.int32), (0, pad_f),
                     constant_values=big)[None, :]
    # a slot with degree 0 is never selected (its output range is empty)
    begin = (ends - deg).astype(jnp.int32)
    planes = jnp.pad(jnp.concatenate([_byte_planes(estart.astype(jnp.int32)),
                                      _byte_planes(begin)]),
                     ((0, 0), (0, pad_f)))
    fp = f + pad_f

    pad_c = (-capacity) % block_c
    cp = capacity + pad_c
    # chunk range per output block: slots below #{ends <= first j} are all
    # counted, slots past #{ends <= last j} are never counted or selected
    first = jnp.arange(0, cp, block_c, dtype=jnp.int32)
    lo = jnp.searchsorted(ends_p[0], first, side="right") // _CHUNK
    hi = jnp.minimum(jnp.searchsorted(ends_p[0], first + block_c - 1,
                                      side="right") // _CHUNK + 1,
                     fp // _CHUNK)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(cp // block_c,),
        in_specs=[pl.BlockSpec((1, fp), lambda jb, lo, hi: (0, 0)),
                  pl.BlockSpec((2 * _PLANES, fp), lambda jb, lo, hi: (0, 0))],
        out_specs=pl.BlockSpec((1, block_c), lambda jb, lo, hi: (0, jb)),
    )
    out = pl.pallas_call(
        functools.partial(_expand_index_kernel, block_c=block_c,
                          frontier=f, num_edges=num_edges),
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((1, cp), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(lo.astype(jnp.int32), hi.astype(jnp.int32), ends_p, planes)
    return out[0, :capacity]
