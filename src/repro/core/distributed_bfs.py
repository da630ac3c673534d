"""Distributed positional BFS — PRecursive over a device mesh, expressed as
an operator pipeline on the SAME :func:`~repro.core.operators.fixed_point`
driver as the single-device engines.

PosDB is "a disk-based *distributed* column-store"; the paper evaluates a
single node.  This module supplies the distributed engine the paper implies,
mapped onto JAX collectives:

* every column of the edge table is row-sharded over the BFS axes
  (``('pod','data')`` on the production mesh) — each device owns a slab of
  edges and builds a *local* CSR join index over them;
* the per-level pipeline is ``CSRIndexJoin`` (shard-local positional
  expansion of the replicated vertex frontier) → ``AppendUnionAll``
  (shard-local result positions) → ``ShardTargetExchange`` (the shard-aware
  operator: ONE tiled ``all_gather`` of vertex ids per level — the only
  collective, O(frontier) bytes, *never* values — followed by replicated
  dedup so every shard derives the identical next frontier);
* result positions stay shard-local; the final late materialization is a
  shard-local gather, so payload bytes cross no link at any point.

This is the paper's late-materialization win restated for a cluster: the
wire carries positions, values move zero times.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .csr import build_csr
from .operators import (AppendUnionAll, Context, CSRIndexJoin, EngineCaps,
                        Pipeline, RawPositions, Seed, ShardTargetExchange,
                        fixed_point)

__all__ = ["make_distributed_pbfs", "distributed_plan"]


def distributed_plan(axis, caps: EngineCaps, max_depth: int) -> Pipeline:
    """The distributed PRecursive pipeline: vertex-seeded (the frontier is
    the replicated target block, not edge positions), emit-inside-the-body
    (``inclusive`` + ``step_tag_offset=0``), shard-aware target union."""
    return Pipeline(
        name="DistributedPRecursive", rep="pos",
        seed=Seed(kind="vertices"),
        ops=(CSRIndexJoin(),
             AppendUnionAll("pos", step_tag_offset=0, append_seed=False),
             ShardTargetExchange(axis)),
        finisher=RawPositions(),
        caps=caps, max_depth=max_depth, inclusive=True)


def make_distributed_pbfs(mesh, axes: Sequence[str], num_vertices: int,
                          *, caps: EngineCaps, max_depth: int,
                          num_payload_cols: int):
    """Build a jitted distributed PRecursive BFS.

    Returns ``fn(from_col, to_col, payload, root) ->
    (positions, values, count, depth, overflow)`` where ``from_col``/
    ``to_col``/(rows of) ``payload`` are sharded over ``axes`` and outputs
    are sharded the same way (shard-local result blocks).
    """
    axes = tuple(axes)
    nshards = 1
    for a in axes:
        nshards *= mesh.shape[a]
    ax = axes if len(axes) > 1 else axes[0]
    plan = distributed_plan(ax, caps, max_depth)

    def bfs_local(from_loc, to_loc, payload_loc, root, shard_base):
        e_loc = from_loc.shape[0]
        ctx = Context(table=None, rows=None,
                      csr=build_csr(from_loc, num_vertices),
                      join_src=from_loc, join_dst=to_loc)
        r = fixed_point(plan, ctx, root, num_vertices)

        # shard-local late materialization: payload bytes never leave the
        # shard
        live = jnp.arange(caps.result, dtype=jnp.int32) < r.count
        safe = jnp.minimum(r.positions, e_loc - 1)
        vals = jnp.where(live[:, None], payload_loc[safe], 0.0)
        gpos = jnp.where(live, r.positions + shard_base, -1)
        return gpos, vals, r.count[None], (r.depth - 1)[None], \
            r.overflow[None]

    pspec = P(ax)
    fn = jax.shard_map(bfs_local, mesh=mesh,
                       in_specs=(pspec, pspec, pspec, P(), pspec),
                       out_specs=(pspec, pspec, pspec, pspec, pspec),
                       check_vma=False)

    @jax.jit
    def run(from_col, to_col, payload, root):
        e = from_col.shape[0]
        shard_base = (jnp.arange(nshards, dtype=jnp.int32) * (e // nshards))
        gpos, vals, counts, depths, ovfs = fn(from_col, to_col, payload, root,
                                              shard_base)
        return gpos, vals, counts, depths, ovfs

    return run
