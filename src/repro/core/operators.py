"""Composable positional operator algebra + the unified fixed-point driver.

The paper describes its engines as *Volcano operator trees* (Fig. 3 for the
tuple-based TRecursive plan, Fig. 4 for the positional PRecursive plan).
This module is that algebra for the TPU port: every recursive engine is a
:class:`Pipeline` — a seed operator, a tuple of per-level operators, and a
finisher — executed by ONE shared :func:`fixed_point` driver (a single
``jax.lax.while_loop``).  The engines in :mod:`repro.core.recursive`,
:mod:`repro.core.bitmap` and :mod:`repro.core.distributed_bfs` are thin
compositions of these operators; ``plan_repr`` in :mod:`repro.core.engine`
renders the *actual* composition, so the paper-figure mapping is auditable.

Operator → paper mapping
------------------------

===================  ======================================================
``Seed``             the non-recursive CTE child (Filter on the root; the
                     row-store variant is a SeqScan over interleaved rows)
``ReadTargets``      per-level read of the join column out of the frontier
                     (positions → one column gather; tuples/rows → free)
``VisitedDedup``     BFS vertex dedup (visited bitmap + scatter-argmin)
``CSRIndexJoin``     Fig. 4's IndexJoin: frontier vertices → edge positions
                     through the CSR join index (positions in, positions out)
``ScanHashJoin``     Fig. 3's HashJoin realized as PostgreSQL does it on a
                     heap table: full SeqScan probing the frontier hash
``DenseBitmapStep``  beyond-paper dense-frontier level (boolean SpMV push)
``EarlyMaterialize`` Fig. 3's per-level Materialize (tuple/row pipelines)
``AppendUnionAll``   the recursive UNION ALL: append the level block to the
                     working result, tagging each row with its BFS level
``LateMaterialize``  Fig. 4's single post-fixed-point Materialize
===================  ======================================================

State contract
--------------

All operators act on one :class:`TraversalState` pytree.  The *frontier
representation* is the axis the paper studies and is explicit per pipeline:

* ``rep='pos'``   — the frontier is a block of edge positions (PRecursive);
* ``rep='vals'``  — a block of materialized column values (TRecursive);
* ``rep='rows'``  — a block of full interleaved rows (row-store emulation);
* ``rep='dense'`` — a boolean vertex bitmap (beyond-paper bitmap engine).

Positions contract: pipelines whose representation carries positions
(``'pos'``/``'dense'``, and any pipeline finished by :class:`TopLevelJoin`)
return real edge positions in ``BFSResult.positions``; pure tuple/row
pipelines return all ``-1`` — positions are *unavailable* after early
materialization, exactly the information loss the paper's Fig. 3 plan pays.

Direction support: the join view (``ctx.join_src``/``ctx.join_dst`` and the
CSR over ``join_src``) decides traversal direction.  ``outbound`` uses
(from, to); ``inbound`` the reverse; ``both`` the FUSED bidirectional view
(``ctx.bidir``): the out- and in-CSRs plus one merged indptr, with a
VIRTUAL 2E join space (position ``p < E`` is edge ``p`` forward,
``p >= E`` backward) whose positions fold back onto real edges at
append/materialize time — same layout the old doubled view materialized,
at E-scale memory.

Direction-optimizing traversal: :class:`PullStep` is the Beamer bottom-up
dual of the push steps (gather over the reverse CSR from unvisited
vertices, testing in-neighbor membership in the frontier bitmap), and
:class:`DirectionSwitch` picks push or pull per level from exact work
terms, with thresholds owned by the planner's refittable cost constants.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .csr import CSRIndex, expand_frontier, expand_frontier_both
from .positions import PosBlock, append_block, block_from_mask, compact_mask
from .semiring import (Semiring, elem_combine, get_semiring, or_combine,
                       scatter_combine)
from .semiring import propagate as sr_propagate
from .table import ColumnTable, RowTable

__all__ = [
    "DIRECTIONS", "check_direction",
    "EngineCaps", "CostEnv", "OpCost",
    "BFSResult", "Context", "TraversalState", "Operator",
    "Seed", "ReadTargets", "VisitedDedup", "CSRIndexJoin", "ScanHashJoin",
    "DenseBitmapStep", "PullStep", "DirectionSwitch", "HybridStep",
    "HybridPullStep", "EarlyMaterialize", "AppendUnionAll",
    "ShardTargetExchange", "LateMaterialize", "EmitTuples", "ProjectRows",
    "CompactEmitted", "DeferredEmit", "TopLevelJoin", "RawPositions",
    "Pipeline", "fixed_point", "fixed_point_batch", "execute",
    "execute_batch", "dedup_targets", "bitmap_level",
    "Semiring", "or_combine", "WeightedExpand", "WeightedDenseStep",
    "MultiQuerySeed", "MultiQueryWordSweep", "MultiQueryEmit",
    "execute_multiquery", "WORD_LANES",
]


DIRECTIONS = ("outbound", "inbound", "both")


def check_direction(direction: str) -> None:
    if direction not in DIRECTIONS:
        raise ValueError(f"unknown direction {direction!r}; "
                         f"expected one of {DIRECTIONS}")


class EngineCaps(NamedTuple):
    """Static buffer capacities (the Volcano block sizes of the TPU port)."""

    frontier: int   # max edges emitted by a single BFS level
    result: int     # max edges in the full result


class CostEnv(NamedTuple):
    """One level's cardinalities + storage widths, fed to each operator's
    :meth:`Operator.estimate` by the planner's cost model.  Cardinalities
    come from sampled graph statistics (:mod:`repro.planner.stats`); widths
    from the dataset's actual column layout.  For finishers the planner sets
    ``frontier_rows``/``emitted_rows`` to the *total* result cardinality.

    The live cardinalities drive output-row estimates; the BYTE estimates of
    block operators are driven by ``frontier_cap``/``result_cap`` instead —
    under the static-shape padding convention every per-level op touches its
    whole fixed-capacity buffer, so capacity (not the live count) is what
    the memory system pays.  That asymmetry is exactly why a dense O(E)
    level can beat a "cheaper" positional level on small graphs with
    generous block sizes."""

    frontier_rows: float       # F: live frontier entries entering the level
    unique_rows: float         # U: frontier rows surviving vertex dedup
    emitted_rows: float        # M: edge rows the level's join emits
    num_vertices: int          # V
    num_edges: int             # EJ: join-space edge count (2E for 'both')
    frontier_cap: int          # static per-level block capacity
    result_cap: int            # static result buffer capacity
    row_bytes: int             # full interleaved row width (bytes/row)
    col_bytes: Any             # Mapping[str, int]: bytes/row per column
    kernel_factor: float = 1.0  # relative cost of a plugged expand kernel
    visited_rows: float = 0.0  # vertices discovered BEFORE this level (the
    #   pull-side work term: unvisited = V - visited_rows)


class OpCost(NamedTuple):
    """One operator's per-level estimate: output cardinality + bytes moved
    through the memory system (the ranking currency of the cost model)."""

    rows: float
    bytes: float


def _cols_bytes(env: CostEnv, cols) -> float:
    """Bytes/row of a materialized tuple over ``cols`` (unknown synthetic
    columns such as ``__next__`` count as one int32)."""
    return float(sum(env.col_bytes.get(c, 4) for c in cols))


class BFSResult(NamedTuple):
    values: Dict[str, jax.Array]   # (result_cap, ...) materialized outputs
    positions: jax.Array           # (result_cap,) edge positions (or -1s)
    count: jax.Array               # () live rows
    depth: jax.Array               # () levels actually executed
    overflow: jax.Array            # () any capacity overflow observed
    row_depths: Optional[jax.Array] = None   # (result_cap,) BFS level per row
    level_dirs: Optional[jax.Array] = None   # (L,) int8 per-level direction
    #   decision of a DirectionSwitch pipeline (-1 unused, 0 push, 1 pull)
    vertex_values: Optional[jax.Array] = None  # (V,) float32 semiring value
    #   plane of a weighted pipeline (None for the boolean reach workload)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Context:
    """Runtime inputs of a pipeline: storage + the direction-resolved join
    view.  ``join_src`` is the column the CSR indexes; ``join_dst`` holds the
    next vertex reached by each join-space edge.

    ``rcsr`` is the REVERSE CSR of the join view (groups join edges by
    ``join_dst``) — the pull-mode operators and the direction-switch
    predicate read it.  ``bidir=True`` selects the FUSED bidirectional view
    for ``direction='both'``: ``join_src``/``join_dst`` stay the E-sized
    base columns and the 2E join space is VIRTUAL (position ``p < E`` is
    edge ``p`` forward, ``p >= E`` is edge ``p-E`` backward), with
    ``both_indptr`` the merged out+in indptr — no 2E array is ever
    materialized.  ``bidir`` is pytree aux data (static under jit)."""

    table: Optional[ColumnTable]
    rows: Optional[RowTable]
    csr: Optional[CSRIndex]
    join_src: jax.Array
    join_dst: jax.Array
    rcsr: Optional[CSRIndex] = None
    both_indptr: Optional[jax.Array] = None
    bidir: bool = False
    edge_weights: Optional[jax.Array] = None   # (E,) float32 per-edge ⊗
    #   weight in REAL position order (shared by both orientations of the
    #   fused bidirectional view); None for unweighted traffic

    def tree_flatten(self):
        return ((self.table, self.rows, self.csr, self.join_src,
                 self.join_dst, self.rcsr, self.both_indptr,
                 self.edge_weights), self.bidir)

    @classmethod
    def tree_unflatten(cls, bidir, children):
        (table, rows, csr, join_src, join_dst, rcsr, both_indptr,
         edge_weights) = children
        return cls(table=table, rows=rows, csr=csr, join_src=join_src,
                   join_dst=join_dst, rcsr=rcsr, both_indptr=both_indptr,
                   bidir=bidir, edge_weights=edge_weights)


class TraversalState(NamedTuple):
    """The shared operator state.  One frontier representation is active per
    pipeline; the others hold zero-size placeholders so every pipeline runs
    through the identical ``while_loop`` structure."""

    frontier_pos: jax.Array            # (F,) int32 join-space edge positions
    frontier_vals: Dict[str, jax.Array]  # tuple rep: name -> (F, ...)
    frontier_rows: jax.Array           # (F, W) row-store rep
    frontier_count: jax.Array          # () int32 live frontier entries
    targets: jax.Array                 # (F,) int32 target vertices
    keep: jax.Array                    # (F,) bool survivors of dedup
    frontier_bits: jax.Array           # (V,) bool dense frontier
    emitted: jax.Array                 # (EJ,) bool emitted-edge mask
    emit_depth: jax.Array              # (EJ,) int32 level of first emission
    visited: jax.Array                 # (V,) bool BFS visited set
    result_pos: jax.Array              # (R,) int32 real result positions
    result_vals: Dict[str, jax.Array]  # materialized result buffers
    result_depth: jax.Array            # (R,) int32 BFS level per result row
    result_count: jax.Array            # () int32
    depth: jax.Array                   # () int32 levels executed
    overflow: jax.Array                # () bool
    vertex_depth: jax.Array            # (V,) int32 BFS depth per vertex
    #   (-1 = undiscovered; deferred-emission pipelines derive the emitted
    #   mask from it ONCE, after the fixed point)
    visited_count: jax.Array           # () int32 discovered vertices so far
    #   (maintained by the deferred dense steps so the switch predicate
    #   reads the unvisited count without a per-level popcount)
    level_dirs: jax.Array              # (L,) int8 per-level switch decision
    #   (-1 = level not executed, 0 = push, 1 = pull)
    frontier_val: jax.Array            # weighted value plane of the frontier:
    #   (F,) value arriving along each frontier edge (positional rep) or
    #   (V,) per-vertex level values (dense rep); zero-size for 'reach'
    vertex_val: jax.Array              # (V,) float32 ⊕-accumulated value per
    #   vertex (semiring identity = unreached); zero-size for 'reach'


# ---------------------------------------------------------------------------
# shared primitives
# ---------------------------------------------------------------------------

def dedup_targets(targets: jax.Array, valid: jax.Array, visited: jax.Array
                  ) -> tuple[jax.Array, jax.Array]:
    """BFS vertex dedup: drop already-visited targets and, within the level,
    keep only the first occurrence of each vertex (scatter-argmin ticket).

    Returns (keep_mask, new_visited)."""
    cap = targets.shape[0]
    nv = visited.shape[0]
    safe = jnp.clip(targets, 0, nv - 1)
    fresh = valid & ~visited[safe]
    slots = jnp.arange(cap, dtype=jnp.int32)
    ticket = jnp.full((nv,), cap, jnp.int32).at[safe].min(
        jnp.where(fresh, slots, cap), mode="drop")
    keep = fresh & (ticket[safe] == slots)
    # boolean ⊕ (scatter-max): dropped duplicates must not race the
    # winner's True write; weighted pipelines use scatter_combine instead
    new_visited = or_combine(visited, safe, keep)
    return keep, new_visited


def bitmap_level(from_col: jax.Array, to_col: jax.Array,
                 frontier_v: jax.Array, visited: jax.Array
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One dense push step.  Returns (edge_hit_mask, next_frontier, visited).

    edge_hit_mask marks edges whose source is in the frontier (these are the
    rows the CTE emits this level)."""
    nv = frontier_v.shape[0]
    hit = frontier_v[jnp.clip(from_col, 0, nv - 1)]
    tgt = jnp.clip(to_col, 0, nv - 1)
    nxt = or_combine(jnp.zeros((nv,), bool), tgt, hit)
    nxt = nxt & ~visited
    visited = visited | nxt
    return hit, nxt, visited


def append_values(bufs, count, vals, block_count, cap_r):
    """Append a value block into larger result buffers (the tuple/row-store
    UNION ALL).  Returns (new_bufs, new_count, overflowed)."""
    cap_f = next(iter(vals.values())).shape[0]
    slots = count + jnp.arange(cap_f, dtype=jnp.int32)
    live = (jnp.arange(cap_f, dtype=jnp.int32) < block_count) & (slots < cap_r)
    safe = jnp.where(live, slots, cap_r)
    out = {}
    for k, buf in bufs.items():
        v = vals[k]
        mask = live.reshape(live.shape + (1,) * (v.ndim - 1))
        out[k] = buf.at[safe].set(jnp.where(mask, v, 0), mode="drop")
    new_count = jnp.minimum(count + block_count, cap_r)
    return out, new_count, (count + block_count) > cap_r


def _num_real_rows(ctx: Context) -> int:
    if ctx.table is not None:
        return ctx.table.num_rows
    if ctx.rows is not None:
        return ctx.rows.num_rows
    return ctx.join_src.shape[0]


def _num_join(ctx: Context) -> int:
    """Join-space edge count EJ (2E under the fused bidirectional view —
    virtual: no 2E array backs it)."""
    n = ctx.join_src.shape[0]
    return 2 * n if ctx.bidir else n


def _to_real(ctx: Context, pos: jax.Array) -> jax.Array:
    """Fold join-space positions back to real edge positions.  Identity for
    outbound/inbound views; a 'both' view (fused-virtual, or a legacy
    materialized doubled view) maps the backward copy of edge ``p`` to
    ``e + p`` (the join-space sentinel ``2e`` folds to ``e``, the
    real-space sentinel)."""
    e = _num_real_rows(ctx)
    if not ctx.bidir and ctx.join_src.shape[0] == e:
        return pos
    return jnp.where(pos < e, pos, pos - e)


def _join_dst_at(ctx: Context, pos: jax.Array) -> jax.Array:
    """The next-vertex column of the join view, gathered at join-space
    positions (callers mask invalid lanes themselves).  Under the fused
    view the gather resolves forward positions through ``to`` and backward
    positions through ``from`` — two E-array gathers, no 2E column."""
    if not ctx.bidir:
        ej = ctx.join_src.shape[0]
        return ctx.join_dst[jnp.minimum(pos, ej - 1)]
    e = ctx.join_src.shape[0]
    fwd = pos < e
    p = jnp.clip(jnp.where(fwd, pos, pos - e), 0, e - 1)
    return jnp.where(fwd, ctx.join_dst[p], ctx.join_src[p])


def _join_src_at(ctx: Context, pos: jax.Array) -> jax.Array:
    """The source-vertex column of the join view at join-space positions."""
    if not ctx.bidir:
        ej = ctx.join_src.shape[0]
        return ctx.join_src[jnp.minimum(pos, ej - 1)]
    e = ctx.join_src.shape[0]
    fwd = pos < e
    p = jnp.clip(jnp.where(fwd, pos, pos - e), 0, e - 1)
    return jnp.where(fwd, ctx.join_src[p], ctx.join_dst[p])


def _seed_mask(ctx: Context, root: jax.Array) -> jax.Array:
    """(EJ,) mask of join edges whose source is the root (the seed
    filter).  Fused view: forward matches on ``from``, backward on ``to``,
    concatenated in join-space order."""
    if not ctx.bidir:
        return ctx.join_src == root
    return jnp.concatenate([ctx.join_src == root, ctx.join_dst == root])


def _hit_mask(ctx: Context, frontier_v: jax.Array) -> jax.Array:
    """(EJ,) mask of join edges whose SOURCE vertex is in ``frontier_v`` —
    the rows one CTE level emits (push-side emission test)."""
    nv = frontier_v.shape[0]
    if not ctx.bidir:
        return frontier_v[jnp.clip(ctx.join_src, 0, nv - 1)]
    return jnp.concatenate([
        frontier_v[jnp.clip(ctx.join_src, 0, nv - 1)],
        frontier_v[jnp.clip(ctx.join_dst, 0, nv - 1)]])


def _edge_weight_at(ctx: Context, pos: jax.Array) -> jax.Array:
    """Per-edge ⊗ weight gathered at JOIN-SPACE positions (callers mask
    invalid lanes themselves).  Weights live in real position order, so the
    fused bidirectional view folds the backward copy onto the same weight;
    a weightless context traverses with all-ones (reach-compatible)."""
    if ctx.edge_weights is None:
        return jnp.ones(pos.shape, jnp.float32)
    e = _num_real_rows(ctx)
    real = _to_real(ctx, pos)
    return ctx.edge_weights[jnp.clip(real, 0, e - 1)]


def _expand_join(ctx: Context, targets: jax.Array, keep: jax.Array,
                 capacity: int, expand_fn=None):
    """CSR expansion over the join view: the plain/Pallas kernel over the
    direction CSR, or the fused bidirectional expansion (out-slice then
    in-slice, join-space positions) when ``bidir``."""
    if ctx.bidir:
        return expand_frontier_both(ctx.csr, ctx.rcsr, ctx.both_indptr,
                                    targets, keep, capacity)
    expand = expand_fn or expand_frontier
    return expand(ctx.csr, targets, keep, capacity)


def _dense_push(ctx: Context, frontier_v: jax.Array, visited: jax.Array
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One dense PUSH step over the join view.  Returns
    (edge_hit_mask (EJ,), next_frontier, visited)."""
    if not ctx.bidir:
        return bitmap_level(ctx.join_src, ctx.join_dst, frontier_v, visited)
    nv = frontier_v.shape[0]
    src = jnp.clip(ctx.join_src, 0, nv - 1)
    dst = jnp.clip(ctx.join_dst, 0, nv - 1)
    hit_f = frontier_v[src]
    hit_b = frontier_v[dst]
    nxt = or_combine(or_combine(jnp.zeros((nv,), bool), dst, hit_f),
                     src, hit_b)
    nxt = nxt & ~visited
    visited = visited | nxt
    return jnp.concatenate([hit_f, hit_b]), nxt, visited


def _dense_pull(ctx: Context, frontier_v: jax.Array, visited: jax.Array,
                pull_fn=None) -> jax.Array:
    """One dense PULL (Beamer bottom-up) step: the next frontier is every
    UNVISITED vertex with an in-neighbor (over the join view) in the
    frontier bitmap.  The default walks the reverse CSR — the candidate
    mask gates the membership gather per reverse-adjacency entry;
    ``pull_fn`` plugs the Pallas ``frontier_pull`` kernel in its place."""
    nv = frontier_v.shape[0]
    cand = ~visited
    if ctx.bidir:
        # fused view: both orientations contribute, natural edge order
        src = jnp.clip(ctx.join_src, 0, nv - 1)
        dst = jnp.clip(ctx.join_dst, 0, nv - 1)
        nxt = or_combine(
            or_combine(jnp.zeros((nv,), bool), dst,
                       cand[dst] & frontier_v[src]),
            src, cand[src] & frontier_v[dst])
        return nxt & cand
    if pull_fn is not None:
        if ctx.rcsr is None:
            raise ValueError(
                "the frontier_pull kernel walks the reverse CSR; call "
                "Dataset.ensure_reverse() (inbound/both views build it "
                "automatically) before plugging PullStep(expand_fn=)")
        nxt = pull_fn(ctx.rcsr, ctx.join_src, ctx.join_dst, frontier_v,
                      visited)
        return nxt & cand
    if ctx.rcsr is not None:
        perm = ctx.rcsr.perm                   # join edges grouped by dst
        nbr = jnp.clip(ctx.join_src[perm], 0, nv - 1)   # in-neighbor
        vtx = jnp.clip(ctx.join_dst[perm], 0, nv - 1)   # owning vertex
        contrib = cand[vtx] & frontier_v[nbr]
        nxt = or_combine(jnp.zeros((nv,), bool), vtx, contrib)
        return nxt & cand
    # no reverse CSR built (outbound-only dataset): the same bottom-up
    # test evaluated in natural edge order — identical result, and plain
    # outbound traffic never pays the reverse-CSR build
    src = jnp.clip(ctx.join_src, 0, nv - 1)
    dst = jnp.clip(ctx.join_dst, 0, nv - 1)
    contrib = cand[dst] & frontier_v[src]
    nxt = or_combine(jnp.zeros((nv,), bool), dst, contrib)
    return nxt & cand


def _tag_depths(result_depth: jax.Array, count: jax.Array, block_cap: int,
                block_count: jax.Array, tag: jax.Array) -> jax.Array:
    """Record the BFS level of every row the current append makes live."""
    cap_r = result_depth.shape[0]
    slots = count + jnp.arange(block_cap, dtype=jnp.int32)
    live = (jnp.arange(block_cap, dtype=jnp.int32) < block_count) & \
           (slots < cap_r)
    return result_depth.at[jnp.where(live, slots, cap_r)].set(
        jnp.broadcast_to(tag, (block_cap,)), mode="drop")


# ---------------------------------------------------------------------------
# operators
# ---------------------------------------------------------------------------

class Operator:
    """Base operator: ``init`` runs once before the fixed point (seed-block
    handling), ``step`` once per level inside the ``while_loop``."""

    def init(self, ctx: Context, state: TraversalState, root: jax.Array
             ) -> TraversalState:
        return state

    def step(self, ctx: Context, state: TraversalState) -> TraversalState:
        return state

    def describe(self) -> str:
        return type(self).__name__

    def estimate(self, env: CostEnv) -> OpCost:
        """Per-level cost annotation: rows flowing out of this operator and
        bytes it drags through the memory system (overridden per class)."""
        return OpCost(env.frontier_rows, 0.0)


@dataclasses.dataclass(frozen=True)
class Seed(Operator):
    """The non-recursive child of the CTE.

    kind='edges'    — Filter[join_src = root] compacted to a position block;
    kind='vertices' — the frontier starts as the root vertex itself
                      (distributed engine: targets are exchanged, not edges);
    kind='dense'    — the root bit in a dense vertex bitmap.
    scan='rows' emulates the PostgreSQL SeqScan (strided read over the
    interleaved row table).  mark_emitted seeds the emitted-edge mask used by
    bitmap-style pipelines.  ``semiring != 'reach'`` additionally seeds the
    value plane: the root's vertex value is the semiring's seed value and
    (edge kind) each seed edge carries seed ⊗ weight."""

    kind: str = "edges"
    scan: str = "columnar"
    label: str = "from"
    mark_emitted: bool = False
    semiring: str = "reach"

    def _init_weighted(self, ctx, state, root):
        sr = get_semiring(self.semiring)
        nv = state.visited.shape[0]
        r = jnp.clip(root, 0, nv - 1)
        visited = state.visited.at[r].set(True)
        vertex_val = state.vertex_val.at[r].set(sr.seed_value)
        if self.kind == "dense":
            bits = jnp.zeros((nv,), bool).at[r].set(True)
            fval = jnp.full((nv,), sr.identity, jnp.float32).at[r].set(
                sr.seed_value)
            return state._replace(frontier_bits=bits, visited=visited,
                                  vertex_val=vertex_val, frontier_val=fval,
                                  frontier_count=jnp.ones((), jnp.int32))
        ej = _num_join(ctx)
        cap = state.frontier_pos.shape[0]
        blk = compact_mask(_seed_mask(ctx, root), cap, ej)
        w = _edge_weight_at(ctx, blk.positions)
        fval = jnp.where(
            blk.valid_mask(),
            sr_propagate(sr, jnp.float32(sr.seed_value), w), sr.identity)
        return state._replace(frontier_pos=blk.positions,
                              frontier_count=blk.count, visited=visited,
                              vertex_val=vertex_val, frontier_val=fval)

    def init(self, ctx, state, root):
        if self.semiring != "reach":
            return self._init_weighted(ctx, state, root)
        if state.vertex_depth.shape[0]:
            # deferred-emission pipeline: the per-vertex depth array IS
            # the visited set and the frontier (no separate bitmaps)
            nvd = state.vertex_depth.shape[0]
            return state._replace(
                vertex_depth=state.vertex_depth.at[
                    jnp.clip(root, 0, nvd - 1)].set(0),
                visited_count=jnp.ones((), jnp.int32),
                frontier_count=jnp.ones((), jnp.int32))
        nv = state.visited.shape[0]
        visited = state.visited.at[jnp.clip(root, 0, nv - 1)].set(True)
        if self.kind == "dense":
            bits = jnp.zeros((nv,), bool).at[jnp.clip(root, 0, nv - 1)
                                             ].set(True)
            return state._replace(frontier_bits=bits, visited=visited,
                                  frontier_count=jnp.ones((), jnp.int32))
        if self.kind == "vertices":
            cap = state.targets.shape[0]
            targets = jnp.full((cap,), -1, jnp.int32).at[0].set(root)
            keep = jnp.zeros((cap,), bool).at[0].set(True)
            return state._replace(targets=targets, keep=keep, visited=visited,
                                  frontier_count=jnp.ones((), jnp.int32))
        ej = _num_join(ctx)
        mask = (ctx.rows.column(self.label).astype(jnp.int32) == root
                if self.scan == "rows" else _seed_mask(ctx, root))
        cap = state.frontier_pos.shape[0]
        blk = compact_mask(mask, cap, ej)
        state = state._replace(frontier_pos=blk.positions,
                               frontier_count=blk.count, visited=visited)
        if self.mark_emitted:
            valid = blk.valid_mask()
            idx = jnp.where(valid, blk.positions, ej)
            emitted = state.emitted.at[idx].set(valid, mode="drop")
            emit_depth = state.emit_depth.at[idx].set(
                jnp.zeros((cap,), jnp.int32), mode="drop")
            state = state._replace(emitted=emitted, emit_depth=emit_depth)
        return state

    def describe(self):
        if self.scan == "rows":
            return f"SeqScan[{self.label} = $root] -> full rows"
        if self.kind == "vertices":
            return "SeedVertices[$root]"
        if self.kind == "dense":
            return "SeedBitmap[$root]"
        return f"Filter[{self.label} = $root] -> PosBlock"

    def estimate(self, env):
        if self.kind == "dense":             # set one bit in a (V,) bitmap
            return OpCost(env.frontier_rows, float(env.num_vertices))
        if self.kind == "vertices":
            return OpCost(env.frontier_rows, 4.0)
        if self.scan == "rows":              # strided scan drags full rows
            return OpCost(env.frontier_rows,
                          float(env.num_edges) * env.row_bytes)
        # columnar filter scan + compaction into the position block
        return OpCost(env.frontier_rows,
                      float(env.num_edges) * 4 + env.frontier_cap * 4.0)


@dataclasses.dataclass(frozen=True)
class ReadTargets(Operator):
    """Per-level read of the join column out of the frontier.  For the
    positional rep this is the ONLY per-level value gather (one column);
    tuple/row reps already paid for it at materialization time."""

    source: str = "pos"     # 'pos' | 'vals' | 'rows'
    col: str = "to"

    def step(self, ctx, state):
        cap = state.targets.shape[0]
        valid = jnp.arange(cap, dtype=jnp.int32) < state.frontier_count
        if self.source == "pos":
            t = _join_dst_at(ctx, state.frontier_pos)
        elif self.source == "vals":
            t = state.frontier_vals[self.col].astype(jnp.int32)
        else:
            t = state.frontier_rows[:, ctx.rows.slot(self.col)
                                    ].astype(jnp.int32)
        return state._replace(targets=jnp.where(valid, t, -1), keep=valid)

    def describe(self):
        what = {"pos": "positions", "vals": "tuple block",
                "rows": "row block"}[self.source]
        return f"ReadCol[{self.col}]({what})"

    def estimate(self, env):
        cap = float(env.frontier_cap)
        if self.source == "pos":     # positions + ONE column gather
            return OpCost(env.frontier_rows, cap * 8.0)
        if self.source == "vals":    # the column is already materialized
            return OpCost(env.frontier_rows, cap * 4.0)
        # strided read over the padded row block
        return OpCost(env.frontier_rows, cap * env.row_bytes)


@dataclasses.dataclass(frozen=True)
class VisitedDedup(Operator):
    """BFS semantics: a vertex expands at most once (visited bitmap +
    within-level scatter-argmin).  Omitted for raw UNION ALL walks."""

    def step(self, ctx, state):
        keep, visited = dedup_targets(state.targets, state.keep,
                                      state.visited)
        return state._replace(targets=jnp.where(keep, state.targets, -1),
                              keep=keep, visited=visited)

    def describe(self):
        return "VisitedDedup[bitmap]"

    def estimate(self, env):
        # scatter-argmin ticket over the padded block + the (V,) ticket /
        # visited arrays rebuilt-or-updated every level
        return OpCost(env.unique_rows,
                      env.frontier_cap * 12.0 + env.num_vertices * 5.0)


@dataclasses.dataclass(frozen=True)
class CSRIndexJoin(Operator):
    """Fig. 4's IndexJoin: expand frontier vertices into the positions of
    their out-edges through the CSR join index — positions in, positions
    out, no values touched.  ``expand_fn`` plugs in the Pallas kernel."""

    expand_fn: Optional[Callable] = None

    def step(self, ctx, state):
        cap = state.frontier_pos.shape[0]
        epos, total, ovf = _expand_join(ctx, state.targets, state.keep, cap,
                                        self.expand_fn)
        return state._replace(frontier_pos=epos, frontier_count=total,
                              overflow=state.overflow | ovf)

    def describe(self):
        return "IndexJoin[CSR(join_src)](CTE, edges)"

    def estimate(self, env):
        # two-phase expansion over the padded block: degrees + cumsum +
        # searchsorted inversion + perm gather, all at capacity
        b = env.frontier_cap * 16.0 + env.unique_rows * 8.0
        if self.expand_fn is not None:
            b *= env.kernel_factor
        return OpCost(env.emitted_rows, b)


@dataclasses.dataclass(frozen=True)
class ScanHashJoin(Operator):
    """Fig. 3's HashJoin as PostgreSQL executes it without an index: build a
    hash of the frontier's vertex set, then SeqScan the WHOLE table probing
    it.  On the row table the scan touches every byte of every row."""

    def step(self, ctx, state):
        nv = state.visited.shape[0]
        e = ctx.rows.num_rows
        cap = state.frontier_pos.shape[0]
        probe = or_combine(jnp.zeros((nv,), bool),
                           jnp.clip(state.targets, 0, nv - 1), state.keep)
        scan_from = ctx.rows.column("from").astype(jnp.int32)  # full scan
        hit = probe[jnp.clip(scan_from, 0, nv - 1)] & (scan_from >= 0)
        blk = compact_mask(hit, cap, e)
        ovf = jnp.sum(hit, dtype=jnp.int32) > cap
        return state._replace(frontier_pos=blk.positions,
                              frontier_count=blk.count,
                              overflow=state.overflow | ovf)

    def describe(self):
        return "HashJoin[from = cte.to](Hash(cte), SeqScan(edges))"

    def estimate(self, env):
        # frontier hash build + a FULL heap scan probing it every level
        return OpCost(env.emitted_rows,
                      env.num_vertices * 1.0 + env.frontier_cap * 4.0
                      + float(env.num_edges) * (env.row_bytes + 1.0))


@dataclasses.dataclass(frozen=True)
class WeightedExpand(Operator):
    """The positional weighted level: one fused ⊗-propagate / ⊕-combine /
    winner-select / IndexJoin step.

    Each frontier entry is a join-space edge position carrying the value
    that arrives along it (``frontier_val``).  The step ⊕-combines the
    arrivals per target vertex into the level plane ``lvl``, folds ``lvl``
    into the per-vertex accumulator, picks ONE expansion slot per active
    vertex with the same scatter-argmin ticket :func:`dedup_targets` uses
    (⊗ distributes over ⊕, so expanding the COMBINED per-vertex value once
    equals expanding every path separately — the UNION-ALL fold), and
    expands the winners through the CSR join index.

    Improving semirings (``shortest_path``) re-expand only vertices whose
    value STRICTLY improved — label-correcting Bellman-Ford whose fixed
    point (empty improved set) is exactly the driver's existing
    ``frontier_count > 0`` convergence test, i.e. value stabilization.
    Walk semirings (the aggregates) re-expand every vertex that received a
    value this level and rely on the pipeline depth bound."""

    semiring: str

    def step(self, ctx, state):
        sr = get_semiring(self.semiring)
        cap = state.frontier_pos.shape[0]
        nv = state.vertex_val.shape[0]
        slots = jnp.arange(cap, dtype=jnp.int32)
        valid = slots < state.frontier_count
        t = _join_dst_at(ctx, state.frontier_pos)
        safe = jnp.clip(t, 0, nv - 1)
        idx = jnp.where(valid, safe, nv)
        prop = state.frontier_val            # ⊗ was applied at expansion
        lvl = scatter_combine(sr, jnp.full((nv,), sr.identity, jnp.float32),
                              idx, prop)
        received = or_combine(jnp.zeros((nv,), bool), idx, valid)
        new_vv = jnp.where(received, elem_combine(sr, state.vertex_val, lvl),
                           state.vertex_val)
        if sr.improving:                     # frontier = strictly improved
            eligible = valid & (lvl < state.vertex_val)[safe]
        else:                                # frontier = all receivers
            eligible = valid
        eidx = jnp.where(eligible, safe, nv)
        ticket = jnp.full((nv,), cap, jnp.int32).at[eidx].min(
            jnp.where(eligible, slots, cap), mode="drop")
        winner = eligible & (ticket[safe] == slots)
        targets = jnp.where(winner, t, -1)
        epos, total, ovf = _expand_join(ctx, targets, winner, cap)
        evalid = jnp.arange(cap, dtype=jnp.int32) < total
        sval = lvl[jnp.clip(_join_src_at(ctx, epos), 0, nv - 1)]
        w = _edge_weight_at(ctx, epos)
        fval = jnp.where(evalid, sr_propagate(sr, sval, w), sr.identity)
        return state._replace(frontier_pos=epos, frontier_count=total,
                              frontier_val=fval, vertex_val=new_vv,
                              targets=targets, keep=winner,
                              overflow=state.overflow | ovf)

    def describe(self):
        return (f"WeightedExpand[{self.semiring}: combine(+)=per-vertex, "
                "winner -> IndexJoin[CSR(join_src)]]")

    def estimate(self, env):
        # the boolean ReadCol+Dedup+IndexJoin work at capacity, plus the
        # value plane: frontier values r/w (8B/slot) and the (V,) level +
        # accumulator planes (two f32 r/w passes)
        b = (env.frontier_cap * 36.0 + env.num_vertices * 5.0
             + env.frontier_cap * 8.0 + env.num_vertices * 16.0)
        return OpCost(env.emitted_rows, b)


@dataclasses.dataclass(frozen=True)
class WeightedDenseStep(Operator):
    """The dense weighted level: ⊗ over the full edge list then one
    ⊕-scatter into the (V,) level plane — the weighted generalization of
    :class:`DenseBitmapStep`'s boolean SpMV.

    For the (sum, ×) semiring the ⊕-scatter IS the fused
    gather-scale-segment-sum the ``kernels/spmm_segment`` kernel implements,
    so ``use_kernel=True`` routes the combine through it (inactive edges
    are disabled with the kernel's own ``src >= N`` padding contract);
    every other ⊕ uses the jnp scatter.  Single-direction views only: the
    planner never offers the dense engine for ``direction='both'`` under a
    weighted workload."""

    semiring: str
    use_kernel: bool = False

    def step(self, ctx, state):
        sr = get_semiring(self.semiring)
        nv = state.vertex_val.shape[0]
        src = jnp.clip(ctx.join_src, 0, nv - 1)
        dst = jnp.clip(ctx.join_dst, 0, nv - 1)
        hit = state.frontier_bits[src]
        w = _edge_weight_at(ctx, jnp.arange(ctx.join_src.shape[0],
                                            dtype=jnp.int32))
        if self.use_kernel and sr.combine == "add" and sr.propagate == "mul":
            from ..kernels.spmm_segment import spmm_segment
            lvl = spmm_segment(state.frontier_val[:, None],
                               jnp.where(hit, src, nv), dst, w, nv,
                               use_pallas=True)[:, 0]
        else:
            prop = sr_propagate(sr, state.frontier_val[src], w)
            lvl = scatter_combine(
                sr, jnp.full((nv,), sr.identity, jnp.float32),
                jnp.where(hit, dst, nv), prop)
        received = or_combine(jnp.zeros((nv,), bool),
                              jnp.where(hit, dst, nv), hit)
        new_vv = jnp.where(received, elem_combine(sr, state.vertex_val, lvl),
                           state.vertex_val)
        if sr.improving:
            nxt = received & (lvl < state.vertex_val)
        else:
            nxt = received
        fval = jnp.where(nxt, lvl, sr.identity)
        new = hit & ~state.emitted
        emit_depth = jnp.where(new, state.depth, state.emit_depth)
        return state._replace(frontier_bits=nxt, frontier_val=fval,
                              vertex_val=new_vv,
                              visited=state.visited | nxt,
                              emitted=state.emitted | hit,
                              emit_depth=emit_depth,
                              frontier_count=jnp.sum(nxt, dtype=jnp.int32))

    def describe(self):
        how = "spmm_segment kernel" if self.use_kernel else "(+)-scatter"
        return f"BitmapStep[weighted {self.semiring}: {how}]"

    def estimate(self, env):
        # the boolean dense step's O(E) traffic, plus the value plane: one
        # f32 propagate per edge and the (V,) level + accumulator planes
        b = (float(env.num_edges) * (10.0 + 8.0)
             + float(env.num_vertices) * (3.0 + 16.0))
        if self.use_kernel:
            b *= env.kernel_factor
        return OpCost(env.emitted_rows, b)


def _record_deferred(state: TraversalState, new: jax.Array
                     ) -> TraversalState:
    """Deferred-emission bookkeeping: the loop carries ONLY the per-vertex
    depth array (frontier = ``vd == depth``, visited = ``vd >= 0`` — no
    separate bitmaps) plus the scalar visited count the switch predicate
    reads.  Newly discovered vertices emit at ``state.depth + 1``; the
    emitted mask is derived once, after the fixed point."""
    count = jnp.sum(new, dtype=jnp.int32)
    vd = jnp.where(new, state.depth + 1, state.vertex_depth)
    return state._replace(vertex_depth=vd, frontier_count=count,
                          visited_count=state.visited_count + count)


@dataclasses.dataclass(frozen=True)
class DenseBitmapStep(Operator):
    """Beyond-paper dense level: the frontier is a vertex bitmap and one
    level is a masked scatter over the full edge list (boolean-semiring
    SpMV) — O(E) work but zero data-dependent shapes.

    ``deferred=True`` (the direction-optimizing pipelines) skips the
    per-level emitted-mask/emit-depth upkeep — two O(E) writes per level —
    and records per-vertex depths instead; :class:`DeferredEmit` rebuilds
    the identical emitted set in ONE O(E) pass after the fixed point."""

    deferred: bool = False

    def deferred_new(self, ctx, state):
        """Narrow deferred protocol: the newly-discovered-vertex mask from
        the per-vertex depth array alone (DirectionSwitch conds over THIS,
        not the whole state, so the branch exchanges one (V,) mask)."""
        vd = state.vertex_depth
        nv = vd.shape[0]
        src = jnp.clip(ctx.join_src, 0, nv - 1)
        dst = jnp.clip(ctx.join_dst, 0, nv - 1)
        # frontier membership fused into the edge gather (vd[src] == depth)
        # — no (V,) frontier mask is ever materialized
        if ctx.bidir:
            tgt = or_combine(
                or_combine(jnp.zeros((nv,), bool), dst,
                           vd[src] == state.depth),
                src, vd[dst] == state.depth)
        else:
            tgt = or_combine(jnp.zeros((nv,), bool), dst,
                             vd[src] == state.depth)
        return tgt & (vd < 0)

    def step(self, ctx, state):
        if self.deferred:
            return _record_deferred(state, self.deferred_new(ctx, state))
        hit, nxt, visited = _dense_push(ctx, state.frontier_bits,
                                        state.visited)
        new = hit & ~state.emitted
        emit_depth = jnp.where(new, state.depth, state.emit_depth)
        return state._replace(frontier_bits=nxt, visited=visited,
                              emitted=state.emitted | hit,
                              emit_depth=emit_depth,
                              frontier_count=jnp.sum(nxt, dtype=jnp.int32))

    def describe(self):
        tag = ", deferred emit" if self.deferred else ""
        return f"BitmapStep[push: frontier bits -> edge mask{tag}]"

    def estimate(self, env):
        # O(E) masked scatter + bitmap updates, independent of frontier
        # size; the deferred variant drops the two per-level O(E) emitted
        # writes (paid once in the finisher instead)
        e_ops = 6.0 if self.deferred else 10.0
        v_ops = 4.0 if self.deferred else 3.0
        return OpCost(env.emitted_rows,
                      float(env.num_edges) * e_ops
                      + float(env.num_vertices) * v_ops)


@dataclasses.dataclass(frozen=True)
class PullStep(Operator):
    """Beamer-style bottom-up level: gather over the REVERSE CSR from
    unvisited vertices, testing membership of their in-neighbors in the
    frontier bitmap — the pull dual of :class:`DenseBitmapStep`'s push.
    ``expand_fn`` plugs the Pallas ``frontier_pull`` kernel
    (:func:`repro.kernels.frontier_pull.frontier_pull_fused`).

    In deferred mode (the diropt pipelines) a pull level touches no
    emitted-edge state at all; in emitted mode the push-side hit mask is
    still computed (emission is defined by the SQL join, not by how the
    next frontier was found), so pull only pays off with deferral."""

    deferred: bool = False
    expand_fn: Optional[Callable] = None

    def deferred_new(self, ctx, state):
        """Narrow deferred protocol (see DenseBitmapStep.deferred_new)."""
        vd = state.vertex_depth
        frontier = vd == state.depth
        return _dense_pull(ctx, frontier, vd >= 0, self.expand_fn)

    def step(self, ctx, state):
        if self.deferred:
            return _record_deferred(state, self.deferred_new(ctx, state))
        nxt = _dense_pull(ctx, state.frontier_bits, state.visited,
                          self.expand_fn)
        visited = state.visited | nxt
        hit = _hit_mask(ctx, state.frontier_bits)
        new = hit & ~state.emitted
        emit_depth = jnp.where(new, state.depth, state.emit_depth)
        return state._replace(frontier_bits=nxt, visited=visited,
                              emitted=state.emitted | hit,
                              emit_depth=emit_depth,
                              frontier_count=jnp.sum(nxt, dtype=jnp.int32))

    def describe(self):
        how = "kernel" if self.expand_fn is not None else "reverse CSR"
        return f"PullStep[bottom-up: unvisited <- frontier bits ({how})]"

    def estimate(self, env):
        # the pull side reads the reverse adjacency of the UNVISITED set:
        # work shrinks as the traversal saturates the graph — exactly the
        # deep/wide regime where push degenerates
        unvis = max(float(env.num_vertices) - env.visited_rows, 0.0)
        frac = unvis / max(float(env.num_vertices), 1.0)
        b = frac * float(env.num_edges) * 8.0 + float(env.num_vertices) * 4.0
        if not self.deferred:
            b += float(env.num_edges) * 4.0       # emitted upkeep anyway
        if self.expand_fn is not None:
            b *= env.kernel_factor
        return OpCost(env.emitted_rows, b)


@dataclasses.dataclass(frozen=True)
class DirectionSwitch(Operator):
    """The direction-optimizing combinator: per level, a ``lax.cond`` picks
    the push or the pull operator by comparing the estimated work terms —
    frontier occupancy x avg out-degree (the push side's emitted edges)
    vs unvisited count x avg in-degree (the pull side's reverse-adjacency
    reads):

        pull  iff  alpha * n_f * avg_out > (V - visited) * avg_in
              and  beta * n_f >= V

    (Beamer's two thresholds; the second keeps shrunk tail frontiers on
    the push side.)  The average degrees are trace-time constants off the
    join view's shapes, so the whole predicate costs one popcount of the
    visited bitmap per level.  ``alpha``/``beta`` are owned by
    :class:`repro.planner.cost.CostConstants` (``pull_alpha`` /
    ``pull_beta``) so the calibrator can refit them; the planner stamps its
    constants' values onto the pipeline it prices.  The decision taken at
    every level is recorded in ``TraversalState.level_dirs`` and surfaces
    in ``BFSResult.level_dirs`` / the plan-store schema."""

    push: Operator
    pull: Operator
    alpha: float = 1.0
    beta: float = 64.0

    def _predicate(self, ctx, state):
        nv = state.vertex_depth.shape[0] or state.visited.shape[0]
        ej = float(_num_join(ctx))
        avg = ej / max(float(nv), 1.0)     # avg out == avg in over the view
        n_f = state.frontier_count
        if state.frontier_bits.shape[0] or state.vertex_depth.shape[0]:
            # dense/deferred frontier: the count is VERTICES — scale by
            # the average out-degree to get the push-side edge work
            m_f = n_f.astype(jnp.float32) * avg
        else:                              # positional frontier: the edge
            m_f = n_f.astype(jnp.float32)  # block IS m_f
        if state.vertex_depth.shape[0]:    # deferred steps keep the scalar
            unvisited = nv - state.visited_count
        else:
            unvisited = nv - jnp.sum(state.visited, dtype=jnp.int32)
        m_u = unvisited.astype(jnp.float32) * avg
        use_pull = self.alpha * m_f > m_u
        use_pull &= self.beta * n_f.astype(jnp.float32) >= float(nv)
        return use_pull

    def step(self, ctx, state):
        use_pull = self._predicate(ctx, state)
        if state.level_dirs.shape[0]:
            idx = jnp.minimum(state.depth, state.level_dirs.shape[0] - 1)
            state = state._replace(level_dirs=state.level_dirs.at[idx].set(
                use_pull.astype(jnp.int8)))
        narrow = (state.vertex_depth.shape[0]
                  and hasattr(self.push, "deferred_new")
                  and hasattr(self.pull, "deferred_new"))
        if narrow:
            # deferred dense steps: the cond exchanges ONE (V,) mask
            # instead of threading the whole traversal state through the
            # branch boundary
            new = jax.lax.cond(
                use_pull,
                lambda: self.pull.deferred_new(ctx, state),
                lambda: self.push.deferred_new(ctx, state))
            return _record_deferred(state, new)
        return jax.lax.cond(use_pull,
                            lambda s: self.pull.step(ctx, s),
                            lambda s: self.push.step(ctx, s), state)

    def describe(self):
        return (f"DirectionSwitch[a={self.alpha:g} b={self.beta:g}: "
                f"{self.push.describe()} | {self.pull.describe()}]")

    def predict(self, env: CostEnv) -> str:
        """The cost model's per-level decision (mirrors the runtime
        predicate on the sampled cardinalities): 'push' or 'pull'."""
        avg = float(env.num_edges) / max(float(env.num_vertices), 1.0)
        unvis = max(float(env.num_vertices) - env.visited_rows, 0.0)
        m_f = env.emitted_rows                 # edges out of the frontier
        m_u = unvis * avg
        n_f = env.frontier_rows
        if self.alpha * m_f > m_u and self.beta * n_f >= env.num_vertices:
            return "pull"
        return "push"

    def estimate(self, env):
        chosen = (self.pull if self.predict(env) == "pull"
                  else self.push).estimate(env)
        # the predicate itself: two degree reductions over (V,)
        return OpCost(chosen.rows,
                      chosen.bytes + float(env.num_vertices) * 2.0)


def _install_edge_frontier(ctx: Context, state: TraversalState,
                           nxt: PosBlock, visited: jax.Array,
                           ovf: jax.Array) -> TraversalState:
    """Shared positional-frontier bookkeeping (HybridStep and its pull
    twin): install the next edge block and mark its positions emitted at
    ``depth + 1``."""
    ej = _num_join(ctx)
    cap = state.frontier_pos.shape[0]
    valid = nxt.valid_mask()
    idx = jnp.where(valid, nxt.positions, ej)
    new = valid & ~state.emitted[jnp.minimum(nxt.positions, ej - 1)]
    emitted = state.emitted.at[idx].set(valid, mode="drop")
    emit_depth = state.emit_depth.at[jnp.where(new, nxt.positions, ej)].set(
        jnp.broadcast_to(state.depth + 1, (cap,)), mode="drop")
    return state._replace(frontier_pos=nxt.positions,
                          frontier_count=nxt.count, visited=visited,
                          emitted=emitted, emit_depth=emit_depth,
                          overflow=state.overflow | ovf)


@dataclasses.dataclass(frozen=True)
class HybridStep(Operator):
    """Direction-optimizing level: positional IndexJoin while the frontier
    is small, dense push once it covers > switch_frac of the vertices."""

    switch_frac: float = 0.05

    def step(self, ctx, state):
        ej = _num_join(ctx)
        nv = state.visited.shape[0]
        cap = state.frontier_pos.shape[0]
        threshold = max(1, int(nv * self.switch_frac))

        def sparse_step(frontier, visited):
            fvalid = frontier.valid_mask()
            targets = jnp.where(fvalid,
                                _join_dst_at(ctx, frontier.positions), -1)
            keep, visited = dedup_targets(targets, fvalid, visited)
            targets = jnp.where(keep, targets, -1)
            epos, total, ovf = _expand_join(ctx, targets, keep, cap)
            return PosBlock(epos, total), visited, ovf

        def dense_step(frontier, visited):
            fvalid = frontier.valid_mask()
            targets = _join_dst_at(ctx, frontier.positions)
            # boolean ⊕ (scatter-max): padded slots (clipped onto a real
            # vertex) must never UNSET a vertex another slot reached
            tgt_v = or_combine(jnp.zeros((nv,), bool),
                               jnp.clip(targets, 0, nv - 1), fvalid)
            tgt_v = tgt_v & ~visited
            visited = visited | tgt_v
            hit = _hit_mask(ctx, tgt_v)
            nxt = compact_mask(hit, cap, ej)
            ovf = jnp.sum(hit, dtype=jnp.int32) > cap
            return nxt, visited, ovf

        frontier = PosBlock(state.frontier_pos, state.frontier_count)
        nxt, visited, ovf = jax.lax.cond(
            state.frontier_count < threshold, sparse_step, dense_step,
            frontier, state.visited)
        return _install_edge_frontier(ctx, state, nxt, visited, ovf)

    def describe(self):
        return (f"DirectionOpt[<{self.switch_frac:g}V: IndexJoin[CSR] | "
                f"else BitmapStep]")

    def estimate(self, env):
        # the sparse branch is the positional loop body at capacity; the
        # dense branch is one bitmap push; emitted-mask upkeep either way
        sparse = env.frontier_cap * 36.0 + env.num_vertices * 5.0
        dense = float(env.num_edges) * 10.0 + float(env.num_vertices) * 3.0
        threshold = max(1.0, env.num_vertices * self.switch_frac)
        chosen = sparse if env.frontier_rows < threshold else dense
        return OpCost(env.emitted_rows, chosen + env.frontier_cap * 5.0)


@dataclasses.dataclass(frozen=True)
class HybridPullStep(Operator):
    """The pull twin of :class:`HybridStep`'s dense branch, for positional
    (edge-block) frontiers: rebuild the previous level's VERTEX set from
    the frontier edges' join sources, bottom-up test the unvisited set
    against it, then emit and compact exactly like the push branch — so a
    :class:`DirectionSwitch` over (HybridStep, HybridPullStep) is
    level-for-level state-identical to plain HybridStep."""

    def step(self, ctx, state):
        ej = _num_join(ctx)
        nv = state.visited.shape[0]
        cap = state.frontier_pos.shape[0]
        fvalid = (jnp.arange(cap, dtype=jnp.int32) < state.frontier_count)
        srcs = _join_src_at(ctx, state.frontier_pos)
        prev_v = or_combine(jnp.zeros((nv,), bool),
                            jnp.clip(srcs, 0, nv - 1), fvalid)
        tgt_v = _dense_pull(ctx, prev_v, state.visited)
        visited = state.visited | tgt_v
        hit = _hit_mask(ctx, tgt_v)
        nxt = compact_mask(hit, cap, ej)
        ovf = jnp.sum(hit, dtype=jnp.int32) > cap
        return _install_edge_frontier(ctx, state, nxt, visited, ovf)

    def describe(self):
        return "PullStep[bottom-up over reverse CSR -> edge block]"

    def estimate(self, env):
        # Only the bottom-up gather shrinks with the unvisited fraction.
        # Everything else is paid IN FULL every pull level: the positional
        # frontier keeps no vertex set between levels, so this step rebuilds
        # the previous-vertex set from scratch (a (V,) plane + a
        # frontier_cap scatter — the same per-row scatter factor as the
        # sparse positional branch), then runs the full-edge hit mask and
        # compaction exactly like the dense push.  The old estimate omitted
        # the rebuild and half the hit/compact work, pricing pull levels
        # ~2.5x under the push branch they replace — which kept
        # diropt_hybrid a near-tied candidate while the paired bench
        # measured it at 0.33-0.37x of its push-only counterpart.
        unvis = max(float(env.num_vertices) - env.visited_rows, 0.0)
        frac = unvis / max(float(env.num_vertices), 1.0)
        return OpCost(env.emitted_rows,
                      frac * float(env.num_edges) * 8.0
                      + env.frontier_cap * 36.0          # prev-set rebuild
                      + float(env.num_edges) * 10.0      # hit + compact
                      + float(env.num_vertices) * 6.0
                      + env.frontier_cap * 5.0)


@dataclasses.dataclass(frozen=True)
class EarlyMaterialize(Operator):
    """Fig. 3's per-level Materialize: turn the positional join output into
    value tuples (or full interleaved rows) IMMEDIATELY — the (3+N) gathers
    per level that the positional plan avoids.  ``with_next`` additionally
    carries the join-space next-vertex column (needed when direction='both'
    makes the next vertex ambiguous after folding to real positions)."""

    cols: Tuple[str, ...] = ()
    rows: bool = False
    with_next: bool = False

    def init(self, ctx, state, root):
        return self._materialize(ctx, state)

    def step(self, ctx, state):
        return self._materialize(ctx, state)

    def _materialize(self, ctx, state):
        pos_real = _to_real(ctx, state.frontier_pos)
        if self.rows:
            return state._replace(frontier_rows=ctx.rows.take_rows(pos_real))
        vals = ctx.table.take(pos_real, self.cols)
        if self.with_next:
            valid = state.frontier_pos < _num_join(ctx)
            vals["__next__"] = jnp.where(
                valid, _join_dst_at(ctx, state.frontier_pos), -1)
        return state._replace(frontier_vals=vals)

    def describe(self):
        if self.rows:
            return "Materialize[* full rows](heap read)"
        return f"Materialize[{', '.join(self.cols)}](EVERY level)"

    def estimate(self, env):
        width = (env.row_bytes if self.rows
                 else _cols_bytes(env, self.cols) + (4.0 if self.with_next
                                                    else 0.0))
        return OpCost(env.emitted_rows, env.frontier_cap * width)


@dataclasses.dataclass(frozen=True)
class AppendUnionAll(Operator):
    """The recursive UNION ALL: append the level's block to the working
    result, tagging every appended row with its BFS level.  ``init`` appends
    the seed block (level 0) when the pipeline is edge-seeded; ``step``
    appends level ``depth + step_tag_offset`` (offset 0 — and no seed append
    — for vertex-seeded pipelines that emit the current level inside the
    loop body)."""

    rep: str = "pos"            # 'pos' | 'vals' | 'rows'
    cols: Tuple[str, ...] = ()  # result columns for rep='vals'
    step_tag_offset: int = 1
    append_seed: bool = True

    def init(self, ctx, state, root):
        if not self.append_seed:
            return state
        return self._append(ctx, state, state.depth)

    def step(self, ctx, state):
        return self._append(ctx, state, state.depth + self.step_tag_offset)

    def _append(self, ctx, state, tag):
        if self.rep == "pos":
            block = PosBlock(_to_real(ctx, state.frontier_pos),
                             state.frontier_count)
            rpos, rcount, ovf = append_block(state.result_pos,
                                             state.result_count, block)
            rdepth = _tag_depths(state.result_depth, state.result_count,
                                 block.capacity, block.count, tag)
            return state._replace(result_pos=rpos, result_count=rcount,
                                  result_depth=rdepth,
                                  overflow=state.overflow | ovf)
        if self.rep == "vals":
            vals = {k: state.frontier_vals[k] for k in self.cols}
        else:
            vals = {"rows": state.frontier_rows}
        cap_r = state.result_depth.shape[0]
        bufs = state.result_vals
        if not bufs:     # first append allocates the result buffers
            bufs = {k: jnp.zeros((cap_r,) + v.shape[1:], v.dtype)
                    for k, v in vals.items()}
        bufs, rcount, ovf = append_values(bufs, state.result_count, vals,
                                          state.frontier_count, cap_r)
        block_cap = next(iter(vals.values())).shape[0]
        rdepth = _tag_depths(state.result_depth, state.result_count,
                             block_cap, state.frontier_count, tag)
        return state._replace(result_vals=bufs, result_count=rcount,
                              result_depth=rdepth,
                              overflow=state.overflow | ovf)

    def describe(self):
        return "UnionAll[append working table]"

    def estimate(self, env):
        width = {"pos": 4.0, "rows": float(env.row_bytes)}.get(
            self.rep, _cols_bytes(env, self.cols))
        # appended block + the per-row depth tag, at block capacity
        return OpCost(env.emitted_rows, env.frontier_cap * (width + 4.0))


@dataclasses.dataclass(frozen=True)
class ShardTargetExchange(Operator):
    """The distributed engine's shard-aware operator: union next-level
    target vertices across shards with ONE tiled ``all_gather`` per level
    (O(frontier) vertex ids — never values), then dedup replicated so every
    shard derives the identical next frontier."""

    axis: Any

    def step(self, ctx, state):
        cap = state.frontier_pos.shape[0]
        live = jnp.arange(cap, dtype=jnp.int32) < state.frontier_count
        tloc = jnp.where(
            live, _join_dst_at(ctx, state.frontier_pos), -1)
        gathered = jax.lax.all_gather(tloc, self.axis, tiled=True)
        gvalid = gathered >= 0
        keep, visited = dedup_targets(gathered, gvalid, state.visited)
        nxt, ovf = block_from_mask(gathered, keep, cap, -1)
        kmask = jnp.arange(cap, dtype=jnp.int32) < nxt.count
        return state._replace(targets=nxt.positions, keep=kmask,
                              frontier_count=nxt.count, visited=visited,
                              overflow=state.overflow | ovf)

    def describe(self):
        return f"AllGatherTargets[axis={self.axis!r}] -> VisitedDedup"

    def estimate(self, env):
        # one tiled all_gather of vertex ids + replicated dedup
        return OpCost(env.unique_rows,
                      env.frontier_cap * 18.0 + env.num_vertices * 5.0)


# ---------------------------------------------------------------------------
# finishers
# ---------------------------------------------------------------------------

def _drain_value_frontier(ctx, pipeline, state):
    """Fold the FINAL frontier's arrivals into the vertex accumulator.

    :class:`WeightedExpand` ⊕-combines the arrivals produced by the
    PREVIOUS expansion at the start of each step, so when the depth bound
    (rather than convergence) stops the loop, the last expansion's rows
    are in the result but their values are still sitting in
    ``frontier_val``.  The dense step combines in the same iteration it
    emits, so only the positional finisher needs this drain; it is a
    no-op on a converged (empty) frontier."""
    cap = state.frontier_pos.shape[0]
    nv = state.vertex_val.shape[0]
    sr = get_semiring(pipeline.semiring)
    slots = jnp.arange(cap, dtype=jnp.int32)
    valid = slots < state.frontier_count
    safe = jnp.clip(_join_dst_at(ctx, state.frontier_pos), 0, nv - 1)
    idx = jnp.where(valid, safe, nv)
    lvl = scatter_combine(sr, jnp.full((nv,), sr.identity, jnp.float32),
                          idx, state.frontier_val)
    received = or_combine(jnp.zeros((nv,), bool), idx, valid)
    return jnp.where(received, elem_combine(sr, state.vertex_val, lvl),
                     state.vertex_val)


@dataclasses.dataclass(frozen=True)
class LateMaterialize:
    """Fig. 4's single Materialize after the fixed point — the paper's core
    win: ALL output columns gathered exactly once, from positions."""

    cols: Tuple[str, ...]

    def finish(self, ctx, pipeline, state):
        values = ctx.table.take(state.result_pos, self.cols)
        vv = (_drain_value_frontier(ctx, pipeline, state)
              if pipeline.semiring != "reach" else None)
        return BFSResult(values, state.result_pos, state.result_count,
                         state.depth, state.overflow, state.result_depth,
                         vertex_values=vv)

    def describe(self):
        return (f"Materialize[{', '.join(self.cols)}]"
                "  <- ONE late gather, after the fixed point")

    def estimate(self, env):
        return OpCost(env.frontier_rows,
                      env.result_cap * (_cols_bytes(env, self.cols) + 4.0))


@dataclasses.dataclass(frozen=True)
class EmitTuples:
    """Tuple-pipeline finisher: the result was materialized level by level;
    positions are unavailable (all -1) — the Fig. 3 contract."""

    cols: Tuple[str, ...]

    def finish(self, ctx, pipeline, state):
        cap_r = state.result_depth.shape[0]
        values = {k: state.result_vals[k] for k in self.cols}
        return BFSResult(values, jnp.full((cap_r,), -1, jnp.int32),
                         state.result_count, state.depth, state.overflow,
                         state.result_depth)

    def describe(self):
        return f"Emit[{', '.join(self.cols)}](pre-materialized; positions=-1)"

    def estimate(self, env):
        return OpCost(env.frontier_rows, 0.0)   # already paid per level


@dataclasses.dataclass(frozen=True)
class ProjectRows:
    """Row-store finisher: project output columns back out of the gathered
    full rows; positions are unavailable (all -1)."""

    cols: Tuple[str, ...]

    def finish(self, ctx, pipeline, state):
        cap_r = state.result_depth.shape[0]
        values = ctx.rows.project(state.result_vals["rows"], self.cols)
        return BFSResult(values, jnp.full((cap_r,), -1, jnp.int32),
                         state.result_count, state.depth, state.overflow,
                         state.result_depth)

    def describe(self):
        return f"Project[{', '.join(self.cols)}](full rows)"

    def estimate(self, env):
        return OpCost(env.frontier_rows, env.result_cap * env.row_bytes)


@dataclasses.dataclass(frozen=True)
class CompactEmitted:
    """Bitmap-pipeline finisher: compact the emitted-edge mask into a
    position block, then late-materialize — the dense plan keeps the
    positional contract."""

    cols: Tuple[str, ...]

    def finish(self, ctx, pipeline, state):
        ej = _num_join(ctx)
        cap_r = pipeline.caps.result
        blk = compact_mask(state.emitted, cap_r, ej)
        pos_real = _to_real(ctx, blk.positions)
        values = ctx.table.take(pos_real, self.cols)
        overflow = state.overflow | (
            jnp.sum(state.emitted, dtype=jnp.int32) > cap_r)
        row_depths = jnp.where(
            blk.valid_mask(),
            state.emit_depth[jnp.minimum(blk.positions, ej - 1)], -1)
        dirs = state.level_dirs if state.level_dirs.shape[0] else None
        vv = state.vertex_val if pipeline.semiring != "reach" else None
        return BFSResult(values, pos_real, blk.count, state.depth, overflow,
                         row_depths, dirs, vertex_values=vv)

    def describe(self):
        return (f"Materialize[{', '.join(self.cols)}](Compact(emitted mask))"
                "  <- ONE late gather")

    def estimate(self, env):
        return OpCost(env.frontier_rows,
                      float(env.num_edges) * 2.0
                      + env.result_cap * (_cols_bytes(env, self.cols)
                                          + 4.0))


@dataclasses.dataclass(frozen=True)
class DeferredEmit:
    """Deferred-emission finisher (the diropt pipelines): the loop carried
    only per-vertex depths, so the emitted-edge mask is DERIVED here in one
    O(EJ) pass — a join edge is emitted iff its source vertex was
    discovered strictly before the last executed level — then compacted
    and late-materialized exactly like :class:`CompactEmitted` (identical
    row set, order and depths)."""

    cols: Tuple[str, ...]

    def finish(self, ctx, pipeline, state):
        ej = _num_join(ctx)
        cap_r = pipeline.caps.result
        vd = state.vertex_depth
        nv = vd.shape[0]
        if ctx.bidir:
            src_depth = jnp.concatenate([
                vd[jnp.clip(ctx.join_src, 0, nv - 1)],
                vd[jnp.clip(ctx.join_dst, 0, nv - 1)]])
        else:
            src_depth = vd[jnp.clip(ctx.join_src, 0, nv - 1)]
        emitted = (src_depth >= 0) & (src_depth < state.depth)
        blk = compact_mask(emitted, cap_r, ej)
        pos_real = _to_real(ctx, blk.positions)
        values = ctx.table.take(pos_real, self.cols)
        overflow = state.overflow | (
            jnp.sum(emitted, dtype=jnp.int32) > cap_r)
        row_depths = jnp.where(
            blk.valid_mask(),
            src_depth[jnp.minimum(blk.positions, ej - 1)], -1)
        dirs = state.level_dirs if state.level_dirs.shape[0] else None
        return BFSResult(values, pos_real, blk.count, state.depth, overflow,
                         row_depths, dirs)

    def describe(self):
        return (f"Materialize[{', '.join(self.cols)}]"
                "(Compact(vertex depths -> emitted))  <- ONE deferred pass")

    def estimate(self, env):
        # one (EJ,) depth gather + mask + compact, then the late gather
        return OpCost(env.frontier_rows,
                      float(env.num_edges) * 3.0
                      + env.result_cap * (_cols_bytes(env, self.cols)
                                          + 4.0))


@dataclasses.dataclass(frozen=True)
class TopLevelJoin:
    """The paper's Exp-3 rewriting: the recursion carried only (id, to); the
    payload columns come back through ONE top-level hash join on ``id``
    (realized as an inverse-permutation probe array).  On the row store the
    join re-gathers full rows — the rewrite cannot rescue a heap table."""

    cols: Tuple[str, ...]
    inner: Any
    use_rows: bool = False

    def finish(self, ctx, pipeline, state):
        slim = self.inner.finish(ctx, pipeline, state)
        if self.use_rows:
            e = ctx.rows.num_rows
            id_col = ctx.rows.column("id").astype(jnp.int32)  # strided scan
            probe = jnp.zeros((e,), jnp.int32).at[
                jnp.clip(id_col, 0, e - 1)].set(
                jnp.arange(e, dtype=jnp.int32), mode="drop")
        else:
            e = ctx.table.num_rows
            id_col = ctx.table.column("id")
            probe = jnp.zeros((e,), jnp.int32).at[id_col].set(
                jnp.arange(e, dtype=jnp.int32), mode="drop")
        cap_r = slim.positions.shape[0]
        live = jnp.arange(cap_r, dtype=jnp.int32) < slim.count
        ids = jnp.where(live, slim.values["id"].astype(jnp.int32), -1)
        pos = jnp.where(live, probe[jnp.clip(ids, 0, e - 1)], e)
        if self.use_rows:
            values = ctx.rows.project(ctx.rows.take_rows(pos), self.cols)
        else:
            values = ctx.table.take(pos, self.cols)
        return BFSResult(values, pos, slim.count, slim.depth, slim.overflow,
                         slim.row_depths, vertex_values=slim.vertex_values)

    def describe(self):
        return (f"HashJoin[id = cte.id](Hash(id -> pos), "
                f"{self.inner.describe()})")

    def estimate(self, env):
        inner = self.inner.estimate(env)
        cap_r = env.result_cap
        if self.use_rows:     # strided id scan + full-row re-gather
            b = float(env.num_edges) * env.row_bytes + cap_r * env.row_bytes
        else:                 # probe-array build + ONE late gather
            b = (float(env.num_edges) * 8.0
                 + cap_r * (_cols_bytes(env, self.cols) + 4.0))
        return OpCost(env.frontier_rows, inner.bytes + b)


@dataclasses.dataclass(frozen=True)
class RawPositions:
    """Return bare result positions (the distributed engine materializes
    shard-locally outside the driver)."""

    def finish(self, ctx, pipeline, state):
        return BFSResult({}, state.result_pos, state.result_count,
                         state.depth, state.overflow, state.result_depth)

    def describe(self):
        return "RawPositions[] (caller materializes shard-locally)"

    def estimate(self, env):
        return OpCost(env.frontier_rows, 0.0)


# ---------------------------------------------------------------------------
# the pipeline + the ONE fixed-point driver
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Pipeline:
    """A declarative recursive plan: seed, per-level operators, finisher.
    Hashable (all-static) so it can be a jit static argument."""

    name: str
    rep: str                 # 'pos' | 'vals' | 'rows' | 'dense'
    seed: Seed
    ops: Tuple[Operator, ...]
    finisher: Any
    caps: EngineCaps
    max_depth: int
    inclusive: bool = False        # cond: depth <= max_depth (dense engines)
    tracks_emitted: bool = False   # carries the (EJ,) emitted-edge mask
    tracks_vertex_depth: bool = False  # deferred emission: (V,) vertex depths
    tracks_switch: bool = False    # records per-level push/pull decisions
    semiring: str = "reach"        # value-plane workload; 'reach' = boolean
    #   BFS with zero-size value placeholders (bit-identical fast path)

    @property
    def carries_positions(self) -> bool:
        """The positions contract: see the module docstring."""
        return (self.rep in ("pos", "dense")
                or isinstance(self.finisher, TopLevelJoin))

    def render(self, root=0) -> str:
        """The Volcano tree of the ACTUAL composition (Fig. 3/4 audit)."""
        loop = "\n".join(f"    {op.describe()}" for op in self.ops)
        seed = self.seed.describe().replace("$root", str(root))
        return (f"{self.finisher.describe()}\n"
                f"  {self.name}(maxrec={self.max_depth})\n"
                f"    {seed}            (non-recursive child)\n"
                f"{loop}")


def _initial_state(pipeline: Pipeline, ctx: Context, num_vertices: int
                   ) -> TraversalState:
    cap_f, cap_r = pipeline.caps.frontier, pipeline.caps.result
    ej = _num_join(ctx)
    e = _num_real_rows(ctx)
    dense = pipeline.rep == "dense"
    track = pipeline.tracks_emitted
    deferred = pipeline.tracks_vertex_depth
    weighted = pipeline.semiring != "reach"
    sr = get_semiring(pipeline.semiring) if weighted else None
    use_result_pos = pipeline.rep == "pos" and not track
    n_levels = pipeline.max_depth + 2          # >= executed iterations
    i32z = jnp.zeros((), jnp.int32)
    return TraversalState(
        frontier_pos=(jnp.zeros((0,), jnp.int32) if dense
                      else jnp.full((cap_f,), ej, jnp.int32)),
        frontier_vals={},
        frontier_rows=jnp.zeros((0, 0), jnp.float32),
        frontier_count=i32z,
        # deferred pipelines carry ONLY the vertex-depth array: no target
        # block, no dedup mask, no per-row result buffers in the loop
        targets=(jnp.zeros((0,), jnp.int32) if deferred
                 else jnp.full((cap_f,), -1, jnp.int32)),
        keep=(jnp.zeros((0,), bool) if deferred
              else jnp.zeros((cap_f,), bool)),
        frontier_bits=(jnp.zeros((num_vertices,), bool)
                       if dense and not pipeline.tracks_vertex_depth
                       else jnp.zeros((0,), bool)),
        emitted=(jnp.zeros((ej,), bool) if track
                 else jnp.zeros((0,), bool)),
        emit_depth=(jnp.full((ej,), -1, jnp.int32) if track
                    else jnp.zeros((0,), jnp.int32)),
        visited=(jnp.zeros((0,), bool) if pipeline.tracks_vertex_depth
                 else jnp.zeros((num_vertices,), bool)),
        result_pos=(jnp.full((cap_r,), e, jnp.int32) if use_result_pos
                    else jnp.zeros((0,), jnp.int32)),
        result_vals={},
        result_depth=(jnp.zeros((0,), jnp.int32) if track or deferred
                      else jnp.full((cap_r,), -1, jnp.int32)),
        result_count=i32z,
        depth=i32z,
        overflow=jnp.zeros((), bool),
        vertex_depth=(jnp.full((num_vertices,), -1, jnp.int32)
                      if pipeline.tracks_vertex_depth
                      else jnp.zeros((0,), jnp.int32)),
        visited_count=i32z,
        level_dirs=(jnp.full((n_levels,), -1, jnp.int8)
                    if pipeline.tracks_switch
                    else jnp.zeros((0,), jnp.int8)),
        # the semiring value plane: zero-size placeholders for 'reach' keep
        # the boolean pipelines' loop state bit-identical to pre-value-plane
        frontier_val=(jnp.zeros((0,), jnp.float32) if not weighted
                      else jnp.full((num_vertices if dense else cap_f,),
                                    sr.identity, jnp.float32)),
        vertex_val=(jnp.full((num_vertices,), sr.identity, jnp.float32)
                    if weighted else jnp.zeros((0,), jnp.float32)),
    )


def fixed_point(pipeline: Pipeline, ctx: Context, root: jax.Array,
                num_vertices: int) -> BFSResult:
    """Run ANY pipeline to its fixed point: one ``jax.lax.while_loop``, the
    operator steps composed in order inside the body.  This is the single
    recursion driver behind every engine variant."""
    root = jnp.asarray(root, jnp.int32)
    state = _initial_state(pipeline, ctx, num_vertices)
    state = pipeline.seed.init(ctx, state, root)
    for op in pipeline.ops:
        state = op.init(ctx, state, root)

    limit = pipeline.max_depth + (1 if pipeline.inclusive else 0)

    def cond(s):
        return (s.frontier_count > 0) & (s.depth < limit)

    def body(s):
        for op in pipeline.ops:
            s = op.step(ctx, s)
        return s._replace(depth=s.depth + 1)

    state = jax.lax.while_loop(cond, body, state)
    return pipeline.finisher.finish(ctx, pipeline, state)


def fixed_point_batch(pipeline: Pipeline, ctx: Context, roots: jax.Array,
                      num_vertices: int) -> BFSResult:
    """Batched fixed point: the per-level operator steps are vmapped over a
    vector of roots inside ONE ``jax.lax.while_loop`` whose predicate is the
    explicit all-lanes-converged test — the loop exits as soon as EVERY
    lane's frontier has died (or hit its depth bound), so a reach-bucketed
    batch stops when its deepest root finishes instead of running to the
    global depth bound.  Lanes that converge early are frozen (their carry
    is masked), so lane ``i`` of the result is bit-identical to
    :func:`fixed_point` on ``roots[i]``."""
    roots = jnp.asarray(roots, jnp.int32)

    def init_one(root):
        state = _initial_state(pipeline, ctx, num_vertices)
        state = pipeline.seed.init(ctx, state, root)
        for op in pipeline.ops:
            state = op.init(ctx, state, root)
        return state

    state = jax.vmap(init_one)(roots)
    limit = pipeline.max_depth + (1 if pipeline.inclusive else 0)

    def lane_active(s):
        return (s.frontier_count > 0) & (s.depth < limit)

    def cond(s):
        return jnp.any(lane_active(s))      # all-lanes-converged early exit

    def step_one(s):
        for op in pipeline.ops:
            s = op.step(ctx, s)
        return s._replace(depth=s.depth + 1)

    def body(s):
        active = lane_active(s)             # (B,)
        nxt = jax.vmap(step_one)(s)

        def freeze(new, old):
            mask = active.reshape((-1,) + (1,) * (new.ndim - 1))
            return jnp.where(mask, new, old)

        return jax.tree_util.tree_map(freeze, nxt, s)

    state = jax.lax.while_loop(cond, body, state)
    return jax.vmap(lambda s: pipeline.finisher.finish(ctx, pipeline, s)
                    )(state)


_execute_impl = jax.jit(fixed_point,
                        static_argnames=("pipeline", "num_vertices"))


def execute(pipeline: Pipeline, ctx: Context, root, num_vertices: int
            ) -> BFSResult:
    """Jitted single-root pipeline execution."""
    return _execute_impl(pipeline, ctx, jnp.asarray(root, jnp.int32),
                         num_vertices)


_batch_impl = jax.jit(fixed_point_batch,
                      static_argnames=("pipeline", "num_vertices"))


def execute_batch(pipeline: Pipeline, ctx: Context, roots,
                  num_vertices: int) -> BFSResult:
    """vmap-batched multi-root execution: ONE jitted XLA dispatch runs the
    whole batch (the serving path — many users' roots per call), through
    :func:`fixed_point_batch` so the dispatch stops when all lanes have
    converged.  Returns a BFSResult whose arrays carry a leading batch
    dimension."""
    roots = jnp.asarray(roots, jnp.int32)
    return _batch_impl(pipeline, ctx, roots, num_vertices)


# ---------------------------------------------------------------------------
# bit-parallel multi-query traversal (MS-BFS)
# ---------------------------------------------------------------------------

# The dense engines carry (V,)-sized boolean planes; the multiquery engine
# widens the ELEMENT TYPE instead of vmapping — one uint32 word per vertex
# packs up to 32 concurrent roots, and a single dense sweep advances every
# lane at once (Then et al., "The More the Merrier").  jnp is x32 by
# default, so the word is uint32; enable x64 before asking for wider words.
_WORD_DTYPE = jnp.uint32
WORD_LANES = 32


class MultiQueryState(NamedTuple):
    """The word-sweep loop carry.  No (lanes, V) plane lives in the loop:
    per-lane vertex depths are reconstructed AFTER the fixed point from the
    per-level new-bits snapshots (``level_words[d]`` holds the word of
    lanes that discovered each vertex at depth ``d`` — bits are set at most
    once per (lane, vertex), so the first set level IS the BFS depth)."""

    frontier_word: jax.Array   # (V,) uint32: lane bits in the frontier
    visited_word: jax.Array    # (V,) uint32: lane bits ever discovered
    level_words: jax.Array     # (max_levels, V) uint32: new bits per level
    lane_depth: jax.Array      # (lanes,) int32: levels executed per lane
    active: jax.Array          # () uint32: lanes still traversing
    depth: jax.Array           # () int32: levels executed (max over lanes)


def _segment_or(words: jax.Array, indptr: jax.Array,
                num_seg: int) -> jax.Array:
    """Per-segment bitwise OR of ``words`` (grouped by segment, boundaries
    in ``indptr``).  JAX scatters have no OR mode, so the dst-grouped
    reduce runs as ONE log-depth segmented associative scan over
    (segment-start flag, word) pairs — the classic segmented-scan combine:
    a start flag on the right operand resets the accumulation."""
    e = words.shape[0]
    if e == 0:
        return jnp.zeros((num_seg,), words.dtype)
    starts = indptr[:-1]
    # a start at position e (empty trailing segments) must not flag e-1
    flags = jnp.zeros((e,), bool).at[
        jnp.where(starts < e, starts, e)].set(True, mode="drop")

    def comb(a, b):
        af, av = a
        bf, bv = b
        return af | bf, jnp.where(bf, bv, av | bv)

    _, acc = jax.lax.associative_scan(comb, (flags, words))
    seg = acc[jnp.clip(indptr[1:] - 1, 0, e - 1)]
    return jnp.where(indptr[1:] > indptr[:-1], seg,
                     jnp.zeros((), words.dtype))


def _word_gather(ctx: Context, frontier_word: jax.Array, nv: int
                 ) -> jax.Array:
    """One packed-word level: for every vertex, the OR of its in-neighbors'
    frontier words (the MS-BFS analogue of :func:`_dense_pull`'s membership
    test, over all 32 lanes at once).  Needs dst-grouped edge orders:
    ``ctx.rcsr`` groups the join edges by ``join_dst`` in every direction
    view; the fused bidirectional view adds the backward orientation
    (grouped by ``join_src``) through ``ctx.csr``."""
    src = jnp.clip(ctx.join_src, 0, nv - 1)
    dst = jnp.clip(ctx.join_dst, 0, nv - 1)
    if ctx.bidir:
        fwd = _segment_or(frontier_word[src[ctx.rcsr.perm]],
                          ctx.rcsr.indptr, nv)
        bwd = _segment_or(frontier_word[dst[ctx.csr.perm]],
                          ctx.csr.indptr, nv)
        return fwd | bwd
    if ctx.rcsr is None:
        raise ValueError(
            "the multiquery word sweep needs dst-grouped edges (the "
            "reverse CSR); call Dataset.ensure_reverse() before dispatch")
    return _segment_or(frontier_word[src[ctx.rcsr.perm]],
                       ctx.rcsr.indptr, nv)


def _or_reduce(words: jax.Array) -> jax.Array:
    return jnp.bitwise_or.reduce(words)


@dataclasses.dataclass(frozen=True)
class MultiQuerySeed(Operator):
    """Scatter each root's lane bit into the packed frontier/visited words
    (lane bits are distinct, so a scatter-ADD of colliding roots IS the
    OR).  ``kind='dense'`` so the cost model prices levels with the dense
    engines' vertex-frontier accounting."""

    lanes: int = WORD_LANES
    kind: str = "dense"

    def describe(self):
        return f"MultiQuerySeed[{self.lanes} lane bits -> (V,) word]"

    def estimate(self, env):
        # two (V,) word planes + the snapshot row + the lane-bit scatter
        return OpCost(float(self.lanes),
                      float(env.num_vertices) * 12.0 + self.lanes * 8.0)


@dataclasses.dataclass(frozen=True)
class MultiQueryWordSweep(Operator):
    """One bit-parallel level: gather every in-neighbor's frontier word,
    segment-OR by destination, mask by ``~visited`` and the active-lane
    word.  Per-level cost is lane-count-INDEPENDENT (that is the whole
    point): E word gathers + the log-depth segmented scan + three (V,)
    word-plane updates, where the vmapped alternative pays its full
    per-level cost once per lane."""

    lanes: int = WORD_LANES

    def describe(self):
        return (f"MultiQueryWordSweep[{self.lanes} lanes/word: "
                "segment-OR pull, per-lane freeze]")

    def estimate(self, env):
        # (E,) word gather + segmented-scan passes (log-depth, priced as a
        # small linear factor) + frontier/visited/snapshot word planes
        return OpCost(env.emitted_rows,
                      float(env.num_edges) * 16.0
                      + float(env.num_vertices) * 16.0)


@dataclasses.dataclass(frozen=True)
class MultiQueryEmit:
    """Per-lane deferred emission: reconstruct each lane's (V,) vertex
    depths from the level snapshots, then derive/compact/materialize the
    emitted edge set exactly like :class:`DeferredEmit` — lane ``l`` of the
    result is row-for-row identical (rows, order, ``row_depths``) to the
    sequential deferred-emission engines on ``roots[l]``."""

    cols: Tuple[str, ...]
    lanes: int = WORD_LANES

    def finish(self, ctx, pipeline, state):
        raise NotImplementedError(
            "multiquery pipelines run through execute_multiquery, not the "
            "scalar fixed_point driver")

    def describe(self):
        return (f"Materialize[{', '.join(self.cols)}]"
                f"(Compact(lane depths -> emitted)) x{self.lanes} lanes")

    def estimate(self, env):
        # per lane: the level->depth reconstruction, one (EJ,) depth
        # gather + mask + compact, and the late materialize
        per_lane = (float(env.num_edges) * 3.0
                    + float(env.num_vertices) * 2.0
                    + env.result_cap * (_cols_bytes(env, self.cols) + 4.0))
        return OpCost(env.frontier_rows, self.lanes * per_lane)


def _multiquery_finish(ctx: Context, pipeline: Pipeline,
                       state: "MultiQueryState", lane_ids: jax.Array,
                       nv: int) -> BFSResult:
    """All-lanes deferred emission in ONE batched pass.

    The emitted-edge test stays bit-parallel: per level, mask the new-bits
    snapshot by the word of lanes whose executed depth exceeds that level,
    OR the levels together into one (V,) emit word, and gather it through
    the join sources — ``emitted_word[j]``'s bits are exactly the lanes
    for which :class:`DeferredEmit` would emit edge ``j``.

    Compaction is the part that cannot stay packed (each lane compacts to
    its own slots).  A vmapped :func:`compact_mask` lowers to per-lane
    ``nonzero`` scatters that dominate the whole dispatch on CPU, so
    instead: one (EJ, lanes) prefix-count cumsum, then the i-th set
    position per lane is recovered by a shared binary search over the
    prefix column — all gathers, no scatters.  Positions come out
    ascending per lane with the join-space sentinel in padding slots, the
    exact :func:`compact_mask` layout."""
    ej = _num_join(ctx)
    cap_r = pipeline.caps.result
    lanes = lane_ids.shape[0]
    n_levels = state.level_words.shape[0]
    # word of lanes for which a vertex discovered at level d is a frontier
    # vertex (d < that lane's executed depth)
    lane_bits = jnp.left_shift(_WORD_DTYPE(1), lane_ids)
    level_mask = jnp.sum(
        jnp.where(jnp.arange(n_levels, dtype=jnp.int32)[:, None]
                  < state.lane_depth[None, :],
                  lane_bits[None, :], 0),
        axis=1, dtype=_WORD_DTYPE)                           # (NL,)
    emit_v = jnp.bitwise_or.reduce(
        state.level_words & level_mask[:, None], axis=0)     # (V,)
    src = jnp.clip(ctx.join_src, 0, nv - 1)
    if ctx.bidir:
        join_v = jnp.concatenate(
            [src, jnp.clip(ctx.join_dst, 0, nv - 1)])        # (EJ,)
    else:
        join_v = src
    emitted_word = emit_v[join_v]                            # (EJ,)
    # per-lane prefix counts, lanes as the vector axis
    bits = ((emitted_word[:, None] >> lane_ids[None, :])
            & _WORD_DTYPE(1)).astype(jnp.int32)              # (EJ, lanes)
    prefix = jnp.cumsum(bits, axis=0)                        # (EJ, lanes)
    total = prefix[-1]                                       # (lanes,)
    count = jnp.minimum(total, cap_r)
    overflow = total > cap_r
    # i-th emitted position per lane = first j with prefix[j] == i+1:
    # one vectorized binary search over the (cap_r, lanes) grid
    want = jnp.arange(1, cap_r + 1, dtype=jnp.int32)[:, None]
    lane_cols = jnp.arange(lanes, dtype=jnp.int32)[None, :]
    lo = jnp.zeros((cap_r, lanes), jnp.int32)
    hi = jnp.full((cap_r, lanes), ej, jnp.int32)
    for _ in range(max(ej, 1).bit_length()):
        mid = (lo + hi) // 2
        val = jnp.where(mid < ej,
                        prefix[jnp.minimum(mid, ej - 1), lane_cols],
                        jnp.int32(1 << 30))
        ge = val >= want
        lo = jnp.where(ge, lo, mid + 1)
        hi = jnp.where(ge, mid, hi)
    positions = lo.T                                         # (lanes, cap_r)
    pos_real = _to_real(ctx, positions)
    values = ctx.table.take(pos_real, pipeline.finisher.cols)
    valid = (jnp.arange(cap_r, dtype=jnp.int32)[None, :] < count[:, None])
    # row depth = the source vertex's per-lane BFS level; recover it from
    # the level snapshots at just the compacted positions (each (lane,
    # vertex) bit is set in at most ONE level, so the overwrite is exact)
    v_at = join_v[jnp.minimum(positions, ej - 1)]            # (lanes, cap_r)
    row_depths = jnp.full((lanes, cap_r), -1, jnp.int32)
    for d in range(n_levels):
        hit = ((state.level_words[d][v_at] >> lane_ids[:, None])
               & _WORD_DTYPE(1)).astype(bool)
        row_depths = jnp.where(hit, jnp.int32(d), row_depths)
    row_depths = jnp.where(valid, row_depths, -1)
    return BFSResult(values, pos_real, count, state.lane_depth, overflow,
                     row_depths)


def multiquery_fixed_point(pipeline: Pipeline, ctx: Context,
                           roots: jax.Array, num_vertices: int,
                           lane_limits: jax.Array) -> BFSResult:
    """The MS-BFS driver: ONE ``jax.lax.while_loop`` advances up to 32
    packed lanes per level.

    Per-lane convergence freezing and depth caps live in the ``active``
    word: a lane leaves it when its frontier bits die or its depth cap
    binds, its bits stop propagating, and its executed-level counter
    freezes — so lane ``l`` of the result is row-identical to the scalar
    driver on ``roots[l]`` with ``max_depth=lane_limits[l]``.
    ``lane_limits`` come from the serving layer's reach buckets (clamped
    to the query's ``max_depth``; estimates never bind below a lane's
    natural convergence depth, so capping is semantics-preserving)."""
    nv = num_vertices
    lanes = roots.shape[0]
    if lanes > WORD_LANES:
        raise ValueError(f"multiquery packs at most {WORD_LANES} roots per "
                         f"{_WORD_DTYPE.dtype.name} word, got {lanes}")
    roots = jnp.clip(jnp.asarray(roots, jnp.int32), 0, nv - 1)
    lane_ids = jnp.arange(lanes, dtype=_WORD_DTYPE)
    lane_bits = jnp.left_shift(_WORD_DTYPE(1), lane_ids)
    # distinct bits per lane: scatter-ADD of colliding roots == OR
    root_word = jnp.zeros((nv,), _WORD_DTYPE).at[roots].add(lane_bits)
    limit = pipeline.max_depth + (1 if pipeline.inclusive else 0)
    bonus = 1 if pipeline.inclusive else 0
    lane_limit = (jnp.minimum(jnp.asarray(lane_limits, jnp.int32),
                              pipeline.max_depth) + bonus)
    n_levels = limit + 1                      # snapshot rows: seed + levels
    level_words = jnp.zeros((n_levels, nv), _WORD_DTYPE).at[0].set(root_word)
    active0 = jnp.sum(jnp.where(lane_limit > 0, lane_bits, 0),
                      dtype=_WORD_DTYPE)
    state = MultiQueryState(
        frontier_word=root_word, visited_word=root_word,
        level_words=level_words,
        lane_depth=jnp.zeros((lanes,), jnp.int32),
        active=active0, depth=jnp.zeros((), jnp.int32))

    def cond(s):
        return (s.active != 0) & (s.depth < limit)

    def body(s):
        gathered = _word_gather(ctx, s.frontier_word, nv)
        new = gathered & ~s.visited_word & s.active
        visited = s.visited_word | new
        depth = s.depth + 1
        # lanes in the active word executed this level
        ran = ((s.active >> lane_ids) & _WORD_DTYPE(1)).astype(jnp.int32)
        lane_depth = s.lane_depth + ran
        # freeze: frontier died (no new bits anywhere) or depth cap bound
        alive = _or_reduce(new)
        within = jnp.sum(jnp.where(lane_depth < lane_limit, lane_bits, 0),
                         dtype=_WORD_DTYPE)
        return MultiQueryState(
            frontier_word=new, visited_word=visited,
            level_words=s.level_words.at[depth].set(new),
            lane_depth=lane_depth, active=s.active & alive & within,
            depth=depth)

    state = jax.lax.while_loop(cond, body, state)
    return _multiquery_finish(ctx, pipeline, state, lane_ids, nv)


_multiquery_impl = jax.jit(multiquery_fixed_point,
                           static_argnames=("pipeline", "num_vertices"))


def execute_multiquery(pipeline: Pipeline, ctx: Context, roots,
                       num_vertices: int,
                       lane_limits=None) -> BFSResult:
    """Jitted bit-parallel multi-root execution: ONE dense word sweep
    answers up to 32 roots.  Returns a BFSResult with a leading
    ``len(roots)`` lane dimension, row-for-row equal per lane to the
    sequential deferred-emission engines.  ``lane_limits`` (optional,
    (lanes,) int32) caps each lane's executed depth — the serving layer
    passes per-lane reach-bucket depth estimates; ``None`` means every
    lane runs to the query's ``max_depth``."""
    roots = jnp.asarray(roots, jnp.int32)
    if lane_limits is None:
        lane_limits = jnp.full((roots.shape[0],), pipeline.max_depth,
                               jnp.int32)
    return _multiquery_impl(pipeline, ctx, roots, num_vertices,
                            jnp.asarray(lane_limits, jnp.int32))
