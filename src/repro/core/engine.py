"""Query layer: paper experiment queries dispatched onto operator pipelines.

A ``RecursiveQuery`` describes the SQL of §5.1 (Listings 1.1/1.2/1.3):
which payload columns exist, what the recursion carries, whether the Exp-3
rewrite is applied, which engine executes it, and the traversal
``direction``.  Engine dispatch is a *plan-builder registry*
(:data:`PLAN_BUILDERS`): every engine name maps to a function producing a
declarative :class:`~repro.core.operators.Pipeline`, and every pipeline runs
through the single shared :func:`~repro.core.operators.fixed_point` driver.

``plan_repr`` renders the Volcano tree *derived from the actual operator
composition* (``Pipeline.render``), so the mapping onto the paper's
Fig. 3/4 operator trees is auditable rather than hand-maintained:

* Fig. 4 (PRecursive)  → Seed → ReadCol → VisitedDedup → CSRIndexJoin →
  AppendUnionAll, finished by one LateMaterialize;
* Fig. 3 (TRecursive)  → the same loop + EarlyMaterialize every level,
  finished by EmitTuples;
* PostgreSQL baseline  → SeqScan seed + ScanHashJoin + full-row gathers.

Serving path: :func:`run_query_batch` vmaps the driver over a vector of
roots — ONE jitted XLA dispatch answers a whole batch of users' traversal
queries (the multi-tenant fan-out the ROADMAP targets).
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Callable, Dict, Literal, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import faultinject as _fault
from repro.obs import trace as _trace

from .bitmap import (bitmap_plan, diropt_hybrid_plan, diropt_plan,
                     hybrid_plan, multiquery_plan, weighted_bitmap_plan)
from .csr import CSRIndex, build_csr, merged_indptr
from .operators import WORD_LANES, BFSResult, Context, EngineCaps, \
    Pipeline, execute, execute_batch, execute_multiquery
from .recursive import (DIRECTIONS, precursive_plan, rowstore_plan,
                        rowstore_rewrite_plan, trecursive_plan,
                        trecursive_rewrite_plan, weighted_precursive_plan)
from .semiring import WORKLOADS
from .table import ColumnTable, RowTable, payload_names

EngineName = Literal["precursive", "trecursive", "rowstore", "rowstore_index",
                     "bitmap", "hybrid", "trecursive_rewrite",
                     "rowstore_rewrite", "rowstore_index_rewrite",
                     "diropt", "diropt_hybrid", "multiquery"]

ENGINE_NAMES: tuple[str, ...] = (
    "precursive", "trecursive", "rowstore", "rowstore_index", "bitmap",
    "hybrid", "trecursive_rewrite", "rowstore_rewrite",
    "rowstore_index_rewrite", "diropt", "diropt_hybrid")

# the bit-parallel MS-BFS engine is a BATCH engine: one dispatch answers up
# to 32 roots, so it is priced per coalesced batch and only becomes a
# candidate when the planner is handed a lane count (> 1).  It deliberately
# stays OUT of ENGINE_NAMES — the single-root enumeration suites, parity
# loops and EXPLAIN listings iterate that tuple.
MULTIQUERY_ENGINE = "multiquery"

# the direction-optimizing engines (per-level push/pull switch) and their
# push-only counterparts — parity suites assert row-for-row equality along
# these pairs, and the perf gate compares diropt cells against the best
# PUSH_ENGINE cell
DIROPT_ENGINE_NAMES: tuple[str, ...] = ("diropt", "diropt_hybrid")
PUSH_COUNTERPART = {"diropt": "bitmap", "diropt_hybrid": "hybrid"}

Direction = Literal["outbound", "inbound", "both"]


@dataclasses.dataclass(frozen=True)
class RecursiveQuery:
    """One recursive CTE query instance (a paper experiment cell)."""

    engine: EngineName
    max_depth: int
    payload_cols: int                 # the paper's N
    caps: EngineCaps
    dedup: bool = True                # BFS semantics (UNION ALL if False)
    direction: Direction = "outbound"
    workload: str = "reach"           # semiring name ('reach' = boolean BFS)
    weight_col: Optional[str] = None  # edge-weight column (weighted only)
    lanes: int = 1                    # coalesced roots per dispatch
    #   (> 1 only for the bit-parallel `multiquery` engine: the planner
    #   prices that engine per coalesced batch, and the serving layer packs
    #   up to WORD_LANES in-flight roots into one word-sweep dispatch)

    @property
    def out_cols(self) -> tuple[str, ...]:
        return ("id", "from", "to", "name",
                *payload_names(self.payload_cols))


# the engines that can carry the semiring value plane; every other engine
# is skipped by the planner for weighted workloads (with a recorded reason)
WEIGHTED_ENGINE_NAMES: tuple[str, ...] = ("precursive", "bitmap")


# ---------------------------------------------------------------------------
# plan-builder registry: engine name -> RecursiveQuery -> Pipeline
# ---------------------------------------------------------------------------

PLAN_BUILDERS: Dict[str, Callable[[RecursiveQuery], Pipeline]] = {
    "precursive": lambda q: precursive_plan(
        q.caps, q.max_depth, q.out_cols, q.dedup, q.direction),
    "trecursive": lambda q: trecursive_plan(
        q.caps, q.max_depth, q.out_cols, q.dedup, q.direction),
    "rowstore": lambda q: rowstore_plan(
        q.caps, q.max_depth, q.out_cols, q.dedup, use_index=False,
        direction=q.direction),
    "rowstore_index": lambda q: rowstore_plan(
        q.caps, q.max_depth, q.out_cols, q.dedup, use_index=True,
        direction=q.direction),
    "bitmap": lambda q: bitmap_plan(
        q.caps, q.max_depth, q.out_cols, q.direction),
    "hybrid": lambda q: hybrid_plan(
        q.caps, q.max_depth, q.out_cols, direction=q.direction),
    "trecursive_rewrite": lambda q: trecursive_rewrite_plan(
        q.caps, q.max_depth, q.out_cols, q.dedup, q.direction),
    "rowstore_rewrite": lambda q: rowstore_rewrite_plan(
        q.caps, q.max_depth, q.out_cols, q.dedup, use_index=False,
        direction=q.direction),
    "rowstore_index_rewrite": lambda q: rowstore_rewrite_plan(
        q.caps, q.max_depth, q.out_cols, q.dedup, use_index=True,
        direction=q.direction),
    "diropt": lambda q: diropt_plan(
        q.caps, q.max_depth, q.out_cols, q.direction),
    "diropt_hybrid": lambda q: diropt_hybrid_plan(
        q.caps, q.max_depth, q.out_cols, direction=q.direction),
    "multiquery": lambda q: multiquery_plan(
        q.caps, q.max_depth, q.out_cols, q.direction,
        lanes=max(getattr(q, "lanes", 1), 1)),
}


def build_plan(q: RecursiveQuery) -> Pipeline:
    workload = getattr(q, "workload", "reach")
    if workload != "reach":
        if workload not in WORKLOADS:
            raise ValueError(f"unknown workload {workload!r}; "
                             f"known: {WORKLOADS}")
        if q.engine == "precursive":
            return weighted_precursive_plan(q.caps, q.max_depth, q.out_cols,
                                            workload, q.direction)
        if q.engine == "bitmap":
            return weighted_bitmap_plan(q.caps, q.max_depth, q.out_cols,
                                        workload, q.direction)
        raise ValueError(
            f"engine {q.engine!r} has no value plane; weighted workloads "
            f"run on {WEIGHTED_ENGINE_NAMES}")
    try:
        builder = PLAN_BUILDERS[q.engine]
    except KeyError:
        raise ValueError(f"unknown engine {q.engine!r}; "
                         f"known: {ENGINE_NAMES}") from None
    return builder(q)


def positions_available(engine: str) -> bool:
    """The positions contract, derived from the engine's actual pipeline:
    True iff ``BFSResult.positions`` holds real edge positions."""
    q = RecursiveQuery(engine=engine, max_depth=1, payload_cols=0,
                       caps=EngineCaps(1, 1))
    return build_plan(q).carries_positions


@dataclasses.dataclass(frozen=True)
class Dataset:
    """A prepared graph: columnar + row layouts + the join index.

    Direction views are built on first use and cached on the instance.
    The reverse CSR (over ``to``) serves THREE consumers — ``inbound``
    traversal, the pull-mode operators' bottom-up gathers, and the fused
    ``both`` view — so ``direction='both'`` adds only one merged (V+1)
    indptr on top of it: E-scale memory, not the old doubled-2E edge
    view (see :func:`~repro.core.csr.expand_frontier_both`)."""

    table: ColumnTable
    rows: RowTable
    csr: CSRIndex
    num_vertices: int
    rcsr: CSRIndex | None = None           # reverse CSR (over `to`)
    both_indptr: object = None             # (V+1,) merged out+in indptr
    stats_cache: dict | None = None        # direction -> GraphStats
    weights_cache: dict | None = None      # weight_col -> (E,) f32 weights

    @classmethod
    def prepare(cls, table: ColumnTable, num_vertices: int) -> "Dataset":
        return cls(table=table, rows=RowTable.from_column_table(table),
                   csr=build_csr(table.column("from"), num_vertices),
                   num_vertices=num_vertices)

    def ensure_reverse(self) -> None:
        """Build + cache the reverse CSR.  ``inbound``/``both`` call this
        automatically; pull-KERNEL users on an outbound-only dataset opt
        in explicitly (the default XLA pull falls back to a natural-order
        formulation when the reverse CSR is absent, so plain outbound
        traffic never pays the extra O(E log E) build)."""
        if self.rcsr is None:
            object.__setattr__(self, "rcsr", build_csr(
                self.table.column("to"), self.num_vertices))

    def ensure_direction(self, direction: str) -> None:
        if direction not in DIRECTIONS:
            raise ValueError(f"unknown direction {direction!r}")
        if direction in ("inbound", "both"):
            self.ensure_reverse()
        if direction == "both" and self.both_indptr is None:
            object.__setattr__(self, "both_indptr",
                               merged_indptr(self.csr, self.rcsr))

    def edge_weights(self, weight_col: str) -> jax.Array:
        """The (E,) float32 ⊗-weight column in real position order — the
        edge-weight positional column of the weighted workloads.  Converted
        once per column and cached on the instance (same array object every
        call, so jitted dispatches keep hitting their compile cache)."""
        cache = self.weights_cache
        if cache is None:
            cache = {}
            object.__setattr__(self, "weights_cache", cache)
        if weight_col not in cache:
            if weight_col not in self.table.names:
                raise ValueError(f"unknown weight column {weight_col!r}; "
                                 f"table has {self.table.names}")
            col = self.table.column(weight_col)
            if col.ndim != 1:
                raise ValueError(
                    f"weight column {weight_col!r} must be 1-D, "
                    f"got shape {tuple(col.shape)}")
            cache[weight_col] = jnp.asarray(col, jnp.float32)
        return cache[weight_col]

    def context(self, direction: str = "outbound",
                weight_col: Optional[str] = None) -> Context:
        """The direction-resolved join view the operators run against.
        ``weight_col`` attaches the edge-weight positional column (weighted
        workloads; None for all-ones weights is expressed by the operators
        themselves, so reach contexts carry no weight array at all)."""
        self.ensure_direction(direction)
        w = self.edge_weights(weight_col) if weight_col is not None else None
        if direction == "inbound":
            return Context(table=self.table, rows=self.rows, csr=self.rcsr,
                           join_src=self.table.column("to"),
                           join_dst=self.table.column("from"),
                           rcsr=self.csr, edge_weights=w)
        if direction == "both":
            return Context(table=self.table, rows=self.rows, csr=self.csr,
                           join_src=self.table.column("from"),
                           join_dst=self.table.column("to"),
                           rcsr=self.rcsr, both_indptr=self.both_indptr,
                           bidir=True, edge_weights=w)
        return Context(table=self.table, rows=self.rows, csr=self.csr,
                       join_src=self.table.column("from"),
                       join_dst=self.table.column("to"), rcsr=self.rcsr,
                       edge_weights=w)

    def edge_view_bytes(self, direction: str = "outbound") -> int:
        """Bytes of the index arrays one direction's join view ADDS beyond
        the always-built outbound CSR (the benchmark's fused-CSR memory
        audit).  ``both`` must come out E-scale: the reverse CSR (shared
        with ``inbound`` and the pull path) plus ONE merged (V+1) indptr —
        the old doubled view added three 2E arrays on top of the same
        baseline."""
        self.ensure_direction(direction)

        def nbytes(a):
            return int(np.asarray(a).size * 4)

        if direction == "outbound":
            return nbytes(self.csr.perm) + nbytes(self.csr.indptr)
        rev = nbytes(self.rcsr.perm) + nbytes(self.rcsr.indptr)
        if direction == "inbound":
            return rev
        return rev + nbytes(self.both_indptr)

    def stats(self, direction: str = "outbound"):
        """Planner statistics hook: per-direction
        :class:`~repro.planner.stats.GraphStats` (degree histogram, sampled
        frontier-growth profile, density/shape flags), computed once and
        cached on the instance like the direction views."""
        cache = self.stats_cache
        if cache is None:
            cache = {}
            object.__setattr__(self, "stats_cache", cache)
        if direction not in cache:
            from repro.planner.stats import compute_stats
            with _trace.trace_span("stats", direction=direction):
                cache[direction] = compute_stats(self, direction)
        return cache[direction]


def query_context(q: RecursiveQuery, ds: Dataset) -> Context:
    """The join view a query runs against: direction-resolved, with the
    edge-weight column attached for weighted workloads."""
    wc = q.weight_col if getattr(q, "workload", "reach") != "reach" else None
    return ds.context(q.direction, weight_col=wc)


def run_query(q: RecursiveQuery, ds: Dataset, root: int) -> BFSResult:
    """Execute one query through the shared fixed-point driver.

    With a tracer installed (:func:`repro.obs.trace.set_tracer`) the
    dispatch is wrapped in a span and per-level traversal events are
    derived from the result — the traced path synchronizes (tracing is an
    enabled-only cost); the untraced path stays fully async."""
    plan = build_plan(q)
    t = _trace.current_tracer()
    if t is None:
        return execute(plan, query_context(q, ds), jnp.int32(root),
                       ds.num_vertices)
    with t.span("dispatch", engine=q.engine, direction=q.direction,
                lanes=1):
        r = execute(plan, query_context(q, ds), jnp.int32(root),
                    ds.num_vertices)
        jax.block_until_ready(r)
    _trace.emit_level_events(t, r, engine=q.engine)
    return r


def run_query_batch(q: RecursiveQuery, ds: Dataset, roots) -> BFSResult:
    """Execute one query for MANY roots in a single jitted XLA dispatch
    (vmap over the fixed-point driver).  Every array in the returned
    ``BFSResult`` gains a leading ``len(roots)`` batch dimension; row i is
    bit-identical to ``run_query(q, ds, roots[i])``."""
    plan = build_plan(q)
    roots = jnp.asarray(roots, jnp.int32)
    t = _trace.current_tracer()
    if t is None:
        return execute_batch(plan, query_context(q, ds), roots,
                             ds.num_vertices)
    with t.span("dispatch", engine=q.engine, direction=q.direction,
                lanes=int(roots.shape[0])):
        r = execute_batch(plan, query_context(q, ds), roots,
                          ds.num_vertices)
        jax.block_until_ready(r)
    _trace.emit_level_events(t, r, engine=q.engine)
    return r


def run_query_multi(q: RecursiveQuery, ds: Dataset, roots,
                    lane_limits=None) -> BFSResult:
    """Execute one query for up to :data:`WORD_LANES` roots in a single
    BIT-PARALLEL dispatch: every root is a bit lane of one packed dense
    frontier word, and one MS-BFS sweep per level advances all of them
    (``q.engine`` must be ``'multiquery'``).  The returned ``BFSResult``
    carries a leading ``len(roots)`` lane dimension; lane i is row-for-row
    identical to ``run_query`` on ``roots[i]`` through a deferred-emission
    engine.  ``lane_limits`` (optional, per-lane depth caps from the reach
    buckets) must never be below a lane's natural convergence depth —
    callers pass estimates only when they are exact."""
    if len(roots) > WORD_LANES:
        raise ValueError(f"multiquery packs at most {WORD_LANES} roots "
                         f"per dispatch, got {len(roots)}")
    mq = q if q.engine == "multiquery" and q.lanes == len(roots) else \
        dataclasses.replace(q, engine="multiquery", lanes=len(roots))
    plan = build_plan(mq)
    ds.ensure_reverse()          # the word sweep gathers dst-grouped edges
    ds.ensure_direction(mq.direction)
    t = _trace.current_tracer()
    if t is None:
        return execute_multiquery(plan, query_context(mq, ds), roots,
                                  ds.num_vertices, lane_limits)
    with t.span("dispatch", engine="multiquery", direction=mq.direction,
                lanes=int(len(roots))):
        r = execute_multiquery(plan, query_context(mq, ds), roots,
                               ds.num_vertices, lane_limits)
        jax.block_until_ready(r)
    _trace.emit_level_events(t, r, engine="multiquery")
    return r


def result_lane(r: BFSResult, lane: int) -> BFSResult:
    """Slice one lane out of a batched BFSResult."""
    return jax.tree_util.tree_map(lambda a: a[lane], r)


@dataclasses.dataclass(frozen=True)
class BucketTiming:
    """One bucket's measured dispatch, reported by
    :func:`dispatch_buckets` to its observer — the planner's calibration
    feedback loop consumes these.

    ``elapsed_us`` attributes DEVICE time to this bucket: the interval from
    max(this bucket's launch, the previous bucket's completion) to this
    bucket's results being materialized.  Buckets are launched back-to-back
    and executed in order on one stream, so without the max() every
    bucket's wait on its predecessors would be double-counted."""

    index: int                 # position in the buckets sequence
    lanes: int                 # real lanes (len(bucket.indices))
    padded_lanes: int          # dispatched lanes (len(bucket.roots))
    caps: EngineCaps           # the caps the MEASURED dispatch ran with
    retried: bool              # True when the fallback-caps retry ran
    elapsed_us: float
    predicted_caps: Optional[EngineCaps] = None
    #   the caps bucketing PREDICTED for this bucket — when ``retried`` is
    #   True these are the caps that overflowed (the measured dispatch ran
    #   at ``caps`` == the fallback), making the silent 2x-dispatch cliff
    #   visible to observers instead of only to the retry branch
    evicted_lanes: int = 0
    #   lanes evicted to SOLO fallback-caps re-dispatches because only they
    #   overflowed the bucket caps — the rest of the bucket kept its caps
    #   (with coalesced lanes, one pathological root must not force the
    #   whole 32-lane word onto fallback caps)


# process-wide visibility for the overflow-retry path: every retry is a
# hidden 2x-dispatch perf cliff (the bucket ran once at its predicted caps,
# overflowed, and ran again at the fallback caps), so it is counted here,
# surfaced on the BucketTiming, traced, and warned about once per process
# (serving sessions additionally warn once per session and count it in
# their metrics registry)
_overflow_state = {"retries": 0, "warned": False, "lane_evictions": 0}


def overflow_retry_count() -> int:
    """Process-wide count of fallback-caps overflow retries."""
    return _overflow_state["retries"]


def lane_eviction_count() -> int:
    """Process-wide count of lanes evicted to solo fallback re-dispatches
    (per-lane overflow handling — the rest of the bucket kept its caps)."""
    return _overflow_state["lane_evictions"]


def _note_overflow_retry(index: int, predicted: EngineCaps,
                         fallback: EngineCaps, tracer) -> None:
    _overflow_state["retries"] += 1
    if tracer is not None:
        tracer.event("overflow_retry", bucket=index,
                     predicted_caps=[predicted.frontier, predicted.result],
                     fallback_caps=[fallback.frontier, fallback.result])
    if not _overflow_state["warned"]:
        _overflow_state["warned"] = True
        warnings.warn(
            f"bucket {index} overflowed its predicted caps "
            f"(frontier={predicted.frontier}, result={predicted.result}) "
            f"and was re-dispatched at the fallback caps "
            f"(frontier={fallback.frontier}, result={fallback.result}) — "
            "a transparent retry that doubles that bucket's dispatch "
            "cost; consider larger caps or fewer buckets "
            "(warned once per process; see ServingSession.metrics() for "
            "counts)", RuntimeWarning, stacklevel=3)


def _note_lane_eviction(index: int, lanes: Sequence[int],
                        predicted: EngineCaps, fallback: EngineCaps,
                        tracer) -> None:
    _overflow_state["lane_evictions"] += len(lanes)
    if tracer is not None:
        tracer.event("overflow_lane_eviction", bucket=index,
                     lanes=list(lanes),
                     predicted_caps=[predicted.frontier, predicted.result],
                     fallback_caps=[fallback.frontier, fallback.result])


def _settle_evicted(r, b, evicted) -> BFSResult:
    """The bucket result as ``finish`` should see it after per-lane
    eviction: only real lanes that kept their rows carry an overflow flag.
    An evicted lane takes its rows from its solo re-dispatch, and padding
    lanes repeat a real root and are dropped, so neither may fail the
    bucket's overflow check."""
    ov = np.asarray(r.overflow)
    keep = np.zeros(ov.shape[0], bool)
    keep[:len(b.indices)] = True
    keep[list(evicted)] = False
    return r._replace(overflow=ov & keep.reshape((-1,) + (1,) * (ov.ndim - 1)))


def _evict_bucket(b, lane: int, caps: EngineCaps):
    """A single-lane bucket for one evicted root, dispatched solo at the
    fallback caps (the original bucket keeps its caps for every other
    lane)."""
    indices = (b.indices[lane],)
    roots = (b.roots[lane],)
    if dataclasses.is_dataclass(b):
        try:
            return dataclasses.replace(b, indices=indices, roots=roots,
                                       caps=caps)
        except TypeError:
            pass
    import types
    return types.SimpleNamespace(indices=indices, roots=roots, caps=caps)


class _SkippedLane:
    """Sentinel filling a lane whose bucket was skipped by the deadline
    budget — callers that passed ``deadline_us`` replace it with a
    classified degraded answer; callers that didn't never see it."""

    def __repr__(self) -> str:           # pragma: no cover - debug aid
        return "<skipped lane>"


SKIPPED = _SkippedLane()


@dataclasses.dataclass
class RetryPolicy:
    """THE retry policy: full-bucket overflow retries, per-lane evictions,
    and guard-degraded re-dispatches all spend from this one bounded
    budget, replacing the former ad-hoc one-retry branches.

    ``max_attempts`` counts dispatches per bucket (initial + retries);
    ``growth`` grows caps geometrically toward the fallback on each retry
    (``None`` jumps straight to fallback caps — the historical behavior);
    ``budget`` bounds TOTAL retries across the policy's lifetime (a
    serving session shares one policy across requests).  When the budget
    is exhausted the executor stops re-dispatching and reports the bucket
    in :attr:`DispatchReport.denied_buckets` — the serving layer then
    degrades that answer (truncated rows, flagged) instead of raising
    mid-request."""

    max_attempts: int = 2
    growth: Optional[float] = None
    budget: Optional[int] = None
    spent: int = 0

    def spend(self) -> bool:
        """Consume one retry if the budget allows it."""
        if self.budget is not None and self.spent >= self.budget:
            return False
        self.spent += 1
        return True

    def next_caps(self, attempt: int, current: EngineCaps,
                  fallback: EngineCaps) -> EngineCaps:
        """Caps for retry number ``attempt`` (1-based): geometric growth
        toward the fallback, or straight to it when ``growth`` is None or
        this is the last allowed attempt."""
        if self.growth is None or attempt + 1 >= self.max_attempts:
            return fallback
        return EngineCaps(
            frontier=min(int(current.frontier * self.growth),
                         fallback.frontier),
            result=min(int(current.result * self.growth), fallback.result))


@dataclasses.dataclass
class DispatchReport:
    """What :func:`dispatch_buckets` did beyond returning rows: which
    buckets were skipped (deadline), straggled, or were denied a retry —
    the explicit flags that replace silent blocking/truncation."""

    skipped_buckets: list = dataclasses.field(default_factory=list)
    skipped_lanes: list = dataclasses.field(default_factory=list)
    #   ORIGINAL root-vector indices whose bucket was never launched
    straggler_buckets: list = dataclasses.field(default_factory=list)
    denied_buckets: list = dataclasses.field(default_factory=list)
    #   overflowed buckets the retry budget refused to re-dispatch: their
    #   rows are TRUNCATED at bucket caps (callers must not overflow-check)
    denied_lanes: list = dataclasses.field(default_factory=list)
    retries: int = 0
    evictions: int = 0

    @property
    def truncated(self) -> bool:
        """True iff any lane's answer is incomplete (skipped or denied)."""
        return bool(self.skipped_buckets or self.denied_buckets)


def dispatch_buckets(buckets: Sequence, dispatch: Callable, *,
                     fallback_caps: EngineCaps,
                     finish: Optional[Callable] = None,
                     observer: Optional[Callable] = None,
                     to_host: bool = False,
                     retry: Optional[RetryPolicy] = None,
                     deadline_us: Optional[float] = None,
                     straggler=None,
                     report: Optional[DispatchReport] = None) -> list:
    """THE bucket-dispatch executor: every reach-bucketed execution path
    (:func:`run_query_buckets`, ``PhysicalChoice.run_bucketed``'s kernel
    branch, ``ServingSession._execute``) delegates here, so the shared
    launch -> overflow-retry -> scatter-by-indices shape exists exactly
    once and cannot drift.

    ``dispatch(index, bucket, caps)`` runs one batched dispatch for a
    bucket at the given caps and returns a batched ``BFSResult`` (leading
    lane dimension).  The executor:

    * launches EVERY bucket before touching any result — dispatches are
      async, and the host-side overflow check must not serialize them.
      EXCEPT under a ``deadline_us`` budget: then buckets launch lazily,
      one at a time, and a bucket is SKIPPED (its lanes filled with the
      :data:`SKIPPED` sentinel, recorded on the ``report``) when the
      budget is already exhausted or the straggler monitor's predicted
      wall time (``straggler.expected``) no longer fits the remainder —
      skip-vs-launch is decided BEFORE paying the dispatch cost.  The
      first bucket always launches: a request makes progress, the budget
      only stops FURTHER work;
    * retries on overflow through the :class:`RetryPolicy` (bucket caps
      are predictions; bucketing must never turn a valid query into a
      truncated result).  When overflow is PER LANE and only some real
      lanes overflowed, just those lanes are EVICTED to solo fallback
      re-dispatches and the rest of the bucket keeps its result at bucket
      caps — with coalesced lanes one pathological root must not force
      the whole word onto worst-case caps.  Only a full-bucket (or
      scalar) overflow still re-dispatches the whole bucket.  A policy
      whose budget is exhausted DENIES the retry: the bucket is recorded
      in ``report.denied_buckets`` and its truncated-at-caps rows stand
      (callers degrade the answer instead of raising mid-request);
    * applies the optional ``finish(index, bucket, result)`` hook to the
      batched result (the serving layer dresses per-bucket results here;
      the report is filled for bucket ``i`` before ``finish(i, ...)``
      runs, so the hook can consult it);
    * scatters lanes back to the ORIGINAL root order via each bucket's
      ``indices`` (``to_host=True`` converts each bucket's result to host
      numpy first — one transfer per bucket, lanes become free views);
    * measures per-bucket wall-clock ONCE, consistently, and reports it to
      ``observer(timing)`` as a :class:`BucketTiming` — this is the single
      measurement point the cost-model calibrator trusts.  When a
      ``straggler`` monitor is passed, every measured bucket feeds its
      EMA and buckets exceeding the straggler deadline are recorded in
      ``report.straggler_buckets``.
    """
    buckets = tuple(buckets)
    total = sum(len(b.indices) for b in buckets)
    out: list = [None] * total
    policy = retry if retry is not None else RetryPolicy()
    rep = report if report is not None else DispatchReport()
    # the executor owns bucket-granular tracing: suppress the global
    # tracer around nested dispatches so per-root instrumentation inside
    # run_query_batch cannot serialize the async launch loop, and emit
    # per-bucket spans/events from the one measurement point instead
    tracer = _trace.current_tracer()
    prev_tracer = _trace.set_tracer(None) if tracer is not None else None
    try:
        lazy = deadline_us is not None
        t_start = time.perf_counter()
        launched = []
        if not lazy:
            for i, b in enumerate(buckets):
                t0 = time.perf_counter()
                launched.append((i, b, t0, dispatch(i, b, b.caps)))
        prev_done = None
        timings = []
        for k in range(len(buckets)):
            if lazy:
                i, b = k, buckets[k]
                elapsed_us = (time.perf_counter() - t_start) * 1e6
                predicted_us = (straggler.expected
                                if straggler is not None else 0.0)
                if timings and elapsed_us + predicted_us >= deadline_us:
                    rep.skipped_buckets.append(i)
                    if tracer is not None:
                        tracer.event("deadline_skip", bucket=i,
                                     lanes=len(b.indices),
                                     elapsed_us=elapsed_us,
                                     predicted_us=predicted_us,
                                     deadline_us=deadline_us)
                    for idx in b.indices:
                        rep.skipped_lanes.append(idx)
                        out[idx] = SKIPPED
                    continue
                t0 = time.perf_counter()
                r = dispatch(i, b, b.caps)
            else:
                i, b, t0, r = launched[k]
            if _fault._ACTIVE:
                d = _fault.consume("straggler_sleep")
                if d:
                    time.sleep(float(d))
            retried = False
            evicted: dict = {}
            if b.caps != fallback_caps:
                ov = np.asarray(r.overflow).reshape(-1)
                n_real = len(b.indices)
                real_ov = ov[:n_real] if ov.size >= n_real else \
                    np.broadcast_to(ov, (n_real,))
                if _fault._ACTIVE and _fault.consume("bucket_overflow"):
                    real_ov = np.ones(n_real, dtype=bool)
                if real_ov.any():
                    if n_real == 1 or real_ov.all():
                        caps_now = b.caps
                        attempt = 1
                        while attempt < policy.max_attempts:
                            if not policy.spend():
                                break
                            caps_now = policy.next_caps(
                                attempt, caps_now, fallback_caps)
                            r = dispatch(i, b, caps_now)
                            retried = True
                            rep.retries += 1
                            _note_overflow_retry(i, b.caps, caps_now,
                                                 tracer)
                            ov = np.asarray(r.overflow).reshape(-1)
                            real_ov = ov[:n_real] if ov.size >= n_real \
                                else np.broadcast_to(ov, (n_real,))
                            attempt += 1
                            if not real_ov.any() \
                                    or caps_now == fallback_caps:
                                break
                        if real_ov.any() and not retried:
                            rep.denied_buckets.append(i)
                            rep.denied_lanes.extend(b.indices)
                    else:
                        # per-lane eviction: solo fallback re-dispatch for
                        # just the overflowing lanes
                        hit = np.nonzero(real_ov)[0].tolist()
                        done = []
                        for lane in hit:
                            if not policy.spend():
                                rep.denied_lanes.append(b.indices[lane])
                                continue
                            sb = _evict_bucket(b, lane, fallback_caps)
                            evicted[lane] = (sb, dispatch(i, sb,
                                                          fallback_caps))
                            done.append(lane)
                            rep.evictions += 1
                        if done:
                            _note_lane_eviction(i, done, b.caps,
                                                fallback_caps, tracer)
                        if len(done) < len(hit):
                            rep.denied_buckets.append(i)
            if finish is not None:
                if evicted:
                    r = _settle_evicted(r, b, evicted)
                r = finish(i, b, r)
                evicted = {lane: (sb, finish(i, sb, rr))
                           for lane, (sb, rr) in evicted.items()}
            if to_host:
                # one device->host transfer per bucket (also synchronizes)
                if tracer is not None:
                    with tracer.span("transfer", bucket=i,
                                     lanes=len(b.indices)):
                        r = jax.tree_util.tree_map(np.asarray, r)
                else:
                    r = jax.tree_util.tree_map(np.asarray, r)
                evicted = {lane: (sb, jax.tree_util.tree_map(np.asarray,
                                                             rr))
                           for lane, (sb, rr) in evicted.items()}
            elif observer is not None or tracer is not None:
                jax.block_until_ready(r)  # timing needs a real completion
                for _, rr in evicted.values():
                    jax.block_until_ready(rr)
            t_done = time.perf_counter()
            for lane, idx in enumerate(b.indices):
                if lane in evicted:
                    out[idx] = jax.tree_util.tree_map(
                        lambda a: a[0], evicted[lane][1])
                else:
                    out[idx] = jax.tree_util.tree_map(
                        lambda a, lane=lane: a[lane], r)
            timing = BucketTiming(
                index=i, lanes=len(b.indices), padded_lanes=len(b.roots),
                caps=(fallback_caps if retried else b.caps),
                retried=retried,
                elapsed_us=(t_done - (t0 if prev_done is None
                                      else max(t0, prev_done))) * 1e6,
                predicted_caps=b.caps, evicted_lanes=len(evicted))
            if straggler is not None and straggler.record(timing.elapsed_us):
                rep.straggler_buckets.append(i)
                if tracer is not None:
                    tracer.event("straggler", bucket=i,
                                 elapsed_us=timing.elapsed_us,
                                 expected_us=straggler.expected)
            if observer is not None:
                observer(timing)
            timings.append((timing, r))
            prev_done = t_done
    finally:
        if tracer is not None:
            _trace.set_tracer(prev_tracer)
    if tracer is not None:
        # spans + level events AFTER the measurement loop, so enabled
        # tracing never sits inside a timed interval the calibrator trusts
        for timing, r in timings:
            with tracer.span("dispatch", bucket=timing.index,
                             lanes=timing.lanes,
                             padded_lanes=timing.padded_lanes,
                             retried=timing.retried,
                             elapsed_us=timing.elapsed_us):
                _trace.emit_level_events(tracer, r, bucket=timing.index)
    if any(x is None for x in out):
        raise ValueError("buckets do not cover lanes 0..%d exactly"
                         % (total - 1))
    return out  # deadline-skipped lanes hold the SKIPPED sentinel


def run_query_buckets(q: RecursiveQuery, ds: Dataset, buckets
                      ) -> list[BFSResult]:
    """Reach-bucketed serving execution: one jitted batched dispatch PER
    BUCKET, each with that bucket's (smaller) ``EngineCaps``, instead of one
    worst-case lockstep dispatch over the whole root vector.

    ``buckets`` is a sequence of bucket objects (see
    :func:`repro.planner.optimize.bucket_roots`) carrying ``roots``,
    ``indices`` (lanes in the original root vector) and ``caps``.  Results
    come back PER ROOT, in the original order; each entry is bit-identical
    to ``run_query(q, ds, root)`` on its root.  Launch ordering, the
    global-caps overflow retry, and the scatter live in
    :func:`dispatch_buckets` (the one shared executor)."""
    def _dispatch(i, b, caps):
        qb = dataclasses.replace(q, caps=caps) if caps != q.caps else q
        return run_query_batch(qb, ds, b.roots)

    return dispatch_buckets(buckets, _dispatch, fallback_caps=q.caps)


def plan_and_run(sql_or_ast, ds: Dataset, roots=None, **kwargs) -> BFSResult:
    """Answer a recursive query WITHOUT an engine name: parse the minimal
    ``WITH RECURSIVE`` dialect (or take a planner AST / LogicalQuery),
    price every legal engine against ``ds.stats()``, and execute the
    cheapest through the same ``PLAN_BUILDERS`` path ``run_query`` uses.

    ``roots`` is one root (scalar) or a sequence (one vmap-batched
    dispatch).  See :func:`repro.planner.plan_and_run` for keyword options
    (``caps``, ``include_kernel``, ``default_max_depth``)."""
    from repro.planner import plan_and_run as _impl
    return _impl(sql_or_ast, ds, roots, **kwargs)


def explain(sql_or_ast, ds: Dataset, **kwargs) -> str:
    """EXPLAIN the query: the ranked candidate engines with per-operator
    estimated rows/bytes (see :mod:`repro.planner.explain`)."""
    from repro.planner import explain as _impl
    return _impl(sql_or_ast, ds, **kwargs)


def explain_analyze(sql_or_ast, ds: Dataset, **kwargs) -> dict:
    """EXPLAIN ANALYZE: plan, EXECUTE, and reconcile predicted vs. actual
    per-operator rows/bytes and per-level push/pull directions (see
    :func:`repro.planner.explain.explain_analyze`)."""
    from repro.planner import explain_analyze as _impl
    return _impl(sql_or_ast, ds, **kwargs)


def plan_repr(engine: str, max_depth: int, payload_cols: int,
              root: int = 0) -> str:
    """Volcano-tree rendering DERIVED from the engine's actual operator
    composition (not a hand-written template)."""
    q = RecursiveQuery(engine=engine, max_depth=max_depth,
                       payload_cols=payload_cols,
                       caps=EngineCaps(frontier=0, result=0))
    return build_plan(q).render(root=root)
