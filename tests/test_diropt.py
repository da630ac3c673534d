"""Direction-optimizing traversal + the fused bidirectional CSR.

The load-bearing guarantees:

* **diropt parity** — the direction-optimizing engines are row-for-row
  IDENTICAL (positions, depths, counts, loop accounting) to their
  push-only counterparts (``diropt`` vs ``bitmap``, ``diropt_hybrid`` vs
  ``hybrid``) on random graphs, every legal direction, regardless of what
  the per-level switch decides — the push and pull branches compute the
  same level, so thresholds steer performance only;
* **forced pull** — pinning the switch to the pull side (huge alpha/beta)
  exercises :class:`PullStep`/:class:`HybridPullStep` on every level and
  must still match the push-only engines, with ``level_dirs`` recording
  all-pull;
* **fused == doubled** — the fused bidirectional view (E-sized columns,
  out/in CSRs + merged indptr, virtual 2E join space) produces results
  bit-identical to the OLD materialized doubled view (2E concat columns +
  2E CSR) for every engine on ``direction='both'``, and the fused view's
  added arrays are E-scale;
* the switch decision surfaces in ``BFSResult.level_dirs`` and in the
  planner's predicted ``PlanCost.level_dirs``.

The deterministic seeded slice always runs; the hypothesis property (real
package or the vendored fallback engine) extends the seed set.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineCaps
from repro.core.bitmap import diropt_hybrid_plan, diropt_plan
from repro.core.csr import build_csr
from repro.core.engine import (DIROPT_ENGINE_NAMES, ENGINE_NAMES,
                               PUSH_COUNTERPART, Dataset, RecursiveQuery,
                               build_plan, run_query)
from repro.core.operators import Context, execute
from repro.core.table import ColumnTable

DIRECTIONS = ("outbound", "inbound", "both")
OUT_COLS = ("id", "from", "to", "name")


def _edge_dataset(src, dst, num_vertices):
    e = len(src)
    cols = {
        "id": np.arange(e, dtype=np.int32),
        "from": np.asarray(src, np.int32),
        "to": np.asarray(dst, np.int32),
        "name": np.zeros((e, 4), np.float32)}
    return Dataset.prepare(ColumnTable.from_numpy(cols), num_vertices)


def _random_graph(seed):
    rng = np.random.default_rng(seed)
    v = int(rng.integers(6, 48))
    e = int(rng.integers(2, 3 * v))
    src = rng.integers(0, v, e).astype(np.int32)
    dst = rng.integers(0, v, e).astype(np.int32)
    depth = int(rng.integers(1, 6))
    root = int(rng.integers(0, v))
    return src, dst, v, root, depth


def _caps(e, direction):
    n = 2 * e if direction == "both" else e
    return EngineCaps(frontier=n + 16, result=n + 16)


def _assert_same(a, b, tag):
    assert int(a.count) == int(b.count), tag
    assert int(a.depth) == int(b.depth), tag
    assert bool(a.overflow) == bool(b.overflow), tag
    assert np.array_equal(np.asarray(a.positions),
                          np.asarray(b.positions)), tag
    assert np.array_equal(np.asarray(a.row_depths),
                          np.asarray(b.row_depths)), tag
    for k in b.values:
        assert np.array_equal(np.asarray(a.values[k]),
                              np.asarray(b.values[k])), (tag, k)


# ---------------------------------------------------------------------------
# 1. diropt engines == their push-only counterparts, every direction
# ---------------------------------------------------------------------------

def _check_diropt_parity(seed):
    src, dst, v, root, depth = _random_graph(seed)
    ds = _edge_dataset(src, dst, v)
    for direction in DIRECTIONS:
        caps = _caps(len(src), direction)
        for eng in DIROPT_ENGINE_NAMES:
            ref = run_query(RecursiveQuery(PUSH_COUNTERPART[eng], depth, 0,
                                           caps, direction=direction),
                            ds, root)
            got = run_query(RecursiveQuery(eng, depth, 0, caps,
                                           direction=direction), ds, root)
            _assert_same(got, ref, (eng, direction, seed))
            dirs = np.asarray(got.level_dirs)
            assert dirs.shape[0] >= int(got.depth)
            assert set(dirs.tolist()) <= {-1, 0, 1}, (eng, direction)


@pytest.mark.parametrize("seed", [0, 3, 11])
def test_diropt_matches_push_only_seeded(seed):
    _check_diropt_parity(seed)


# ---------------------------------------------------------------------------
# 2. forced pull: every level bottom-up, same rows
# ---------------------------------------------------------------------------

def _check_forced_pull(seed):
    src, dst, v, root, depth = _random_graph(seed)
    ds = _edge_dataset(src, dst, v)
    for direction in DIRECTIONS:
        caps = _caps(len(src), direction)
        ref_b = run_query(RecursiveQuery("bitmap", depth, 0, caps,
                                         direction=direction), ds, root)
        plan = diropt_plan(caps, depth, OUT_COLS, direction=direction,
                           alpha=1e9, beta=1e9)
        got = execute(plan, ds.context(direction), root, v)
        _assert_same(got, ref_b, ("diropt-pull", direction, seed))
        dirs = np.asarray(got.level_dirs)
        assert (dirs[: int(got.depth)] == 1).all(), (direction, seed)

        ref_h = run_query(RecursiveQuery("hybrid", depth, 0, caps,
                                         direction=direction), ds, root)
        hplan = diropt_hybrid_plan(caps, depth, OUT_COLS,
                                   direction=direction, alpha=1e9,
                                   beta=1e9)
        goth = execute(hplan, ds.context(direction), root, v)
        _assert_same(goth, ref_h, ("hybrid-pull", direction, seed))


@pytest.mark.parametrize("seed", [1, 7])
def test_forced_pull_matches_push_seeded(seed):
    _check_forced_pull(seed)


def test_pull_kernel_plugs_into_diropt():
    """The Pallas frontier_pull kernel (interpret mode) as PullStep's
    expand_fn: same rows as the XLA pull and the push baseline."""
    from repro.planner.calibrate import kernel_pull_fn

    src, dst, v, root, depth = _random_graph(23)
    ds = _edge_dataset(src, dst, v)
    ds.ensure_reverse()                     # the pull kernel walks it
    caps = _caps(len(src), "outbound")
    ref = run_query(RecursiveQuery("bitmap", depth, 0, caps), ds, root)
    plan = diropt_plan(caps, depth, OUT_COLS, alpha=1e9, beta=1e9,
                       pull_fn=kernel_pull_fn())
    got = execute(plan, ds.context("outbound"), root, v)
    _assert_same(got, ref, "kernel-pull")


# ---------------------------------------------------------------------------
# 3. fused bidirectional CSR == the old doubled 2E view, every engine
# ---------------------------------------------------------------------------

def _doubled_context(ds: Dataset) -> Context:
    """The PRE-FUSION 'both' view, reconstructed: materialized 2E concat
    columns and a CSR over them (what Dataset used to cache)."""
    both_src = jnp.concatenate([ds.table.column("from"),
                                ds.table.column("to")])
    both_dst = jnp.concatenate([ds.table.column("to"),
                                ds.table.column("from")])
    return Context(table=ds.table, rows=ds.rows,
                   csr=build_csr(both_src, ds.num_vertices),
                   join_src=both_src, join_dst=both_dst,
                   rcsr=build_csr(both_dst, ds.num_vertices))


def _check_fused_equals_doubled(seed):
    src, dst, v, root, depth = _random_graph(seed)
    ds = _edge_dataset(src, dst, v)
    caps = _caps(len(src), "both")
    old_ctx = _doubled_context(ds)
    fused_ctx = ds.context("both")
    assert fused_ctx.bidir and not old_ctx.bidir
    for eng in ENGINE_NAMES:
        if eng.startswith("rowstore"):
            continue                       # outbound-only baseline
        plan = build_plan(RecursiveQuery(eng, depth, 0, caps,
                                         direction="both"))
        got = execute(plan, fused_ctx, root, v)
        want = execute(plan, old_ctx, root, v)
        _assert_same(got, want, (eng, seed))


@pytest.mark.parametrize("seed", [2, 5, 13])
def test_fused_both_view_equals_doubled_seeded(seed):
    _check_fused_equals_doubled(seed)


def test_fused_inbound_unchanged_by_rcsr_sharing():
    """inbound (which now shares its CSR with the pull path and the fused
    view) still equals a hand-built reverse context."""
    src, dst, v, root, depth = _random_graph(17)
    ds = _edge_dataset(src, dst, v)
    caps = _caps(len(src), "inbound")
    plan = build_plan(RecursiveQuery("precursive", depth, 0, caps,
                                     direction="inbound"))
    manual = Context(table=ds.table, rows=ds.rows,
                     csr=build_csr(ds.table.column("to"), v),
                     join_src=ds.table.column("to"),
                     join_dst=ds.table.column("from"))
    got = execute(plan, ds.context("inbound"), root, v)
    want = execute(plan, manual, root, v)
    _assert_same(got, want, "inbound")


def test_fused_view_memory_is_e_scale():
    """The 'both' view adds the reverse CSR + ONE merged indptr — no
    2E-sized array anywhere on the Dataset."""
    src, dst, v, _, _ = _random_graph(4)
    ds = _edge_dataset(src, dst, v)
    e = len(src)
    added = ds.edge_view_bytes("both")
    doubled_added = 3 * (2 * e * 4) + (v + 1) * 4
    # reverse perm (E) + reverse indptr (V+1) + merged indptr (V+1)
    assert added == 4 * (e + 2 * (v + 1))
    assert added < doubled_added
    assert int(np.asarray(ds.both_indptr)[-1]) == 2 * e  # merged covers 2E
    ctx = ds.context("both")
    assert ctx.join_src.shape[0] == e                    # no 2E columns


# ---------------------------------------------------------------------------
# 4. the switch decision is recorded and predicted
# ---------------------------------------------------------------------------

def test_level_dirs_recorded_and_push_only_for_counterparts():
    src, dst, v, root, depth = _random_graph(9)
    ds = _edge_dataset(src, dst, v)
    caps = _caps(len(src), "outbound")
    r = run_query(RecursiveQuery("diropt", depth, 0, caps), ds, root)
    dirs = np.asarray(r.level_dirs)
    assert (dirs[: int(r.depth)] >= 0).all()     # every level decided
    assert (dirs[int(r.depth):] == -1).all()     # unexecuted levels marked
    # push-only engines carry no switch log
    rb = run_query(RecursiveQuery("bitmap", depth, 0, caps), ds, root)
    assert rb.level_dirs is None


def test_planner_predicts_level_dirs_for_diropt():
    from repro.planner import plan

    src, dst, v, root, depth = _random_graph(31)
    ds = _edge_dataset(src, dst, v)
    caps = _caps(len(src), "outbound")
    sql = f"""
        WITH RECURSIVE t (id, "from", "to", depth) AS (
          SELECT id, "from", "to", 0 FROM edges WHERE "from" = {root}
          UNION
          SELECT e.id, e."from", e."to", t.depth + 1
          FROM edges e JOIN t ON e."from" = t."to"
          WHERE t.depth < {depth}
        ) SELECT * FROM t"""
    report = plan(sql, ds, caps=caps)
    by_label = {c.label: c for c in report.ranked}
    for eng in DIROPT_ENGINE_NAMES:
        dirs = by_label[eng].cost.level_dirs
        assert len(dirs) == by_label[eng].cost.levels
        assert set(dirs) <= {"push", "pull"}
    assert by_label["bitmap"].cost.level_dirs == ()
    # thresholds flow from the constants into the priced pipeline
    from repro.core.operators import DirectionSwitch
    switch = next(op for op in by_label["diropt"].pipeline.ops
                  if isinstance(op, DirectionSwitch))
    assert (switch.alpha, switch.beta) == (report.constants.pull_alpha,
                                           report.constants.pull_beta)


def test_deferred_emit_overflow_flag():
    src, dst, v, root, _ = _random_graph(6)
    ds = _edge_dataset(src, dst, v)
    tiny = EngineCaps(frontier=len(src) + 16, result=2)
    r = run_query(RecursiveQuery("diropt", 4, 0, tiny), ds, root)
    rb = run_query(RecursiveQuery("bitmap", 4, 0, tiny), ds, root)
    assert bool(r.overflow) == bool(rb.overflow)


# ---------------------------------------------------------------------------
# 5. the diropt_hybrid mispricing regression
# ---------------------------------------------------------------------------
# HybridPullStep.estimate used to omit the per-level previous-vertex-set
# rebuild (a positional frontier keeps no vertex set between levels) and
# half the hit/compact work, pricing a pull level ~2.5x UNDER the dense
# push it replaces.  That kept diropt_hybrid a near-tied planner candidate
# while the paired bench measured it at 0.33-0.37x of plain hybrid on the
# bench tree profile.

def test_hybrid_pull_estimate_prices_prev_set_rebuild():
    from repro.core.operators import CostEnv, HybridPullStep, HybridStep

    def env(frontier_cap, visited_rows=0.0):
        return CostEnv(frontier_rows=5_000, unique_rows=5_000,
                       emitted_rows=25_000, num_vertices=20_000,
                       num_edges=100_000, frontier_cap=frontier_cap,
                       result_cap=100_008, row_bytes=28, col_bytes={},
                       visited_rows=visited_rows)

    # the rebuild term scales with the frontier cap (>= 36 B per slot,
    # the same per-row scatter factor as the sparse positional branch)
    lo = HybridPullStep().estimate(env(1_000)).bytes
    hi = HybridPullStep().estimate(env(101_000)).bytes
    assert hi - lo >= 100_000 * 36.0

    # a pull level is never priced below the dense push it replaces —
    # even at the pull-friendliest extreme (everything already visited,
    # so the bottom-up gather is free); the old estimate inverted this
    for visited in (0.0, 10_000.0, 20_000.0):
        e = env(100_008, visited_rows=visited)
        assert (HybridPullStep().estimate(e).bytes
                >= HybridStep().estimate(e).bytes), visited


def test_planner_never_picks_diropt_hybrid_on_the_tree_profile(
        tree_dataset):
    """The bench-tree profile (scaled): the paired exp1 bench measures
    diropt_hybrid at ~0.35x of its push-only counterpart there, so a
    planner that ranks it FIRST is mispricing the pull branch."""
    from repro.planner import plan

    _, ds, _ = tree_dataset
    for depth in (4, 8):
        sql = f"""
            WITH RECURSIVE t (id, "from", "to", depth) AS (
              SELECT id, "from", "to", 0 FROM edges WHERE "from" = 0
              UNION
              SELECT e.id, e."from", e."to", t.depth + 1
              FROM edges e JOIN t ON e."from" = t."to"
              WHERE t.depth < {depth}
            ) SELECT * FROM t"""
        report = plan(sql, ds, caps=EngineCaps(frontier=2048, result=4096))
        assert report.best.label != "diropt_hybrid", depth
        # and the candidate is still ranked (the fix reprices, not bans)
        assert any(c.label == "diropt_hybrid" for c in report.ranked)


# ---------------------------------------------------------------------------
# 6. batches: one direction per level where the lanes agree
# ---------------------------------------------------------------------------
# Twin hubs 0 and 1 fan out to 2..21, which all lead to 22, then 23: from
# either hub the second level pulls, from 2 or 22 every level pushes.  So
# (0, 1) agree at every level (one of them a pull) and (0, 2, 1, 22)
# disagree at the second level.

_TWIN_SRC = [0] * 20 + [1] * 20 + list(range(2, 22)) + [22]
_TWIN_DST = list(range(2, 22)) * 2 + [22] * 20 + [23]
BATCHES = {"agree": (0, 1), "mixed": (0, 2, 1, 22)}


@pytest.fixture(scope="module")
def twin_hubs():
    return _edge_dataset(np.array(_TWIN_SRC), np.array(_TWIN_DST), 24)


def _agreement(dirs):
    """(uniform, mixed) level counts of per-lane ``level_dirs`` rows."""
    uniform = mixed = 0
    for col in np.asarray(dirs).T:
        taken = set(col[col >= 0].tolist())
        uniform += len(taken) == 1
        mixed += len(taken) > 1
    return uniform, mixed


@pytest.mark.parametrize("batch", sorted(BATCHES))
@pytest.mark.parametrize("engine", DIROPT_ENGINE_NAMES)
def test_batch_lanes_match_single_root_runs(twin_hubs, engine, batch):
    from repro.core.engine import result_lane, run_query_batch

    roots = BATCHES[batch]
    q = RecursiveQuery(engine, 5, 0, _caps(len(_TWIN_SRC), "outbound"))
    singles = [run_query(q, twin_hubs, r) for r in roots]
    dirs = np.stack([np.asarray(s.level_dirs) for s in singles])
    assert (dirs == 1).any()                           # some level pulls
    assert _agreement(dirs)[1] == (batch == "mixed")   # the case holds
    got = run_query_batch(q, twin_hubs, roots)
    for lane, (root, want) in enumerate(zip(roots, singles)):
        one = result_lane(got, lane)
        _assert_same(one, want, (engine, batch, root))
        assert np.array_equal(np.asarray(one.level_dirs),
                              np.asarray(want.level_dirs)), (engine, root)


@pytest.mark.parametrize("path", ["run_query_batch", "executor"])
@pytest.mark.parametrize("engine", DIROPT_ENGINE_NAMES)
def test_dispatch_span_counts_uniform_and_mixed_levels(twin_hubs, engine,
                                                       path):
    from repro.core.engine import run_query_batch, run_query_buckets
    from repro.obs.trace import Tracer, set_tracer
    from repro.planner.optimize import RootBucket

    roots = BATCHES["mixed"]
    q = RecursiveQuery(engine, 5, 0, _caps(len(_TWIN_SRC), "outbound"))
    want = _agreement(np.stack([np.asarray(run_query(q, twin_hubs,
                                                     r).level_dirs)
                                for r in roots]))
    assert want[1] == 1

    def dispatch_attrs(tracer):
        prev = set_tracer(tracer)
        try:
            if path == "executor":
                run_query_buckets(q, twin_hubs, [RootBucket(
                    indices=tuple(range(len(roots))), roots=roots,
                    caps=q.caps, predicted_reach=41, predicted_depth=4)])
            else:
                run_query_batch(q, twin_hubs, roots)
        finally:
            set_tracer(prev)
        (span,) = [r for r in tracer.records
                   if r["type"] == "span" and r["name"] == "dispatch"]
        return span["attrs"]

    attrs = dispatch_attrs(Tracer())
    assert (attrs["levels_uniform"], attrs["levels_mixed"]) == want
    # without level events the path reads nothing back for them
    quiet = dispatch_attrs(Tracer(level_events=False))
    assert "levels_uniform" not in quiet and "levels_mixed" not in quiet


# ---------------------------------------------------------------------------
# hypothesis extension (real package, or the vendored fallback engine)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                       # pragma: no cover
    pass
else:
    @settings(max_examples=2, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_diropt_matches_push_only_random(seed):
        _check_diropt_parity(seed)

    @settings(max_examples=2, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_forced_pull_matches_push_random(seed):
        _check_forced_pull(seed)

    @settings(max_examples=2, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_fused_both_view_equals_doubled_random(seed):
        _check_fused_equals_doubled(seed)
