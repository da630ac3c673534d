"""Reach-bucketed batch serving: root-conditional estimates, bucketed
parity, the plan cache, the machine-readable plan, and the batched-driver
early exit.

The load-bearing guarantees:

* bucketed ``run_query_buckets`` is ROW-FOR-ROW identical to a Python loop
  of ``run_query`` over the same roots — all nine engines x every legal
  direction, on random graphs (seeded slice always runs; hypothesis extends
  it when installed);
* bucket caps never exceed the global caps (a bucket can only SHRINK a
  lane's padding, never grow the worst case);
* root-conditional estimates are EXACT for sampled roots and
  degree-conditioned otherwise;
* ``default_caps`` sizes raw UNION ALL walks from the walk profile — a
  cyclic walk legally emitting far more than 4E rows no longer dies with a
  spurious capacity-overflow RuntimeError;
* ``PhysicalChoice.run`` applies one identical root coercion on the kernel
  and non-kernel paths;
* the serving session caches plans per (shape, direction, bucket
  signature) and its JSON plan round-trips through ``json.dumps``;
* the batched fixed-point driver freezes converged lanes (per-lane depth
  is exact, not the bucket's worst).
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.core import EngineCaps
from repro.core.engine import (ENGINE_NAMES, Dataset, RecursiveQuery,
                               run_query, run_query_batch,
                               run_query_buckets)
from repro.core.table import ColumnTable
from repro.data.treegen import TreeSpec, make_edge_table
from repro.planner import (ServingSession, bucket_roots, default_caps,
                           paper_listing, plan, root_estimates, to_json)
from repro.planner.ast import LogicalQuery
from repro.planner.optimize import RootBucket

CAPS = EngineCaps(frontier=2048, result=4096)


def _edge_dataset(src, dst, num_vertices, payload_cols=0):
    e = len(src)
    cols = {
        "id": np.arange(e, dtype=np.int32),
        "from": np.asarray(src, np.int32),
        "to": np.asarray(dst, np.int32),
        "name": np.zeros((e, 4), np.float32)}
    for i in range(payload_cols):
        cols[f"column{i + 1}"] = np.full((e,), float(i), np.float32)
    return Dataset.prepare(ColumnTable.from_numpy(cols), num_vertices)


@pytest.fixture(scope="module")
def tree_ds():
    spec = TreeSpec(num_vertices=3000, height=10, payload_cols=2, seed=11)
    return Dataset.prepare(make_edge_table(spec), spec.num_vertices)


def _assert_same_result(got, want, key):
    n = int(want.count)
    assert int(got.count) == n, key
    assert int(got.depth) == int(want.depth), key
    for k in want.values:
        assert np.array_equal(np.asarray(got.values[k])[:n],
                              np.asarray(want.values[k])[:n]), (key, k)
    if want.row_depths is not None:
        assert np.array_equal(np.asarray(got.row_depths)[:n],
                              np.asarray(want.row_depths)[:n]), key


# ---------------------------------------------------------------------------
# root-conditional estimates
# ---------------------------------------------------------------------------

def test_root_estimate_exact_for_sampled_roots(tree_ds):
    stats = tree_ds.stats("outbound")
    assert stats.root_profiles, "sample profiles must be recorded"
    root, profile = stats.root_profiles[0]
    est = stats.estimate_root(root, out_degree=1, max_depth=4)
    assert est.exact
    assert est.reach_rows == float(sum(profile[:5]))
    assert est.max_level_rows == float(max(profile[:5], default=0))


def test_root_estimate_degree_conditioned_for_unsampled(tree_ds):
    stats = tree_ds.stats("outbound")
    sampled = {r for r, _ in stats.root_profiles}
    indptr = np.asarray(tree_ds.context("outbound").csr.indptr)
    unsampled = next(v for v in range(tree_ds.num_vertices)
                     if v not in sampled and indptr[v + 1] - indptr[v] > 0)
    deg = int(indptr[unsampled + 1] - indptr[unsampled])
    est = stats.estimate_root(unsampled, deg, max_depth=6)
    assert not est.exact
    assert est.reach_rows >= deg          # level 0 is the degree, exactly
    # a leaf predicts zero reach, exactly
    leaf = next(v for v in range(tree_ds.num_vertices)
                if indptr[v + 1] - indptr[v] == 0)
    leaf_est = stats.estimate_root(leaf, 0, max_depth=6)
    assert leaf_est.exact and leaf_est.reach_rows == 0.0


def test_root_estimates_batch_helper(tree_ds):
    ests = root_estimates(tree_ds, "outbound", [0, 1, 2999], max_depth=4)
    assert len(ests) == 3
    assert [e.root for e in ests] == [0, 1, 2999]
    assert all(e.reach_rows >= 0 for e in ests)


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------

def test_bucket_caps_never_exceed_global(tree_ds):
    roots = [0, 1, 5, 77, 500, 1500, 2999]
    buckets = bucket_roots(tree_ds, roots, direction="outbound",
                           max_depth=6, dedup=True, caps=CAPS,
                           max_buckets=4)
    assert 1 <= len(buckets) <= 4
    covered = sorted(i for b in buckets for i in b.indices)
    assert covered == list(range(len(roots)))
    for b in buckets:
        assert b.caps.frontier <= CAPS.frontier
        assert b.caps.result <= CAPS.result
        for lane in b.indices:
            assert b.roots[b.indices.index(lane)] == roots[lane]


def test_bucket_roots_union_all_falls_back_to_single_bucket(tree_ds):
    buckets = bucket_roots(tree_ds, [0, 1, 2], direction="outbound",
                           max_depth=3, dedup=False, caps=CAPS)
    assert len(buckets) == 1
    assert buckets[0].caps == CAPS


def test_bucketed_overflow_falls_back_to_global_caps(tree_ds):
    # deliberately absurd bucket caps: the fallback must restore parity
    q = RecursiveQuery(engine="precursive", max_depth=4, payload_cols=0,
                       caps=CAPS)
    roots = (0, 1)
    bad = RootBucket(indices=(0, 1), roots=roots,
                     caps=EngineCaps(frontier=2, result=2),
                     predicted_reach=1.0, predicted_depth=1)
    got = run_query_buckets(q, tree_ds, (bad,))
    for i, r in enumerate(roots):
        _assert_same_result(got[i], run_query(q, tree_ds, r), r)


# ---------------------------------------------------------------------------
# parity property: bucketed batch == sequential loop, all engines
# ---------------------------------------------------------------------------

def _legal(engine, direction, dedup=True):
    if direction != "outbound" and engine.startswith("rowstore"):
        return False
    if not dedup and engine in ("bitmap", "hybrid"):
        return False
    return True


def _check_bucketed_parity(seed):
    rng = np.random.default_rng(seed)
    v = int(rng.integers(6, 60))
    e = int(rng.integers(2, 4 * v))
    src = rng.integers(0, v, e).astype(np.int32)
    dst = rng.integers(0, v, e).astype(np.int32)
    ds = _edge_dataset(src, dst, v)
    depth = int(rng.integers(1, 6))
    nroots = int(rng.integers(2, 7))
    roots = rng.integers(0, v, nroots).tolist()
    caps = EngineCaps(frontier=e + 16, result=e + 16)
    for direction in ("outbound", "inbound", "both"):
        buckets = bucket_roots(ds, roots, direction=direction,
                               max_depth=depth, dedup=True, caps=caps)
        for eng in ENGINE_NAMES:
            if not _legal(eng, direction):
                continue
            q = RecursiveQuery(engine=eng, max_depth=depth, payload_cols=0,
                               caps=caps, direction=direction)
            got = run_query_buckets(q, ds, buckets)
            assert len(got) == len(roots)
            for i, r in enumerate(roots):
                _assert_same_result(got[i], run_query(q, ds, r),
                                    (eng, direction, r, seed))


@pytest.mark.parametrize("seed", [0, 7])
def test_bucketed_batch_matches_sequential_loop_seeded(seed):
    """Deterministic slice of the property (always runs, even without
    hypothesis)."""
    _check_bucketed_parity(seed)


try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                       # pragma: no cover
    pass
else:
    @settings(max_examples=3, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_bucketed_batch_matches_sequential_loop_random(seed):
        _check_bucketed_parity(seed)


def test_batched_driver_freezes_converged_lanes(tree_ds):
    """Per-lane depth must be the lane's OWN convergence depth, not the
    bucket's worst — converged lanes are frozen inside the while_loop."""
    q = RecursiveQuery(engine="precursive", max_depth=8, payload_cols=0,
                       caps=CAPS)
    indptr = np.asarray(tree_ds.context("outbound").csr.indptr)
    leaf = next(v for v in range(tree_ds.num_vertices)
                if indptr[v + 1] - indptr[v] == 0)
    roots = [0, leaf]                      # deep hub + depth-0 leaf
    r = run_query_batch(q, tree_ds, roots)
    assert int(r.depth[1]) == 0
    assert int(r.depth[0]) == int(run_query(q, tree_ds, 0).depth)
    assert int(r.depth[0]) > 0


# ---------------------------------------------------------------------------
# satellite: PhysicalChoice.run root coercion, kernel and non-kernel paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("mk_roots", [
    lambda: [1, 2, 5],                               # Python list
    lambda: np.array([1, 2, 5], dtype=np.int64),     # int64 vector
], ids=["pylist", "int64"])
def test_physical_choice_run_coerces_roots(tree_ds, use_kernel, mk_roots):
    sql = paper_listing(1, root=0, depth=3)
    report = plan(sql, tree_ds, caps=CAPS, include_kernel=use_kernel)
    if use_kernel:
        choice = next(c for c in report.ranked if c.use_kernel)
    else:
        choice = next(c for c in report.ranked if not c.use_kernel)
    got = choice.run(tree_ds, mk_roots())
    want = choice.run(tree_ds, np.array([1, 2, 5], dtype=np.int32))
    for i in range(3):
        n = int(np.asarray(want.count)[i])
        assert int(np.asarray(got.count)[i]) == n
        for k in want.values:
            assert np.array_equal(np.asarray(got.values[k])[i][:n],
                                  np.asarray(want.values[k])[i][:n])


# ---------------------------------------------------------------------------
# satellite: cyclic UNION ALL walks are sized from the walk profile
# ---------------------------------------------------------------------------

def _parallel_chain(hops, width=2):
    """A chain of ``hops`` hops with ``width`` parallel edges per hop: a
    depth-d walk emits width^(l+1) rows at level l — far more than 4E."""
    src, dst = [], []
    for h in range(hops):
        for _ in range(width):
            src.append(h)
            dst.append(h + 1)
    return _edge_dataset(src, dst, hops + 1)


def test_union_all_walk_caps_cover_path_blowup():
    hops, depth = 12, 11
    ds = _parallel_chain(hops)
    # rows at level l: 2^(l+1); total over levels 0..11 = 2^13 - 2 = 8190,
    # while 4E = 96 and the old clamp allowed only max(4E, 4096) = 4096
    want_rows = sum(2 ** (l + 1) for l in range(depth + 1))
    lq = LogicalQuery(root=0, max_depth=depth, payload_cols=0, dedup=False,
                      direction="outbound", want_cols=("id", "to"),
                      want_depth=False, union_all=True)
    stats = ds.stats("outbound")
    caps = default_caps(stats, lq)
    assert caps.result >= want_rows
    assert caps.frontier >= 2 ** (depth + 1)
    report = plan(lq, ds)
    r = report.best.run(ds, 0)            # raised RuntimeError before
    assert int(r.count) == want_rows
    assert not bool(np.asarray(r.overflow))


def test_union_all_walk_estimate_extrapolates_past_sample():
    # a doubling RING: walks never die and the sampled walk profile is
    # truncated at its horizon, so a deeper bound must be covered by the
    # geometric extrapolation, not flatline at the sampled sum
    src = [0, 0, 1, 1, 2, 2]
    dst = [1, 1, 2, 2, 0, 0]
    ds = _edge_dataset(src, dst, 3)
    stats = ds.stats("outbound")
    horizon = len(stats.level_walk_edges)
    deeper = horizon + 5
    assert stats.total_walk_rows(deeper) > stats.total_walk_rows(
        horizon - 1) * 8


def test_terminated_walk_does_not_extrapolate(tree_ds):
    """Regression: a walk whose frontier DIED inside the sample horizon
    (e.g. any acyclic graph) must not be geometrically extrapolated — a
    deep depth bound adds nothing past the walk's last live level, so
    non-dedup caps stay proportional to the true walk size."""
    stats = tree_ds.stats("outbound")
    horizon = len(stats.level_walk_edges)
    assert stats.total_walk_rows(horizon + 50) == \
        stats.total_walk_rows(horizon - 1)
    lq = LogicalQuery(root=0, max_depth=horizon + 50, payload_cols=0,
                      dedup=False, direction="outbound",
                      want_cols=("id",), want_depth=False, union_all=True)
    caps = default_caps(stats, lq)
    assert caps.result <= 8 * stats.total_walk_rows(horizon - 1) + 4096


def test_dedup_caps_unchanged_by_walk_sizing(tree_ds):
    lq = LogicalQuery(root=0, max_depth=5, payload_cols=0, dedup=True,
                      direction="outbound", want_cols=("id",),
                      want_depth=False, union_all=False)
    stats = tree_ds.stats("outbound")
    caps = default_caps(stats, lq)
    assert caps.result == stats.num_edges + 8


# ---------------------------------------------------------------------------
# the serving session + plan cache + machine-readable plan
# ---------------------------------------------------------------------------

def _row_set(r):
    """Order-insensitive view of a dressed result: sorted (id, depth)
    pairs.  Session-level parity is row-SET parity — each bucket runs its
    own chosen engine, and engines are free to order rows differently."""
    n = int(r.count)
    ids = np.asarray(r.values["id"])[:n].tolist()
    depths = (np.asarray(r.values["depth"])[:n].tolist()
              if "depth" in r.values else
              np.asarray(r.row_depths)[:n].tolist())
    return sorted(zip(ids, depths))


def test_serving_session_caches_plans(tree_ds):
    sql = paper_listing(1, root=0, depth=4)
    session = ServingSession(tree_ds, caps=CAPS)
    roots = [0, 1, 2, 3]
    first = session.submit(sql, roots)
    again = session.submit(sql, roots)
    assert session.stats["plan_misses"] == 1
    assert session.stats["plan_hits"] == 1
    assert session.stats["cached_shapes"] == 1
    for a, b in zip(first, again):
        _assert_same_result(a, b, "cache hit changed the answer")
    # per-root row-set parity with the planner's single-root path
    for i, r in enumerate(roots):
        want = plan(sql, tree_ds, caps=CAPS).best.run(tree_ds, r)
        assert _row_set(again[i]) == _row_set(want), r


def test_serving_session_rebinds_same_signature(tree_ds):
    """Same shape + same bucket signature with DIFFERENT roots must reuse
    the cached plan (hit) and still answer for the new roots."""
    sql = paper_listing(1, root=0, depth=4)
    session = ServingSession(tree_ds, caps=CAPS)
    session.submit(sql, [10, 11])
    got = session.submit(sql, [12, 13])
    if session.stats["plan_misses"] == 1:      # identical signature
        assert session.stats["plan_hits"] == 1
    for i, r in enumerate([12, 13]):
        want = plan(sql, tree_ds, caps=CAPS).best.run(tree_ds, r)
        assert _row_set(got[i]) == _row_set(want), r


def test_serving_permuted_roots_keep_request_order(tree_ds):
    """Regression: a repeat request whose roots are a PERMUTATION of a
    cached entry's roots (same bucket signature) must still return results
    in ITS OWN request order, not the cached lane mapping's."""
    sql = paper_listing(1, root=0, depth=4)
    session = ServingSession(tree_ds, caps=CAPS)
    fwd = [0, 1]                 # hub first
    rev = [1, 0]                 # hub last — likely the same signature
    a = session.submit(sql, fwd)
    b = session.submit(sql, rev)
    for i in range(2):
        want = plan(sql, tree_ds, caps=CAPS).best.run(tree_ds, rev[i])
        assert _row_set(b[i]) == _row_set(want), rev[i]
    assert _row_set(a[0]) == _row_set(b[1])
    assert _row_set(a[1]) == _row_set(b[0])
    # and an identical repeat is a true hit (no rebind)
    before = session.plan_for(sql, rev).roots
    session.submit(sql, rev)
    assert session.plan_for(sql, rev).roots == before == tuple(rev)


def test_serving_per_bucket_engine_choice(tree_ds):
    """Buckets are re-costed with their own caps AND lane counts: the
    cached plan records one engine per bucket, and every per-bucket
    engine is a legal candidate of the shape-level report — or the
    batch-only bit-parallel ``multiquery`` engine, which only a
    multi-lane bucket can admit (``lanes == len(bucket.roots) > 1``)."""
    sql = paper_listing(1, root=0, depth=4)
    session = ServingSession(tree_ds, caps=CAPS)
    roots = [0, 1, 2, 3]
    session.submit(sql, roots)
    entry = session.plan_for(sql, roots)
    assert len(entry.bucket_choices) == len(entry.buckets)
    legal = {c.label for c in entry.report.ranked}
    for c, b in zip(entry.bucket_choices, entry.buckets):
        if c.label == "multiquery":
            assert c.query.lanes == len(b.roots) > 1
        else:
            assert c.label in legal
    for bj, c in zip(entry.plan_json["buckets"], entry.bucket_choices):
        assert bj["engine"] == c.label


def test_plan_json_schema_and_roundtrip(tree_ds):
    sql = paper_listing(1, root=0, depth=4)
    session = ServingSession(tree_ds, caps=CAPS)
    doc = session.plan_json(sql, [0, 1, 2])
    text = json.dumps(doc)                     # strict-JSON serializable
    doc2 = json.loads(text)
    assert doc2["schema_version"] == 6
    assert doc2["analyze"] is None      # v4: filled by explain_analyze only
    assert doc2["admission"] is None    # v6: stamped by a guarded submit
    assert doc2["chosen"] in [c["label"] for c in doc2["candidates"]]
    assert sum(c["chosen"] for c in doc2["candidates"]) == 1
    assert doc2["logical"]["max_depth"] == 4
    assert doc2["stats"]["num_vertices"] == tree_ds.num_vertices
    # v2: full stats (rehydratable) + the constants the pass priced with
    assert doc2["stats"]["root_profiles"]
    assert "level_walk_edges" in doc2["stats"]
    assert doc2["cost_constants"]["bytes_per_us"] > 0
    for c in doc2["candidates"]:
        assert {"label", "engine", "caps", "cost", "ops"} <= set(c)
        assert c["cost"]["est_us"] > 0
        # the factor-independent byte split is consistent with the total
        kf = doc2["cost_constants"]["kernel_factor"] or 0.0
        assert c["cost"]["total_bytes"] == pytest.approx(
            c["cost"]["plain_bytes"] + kf * c["cost"]["kernel_bytes"])
    lanes = sorted(l for b in doc2["buckets"] for l in b["lanes"])
    assert lanes == [0, 1, 2]
    for b in doc2["buckets"]:
        assert b["caps"]["frontier"] <= CAPS.frontier
        assert b["caps"]["result"] <= CAPS.result


def test_to_json_without_buckets(tree_ds):
    report = plan(paper_listing(1, root=0, depth=4), tree_ds, caps=CAPS)
    doc = to_json(report)
    json.dumps(doc)
    assert "buckets" not in doc
    assert len(doc["candidates"]) == len(report.ranked)


def test_run_bucketed_matches_run(tree_ds):
    sql = paper_listing(2, root=0, depth=5, payload_cols=2)
    report = plan(sql, tree_ds, caps=CAPS)
    roots = [0, 1, 4, 2999]
    per_root = report.best.run_bucketed(tree_ds, roots)
    for i, r in enumerate(roots):
        want = report.best.run(tree_ds, r)
        _assert_same_result(per_root[i], want, r)


def test_submit_serves_a_bucket_with_an_evicted_lane():
    """A coalesced bucket in which only some lanes overflow their bucket
    caps: those lanes are evicted to solo re-dispatches, and the request
    still answers every root in full.  The evicted lane's bucket-caps
    overflow flag (and that of the padding lane repeating it) must not
    fail the bucket's overflow check — this graph and root mix once raised
    ``RuntimeError: capacity overflow`` here."""
    spec = TreeSpec(num_vertices=300, height=6, payload_cols=2, seed=230)
    ds = Dataset.prepare(make_edge_table(spec), spec.num_vertices)
    caps = EngineCaps(frontier=1024, result=2048)
    sql = paper_listing(1, root=0, depth=3)
    roots = [154, 42, 274]
    session = ServingSession(ds, caps=caps)
    out = session.submit(sql, roots)
    assert session.last_report.evictions >= 1
    assert not session.last_report.truncated
    solo = plan(sql, ds, caps=caps).best
    for root, r in zip(roots, out):
        assert _row_set(r) == _row_set(solo.run(ds, root))
