"""Operator-algebra refactor guarantees.

* golden parity: every engine name produces IDENTICAL (values, count,
  depth) to the pre-refactor reference engines on a fixed seeded graph
  (constants below were captured by running the original monolithic
  engines of commit 2636a20 on TreeSpec(3000, 10, 4, seed=11));
* batched multi-root execution matches per-root sequential runs and is
  served by a single jitted dispatch;
* the positions contract: positional pipelines carry real edge positions,
  tuple/row pipelines mark them unavailable (all -1);
* per-row depth tracking matches the python BFS oracle;
* direction (outbound / inbound / both) is consistent across engines.
"""
import hashlib

import numpy as np
import pytest

from repro.core import EngineCaps
from repro.core import operators
from repro.core.engine import (ENGINE_NAMES, Dataset, RecursiveQuery,
                               plan_repr, positions_available, run_query,
                               run_query_batch)
from repro.data.treegen import TreeSpec, bfs_reference, make_edge_table

CAPS = EngineCaps(frontier=2048, result=4096)

# (count, depth, sha256(sorted ids)[:16], sum(sorted column2 payload)) per
# (engine, max_depth), captured from the pre-refactor engines.
_POSITIONAL_GOLDEN = {
    0: (61, 0, "702e2ad5216fae7b", -3.68),
    3: (816, 3, "df7c8c7255be3827", 5.651),
    7: (1898, 7, "b4e8619e95a1430f", -53.498),
}
# the dense engine's loop is emit-inside-the-body: depth runs one higher
_BITMAP_GOLDEN = {
    0: (61, 1, "702e2ad5216fae7b", -3.68),
    3: (816, 4, "df7c8c7255be3827", 5.651),
    7: (1898, 8, "b4e8619e95a1430f", -53.498),
}
# diropt shares bitmap's emit-inside-the-body loop accounting (its
# push-only counterpart); diropt_hybrid shares hybrid's positional one
GOLDEN = {(eng, d): (_BITMAP_GOLDEN if eng in ("bitmap", "diropt")
                     else _POSITIONAL_GOLDEN)[d]
          for eng in ENGINE_NAMES for d in (0, 3, 7)}


@pytest.fixture(scope="module")
def golden_dataset():
    spec = TreeSpec(num_vertices=3000, height=10, payload_cols=4, seed=11)
    table = make_edge_table(spec)
    ds = Dataset.prepare(table, spec.num_vertices)
    src = np.asarray(table.column("from"))
    dst = np.asarray(table.column("to"))
    levels = bfs_reference(src, dst, 0, 10, spec.num_vertices)
    return ds, levels


@pytest.mark.parametrize("engine", ENGINE_NAMES)
@pytest.mark.parametrize("depth", [0, 3, 7])
def test_golden_parity_with_pre_refactor_engines(golden_dataset, engine,
                                                 depth):
    ds, _ = golden_dataset
    r = run_query(RecursiveQuery(engine, depth, 4, CAPS), ds, 0)
    n = int(r.count)
    ids = np.sort(np.asarray(r.values["id"])[:n].astype(np.int64))
    h = hashlib.sha256(ids.tobytes()).hexdigest()[:16]
    pay = round(float(np.sort(
        np.asarray(r.values["column2"])[:n].ravel()).sum()), 3)
    assert (n, int(r.depth), h, pay) == GOLDEN[(engine, depth)]
    assert not bool(r.overflow)


@pytest.mark.parametrize("engine", ENGINE_NAMES)
def test_positions_contract(golden_dataset, engine):
    """Positional pipelines carry real positions; tuple/row pipelines mark
    them unavailable — explicit in Pipeline.carries_positions."""
    ds, _ = golden_dataset
    r = run_query(RecursiveQuery(engine, 4, 4, CAPS), ds, 0)
    n = int(r.count)
    pos = np.asarray(r.positions)
    if positions_available(engine):
        assert (pos[:n] >= 0).all() and (pos[:n] < ds.table.num_rows).all()
    else:
        assert (pos == -1).all()


EXPECT_POSITIONAL = {"precursive", "bitmap", "hybrid", "trecursive_rewrite",
                     "rowstore_rewrite", "rowstore_index_rewrite",
                     "diropt", "diropt_hybrid"}


def test_positions_contract_matches_expectation():
    got = {e for e in ENGINE_NAMES if positions_available(e)}
    assert got == EXPECT_POSITIONAL


@pytest.mark.parametrize("engine", ENGINE_NAMES)
def test_row_depth_tracking(golden_dataset, engine):
    """Every emitted row is tagged with its BFS level."""
    ds, levels = golden_dataset
    lvl_of = {p: i for i, s in enumerate(levels) for p in s}
    r = run_query(RecursiveQuery(engine, 5, 4, CAPS), ds, 0)
    n = int(r.count)
    rd = np.asarray(r.row_depths)[:n]
    if positions_available(engine):
        pos = np.asarray(r.positions)[:n]
        assert np.array_equal(rd, np.array([lvl_of[p] for p in pos]))
    else:
        # no positions: check the per-level cardinalities instead
        want = {i: len(levels[i]) for i in range(6) if levels[i]}
        got = dict(zip(*np.unique(rd, return_counts=True)))
        assert {int(k): int(v) for k, v in got.items()} == want


@pytest.mark.parametrize("engine", ENGINE_NAMES)
def test_batch_matches_sequential(golden_dataset, engine):
    """run_query_batch over >= 8 roots == per-root run_query, bit-exact."""
    ds, _ = golden_dataset
    roots = [0, 1, 2, 5, 17, 100, 2000, 2999]
    q = RecursiveQuery(engine, 4, 4, CAPS)
    rb = run_query_batch(q, ds, roots)
    assert rb.count.shape == (len(roots),)
    for i, root in enumerate(roots):
        r1 = run_query(q, ds, root)
        assert int(r1.count) == int(rb.count[i])
        assert int(r1.depth) == int(rb.depth[i])
        n = int(r1.count)
        for k in r1.values:
            assert np.array_equal(np.asarray(r1.values[k])[:n],
                                  np.asarray(rb.values[k][i])[:n]), (root, k)
        assert np.array_equal(np.asarray(r1.positions),
                              np.asarray(rb.positions[i]))
        assert np.array_equal(np.asarray(r1.row_depths),
                              np.asarray(rb.row_depths[i]))


def test_batch_is_single_jitted_dispatch(golden_dataset):
    """The whole batch is served by ONE jitted executable: repeat calls with
    the same plan hit the compile cache; the vmapped result carries the
    batch dimension."""
    ds, _ = golden_dataset
    q = RecursiveQuery("precursive", 3, 4, CAPS)
    run_query_batch(q, ds, list(range(8)))           # compile
    cache_size = getattr(operators._batch_impl, "_cache_size", None)
    before = cache_size() if cache_size else None
    rb = run_query_batch(q, ds, list(range(8, 16)))  # cached dispatch
    if cache_size:  # private jax API; skip the cache probe if it moves
        assert cache_size() == before
    assert rb.count.shape == (8,)


def _gather_scopes(eqns) -> set:
    """Operator scopes of every gather among ``eqns``, sub-jaxprs
    included."""
    from jax.extend import core as jcore

    found = set()
    for eqn in eqns:
        if eqn.primitive.name == "gather":
            found |= {s for s in ("DenseBitmapStep", "PullStep")
                      if s in str(eqn.source_info.name_stack)}
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    found |= _gather_scopes(sub.eqns)
    return found


@pytest.mark.parametrize("lanes", [1, 4])
def test_batched_switch_is_a_real_branch(golden_dataset, lanes):
    """Under fixed_point_batch's vmap the diropt switch branches on the
    lanes' vote, an unbatched index: push and pull gathers sit in separate
    branches (not both computed and merged by a select_n), and only a
    multi-lane batch has the third, mixed branch that holds both."""
    import jax
    import jax.numpy as jnp

    from repro.core.engine import build_plan, query_context

    ds, _ = golden_dataset
    q = RecursiveQuery("diropt", 4, 0, CAPS)
    plan = build_plan(q)
    jaxpr = jax.make_jaxpr(lambda c, r: operators.fixed_point_batch(
        plan, c, r, ds.num_vertices))(query_context(q, ds),
                                      jnp.arange(lanes, dtype=jnp.int32))
    (loop,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "while"]
    body = loop.params["body_jaxpr"].jaxpr
    (switch,) = [e for e in body.eqns if e.primitive.name == "cond"]
    assert switch.invars[0].aval.shape == ()          # unbatched index
    branches = [_gather_scopes(b.jaxpr.eqns)
                for b in switch.params["branches"]]
    assert branches[:2] == [{"DenseBitmapStep"}, {"PullStep"}]
    assert branches[2:] == ([] if lanes == 1
                            else [{"DenseBitmapStep", "PullStep"}])
    assert not _gather_scopes([e for e in body.eqns if e is not switch])


def test_direction_inbound_walks_ancestors(golden_dataset):
    ds, _ = golden_dataset
    src = np.asarray(ds.table.column("from"))
    dst = np.asarray(ds.table.column("to"))
    leaf = int(dst[-1])
    parent = {int(d): (i, int(s)) for i, (s, d) in enumerate(zip(src, dst))}
    anc, v = set(), leaf
    while v in parent:
        i, v = parent[v]
        anc.add(i)
    r = run_query(RecursiveQuery("precursive", 10, 4, CAPS,
                                 direction="inbound"), ds, leaf)
    n = int(r.count)
    assert set(np.asarray(r.positions)[:n].tolist()) == anc


def test_direction_both_consistent_across_engines(golden_dataset):
    ds, _ = golden_dataset
    root = int(np.asarray(ds.table.column("to"))[0])
    results = {}
    for eng in ("precursive", "trecursive", "bitmap"):
        r = run_query(RecursiveQuery(eng, 2, 4, CAPS, direction="both"),
                      ds, root)
        n = int(r.count)
        results[eng] = sorted(np.asarray(r.values["id"])[:n].tolist())
    assert results["precursive"] == results["trecursive"] == results["bitmap"]
    # undirected reach must strictly include the directed reach
    fwd = run_query(RecursiveQuery("precursive", 2, 4, CAPS), ds, root)
    assert len(results["precursive"]) > int(fwd.count)


def test_rowstore_rejects_non_outbound(golden_dataset):
    ds, _ = golden_dataset
    with pytest.raises(ValueError, match="outbound-only"):
        run_query(RecursiveQuery("rowstore", 3, 4, CAPS,
                                 direction="inbound"), ds, 0)


def test_plan_repr_is_derived_from_composition():
    """plan_repr must render the actual pipeline, not a template: every
    loop operator's description appears."""
    from repro.core.engine import build_plan
    q = RecursiveQuery("precursive", 4, 2, CAPS)
    plan = build_plan(q)
    rendered = plan_repr("precursive", 4, 2)
    for op in plan.ops:
        assert op.describe() in rendered
    assert plan.finisher.describe() in rendered
    assert plan.seed.describe().replace("$root", "0") in rendered
