"""The semiring value plane, end to end.

The refactor's contract has two halves, and this suite pins both:

* **reach is bit-identical** — the boolean workload now runs through the
  same split ⊗-propagate / ⊕-combine operators as the weighted ones, with
  ``or_combine`` as its ⊕.  ``tests/golden/reach_parity.json`` froze the
  EXACT pre-refactor output (positions in emission order, ids, depths,
  overflow) of every engine x direction on two seeded graphs;
  ``test_reach_golden_parity`` replays all of it and compares bytes, not
  row sets.
* **weighted workloads are correct** — (min, +) shortest path keeps the
  MINIMUM distance over competing paths (the satellite-1 regression: a
  2-hop detour must beat a heavier direct edge), walk aggregations fold
  ``⊕ over paths of ⊗ over edges`` exactly like the UNION ALL reference,
  and the whole planner/serving/plan-store stack carries the workload
  axis: SQL with ``t.depth + e.w`` or ``SUM(t.value * e.qty)`` plans onto
  the weighted engines, buckets through the shared executor, survives an
  EXPLAIN round trip at schema v5 and a plan-store rehydration.

The ``spmm_segment`` cells check the dense ⊕-combine kernel (satellite
2): interpret-mode parity against the jnp reference and a finite measured
kernel factor for the cost model.
"""
import json
import os

import numpy as np
import pytest

from repro.core import EngineCaps
from repro.core.engine import (ENGINE_NAMES, WEIGHTED_ENGINE_NAMES, Dataset,
                               RecursiveQuery, build_plan, run_query,
                               run_query_batch)
from repro.core.semiring import (SEMIRINGS, WORKLOADS, get_semiring,
                                 or_combine)
from repro.core.table import ColumnTable

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
DIRECTIONS = ("outbound", "inbound", "both")


def _edge_dataset(src, dst, num_vertices, w=None, payload=4):
    e = len(src)
    cols = {
        "id": np.arange(e, dtype=np.int32),
        "from": np.asarray(src, np.int32),
        "to": np.asarray(dst, np.int32),
        "name": np.zeros((e, payload), np.float32)}
    if w is not None:
        cols["w"] = np.asarray(w, np.float32)
    return Dataset.prepare(ColumnTable.from_numpy(cols), num_vertices)


def _weighted_query(engine, workload, *, max_depth, caps,
                    direction="outbound"):
    return RecursiveQuery(engine=engine, max_depth=max_depth,
                          payload_cols=0, caps=caps, dedup=False,
                          direction=direction, workload=workload,
                          weight_col="w")


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

def test_semiring_registry():
    assert set(SEMIRINGS) == {"shortest_path", "aggregate_sum",
                              "aggregate_max", "aggregate_min",
                              "aggregate_mul"}
    assert WORKLOADS == ("reach", *SEMIRINGS)
    sp = get_semiring("shortest_path")
    assert sp.improving and np.isinf(sp.identity)
    assert get_semiring("aggregate_sum").identity == 0.0
    # 'reach' deliberately has NO registry entry: boolean BFS never goes
    # through the generic ⊕-scatter, so asking for it is a bug
    with pytest.raises(ValueError):
        get_semiring("reach")
    with pytest.raises(ValueError):
        get_semiring("nope")


def test_or_combine_is_the_boolean_plus():
    import jax.numpy as jnp
    acc = jnp.zeros(4, jnp.int32)
    out = or_combine(acc, jnp.asarray([1, 1, 3]), jnp.asarray([1, 1, 1]))
    assert out.tolist() == [0, 1, 0, 1]


# ---------------------------------------------------------------------------
# satellite 1: minimum distance survives competing paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", WEIGHTED_ENGINE_NAMES)
def test_sssp_min_distance_regression(engine):
    """0→1 direct costs 10; 0→2→1 costs 2.  A max/last-writer dedup
    scatter (the pre-refactor ``.at[...].max``) would keep 10."""
    ds = _edge_dataset([0, 0, 2], [1, 2, 1], 3, w=[10.0, 1.0, 1.0])
    caps = EngineCaps(frontier=32, result=64)
    q = _weighted_query(engine, "shortest_path", max_depth=4, caps=caps)
    r = run_query(q, ds, 0)
    vv = np.asarray(r.vertex_values)
    assert vv[0] == 0.0
    assert vv[2] == 1.0
    assert vv[1] == 2.0, f"{engine} kept {vv[1]}, not the min-distance 2.0"


@pytest.mark.parametrize("engine", WEIGHTED_ENGINE_NAMES)
def test_sssp_label_correcting_convergence(engine):
    """A longer-hop cheaper path found AFTER a shorter-hop expensive one
    must still win: fixed_point converges on value stabilization, not on
    first visit (1-hop w=9 vs 3-hop w=3)."""
    ds = _edge_dataset([0, 0, 2, 3], [1, 2, 3, 1], 4,
                       w=[9.0, 1.0, 1.0, 1.0])
    caps = EngineCaps(frontier=32, result=64)
    q = _weighted_query(engine, "shortest_path", max_depth=6, caps=caps)
    vv = np.asarray(run_query(q, ds, 0).vertex_values)
    assert vv.tolist() == [0.0, 3.0, 1.0, 2.0]


# ---------------------------------------------------------------------------
# walk aggregation == the UNION ALL per-path fold
# ---------------------------------------------------------------------------

def _reference_fold(src, dst, w, root, max_depth, combine, prop, init, seed):
    """Per-vertex fold of ⊗-products over ALL depth-bounded walks from the
    root — the semantics of the UNION ALL recursive CTE the aggregate
    workloads replace."""
    vals = {root: seed}          # walk-value mass arriving at each vertex
    total = {root: seed}
    frontier = {root: seed}
    for _ in range(max_depth + 1):
        nxt = {}
        for s, d, wt in zip(src, dst, w):
            if s in frontier:
                x = prop(frontier[s], wt)
                nxt[d] = combine(nxt.get(d, init), x)
        if not nxt:
            break
        for k, v in nxt.items():
            total[k] = combine(total.get(k, init), v)
        frontier = nxt
    return total


@pytest.mark.parametrize("engine", WEIGHTED_ENGINE_NAMES)
@pytest.mark.parametrize("workload,combine,prop,init,seedv", [
    ("aggregate_sum", lambda a, b: a + b, lambda a, b: a * b, 0.0, 1.0),
    ("aggregate_max", max, lambda a, b: a * b, -np.inf, 1.0),
    ("aggregate_min", min, lambda a, b: a * b, np.inf, 1.0),
])
def test_aggregate_matches_reference_fold(engine, workload, combine, prop,
                                          init, seedv):
    rng = np.random.default_rng(5)
    v, e = 12, 20
    src = rng.integers(0, v, e)
    dst = rng.integers(0, v, e)
    w = rng.uniform(0.5, 2.0, e)
    depth = 3
    ds = _edge_dataset(src, dst, v, w=w)
    want = _reference_fold(src, dst, w, 0, depth, combine, prop, init, seedv)
    caps = EngineCaps(frontier=e + 16, result=4 * e + 16)
    q = _weighted_query(engine, workload, max_depth=depth, caps=caps)
    vv = np.asarray(run_query(q, ds, 0).vertex_values)
    for vertex, val in want.items():
        assert vv[vertex] == pytest.approx(val, rel=1e-5), (engine, vertex)


# ---------------------------------------------------------------------------
# the bit-identity anchor: pre-refactor reach golden, all engines x dirs
# ---------------------------------------------------------------------------

_GOLDEN_GRAPHS = (
    dict(seed=3, num_vertices=17, num_edges=40, max_depth=4),
    dict(seed=12, num_vertices=29, num_edges=70, max_depth=6),
)


def _golden_dataset(g):
    rng = np.random.default_rng(g["seed"])
    src = rng.integers(0, g["num_vertices"], size=g["num_edges"])
    dst = rng.integers(0, g["num_vertices"], size=g["num_edges"])
    table = ColumnTable.from_numpy({
        "id": np.arange(g["num_edges"], dtype=np.int32),
        "from": src.astype(np.int32),
        "to": dst.astype(np.int32),
        "name": rng.standard_normal(
            (g["num_edges"], 4)).astype(np.float32),
    })
    return Dataset.prepare(table, g["num_vertices"])


@pytest.mark.parametrize("g", _GOLDEN_GRAPHS,
                         ids=[f"g{g['seed']}" for g in _GOLDEN_GRAPHS])
def test_reach_golden_parity(g):
    """Every engine x legal direction reproduces the pre-refactor snapshot
    EXACTLY — counts, final depth, overflow, positions in emission order,
    ids, row depths.  Regenerate only for an intended output change:
    ``PYTHONPATH=src python scripts/gen_reach_golden.py``."""
    with open(os.path.join(GOLDEN_DIR, "reach_parity.json")) as f:
        golden = json.load(f)
    ds = _golden_dataset(g)
    caps = EngineCaps(frontier=g["num_edges"] + 16,
                      result=4 * g["num_edges"] + 16)
    compared = 0
    for engine in ENGINE_NAMES:
        for direction in DIRECTIONS:
            key = f"g{g['seed']}/{engine}/{direction}"
            if key not in golden:
                continue
            q = RecursiveQuery(engine=engine, max_depth=g["max_depth"],
                               payload_cols=0, caps=caps,
                               direction=direction)
            r = run_query(q, ds, root=0)
            want = golden[key]
            assert int(r.count) == want["count"], key
            assert int(r.depth) == want["depth"], key
            assert bool(r.overflow) == want["overflow"], key
            assert np.asarray(r.positions).tolist() == want["positions"], key
            assert (np.asarray(r.values["id"]).tolist()
                    == want["ids"]), key
            if "row_depths" in want:
                assert (np.asarray(r.row_depths).tolist()
                        == want["row_depths"]), key
            compared += 1
    assert compared >= 20    # both graphs together cover all 50 cells


def test_reach_has_no_value_plane():
    ds = _golden_dataset(_GOLDEN_GRAPHS[0])
    caps = EngineCaps(frontier=64, result=176)
    q = RecursiveQuery(engine="precursive", max_depth=4, payload_cols=0,
                       caps=caps)
    r = run_query(q, ds, 0)
    assert r.vertex_values is None


# ---------------------------------------------------------------------------
# satellite 2: the dense ⊕-combine kernel
# ---------------------------------------------------------------------------

def test_spmm_segment_interpret_parity():
    from repro.kernels.spmm_segment import spmm_segment, spmm_segment_ref
    rng = np.random.default_rng(9)
    n, e, d = 37, 90, 4
    x = rng.standard_normal((n, d)).astype(np.float32)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    w = rng.random(e).astype(np.float32)
    # disable a few edges the WeightedDenseStep way: src index == n
    src[::7] = n
    ref = spmm_segment_ref(x, src, dst, w, n)
    got = spmm_segment(x, src, dst, w, n, use_pallas=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_weighted_dense_kernel_path_matches_plain():
    """The bitmap plan with ``use_kernel=True`` (spmm_segment ⊕-combine in
    interpret mode) returns the same distances as the plain scatter."""
    from repro.core.bitmap import weighted_bitmap_plan
    from repro.core.operators import execute
    rng = np.random.default_rng(4)
    v, e = 24, 60
    src = rng.integers(0, v, e)
    dst = rng.integers(0, v, e)
    w = rng.uniform(0.5, 2.0, e)
    ds = _edge_dataset(src, dst, v, w=w)
    caps = EngineCaps(frontier=e + 16, result=4 * e + 16)
    q = _weighted_query("bitmap", "shortest_path", max_depth=5, caps=caps)
    plain = run_query(q, ds, 0)
    kplan = weighted_bitmap_plan(caps, 5, q.out_cols, "shortest_path",
                                 use_kernel=True)
    ctx = ds.context("outbound", weight_col="w")
    kern = execute(kplan, ctx, 0, ds.num_vertices)
    np.testing.assert_allclose(np.asarray(kern.vertex_values),
                               np.asarray(plain.vertex_values),
                               rtol=1e-5, atol=1e-5)


def test_measured_spmm_kernel_factor():
    from repro.planner.calibrate import KERNEL_NAMES, measured_kernel_factor
    assert "spmm_segment" in KERNEL_NAMES
    f = measured_kernel_factor(kernel="spmm_segment")
    assert np.isfinite(f) and f > 0.0
    # cached per (backend, kernel)
    assert measured_kernel_factor(kernel="spmm_segment") == f


# ---------------------------------------------------------------------------
# planner + serving + plan store: the workload axis end to end
# ---------------------------------------------------------------------------

def _weighted_graph_dataset():
    rng = np.random.default_rng(21)
    v, e = 50, 140
    src = rng.integers(0, v, e)
    dst = rng.integers(0, v, e)
    w = rng.uniform(0.5, 3.0, e)
    return _edge_dataset(src, dst, v, w=w), v, e


def test_weighted_sql_plans_onto_weighted_engines():
    from repro.planner import plan
    from repro.planner.ast import parse, weighted_listing
    ds, v, e = _weighted_graph_dataset()
    caps = EngineCaps(frontier=e + 16, result=4 * e + 16)
    sql = weighted_listing("shortest_path", root=0, depth=6, weight_col="w")
    ast = parse(sql)
    assert ast.workload == "shortest_path" and ast.weight_col == "w"
    report = plan(sql, ds, caps=caps)
    ranked = {c.label for c in report.ranked}
    assert ranked == set(WEIGHTED_ENGINE_NAMES)
    reasons = dict(report.skipped)
    for eng in ENGINE_NAMES:
        if eng not in WEIGHTED_ENGINE_NAMES:
            assert "value plane" in reasons[eng], eng
    # dressed rows carry the value column, min-folded per vertex
    r = report.best.run(ds, 0)
    n = int(r.count)
    vals = np.asarray(r.values["value"])[:n]
    tos = np.asarray(r.values["to"])[:n]
    vv = np.asarray(r.vertex_values)
    for t, val in zip(tos, vals):
        assert val >= vv[int(t)] - 1e-6


def test_weighted_aggregate_sql_round_trip():
    from repro.planner import plan
    from repro.planner.ast import parse, weighted_listing
    ds, v, e = _weighted_graph_dataset()
    caps = EngineCaps(frontier=e + 16, result=4 * e + 16)
    sql = weighted_listing("aggregate_sum", root=0, depth=3, weight_col="w")
    ast = parse(sql)
    assert ast.workload == "aggregate_sum" and ast.union_all
    report = plan(sql, ds, caps=caps)
    assert report.best.label in WEIGHTED_ENGINE_NAMES
    r = report.best.run(ds, 0)
    assert r.vertex_values is not None and int(r.count) > 0


def test_weighted_serving_and_plan_store_round_trip(tmp_path):
    from repro.planner.ast import weighted_listing
    from repro.planner.explain import PLAN_SCHEMA_VERSION
    from repro.planner.plan_store import migrate_plan_doc
    from repro.planner.serving import ServingSession, shape_key
    ds, v, e = _weighted_graph_dataset()
    caps = EngineCaps(frontier=e + 16, result=4 * e + 16)
    sql = weighted_listing("shortest_path", root=0, depth=6, weight_col="w")
    roots = [0, 3, 7]

    sess = ServingSession(ds, caps=caps)
    out = sess.submit(sql, roots)
    assert len(out) == len(roots)
    entry = sess.plan_for(sql, roots)
    assert shape_key(entry.report.logical)[-2:] == ("shortest_path", "w")

    doc = sess.explain_analyze(sql, roots)
    assert doc["schema_version"] == PLAN_SCHEMA_VERSION
    assert doc["logical"]["workload"] == "shortest_path"
    assert doc["logical"]["weight_col"] == "w"
    assert doc["analyze"]["mode"] == "serving"

    def _min_fold(r):
        n = int(r.count)
        out = {}
        for t, val in zip(np.asarray(r.values["to"])[:n],
                          np.asarray(r.values["value"])[:n]):
            t = int(t)
            out[t] = min(out.get(t, np.inf), float(val))
        return out

    path = str(tmp_path / "store.json")
    sess.save_plan_store(path)
    warm = ServingSession(ds, caps=caps, plan_store=path)
    out2 = warm.submit(sql, roots)
    assert warm.counters["parse_calls"] == 0
    assert warm.counters["cost_calls"] == 0
    for a, b in zip(out, out2):
        fa, fb = _min_fold(a), _min_fold(b)
        assert set(fa) == set(fb)
        for k in fa:
            assert fa[k] == pytest.approx(fb[k], rel=1e-6)


def test_plan_doc_v4_migrates_with_reach_defaults():
    from repro.planner.ast import weighted_listing
    from repro.planner.explain import PLAN_SCHEMA_VERSION
    from repro.planner.plan_store import migrate_plan_doc
    from repro.planner.serving import ServingSession
    ds, v, e = _weighted_graph_dataset()
    caps = EngineCaps(frontier=e + 16, result=4 * e + 16)
    sess = ServingSession(ds, caps=caps)
    doc = sess.plan_json(
        weighted_listing("shortest_path", root=0, depth=4, weight_col="w"),
        [0])
    v4 = json.loads(json.dumps(doc))
    v4["schema_version"] = 4
    v4["logical"].pop("workload", None)
    v4["logical"].pop("weight_col", None)
    for c in v4.get("candidates", []):
        c.pop("semiring", None)
    m = migrate_plan_doc(v4)
    assert m["schema_version"] == PLAN_SCHEMA_VERSION
    assert m["logical"]["workload"] == "reach"
    assert m["logical"]["weight_col"] is None
    assert all(c.get("semiring") == "reach"
               for c in m.get("candidates", []))


def test_plan_signature_carries_workload():
    from repro.planner.calibrate import plan_signature
    caps = EngineCaps(frontier=64, result=64)
    a = plan_signature("precursive", "outbound", caps, "digest",
                       workload="shortest_path")
    b = plan_signature("precursive", "outbound", caps, "digest")
    assert a != b
    assert a[-1] == "shortest_path" and b[-1] == "reach"


def test_weighted_plan_golden_snapshot():
    """The weighted plan document (schema v5) is golden-snapshotted like
    the three reach listings: an unintended costing or schema change for
    the weighted path must show up as a diff.  Regenerate with
    ``PYTHONPATH=src python scripts/gen_plan_weighted_golden.py`` after an
    INTENDED change."""
    from repro.planner import explain_json
    from repro.planner.ast import weighted_listing
    ds, v, e = _weighted_graph_dataset()
    caps = EngineCaps(frontier=e + 16, result=4 * e + 16)
    sql = weighted_listing("shortest_path", root=0, depth=6, weight_col="w")
    got = explain_json(sql, ds, caps=caps)
    with open(os.path.join(GOLDEN_DIR, "plan_weighted.json")) as f:
        want = json.load(f)
    assert got == want
    assert json.loads(json.dumps(got)) == want


# ---------------------------------------------------------------------------
# weighted buckets through the shared executor
# ---------------------------------------------------------------------------

def test_weighted_bucketed_dispatch_matches_lockstep():
    from repro.core.engine import dispatch_buckets
    from repro.planner.ast import normalize, parse, weighted_listing
    from repro.planner.optimize import bucket_roots, plan
    ds, v, e = _weighted_graph_dataset()
    caps = EngineCaps(frontier=e + 16, result=4 * e + 16)
    sql = weighted_listing("shortest_path", root=0, depth=6, weight_col="w")
    lg = normalize(parse(sql), ds)
    best = plan(lg, ds, caps=caps).best
    roots = [0, 5, 9, 14, 20]
    buckets = bucket_roots(ds, roots, direction="outbound", max_depth=6,
                           dedup=best.query.dedup, caps=caps, max_buckets=3)

    import dataclasses as _dc

    def _dispatch(i, b, bcaps):
        q = (best.query if bcaps == best.query.caps
             else _dc.replace(best.query, caps=bcaps))
        return run_query_batch(q, ds, list(b.roots))

    out = dispatch_buckets(buckets, _dispatch, fallback_caps=caps,
                           to_host=False)
    lockstep = run_query_batch(best.query, ds, roots)
    for i, r in enumerate(out):
        np.testing.assert_allclose(
            np.asarray(r.vertex_values),
            np.asarray(lockstep.vertex_values[i]), rtol=1e-5, atol=1e-5)
