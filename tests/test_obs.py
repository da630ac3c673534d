"""Observability layer: metrics, structured tracing, EXPLAIN ANALYZE, and
the serving surface.

The load-bearing guarantees:

* the JSONL trace round-trips (``write_jsonl`` -> ``read_jsonl`` is the
  identity on records) and passes the CI checker
  (``scripts/check_trace.py``: header, span fields, id/parent forest, time
  nesting);
* spans NEST: every child span's interval sits inside its parent's, and
  each is also a profiler annotation, on the device trace's clock;
* a DISABLED tracer records nothing, and the uninstalled-tracer path
  returns one shared no-op context manager (the hot-path cost is an
  attribute read — the perf gate's ``disabled_tracer_ratio`` cell holds
  the measured cost at parity);
* EXPLAIN ANALYZE's actual per-operator rows are EXACT: derived from the
  executed ``BFSResult`` (``row_depths`` histogram == the fixed point's
  per-level emissions), and on graphs whose sampled stats are exact (a
  star: the only source vertex IS the sampled root) predicted == actual
  for every engine, including the per-level push/pull directions the
  direction-optimizing engines took;
* the serving session surfaces overflow retries (metrics counter +
  ``stats['overflow_retries']`` + a once-per-session warning) instead of
  absorbing them silently, and ``stats`` keeps every pre-observability
  key while adding histogram-backed latency quantiles.
"""
import json
import warnings

import numpy as np
import pytest

from repro.core import EngineCaps
from repro.core.engine import (DIROPT_ENGINE_NAMES, ENGINE_NAMES,
                               BucketTiming, Dataset, RecursiveQuery,
                               overflow_retry_count, run_query,
                               run_query_buckets)
from repro.core.table import ColumnTable
from repro.data.treegen import TreeSpec, make_edge_table
from repro.obs import (MetricsRegistry, Tracer, current_tracer, read_jsonl,
                       set_tracer, trace_span)
from repro.obs.metrics import Histogram
from repro.planner import (ServingSession, explain_analyze, paper_listing,
                           render_analyze)
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


def _star_dataset(spokes, payload_cols=0):
    """Vertex 0 -> 1..spokes.  The ONLY source vertex is 0, so the stats
    sampler's roots are exactly {0} and the frontier profile is EXACT —
    the graph where predicted must equal actual to the row."""
    src = np.zeros(spokes, np.int32)
    dst = np.arange(1, spokes + 1, dtype=np.int32)
    return _edge_dataset(src, dst, spokes + 1, payload_cols)


@pytest.fixture(scope="module")
def tree_ds():
    spec = TreeSpec(num_vertices=3000, height=10, payload_cols=4, seed=11)
    return Dataset.prepare(make_edge_table(spec), spec.num_vertices)


def _load_check_trace():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                        "check_trace.py")
    spec = importlib.util.spec_from_file_location("check_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_and_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "a counter")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g", "a gauge")
    g.set(7)
    g.inc(-2)
    assert g.value == 5
    # get-or-create returns the SAME instrument; kind mismatch is an error
    assert reg.counter("c_total") is c
    with pytest.raises(TypeError):
        reg.gauge("c_total")


def test_histogram_quantiles_bounded_memory():
    h = Histogram("h_us")
    for v in range(1, 1001):
        h.observe(float(v))
    snap = h.snapshot()
    assert snap["count"] == 1000
    assert snap["sum"] == pytest.approx(500500.0)
    assert snap["min"] == 1.0 and snap["max"] == 1000.0
    # log-bucketed: quantiles are approximate but bucket-bounded
    assert 350 <= snap["p50"] <= 700
    assert 800 <= snap["p95"] <= 1000
    assert 900 <= snap["p99"] <= 1000
    assert snap["p50"] <= snap["p95"] <= snap["p99"]
    # memory is the FIXED bucket vector, not the observation count
    assert len(h.counts) == len(h.bounds) + 1
    h.observe(1e12)                      # beyond the top bound -> overflow
    assert h.snapshot()["max"] == 1e12


def test_registry_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("repro_x_total", "help text").inc(2)
    reg.histogram("repro_lat_us", "latency").observe(5.0)
    text = reg.render_text()
    assert "# HELP repro_x_total help text" in text
    assert "# TYPE repro_x_total counter" in text
    assert "repro_x_total 2" in text
    assert "# TYPE repro_lat_us histogram" in text
    assert 'repro_lat_us_bucket{le="+Inf"} 1' in text
    assert "repro_lat_us_count 1" in text
    # cumulative buckets are monotone nondecreasing
    counts = [float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
              if line.startswith("repro_lat_us_bucket")]
    assert counts == sorted(counts)


# ---------------------------------------------------------------------------
# tracer: roundtrip, nesting, profiler annotations, disabled path
# ---------------------------------------------------------------------------

def test_trace_jsonl_roundtrip_and_checker(tmp_path):
    tr = Tracer(meta={"suite": "test_obs"})
    with tr.span("request", n=1):
        with tr.span("parse"):
            pass
        with tr.span("dispatch", engine="bitmap") as attrs:
            tr.event("level", level=0, dir="push", edges=4, frontier=1)
            attrs["rows"] = 4
    path = str(tmp_path / "trace.jsonl")
    tr.write_jsonl(path)
    back = read_jsonl(path)
    assert back == list(tr.iter_records())
    assert back[0]["type"] == "header"
    assert back[0]["meta"] == {"suite": "test_obs"}
    # the attrs dict mutated mid-span landed in the record
    disp = next(r for r in back if r.get("name") == "dispatch")
    assert disp["attrs"] == {"engine": "bitmap", "rows": 4}
    # the CI checker accepts it
    mod = _load_check_trace()
    assert mod.check_trace(back, min_spans=3) == []
    # ...and rejects a corrupted parent and a broken nesting
    bad = json.loads(json.dumps(back))
    next(r for r in bad if r.get("name") == "parse")["parent"] = 999
    assert any("parent 999" in e for e in mod.check_trace(bad))
    bad2 = json.loads(json.dumps(back))
    next(r for r in bad2 if r.get("name") == "parse")["ts_us"] = 1e9
    assert any("does not nest" in e for e in mod.check_trace(bad2))


def test_spans_nest_in_time():
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    spans = {r["id"]: r for r in tr.records if r["type"] == "span"}
    inner = next(r for r in spans.values() if r["name"] == "inner")
    outer = next(r for r in spans.values() if r["name"] == "outer")
    assert inner["parent"] == outer["id"] and outer["parent"] is None
    assert inner["ts_us"] >= outer["ts_us"]
    assert inner["ts_us"] + inner["dur_us"] \
        <= outer["ts_us"] + outer["dur_us"]


def _host_events(log_dir) -> list:
    """``(name, start_ns, end_ns, thread)`` of every host event in the one
    profile written under ``log_dir``."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, ln.name)
            for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]


def test_spans_are_profiler_annotations(tmp_path):
    import jax

    tr = Tracer()
    off = Tracer(enabled=False)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("window"):
            with tr.span("outer"):
                with tr.span("inner"):
                    with off.span("hidden"):
                        pass
    evs = {n: (s, e, th) for n, s, e, th in _host_events(tmp_path)}
    assert "span:hidden" not in evs       # a disabled tracer: no annotation
    win, outer, inner = evs["window"], evs["span:outer"], evs["span:inner"]
    # one thread, one clock: the spans nest inside the window annotation
    assert win[2] == outer[2] == inner[2]
    assert win[0] <= outer[0] <= inner[0]
    assert inner[1] <= outer[1] <= win[1]
    # ...and the records the tracer keeps are unchanged
    assert [r["name"] for r in tr.records] == ["inner", "outer"]


def test_serve_profile_holds_session_spans(tmp_path):
    import glob
    import gzip
    import types

    from repro.launch import serve

    args = types.SimpleNamespace(
        vertices=300, height=5, depth=3, batch=2, requests=2, trace=None,
        profile=str(tmp_path / "prof"), plan_store=None, no_guards=False,
        deadline_us=None, metrics=False)
    serve.serve_traversals(args)
    names = {n for n, _, _, _ in _host_events(args.profile)}
    assert {"span:request", "span:parse", "span:admission", "span:plan",
            "span:launch", "span:dispatch", "span:device_wait",
            "span:dress", "span:transfer"} <= names
    (perfetto,) = glob.glob(f"{args.profile}/**/perfetto_trace.json.gz",
                            recursive=True)
    doc = json.loads(gzip.open(perfetto).read())
    assert {"request", "dispatch"} <= {e.get("name")
                                       for e in doc["traceEvents"]}


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("x"):
        tr.event("y")
    assert tr.records == []
    prev = set_tracer(tr)
    try:
        assert current_tracer() is None      # disabled == not installed
        # the uninstalled/disabled hot path: ONE shared no-op context
        assert trace_span("a") is trace_span("b")
    finally:
        set_tracer(prev)


def test_engine_emits_dispatch_span_and_level_events(tree_ds):
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        r = run_query(RecursiveQuery("bitmap", 5, 0, CAPS), tree_ds, 0)
    finally:
        set_tracer(prev)
    spans = [x for x in tr.records if x["type"] == "span"]
    assert any(s["name"] == "dispatch" for s in spans)
    levels = [x for x in tr.records
              if x["type"] == "event" and x["name"] == "level"]
    assert levels, "enabled tracer must emit per-level events"
    # the traced per-level edge counts ARE the executed result's rows
    assert sum(e["attrs"]["edges"] for e in levels) == int(r.count)
    assert [e["attrs"]["level"] for e in levels] \
        == list(range(len(levels)))
    for e in levels:
        assert e["attrs"]["dir"] in (None, "push", "pull", "mixed")


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE: actuals are exact; predictions exact on exact stats
# ---------------------------------------------------------------------------

def _assert_exact(doc):
    a = doc["analyze"]
    assert doc["schema_version"] == 6
    assert a["actual"]["rows"] == a["result_count"]
    assert a["predicted"]["rows"] == pytest.approx(a["actual"]["rows"])
    assert a["predicted"]["levels"] == a["actual"]["levels"]
    for op in a["ops"]:
        assert {"label", "rows_predicted", "bytes_predicted",
                "rows_actual", "bytes_actual"} <= set(op)
        assert op["rows_predicted"] == pytest.approx(op["rows_actual"])
        assert op["bytes_predicted"] == pytest.approx(op["bytes_actual"])
    for lv in a["levels"]:
        assert lv["edges_predicted"] == pytest.approx(lv["edges_actual"])
    return a


@pytest.mark.parametrize("engine", ENGINE_NAMES)
def test_explain_analyze_exact_on_star_every_engine(engine):
    ds = _star_dataset(48)
    sql = paper_listing(1, root=0, depth=3)
    doc = explain_analyze(sql, ds, engine=engine, caps=CAPS)
    a = _assert_exact(doc)
    assert a["engine"] == engine
    assert a["result_count"] == 48
    assert not a["overflow"]


@pytest.mark.parametrize("engine", DIROPT_ENGINE_NAMES)
def test_explain_analyze_direction_reconciliation(engine, tree_ds):
    """Direction-optimizing engines: the analyze doc reports BOTH the
    predicted and the taken per-level push/pull, decoded from the executed
    result's ``level_dirs``."""
    sql = paper_listing(1, root=0, depth=6)
    doc = explain_analyze(sql, tree_ds, engine=engine, caps=CAPS)
    a = doc["analyze"]
    assert a["actual"]["rows"] == a["result_count"]
    taken = [lv["dir_taken"] for lv in a["levels"]]
    predicted = [lv["dir_predicted"] for lv in a["levels"]]
    assert any(d in ("push", "pull") for d in taken)
    assert all(d in (None, "push", "pull") for d in taken + predicted)
    assert a["actual"]["level_dirs"] == taken


@pytest.mark.parametrize("listing", [1, 2, 3])
def test_explain_analyze_listings_actuals_exact(listing, tree_ds):
    """The acceptance bar: on Listings 1.1-1.3 the per-op actual rows are
    EXACTLY the executed BFSResult's counts (sampled tree stats make the
    PREDICTIONS approximate; the ACTUALS are derived from the result)."""
    from repro.planner import plan

    n_pay = 0 if listing == 1 else 4
    sql = paper_listing(listing, root=0, depth=7, payload_cols=n_pay)
    doc = explain_analyze(sql, tree_ds, caps=CAPS)
    a = doc["analyze"]
    report = plan(sql, tree_ds, caps=CAPS)     # the same chosen plan
    assert report.best.label == a["engine"]
    r = report.best.run(tree_ds, 0)
    n = int(r.count)
    assert a["result_count"] == n
    assert a["actual"]["rows"] == n
    rd = np.asarray(r.row_depths)[:n]
    want_levels = np.bincount(rd[rd >= 0]).tolist()
    got_levels = [lv["edges_actual"] for lv in a["levels"]]
    assert got_levels[:len(want_levels)] == want_levels
    assert all(e == 0 for e in got_levels[len(want_levels):])
    for op in a["ops"]:
        assert op["rows_actual"] >= 0
    text = render_analyze(doc)
    assert "predicted" in text and a["engine"] in text


def _check_star_seed(seed):
    rng = np.random.RandomState(seed)
    spokes = int(rng.randint(4, 200))
    ds = _star_dataset(spokes)
    doc = explain_analyze(paper_listing(1, root=0, depth=2), ds, caps=CAPS)
    a = _assert_exact(doc)
    assert a["result_count"] == spokes


@pytest.mark.parametrize("seed", [0, 3, 17, 255])
def test_explain_analyze_exact_star_seeded(seed):
    _check_star_seed(seed)


try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                       # pragma: no cover
    pass
else:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_explain_analyze_exact_star_random(seed):
        _check_star_seed(seed)


# ---------------------------------------------------------------------------
# overflow-retry surfacing (engine executor + serving session)
# ---------------------------------------------------------------------------

def test_dispatch_retry_counted_and_stamped(tree_ds):
    from repro.core import engine as eng_mod

    q = RecursiveQuery("bitmap", 6, 0, CAPS)
    tiny = EngineCaps(frontier=4, result=8)       # guaranteed overflow
    buckets = [RootBucket(indices=(0,), roots=(0,), caps=tiny,
                          predicted_reach=8, predicted_depth=6)]
    eng_mod._overflow_state["warned"] = False     # arm the one-shot warn
    before = overflow_retry_count()
    with pytest.warns(RuntimeWarning, match="overflow"):
        out = run_query_buckets(q, tree_ds, buckets)
    assert overflow_retry_count() == before + 1
    # the retry is TRANSPARENT: the result matches an unbucketed run
    want = run_query(q, tree_ds, 0)
    assert int(out[0].count) == int(want.count)
    # ...and a second retry does not warn again (once per process)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        run_query_buckets(q, tree_ds, buckets)
    assert overflow_retry_count() == before + 2


def test_bucket_timing_carries_predicted_caps(tree_ds):
    from repro.core.engine import dispatch_buckets, run_query_batch

    q = RecursiveQuery("bitmap", 6, 0, CAPS)
    tiny = EngineCaps(frontier=4, result=8)
    buckets = [RootBucket(indices=(0,), roots=(0,), caps=tiny,
                          predicted_reach=8, predicted_depth=6)]
    import dataclasses as dc
    timings = []

    def _dispatch(i, b, caps):
        qb = dc.replace(q, caps=caps) if caps != q.caps else q
        return run_query_batch(qb, tree_ds, b.roots)

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        dispatch_buckets(buckets, _dispatch, fallback_caps=CAPS,
                         observer=timings.append)
    (t,) = timings
    assert isinstance(t, BucketTiming)
    assert t.retried
    assert t.predicted_caps == tiny               # what bucketing PRICED
    assert t.caps == CAPS                         # what the retry RAN with


def test_executor_spans_nest_live(tree_ds):
    import dataclasses as dc

    from repro.core.engine import dispatch_buckets, run_query_batch

    q = RecursiveQuery("bitmap", 6, 0, CAPS)
    tiny = EngineCaps(frontier=4, result=8)       # bucket 1 retries
    buckets = [RootBucket(indices=(0, 1), roots=(0, 1), caps=CAPS,
                          predicted_reach=8, predicted_depth=6),
               RootBucket(indices=(2,), roots=(0,), caps=tiny,
                          predicted_reach=8, predicted_depth=6)]
    timings = []

    def _dispatch(i, b, caps):
        qb = dc.replace(q, caps=caps) if caps != q.caps else q
        return run_query_batch(qb, tree_ds, b.roots)

    tr = Tracer()
    prev = set_tracer(tr)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            with tr.span("request"):
                out = dispatch_buckets(
                    buckets, _dispatch, fallback_caps=CAPS,
                    finish=lambda i, b, r: r, to_host=True,
                    observer=timings.append)
    finally:
        set_tracer(prev)
    assert int(out[2].count) == int(out[0].count)
    spans = [r for r in tr.records if r["type"] == "span"]
    by_id = {s["id"]: s for s in spans}
    req = next(s for s in spans if s["name"] == "request")
    for i, t in enumerate(timings):
        assert t.index == i

        def one(name, i=i):
            (sp,) = [s for s in spans if s["name"] == name
                     and s["attrs"].get("bucket") == i]
            return sp

        launch, disp = one("launch"), one("dispatch")
        # launch and dispatch are siblings under the request, in order
        assert launch["parent"] == disp["parent"] == req["id"]
        assert launch["ts_us"] + launch["dur_us"] <= disp["ts_us"]
        kids = ["device_wait"] + (["retry"] if i == 1 else []) \
            + ["dress", "transfer"]
        got = sorted((s for s in spans if s["parent"] == disp["id"]),
                     key=lambda s: s["ts_us"])
        assert [s["name"] for s in got] == kids
        # the span carries the executor's one measurement, not its own
        assert disp["attrs"]["elapsed_us"] == t.elapsed_us
        assert disp["attrs"]["retried"] == t.retried == (i == 1)
        assert sum(s["dur_us"] for s in got) <= t.elapsed_us
        assert by_id[disp["parent"]] is req
    mod = _load_check_trace()
    assert mod.check_trace(list(tr.iter_records()), min_spans=5) == []


def test_serving_surfaces_overflow_retry(tree_ds):
    sql = paper_listing(1, root=0, depth=4)
    session = ServingSession(tree_ds, caps=CAPS)
    session.submit(sql, [0, 1])
    entry = session.plan_for(sql, [0, 1])
    observe = session._observer(entry, calibrate=False)
    tiny = EngineCaps(frontier=4, result=8)
    timing = BucketTiming(index=0, lanes=1, padded_lanes=1, caps=CAPS,
                          retried=True, elapsed_us=123.0,
                          predicted_caps=tiny)
    with pytest.warns(RuntimeWarning, match="overflowed its predicted"):
        observe(timing)
    observe(timing)                    # second retry: counted, NOT rewarned
    st = session.stats
    assert st["overflow_retries"] == 2
    assert session.metrics()["repro_overflow_retries_total"] == 2


# ---------------------------------------------------------------------------
# serving session: stats compatibility + metrics + explain_analyze
# ---------------------------------------------------------------------------

def test_serving_stats_keeps_old_keys_adds_quantiles(tree_ds):
    sql = paper_listing(1, root=0, depth=4)
    session = ServingSession(tree_ds, caps=CAPS)
    for _ in range(3):
        session.submit(sql, [0, 1, 2])
    st = session.stats
    # every pre-observability key survives
    for k in ("requests", "plan_hits", "plan_misses", "cached_shapes",
              "cached_plans", "last_latency_us", "parse_calls",
              "stats_calls", "cost_calls", "calibration_observations",
              "calibration_refits"):
        assert k in st, k
    assert st["requests"] == 3
    # ...plus the histogram-backed view
    assert 0.0 <= st["plan_hit_rate"] <= 1.0
    assert st["latency_us_p50"] > 0
    assert st["latency_us_p50"] <= st["latency_us_p95"] \
        <= st["latency_us_p99"]
    assert st["overflow_retries"] == 0
    assert st["calibration_refits_rejected"] >= 0


def test_serving_metrics_registry_and_text(tree_ds):
    sql = paper_listing(1, root=0, depth=4)
    session = ServingSession(tree_ds, caps=CAPS)
    session.submit(sql, [0, 1])
    session.submit(sql, [0, 1])
    m = session.metrics()
    assert m["repro_requests_total"] == 2
    assert m["repro_roots_served_total"] == 4
    assert m["repro_request_latency_us"]["count"] == 2
    assert m["repro_plan_cache_hits_total"] \
        + m["repro_plan_cache_misses_total"] > 0
    text = session.metrics_text()
    assert "# TYPE repro_request_latency_us histogram" in text
    assert "repro_requests_total 2" in text
    assert "repro_calibration_refits_total" in text


def test_serving_session_tracer_traces_requests(tree_ds):
    tr = Tracer()
    sql = paper_listing(1, root=0, depth=4)
    session = ServingSession(tree_ds, caps=CAPS, tracer=tr)
    session.submit(sql, [0, 1])
    session.submit(sql, [0, 1])
    assert current_tracer() is None          # restored after each request
    spans = [r for r in tr.records if r["type"] == "span"]
    names = [s["name"] for s in spans]
    assert names.count("request") == 2
    assert "parse" in names and "plan" in names
    assert "compile" in names                # the cold first serve
    assert "dispatch" in names and "transfer" in names
    # warm flag flips between the two requests
    reqs = [s for s in spans if s["name"] == "request"]
    assert [r["attrs"]["warm"] for r in reqs] == [False, True]
    # every span parents back to a request span (forest nesting)
    mod = _load_check_trace()
    assert mod.check_trace(list(tr.iter_records()), min_spans=5) == []
    levels = [r for r in tr.records
              if r["type"] == "event" and r["name"] == "level"]
    assert levels


def test_serving_explain_analyze_groups_by_bucket(tree_ds):
    sql = paper_listing(1, root=0, depth=4)
    session = ServingSession(tree_ds, caps=CAPS)
    roots = [0, 1, 2, 7]
    doc = session.explain_analyze(sql, roots)
    assert doc["schema_version"] == 6
    an = doc["analyze"]
    assert an["mode"] == "serving"
    seen_roots = []
    for b in an["buckets"]:
        assert b["engine"]
        for root, a in zip(b["roots"], b["analyze"]):
            assert a["root"] == root
            assert a["actual"]["rows"] == a["result_count"]
            seen_roots.append(root)
    assert sorted(seen_roots) == sorted(roots)
    # per-root actuals reconcile against direct single-root runs (a
    # multi-lane bucket may plan the batch-only bit-parallel engine,
    # which has no single-root form — every engine is row-count
    # identical, so reconcile those against the bitmap reference)
    eng = an["buckets"][0]["engine"]
    if eng == "multiquery":
        eng = "bitmap"
    want = {r: int(run_query(
        RecursiveQuery(eng, 4, 0, CAPS),
        tree_ds, r).count) for r in (0,)}
    a0 = next(a for b in an["buckets"] for r, a in zip(b["roots"],
              b["analyze"]) if r == 0)
    assert a0["result_count"] == want[0]
