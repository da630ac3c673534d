"""Device self time per operator scope (``bench/scopes.py``): the self
time of nested ops, the scope of a name stack, the reduction of a window,
and the XSpace reader on traces recorded on one TPU v5e chip."""
from pathlib import Path

import bench_tiny  # noqa: F401  (import paths)
import pytest

from bench import scopes, trace_reduce

DATA = Path(__file__).parent / "data"
OLD = DATA / "full.xplane.pb.gz"          # recorded before the scopes
# five whole-tree requests recorded with the scopes and the program's span
# annotations (posdb-tree.full, --seconds 5 --trace 1)
SCOPED = DATA / "full-scoped.xplane.pb.gz"

LOOP = "jit(fixed_point_batch)/while"
BODY = LOOP + "/body/vmap(DirectionSwitch)"


def test_self_time_takes_out_the_ops_nested_inside():
    ops = [(LOOP + ":", 0, 100),                    # the loop holds 3 ops
           (BODY + "/PullStep/gather:", 10, 20),
           (BODY + "/DenseBitmapStep/scatter:", 40, 20),
           (BODY + "/DenseBitmapStep/gather:", 45, 5),  # nested once more
           ("jit(fixed_point_batch)/vmap(DeferredEmit)/gather:", 100, 30)]
    own = [t for _, _, t in scopes.self_times(ops)]
    assert own == [60, 20, 15, 5, 30]
    assert sum(own) == 130                          # the busy time
    r = scopes.reduce_lines([ops], 0, 200, {"DeferredEmit"})
    assert r["busy_s"] * 1e9 == pytest.approx(130)
    assert r["loop_s"] * 1e9 == pytest.approx(100)
    assert r["finish_s"] * 1e9 == pytest.approx(30)
    assert [[k, v * 1e9] for k, v in r["scopes"]] == [
        ["while", pytest.approx(60)], ["DeferredEmit", pytest.approx(30)],
        ["PullStep", pytest.approx(20)],
        ["DenseBitmapStep", pytest.approx(20)]]
    # an op counts where it starts: a window that ends at 100 drops the
    # finisher and keeps the whole loop
    r = scopes.reduce_lines([ops], 0, 100, {"DeferredEmit"})
    assert r["busy_s"] * 1e9 == pytest.approx(100) and r["finish_s"] == 0


@pytest.mark.parametrize("name, want", [
    (BODY + "/PullStep/gather:", ("PullStep", True, "DirectionSwitch")),
    (LOOP + ":", ("while", True, None)),
    (LOOP + "/body/select_n:", ("while", True, None)),
    ("jit(fixed_point_batch)/vmap(DeferredEmit)/vmap(jit(_take))/gather:",
     ("DeferredEmit", False, "DeferredEmit")),
    # a while inside an operator is the operator's, not the loop's
    ("jit(fixed_point_batch)/vmap(LateMaterialize)/jit(searchsorted)/while"
     "/body/lt:", ("LateMaterialize", False, "LateMaterialize")),
    ("jit(multiquery_fixed_point)/MultiQuerySeed/scatter-add:;x/y:",
     ("MultiQuerySeed", False, "MultiQuerySeed")),
    ("jit(fixed_point_batch)/vmap()/gather:",
     ("outside_operators", False, None)),
    (None, ("outside_operators", False, None)),
])
def test_scope_of_a_name_stack(name, want):
    assert scopes.scope_of(name) == want


def test_finishers_are_the_classes_with_finish():
    fin = scopes.finishers()
    assert {"DeferredEmit", "LateMaterialize", "CompactEmitted",
            "MultiQueryEmit"} <= fin
    assert not {"Seed", "PullStep", "DirectionSwitch"} & fin


def test_reader_matches_the_profiler_on_a_recorded_trace():
    # the wire-format reader finds the ops jax.profiler finds, at
    # picosecond rather than nanosecond resolution
    mine = scopes.device_op_lines(scopes.read_space(str(OLD)))
    theirs = trace_reduce.device_ops(trace_reduce.load(str(OLD)))
    assert len(mine) == len(theirs) == 1
    assert sorted((int(s), int(d)) for _, s, d in mine[0]) == sorted(
        (int(s), int(d)) for _, s, d in theirs[0])
    assert sum(op is not None for op, _, _ in mine[0]) > 500


def test_a_trace_without_scopes_splits_loop_from_the_rest():
    # five whole-tree requests recorded before the program named its
    # operators: the loop and everything else, and nothing under a class
    r = scopes.reduce(str(OLD))
    busy = trace_reduce.reduce(trace_reduce.load(str(OLD)))["busy_s"]
    assert r["busy_s"] == pytest.approx(busy, rel=1e-6)
    assert r["loop_s"] == pytest.approx(3.7732460, rel=1e-6)
    assert r["finish_s"] == 0
    assert [k for k, _ in r["scopes"]] == ["while", "outside_operators"]


@pytest.fixture(scope="module")
def scoped():
    return scopes.reduce(str(SCOPED))


def test_scopes_name_the_operators_on_the_chip(scoped):
    per_request_ms = {k: v / 5 * 1e3 for k, v in scoped["scopes"]}
    # both sides of the direction switch run at every level, as does the
    # finisher's late materialization once per request
    assert {"PullStep", "DenseBitmapStep", "DeferredEmit",
            "Seed"} <= set(per_request_ms)
    assert per_request_ms["PullStep"] == pytest.approx(284.15, abs=0.01)
    assert per_request_ms["DenseBitmapStep"] == pytest.approx(128.35,
                                                              abs=0.01)
    assert per_request_ms["DeferredEmit"] == pytest.approx(216.23, abs=0.01)
    # the loop's own share: the two scatter-or fusions, which the TPU
    # compiler emits without metadata, and their index sorts
    assert per_request_ms["while"] == pytest.approx(342.14, abs=0.01)
    assert scoped["finish_s"] == pytest.approx(
        per_request_ms["DeferredEmit"] * 5 / 1e3)
    # the loop and the finisher hold the device's busy time
    assert scoped["loop_s"] + scoped["finish_s"] >= 0.95 * scoped["busy_s"]
    busy = trace_reduce.reduce(trace_reduce.load(str(SCOPED)))["busy_s"]
    assert scoped["busy_s"] == pytest.approx(busy, rel=1e-6)


def test_program_spans_name_every_idle_gap():
    # no alignment: the spans are annotations on the window's thread
    planes = trace_reduce.load(str(SCOPED))
    gaps = trace_reduce.reduce(planes)["idle_gaps"]
    assert len(gaps) == 10
    assert all(label.startswith("span:") for label, _ in gaps)
    assert gaps[0][0].startswith("span:transfer")
    names = {n for n, _, _ in trace_reduce.host_events(planes)}
    assert {"span:request", "span:launch", "span:dispatch",
            "span:device_wait", "span:dress", "span:transfer"} <= names


def test_span_annotations_leave_the_device_readings_alone():
    # the device readers (device_idle_pct, traversal_hbm_pct) read the
    # same numbers with and without the program's annotations
    planes = trace_reduce.load(str(SCOPED))
    bare = [(p, [(ln, [e for e in evs if not e[0].startswith("span:")])
                 for ln, evs in lines]) for p, lines in planes]
    a, b = trace_reduce.reduce(planes), trace_reduce.reduce(bare)
    for k in ("busy_s", "window_s", "idle_pct", "device_ops"):
        assert a[k] == b[k]
