"""The metric readers, the trace reduction and the traffic generator on
synthetic inputs, and a new configuration, traffic mix and metric picked up
by the harness as nothing but new files."""
import json
import types

import numpy as np
import pytest
from bench_tiny import REPO, run_tiny, tiny_bench  # noqa: F401

from bench import harness, loadgen, readings, trace_reduce

METRICS = REPO / "bench" / "metrics"


def reader(name):
    return harness.load_module(METRICS / f"{name}.py").read


def _span(i, name, ts, dur, parent=None, **attrs):
    return {"type": "span", "id": i, "parent": parent, "name": name,
            "ts_us": ts, "dur_us": dur, "attrs": attrs}


def _run(**kw):
    base = dict(served=[], window_s=1.0, setup_s=2.0, spans=None,
                device=None, answer_counts=None, peaks=None)
    base.update(kw)
    return types.SimpleNamespace(**base)


def _served(lat, roots=1, why=None):
    return types.SimpleNamespace(latency_s=lat, roots=[0] * roots, why=why)


def test_span_readers():
    # two requests: 1000us and 2000us spans whose latency windows are 600us
    # and 1500us; each has a plan span of 100us and dispatch spans whose
    # elapsed_us (not duration) counts
    spans = [
        _span(1, "parse", 0, 50, parent=0),
        _span(2, "plan", 100, 100, parent=0),
        _span(3, "transfer", 300, 40, parent=0),
        _span(4, "dispatch", 900, 5, parent=0, elapsed_us=300.0),
        _span(0, "request", 0, 1000, latency_us=600.0),
        _span(6, "plan", 1100, 100, parent=5),
        _span(7, "transfer", 1300, 60, parent=5),
        _span(8, "dispatch", 2900, 5, parent=5, elapsed_us=500.0),
        _span(9, "dispatch", 2950, 5, parent=5, elapsed_us=200.0),
        _span(5, "request", 1000, 2000, latency_us=1500.0),
    ]
    run = _run(spans=spans)
    assert reader("frontdoor_ms")(run) == pytest.approx(
        ((400 + 100) + (500 + 100)) / 2 / 1e3)
    assert reader("bucket_ms")(run) == pytest.approx(1000 / 2 / 1e3)
    assert reader("transfer_ms")(run) == pytest.approx(100 / 2 / 1e3)
    assert reader("frontdoor_ms")(_run(spans=[])) is None


def test_clock_readers():
    # a failed request counts: the median is of all requests
    served = [_served(t / 1e3) for t in range(1, 101)]
    served.append(_served(5.0, roots=32, why="rejected"))
    run = _run(served=served, window_s=2.0)
    assert reader("p50_ms")(run) == pytest.approx(51.0)
    assert reader("setup_s")(run) == 2.0
    assert reader("p50_ms")(_run()) is None


def test_device_readers():
    dev = {"idle_pct": 75.0, "busy_s": 0.5, "window_s": 2.0}
    counts = [(1000, 192, 0), (10, 12, 4096)]
    run = _run(device=dev, answer_counts=counts,
               peaks={"hbm_bytes_per_s": 1e9})
    need = 1000 * 2 * 192 + 10 * 2 * 12 + 4096
    assert readings.least_bytes(run) == need
    assert reader("traversal_hbm_pct")(run) == pytest.approx(
        100 * need / 0.5 / 1e9)
    assert reader("device_idle_pct")(run) == 75.0
    # nothing to read: nothing returned, never a 0 share
    assert reader("traversal_hbm_pct")(_run()) is None
    assert reader("device_idle_pct")(_run()) is None


def _planes(ops, host):
    return [("/host:CPU", [("python", host)]),
            ("/device:TPU:0", [("XLA Modules", [("m", 0.0, 1e9)]),
                               ("XLA Ops", ops)])]


def test_trace_reduction():
    host = [("bench.window", 100.0, 1000.0), ("bench.request", 100.0, 500.0),
            ("plan", 300.0, 100.0)]
    # overlapping ops 150-250 and 200-300, one at 700-800, one outside
    ops = [("fusion", 150.0, 100.0), ("scatter", 200.0, 100.0),
           ("fusion", 700.0, 100.0), ("late", 2000.0, 50.0)]
    red = trace_reduce.reduce(_planes(ops, host))
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(250e-9)
    assert red["idle_pct"] == pytest.approx(75.0)
    assert red["device_ops"][0] == ["fusion", pytest.approx(200e-9)]
    # gaps: 300-700 (400ns, mid 500 inside bench.request only), 800-1100,
    # 100-150
    assert [round(g * 1e9) for _, g in red["idle_gaps"]] == [400, 300, 50]
    assert red["idle_gaps"][0][0] == "bench.request"
    assert red["idle_gaps"][1][0] == "no host event"
    named = trace_reduce.reduce(_planes(ops, host),
                                extra_host=[("span:transfer", 400.0, 200.0)])
    assert named["idle_gaps"][0][0] == "span:transfer | bench.request"
    with pytest.raises(ValueError):
        trace_reduce.reduce([("/host:CPU", [("python", host)])])


def test_roots_are_seeded_and_streams_differ():
    pop = np.arange(10, 1010)
    spec = {"kind": "uniform"}
    a = loadgen.Roots(spec, 2 ** 31 + 5, pop)
    b = loadgen.Roots(spec, 2 ** 31 + 5, pop)
    win = loadgen.requests({"kind": "closed"}, a, loadgen.WINDOW)
    again = loadgen.requests({"kind": "closed"}, b, loadgen.WINDOW)
    first = [next(win)[0] for _ in range(5000)]
    assert first == [next(again)[0] for _ in range(5000)]
    warm = loadgen.requests({"kind": "closed"}, a, loadgen.WARMUP)
    assert first[:50] != [next(warm)[0] for _ in range(50)]
    assert set(first) <= set(pop.tolist())
    pair = loadgen.requests({"kind": "closed", "roots_per_request": 2}, a,
                            loadgen.WINDOW)
    assert len(next(pair)) == 2
    fixed = loadgen.requests({"kind": "closed"}, loadgen.Roots(
        {"kind": "fixed", "root": 7}, 1, None), loadgen.WINDOW)
    assert [next(fixed) for _ in range(3)] == [[7]] * 3


def test_population_by_out_degree():
    # out-degrees: 0 -> 3, 1 -> 1, 2 -> 3, 3 -> 0, 4 -> 2
    src = np.array([0, 0, 0, 1, 2, 2, 2, 4, 4])
    assert loadgen.population({"min_out_degree": 1}, src, 5).tolist() == [
        0, 1, 2, 4]
    assert loadgen.population({}, src, 5).tolist() == [0, 1, 2, 3, 4]
    # the hubs: ties go to the smaller vertex
    assert loadgen.population({"top_out_degree": 1}, src, 5).tolist() == [0]
    assert loadgen.population({"top_out_degree": 3}, src, 5).tolist() == [
        0, 2, 4]
    with pytest.raises(ValueError):
        loadgen.population({"min_out_degree": 4}, src, 5)


def test_new_cell_and_metric_are_new_files_only(tiny_bench, tmp_path):
    # a throwaway configuration, traffic mix and metric, added as files
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "reference").mkdir()
    cfg = json.loads((tiny_bench.parent / "configs" /
                      "tiny-tree.json").read_text())
    cfg["name"] = "throwaway"
    cfg["params"]["num_vertices"] = 700
    (tmp_path / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    (tmp_path / "reference" / "throwaway.py").write_text(
        (tiny_bench.parent / "reference" / "tiny-tree.py").read_text())
    (tmp_path / "traffic" / "listing1.json").write_text(json.dumps({
        "query": {"listing": 1},
        "roots": {"kind": "uniform", "population": {"min_out_degree": 1}},
        "loop": {"kind": "closed", "roots_per_request": 2},
        "warmup": {"quiet_requests": 2, "max_requests": 20},
        "check": {"answers": 10}}))
    (tmp_path / "metrics" / "requests_seen.py").write_text(
        "def read(run):\n    return float(len(run.served))\n")
    spec = json.loads(tiny_bench.read_text())
    spec["workloads"].append({"name": "throwaway.listing1",
                              "config": "throwaway", "traffic": "listing1",
                              "chips": 1, "why": "a test"})
    spec["end_to_end"].append({"name": "requests_seen", "unit": "requests",
                               "better": "higher", "bound": 0.1,
                               "source": "host_clock",
                               "workloads": ["throwaway.listing1"]})
    bench_json = tmp_path / "BENCHMARK.json"
    bench_json.write_text(json.dumps(spec))
    result, err = run_tiny(bench_json, "throwaway.listing1", dirs=())
    assert result["correct"], err
    assert result["metrics"]["requests_seen"]["value"] > 0
    assert set(result["metrics"]) == {"requests_seen", "setup_s"}
