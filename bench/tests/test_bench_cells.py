"""Each cell of BENCHMARK.json, shrunk, served on the CPU and compared with
its reference: the run is correct and reports the cell's metrics.  And the
metrics' cell lists name the cells that exist."""
import pytest
from bench_tiny import bench_spec, rename, run_tiny, tiny_bench  # noqa: F401

SPEC = bench_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def _expected(group: str, cell: str) -> set:
    return {m["name"] for m in SPEC[group]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_names_only_cells_that_exist(metric):
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_p50_and_a_layer(cell):
    assert {"p50_ms", "setup_s"} <= _expected("end_to_end", cell)
    assert _expected("per_layer", cell)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_correct_and_reports_its_metrics(tiny_bench, cell):
    result, err = run_tiny(tiny_bench, rename(cell))
    assert result["correct"], err
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == _expected("end_to_end", cell)
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert err.rstrip().splitlines()[-1].startswith("check ")


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_span_metrics(tiny_bench, cell):
    # the CPU has no device plane, so only the span and counter readers
    # find something to read here
    result, err = run_tiny(tiny_bench, rename(cell), trace=True)
    assert result["correct"], err
    want = {m["name"] for m in SPEC["per_layer"]
            if cell in m.get("workloads", [cell])
            and m["source"] in ("program_span", "program_counter")}
    assert want <= set(result["metrics"])
    assert "frontdoor_ms" in result["metrics"]
