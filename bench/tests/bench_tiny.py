"""Tiny copies of the benchmark's cells, for the CPU, and the import paths
of the benchmark's tests (kept out of a ``conftest.py``, whose module name
``tests/`` already takes).

``write_tiny_bench`` writes a ``BENCHMARK.json`` and configurations shrunk to
a few thousand rows into a directory; the harness finds them there first,
and the traffic mixes, metric readers and references in ``bench/``.
"""
import json
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = Path(__file__).resolve().parents[2]
for _p in (str(REPO), str(REPO / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TINY = {"posdb-tree": ("tiny-tree", {"num_vertices": 3000, "height": 8,
                                     "payload_cols": 2},
                       {"depth": 8, "payload_cols": 2}),
        "graph500-s17": ("tiny-kron", {"scale": 9}, None)}


def bench_spec() -> dict:
    """The repository's ``BENCHMARK.json``."""
    return json.loads((REPO / "BENCHMARK.json").read_text())


def rename(name: str) -> str:
    for real, (tiny, _, _) in TINY.items():
        name = name.replace(real, tiny)
    return name


def write_tiny_bench(root: Path) -> Path:
    """Shrunk configurations, references bound to them, and a
    ``BENCHMARK.json`` naming the tiny cells, under ``root``."""
    (root / "configs").mkdir(parents=True, exist_ok=True)
    (root / "reference").mkdir(exist_ok=True)
    for real, (tiny, params, query) in TINY.items():
        cfg = json.loads((REPO / "bench" / "configs" /
                          f"{real}.json").read_text())
        cfg["name"] = tiny
        cfg["params"].update(params)
        if query:
            cfg["query"] = query
        (root / "configs" / f"{tiny}.json").write_text(json.dumps(cfg))
        (root / "reference" / f"{tiny}.py").write_text(
            "from bench.harness import BENCH, load_module\n"
            f"_real = load_module(BENCH / 'reference' / '{real}.py')\n"
            "NEEDS, LIMITS, Reference = (getattr(_real, 'NEEDS', None), "
            "_real.LIMITS, _real.Reference)\n")
    spec = bench_spec()
    for w in spec["workloads"]:
        w["name"], w["config"] = rename(w["name"]), rename(w["config"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename(w) for w in m["workloads"]]
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps(spec, indent=1))
    return path


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory) -> Path:
    """A tiny ``BENCHMARK.json`` with its configurations, per test module."""
    return write_tiny_bench(tmp_path_factory.mktemp("bench"))


def run_tiny(bench_json: Path, workload: str, *, seed: int = 7,
             seconds: float = 0.5, trace: bool = False, dirs=()):
    """One run of a tiny cell on the CPU; returns (result, stderr text)."""
    import io
    import time

    from bench import harness

    out, err = io.StringIO(), io.StringIO()
    result = harness.run(
        workload, seed, seconds, trace, t_start=time.perf_counter(),
        require_tpu=False, bench_json=bench_json,
        dirs=(bench_json.parent, *dirs), compile_cache=False,
        trace_dir=bench_json.parent / f"trace-{workload}", out=out, err=err)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == result
    return result, err.getvalue()
