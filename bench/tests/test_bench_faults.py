"""The comparison that decides ``correct`` fails what it should.

The harness is driven on the CPU with the timed path broken underneath it
(an answer altered where it is produced: a row's target moved, its last row
dropped, a returned value changed) and must report ``correct: false``.  The control, the
reference computed in bfloat16 in the program's place, must fail the
limits too.  And the entry point must refuse to run without a TPU.
"""
import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest
from bench_tiny import REPO, rename, run_tiny, tiny_bench  # noqa: F401

from repro.planner.optimize import PhysicalChoice


def _alter_answer(r):
    v = r.values
    return r._replace(values={**v, "to": v["to"].at[..., 0].add(1)})


def _drop_row(r):
    return r._replace(count=jnp.asarray(r.count) - 1)


def _alter_value(r):
    v = r.values
    k = "value" if "value" in v else "column1"
    return r._replace(values={**v, k: v[k].at[..., 0].add(0.01)})


@pytest.mark.parametrize("fault", [_alter_answer, _drop_row, _alter_value])
@pytest.mark.parametrize("cell", ["posdb-tree.full", "graph500-s17.sssp"])
def test_broken_timed_path_is_not_correct(tiny_bench, monkeypatch, cell,
                                          fault):
    dress = PhysicalChoice.dress

    def broken(self, r, **kw):
        return fault(dress(self, r, **kw))

    monkeypatch.setattr(PhysicalChoice, "dress", broken)
    result, err = run_tiny(tiny_bench, rename(cell))
    assert result["correct"] is False, err
    over = [k for k, c in result["checks"].items()
            if k != "answers_compared" and c["value"] > c["limit"]]
    assert over, result["checks"]


@pytest.mark.parametrize("config", ["posdb-tree", "graph500-s17"])
def test_bfloat16_control_fails_the_limits(tiny_bench, config):
    from bench import harness

    tiny = rename(config)
    cell = harness.load_cell(f"{tiny}.{'sssp' if 'graph' in config else 'full'}",
                             False, tiny_bench, (tiny_bench.parent,))
    cols, v, _ = harness.build(cell, 11)
    host = harness.host_columns(cols)
    ref = cell.part("reference", tiny)
    r = ref.Reference(host, v, cell.config, cell.traffic)
    roots = [0] if "tree" in config else [int(np.argmax(
        np.bincount(host["from"], minlength=v)))]
    readings, _ = r.compare(roots, r.control(roots))
    assert any(readings[k] > lim for k, lim in ref.LIMITS.items()), readings
    exact, _ = r.compare(roots, [_exact(r, roots[0], config)])
    assert all(exact[k] <= lim for k, lim in ref.LIMITS.items()), exact


def _exact(r, root, config):
    """The reference's own answer at full precision passes."""
    from bench.reference import plain

    if "tree" in config:
        pos, lvl = plain.bfs_rows(r.g, root, r.depth)
        vals = {k: r.cols[k][pos] for k in r.returned}
        vals["depth"] = lvl
        return plain.answer(vals, pos.size)
    d = plain.shortest_paths(r.graph, [root])[0].astype(np.float32)
    to = r.g.dst[np.isfinite(d)[r.g.src]]
    return plain.answer({"to": to, "value": d[to]}, to.size, d)


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


ARGS = ("--workload", "posdb-tree.full", "--seed", "3", "--seconds", "1",
        "--trace", "0")


def test_no_tpu_means_no_result():
    p = _run(REPO, *ARGS)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, *ARGS)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
