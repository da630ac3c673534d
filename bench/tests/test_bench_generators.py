"""The graph comes from the configuration's ``graph_seed`` and the rest of
the table from the run's seed, so every run of a cell does the same work;
and the answers a run compares are held until its window closes."""
import json
from types import SimpleNamespace

import numpy as np
import pytest
from bench_tiny import TINY

from bench.harness import BENCH, Sample, load_module


def _params(config: str) -> tuple:
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    return (load_module(BENCH / "generators" / f"{cfg['generator']}.py"),
            {**cfg["params"], **TINY[config][1]})


def _edges(cols) -> np.ndarray:
    """The edge multiset, whatever the order of the rows."""
    keys = [np.asarray(cols[k]).astype(np.float64) for k in ("from", "to")]
    if "w" in cols:
        keys.append(np.asarray(cols["w"]).astype(np.float64))
    e = np.stack(keys, axis=1)
    return e[np.lexsort(e.T[::-1])]


@pytest.mark.parametrize("config", sorted(TINY))
def test_graph_comes_from_graph_seed(config):
    gen, params = _params(config)
    a, va = gen.generate(params, 5)
    b, vb = gen.generate(params, 2**40 + 6)
    assert va == vb
    np.testing.assert_array_equal(_edges(a), _edges(b))
    for k in ("id", "name") + (("column1",) if "column1" in a else ()):
        assert not np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k
    c, _ = gen.generate({**params, "graph_seed": params["graph_seed"] + 1},
                        5)
    assert not np.array_equal(_edges(a), _edges(c))


def test_tree_rows_keep_their_place():
    # the tree's rows are in the order of their target vertex, so two runs
    # of one graph_seed read the same from/to columns, row for row
    gen, params = _params("posdb-tree")
    a, _ = gen.generate(params, 1)
    b, _ = gen.generate(params, 2)
    np.testing.assert_array_equal(a["from"], b["from"])
    np.testing.assert_array_equal(a["to"], b["to"])


def test_sample_holds_every_answer_it_took():
    sample = Sample(3, 2**33 + 7)
    taken = []
    for i, c in enumerate([5, 1, 2, 9, 3, 3, 4, 9, 1, 2] * 5):
        r = SimpleNamespace(count=c)
        sample.offer(i, r)
        if any(x is r for _, x in sample.kept) or sample.largest[1] is r:
            taken.append(r)
    # released by the reservoir or not, each stays held, once
    assert len(taken) > 4
    assert [id(x) for x in sample.held] == [id(x) for x in taken]
    got = sample.answers()
    assert len(got) == 4 and got[-1] == (3, taken[3])


def test_sample_draws_from_the_seed():
    def draw(seed):
        s = Sample(3, seed)
        for i in range(40):
            s.offer(i, SimpleNamespace(count=1))
        return [root for root, _ in s.answers()]

    assert draw(11) == draw(11)
    assert draw(11) != draw(12)
