"""The paper's edge table over a random tree (arXiv:2308.08702 §5.1).

A copy of the repository's tree generator, kept here so that the benchmark's
data cannot change under it.  Vertices ``1..V-1`` are carved into ``height``
levels of random width and each attaches to a parent drawn from the level
above, so the tree has exactly ``height`` levels below its root 0.  The edge
table has ``id`` (a permutation of the rows), ``from``, ``to``, ``name``
(varchar(15), as 4 float32) and ``payload_cols`` payload columns
(varchar(20), as 5 float32 each).

The tree (its level widths and every parent) comes from the
configuration's ``graph_seed``, as the paper measures one tree, so every
run traverses the same shape; the run's seed draws ``id``, ``name`` and the
payload columns.
"""
from __future__ import annotations

import numpy as np


def tree_edges(num_vertices: int, height: int, rng) -> tuple:
    remaining = num_vertices - 1
    widths = []
    for lvl in range(height):
        left = height - lvl
        if left == 1:
            w = remaining
        else:
            hi = max(1, remaining - (left - 1))
            grow = min(hi, max(1, int(remaining / left * 1.5)))
            w = int(rng.integers(1, grow + 1))
        widths.append(w)
        remaining -= w
    src = np.empty(num_vertices - 1, np.int64)
    prev = np.array([0])
    start = 1
    for w in widths:
        src[start - 1:start - 1 + w] = rng.choice(prev, size=w)
        prev = np.arange(start, start + w)
        start += w
    return src.astype(np.int32), np.arange(1, num_vertices, dtype=np.int32)


def generate(params: dict, seed: int) -> tuple[dict, int]:
    """Host columns of the edge table and the vertex count."""
    v = int(params["num_vertices"])
    src, dst = tree_edges(v, int(params["height"]),
                          np.random.default_rng(int(params["graph_seed"])))
    rng = np.random.default_rng(seed)
    e = src.shape[0]
    cols = {"id": rng.permutation(e).astype(np.int32), "from": src,
            "to": dst,
            "name": rng.standard_normal((e, 4), np.float32)}
    for i in range(int(params["payload_cols"])):
        cols[f"column{i + 1}"] = rng.standard_normal((e, 5), np.float32)
    return cols, v
