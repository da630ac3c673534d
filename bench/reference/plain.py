"""Plain host implementations of the recursive queries the cells serve.

Independent of the system under test: numpy and scipy over the generated
columns.  ``bfs_rows`` is Listing 1.2's answer under the system's SQL
semantics: the rows at level 0 are the edges out of the root; each next
level is every edge out of the vertices first reached by the level before
(a vertex already reached is not expanded again), up to ``max_depth``.
``shortest_paths`` is the least weight sum from each root to every vertex.
"""
from __future__ import annotations

import types

import ml_dtypes
import numpy as np


def out_ranges(indptr: np.ndarray, verts: np.ndarray) -> np.ndarray:
    """Positions ``indptr[v]..indptr[v+1]`` of every ``v`` in ``verts``."""
    starts, ends = indptr[verts], indptr[verts + 1]
    lens = ends - starts
    total = int(lens.sum())
    if total == 0:
        return np.zeros(0, np.int64)
    offs = np.repeat(starts - np.concatenate([[0], np.cumsum(lens)[:-1]]),
                     lens)
    return offs + np.arange(total)


class Edges:
    """An edge list with its rows grouped by source vertex."""

    def __init__(self, src: np.ndarray, dst: np.ndarray, num_vertices: int):
        self.src = np.asarray(src)
        self.dst = np.asarray(dst)
        self.num_vertices = int(num_vertices)
        self.by_src = np.argsort(self.src)
        self.indptr = np.concatenate([[0], np.cumsum(
            np.bincount(self.src, minlength=self.num_vertices))])

    def out_edges(self, verts: np.ndarray) -> np.ndarray:
        return self.by_src[out_ranges(self.indptr, verts)]


def bfs_rows(g: Edges, root: int, max_depth: int):
    """(edge positions, level of each) of the depth-bounded traversal."""
    seen = np.zeros(g.num_vertices, bool)
    seen[root] = True
    frontier = np.array([root], np.int64)
    pos, lvl = [], []
    for level in range(max_depth + 1):
        p = g.out_edges(frontier)
        if p.size == 0:
            break
        pos.append(p)
        lvl.append(np.full(p.size, level, np.int32))
        t = np.unique(g.dst[p])
        frontier = t[~seen[t]]
        seen[frontier] = True
    if not pos:
        return np.zeros(0, np.int64), np.zeros(0, np.int32)
    return np.concatenate(pos), np.concatenate(lvl)


def weighted_graph(g: Edges, w: np.ndarray):
    """The scipy graph of every row, duplicates kept: Dijkstra relaxes each
    stored entry, so a repeated (from, to) pair counts at its least weight.
    A weight of exactly 0 becomes 1e-300, so that no sparse-format rule
    can read it as a missing edge."""
    import scipy.sparse as sp

    v = g.num_vertices
    data = np.maximum(np.asarray(w, np.float64)[g.by_src], 1e-300)
    return sp.csr_matrix((data, g.dst[g.by_src], g.indptr), shape=(v, v))


def shortest_paths(graph, roots) -> np.ndarray:
    """(len(roots), V) float64 least distances; inf where not reached."""
    from scipy.sparse.csgraph import dijkstra

    return np.atleast_2d(dijkstra(graph, directed=True,
                                  indices=np.asarray(roots, np.int64)))


def to_bfloat16(a: np.ndarray) -> np.ndarray:
    """``a`` held in bfloat16, read back as float32: the control's
    precision, one step below the float32 the configurations state."""
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def answer(values: dict, count: int, vertex_values=None):
    """An answer shaped like the served one, for the control."""
    return types.SimpleNamespace(values=values, count=np.int32(count),
                                 overflow=np.zeros((), bool),
                                 vertex_values=vertex_values)
