"""Plain reference of Graph500 Kernel 3 (SSSP) on the Kronecker graph.

Distances are scipy's Dijkstra in float64 over every row (a repeated
(from, to) pair counts at its least weight).  An answer is right when it reaches the same vertices, holds
one row per edge out of a reached vertex (the multiset of ``to`` matches),
and no distance, per row or per vertex, lies farther from the reference than
``dist_gap``'s limit.  The distance bound of the query must not bind: every
reference distance lies below it.
"""
from __future__ import annotations

import numpy as np

from bench.reference import plain

NEEDS = ("from", "to", "w")
LIMITS = {"wrong_answers": 0, "dist_gap": 1e-3}


class Reference:
    def __init__(self, cols: dict, num_vertices: int, cfg: dict,
                 traffic: dict):
        self.g = plain.Edges(cols["from"], cols["to"], num_vertices)
        self.graph = plain.weighted_graph(self.g, cols["w"])
        self.bound = float(traffic["query"]["shortest_path"]["bound"])

    def _check(self, d: np.ndarray, r) -> tuple[str | None, float]:
        if bool(np.any(np.asarray(r.overflow))):
            return "overflow flagged", 0.0
        reached = np.isfinite(d)
        if d[reached].max() >= self.bound:
            return "the distance bound binds", 0.0
        vv = np.asarray(r.vertex_values, np.float64)
        if not np.array_equal(np.isfinite(vv), reached):
            return "reached vertices differ", 0.0
        n = int(r.count)
        to = np.asarray(r.values["to"])[:n].astype(np.int64)
        v = self.g.num_vertices
        if n and (to.min() < 0 or to.max() >= v):
            return "a target outside the graph", 0.0
        want = np.bincount(self.g.dst[reached[self.g.src]], minlength=v)
        if not np.array_equal(np.bincount(to, minlength=v), want):
            return "rows differ from the edges out of reached vertices", 0.0
        row = np.asarray(r.values["value"], np.float64)[:n]
        gap = max(float(np.max(np.abs(vv[reached] - d[reached]))),
                  float(np.max(np.abs(row - d[to]), initial=0.0)))
        return None, gap

    def compare(self, roots, answers) -> tuple[dict, list]:
        dist = plain.shortest_paths(self.graph, roots)
        bad, gap = [], 0.0
        for d, root, r in zip(dist, roots, answers):
            why, g = self._check(d, r)
            gap = max(gap, g)
            if why is not None:
                bad.append((root, why))
        return {"wrong_answers": len(bad), "dist_gap": gap}, bad

    def control(self, roots) -> list:
        """The reference's distances held in bfloat16."""
        out = []
        for d in plain.shortest_paths(self.graph, roots):
            d16 = plain.to_bfloat16(d)
            to = self.g.dst[np.isfinite(d)[self.g.src]]
            out.append(plain.answer({"to": to, "value": d16[to]}, to.size,
                                    vertex_values=d16))
        return out
