"""The one traffic generator: a traffic mix's data file becomes requests.

A mix (``bench/traffic/<name>.json``) names the query, the distribution its
roots are drawn from and the loop that offers them.  Nothing here knows a
mix by name, so a new mix is a new data file.

* ``query``: ``{"listing": 1|2|3}`` for the paper's Listings 1.1-1.3, with
  the depth and payload columns of the configuration's ``query`` block, or
  ``{"shortest_path": {"bound": B}}`` for weighted shortest paths over the
  configuration's ``weight_col``.
* ``roots``: ``{"kind": "fixed", "root": r}`` or ``{"kind": "uniform"}``
  over a ``population``: the vertices with at least ``min_out_degree``
  out-edges, and of those the ``top_out_degree`` with the most (a graph's
  hubs), where that is given.
* ``loop``: ``{"kind": "closed", "roots_per_request": n}`` sends one
  ``submit`` after another.

Every draw comes from the run's seed, and the warm-up draws from a stream
of its own, so the warm-up serves other draws than the window.
"""
from __future__ import annotations

import numpy as np

WINDOW, WARMUP = 2, 3          # stream ids under the run's seed


def sql(query: dict, cfg_query: dict) -> str:
    """The SQL text of a mix's query on one configuration."""
    if "listing" in query:
        return listing(int(query["listing"]), depth=int(cfg_query["depth"]),
                       payload_cols=int(cfg_query.get("payload_cols", 0)))
    if "shortest_path" in query:
        return shortest_path(int(query["shortest_path"]["bound"]),
                             cfg_query["weight_col"])
    raise ValueError(f"unknown query {query!r}")


def listing(n: int, *, depth: int, payload_cols: int) -> str:
    """Listings 1.1 (traversal columns), 1.2 (payloads carried through the
    recursion) and 1.3 (slim recursion plus one top-level join)."""
    pays = [f"column{i + 1}" for i in range(payload_cols)]
    cols = {1: ["id", '"from"', '"to"', "name"],
            2: ["id", '"from"', '"to"', "name"] + pays,
            3: ["id", '"to"']}[n]
    names = ", ".join(c.strip('"') for c in cols)
    body = (f"WITH RECURSIVE t ({names}, depth) AS (\n"
            f"  SELECT {', '.join(cols)}, 0 FROM edges WHERE \"from\" = 0\n"
            f"  UNION ALL\n"
            f"  SELECT {', '.join(f'e.{c}' for c in cols)}, t.depth + 1\n"
            f"  FROM edges AS e JOIN t ON e.\"from\" = t.\"to\"\n"
            f"  WHERE t.depth < {depth}\n"
            f")\n")
    if n == 3:
        return body + "SELECT e.* FROM t JOIN edges AS e ON t.id = e.id"
    return body + "SELECT * FROM t"


def shortest_path(bound: int, weight_col: str) -> str:
    return (f'WITH RECURSIVE t ("to", depth) AS (\n'
            f'  SELECT "to", 0 FROM edges WHERE "from" = 0\n'
            f'  UNION\n'
            f'  SELECT e."to", t.depth + e.{weight_col}\n'
            f'  FROM edges AS e JOIN t ON e."from" = t."to"\n'
            f'  WHERE t.depth < {bound}\n'
            f')\nSELECT * FROM t')


def population(spec: dict, src: np.ndarray, num_vertices: int) -> np.ndarray:
    """The sorted vertices a mix draws its roots from."""
    deg = np.bincount(src, minlength=num_vertices)
    verts = np.nonzero(deg >= int(spec.get("min_out_degree", 0)))[0]
    if "top_out_degree" in spec:
        most = np.argsort(-deg[verts], kind="stable")
        verts = np.sort(verts[most[:int(spec["top_out_degree"])]])
    if verts.size == 0:
        raise ValueError(f"empty root population {spec!r}")
    return verts


class Roots:
    """Root draws of one mix on one graph.  ``stream(k)`` iterates the roots
    of stream ``k`` (the window's or the warm-up's) under the run's seed,
    the same sequence on every call."""

    def __init__(self, spec: dict, seed: int, pop):
        self.spec = spec
        self.seed = int(seed)
        self.kind = spec["kind"]
        if self.kind == "fixed":
            return
        self.pop = np.asarray(pop)
        if self.kind != "uniform":
            raise ValueError(f"unknown root distribution {self.kind!r}")

    def stream(self, k: int, chunk: int = 4096):
        rng = np.random.default_rng([self.seed, k])
        while True:
            if self.kind == "fixed":
                block = np.full(chunk, int(self.spec["root"]))
            else:
                block = self.pop[rng.integers(0, self.pop.size, chunk)]
            yield from (int(r) for r in block)


def requests(loop: dict, roots: Roots, stream: int):
    """An endless iterator of requests, each a list of roots."""
    per = int(loop.get("roots_per_request", 1))
    it = roots.stream(stream)
    while True:
        yield [next(it) for _ in range(per)]
