"""Plain reference of Listings 1.1 and 1.2 on the paper's tree.

An answer is right when it holds exactly the reference's rows: the same edge
positions (read back through the ``id`` permutation), each at its BFS level
in ``depth``, with every stored column equal to the generated table at that
position.  The comparison is exact, so its limit is 0.
"""
from __future__ import annotations

import numpy as np

from bench.reference import plain

NEEDS = None            # every generated column
LIMITS = {"wrong_answers": 0}


class Reference:
    def __init__(self, cols: dict, num_vertices: int, cfg: dict,
                 traffic: dict):
        self.cols = cols
        self.depth = int(cfg["query"]["depth"])
        listing = int(traffic["query"]["listing"])
        if listing not in (1, 2):
            raise ValueError(f"no reference for Listing 1.{listing}")
        self.returned = ["id", "from", "to", "name"] + [
            f"column{i + 1}" for i in range(int(cfg["query"]["payload_cols"]))
            if listing == 2]
        self.g = plain.Edges(cols["from"], cols["to"], num_vertices)
        self.inv_id = np.argsort(cols["id"])
        self.level = np.full(self.g.src.size, -1, np.int32)

    def _rows(self, root: int):
        return plain.bfs_rows(self.g, root, self.depth)

    def wrong(self, root: int, r) -> str | None:
        """Why one answer differs from the reference, or None."""
        if bool(np.any(np.asarray(r.overflow))):
            return "overflow flagged"
        if set(r.values) != set(self.returned) | {"depth"}:
            return f"columns {sorted(r.values)}"
        pos_ref, lvl_ref = self._rows(root)
        n = int(r.count)
        if n != pos_ref.size:
            return f"{n} rows, the reference has {pos_ref.size}"
        ids = np.asarray(r.values["id"])[:n]
        if n and (ids.min() < 0 or ids.max() >= self.inv_id.size):
            return "an id outside the table"
        pos = self.inv_id[ids]
        self.level[pos_ref] = lvl_ref
        try:
            got_lvl = self.level[pos]
            if np.unique(pos).size != n or np.any(got_lvl < 0):
                return "rows differ from the reference's"
            if not np.array_equal(np.asarray(r.values["depth"])[:n],
                                  got_lvl):
                return "depth differs from the BFS level"
            for k in self.returned:
                if not np.array_equal(np.asarray(r.values[k])[:n],
                                      self.cols[k][pos]):
                    return f"column {k} differs from the table"
        finally:
            self.level[pos_ref] = -1
        return None

    def compare(self, roots, answers) -> tuple[dict, list]:
        why = [(root, self.wrong(root, r)) for root, r in zip(roots, answers)]
        bad = [(root, w) for root, w in why if w is not None]
        return {"wrong_answers": len(bad)}, bad

    def control(self, roots) -> list:
        """The reference's own answers with the varchar columns held in
        bfloat16."""
        out = []
        for root in roots:
            pos, lvl = self._rows(root)
            vals = {k: self.cols[k][pos] for k in self.returned}
            for k in self.returned[3:]:
                vals[k] = plain.to_bfloat16(vals[k])
            vals["depth"] = lvl
            out.append(plain.answer(vals, pos.size))
        return out
