"""Bring-up smoke test of the served traversal path on a TPU.

Drives the posdb-bfs deployment (``src/repro/configs/posdb_bfs.py``: a
2^20-vertex tree, 8 payload columns, depth 16) through the entry points a
user calls, and checks every answer row for row against a plain reference.

    python chip_smoke.py             # one chip: device, data, served path,
                                     # and the four traversal kernels
    python chip_smoke.py --chips 4   # four chips: the sharded engine against
                                     # the single-chip engine, nothing else

Every phase prints one line.  The run fails (non-zero exit, no result line)
if JAX finds no TPU, if any phase fails, or outside a checkout of the
repository.  The last line of stdout is one JSON object naming the device.
Everything runs in this one process: a process that has touched JAX holds
the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402

from repro.configs.posdb_bfs import CONFIG                  # noqa: E402
from repro.core import EngineCaps                           # noqa: E402
from repro.core.engine import (Dataset, RecursiveQuery,     # noqa: E402
                               run_query)
from repro.core.table import ColumnTable, payload_names     # noqa: E402
from repro.data.treegen import (TreeSpec, bfs_reference,    # noqa: E402
                                make_edge_table)
from repro.launch import compile_cache                      # noqa: E402
from repro.planner import ServingSession, paper_listing    # noqa: E402
from repro.planner.ast import weighted_listing              # noqa: E402
from repro.planner.calibrate import (KERNEL_NAMES,          # noqa: E402
                                     measured_kernel_factor)

SEED = 0
NUM_VERTICES = CONFIG.num_vertices        # 2^20
DEPTH = CONFIG.max_depth                  # 16: the tree's height and the query
PAYLOAD_COLS = CONFIG.payload_cols        # 8
FRONTIER = CONFIG.frontier_cap            # 2^15: the kernels' frontier width
SUBMIT_BATCHES = 3                        # submit() batches of 8 roots
COALESCED_ROOTS = 32                      # one enqueue/flush word


class SmokeFailure(RuntimeError):
    """A phase produced a wrong answer."""


def require(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, text: str) -> None:
    print(f"[{phase}] {text}", flush=True)


# ---------------------------------------------------------------------------
# device and data
# ---------------------------------------------------------------------------

def device_phase(chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX's first device is "
                         f"{d.platform!r}); refusing to run elsewhere")
    require(len(devs) >= chips, f"{chips} chips asked, {len(devs)} found")
    say("device", f"platform={d.platform} kind={d.device_kind} "
                  f"count={len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


class Data:
    """The posdb-bfs edge table on the device, plus host copies of the
    columns the references read."""

    def __init__(self, pad_to: int = 1):
        spec = TreeSpec(num_vertices=NUM_VERTICES, height=DEPTH,
                        payload_cols=PAYLOAD_COLS, seed=SEED)
        table = make_edge_table(spec)
        rng = np.random.default_rng(SEED + 2)
        cols = {k: np.asarray(v) for k, v in table.columns.items()}
        cols["w"] = rng.random(spec.num_edges).astype(np.float32)
        pad = (-spec.num_edges) % pad_to
        if pad:
            # rows from an out-of-range vertex: never in any CSR range, so
            # no traversal emits them (they only make the rows divisible)
            for k, v in cols.items():
                fill = NUM_VERTICES if k in ("from", "to") else 0
                cols[k] = np.concatenate(
                    [v, np.full((pad,) + v.shape[1:], fill, v.dtype)])
        self.cols = cols
        self.src, self.dst = cols["from"], cols["to"]
        self.num_edges = spec.num_edges
        self.inv_id = np.argsort(cols["id"][:spec.num_edges])
        self.table = ColumnTable.from_numpy(cols)
        self.ds = Dataset.prepare(self.table, NUM_VERTICES)
        self._levels: dict = {}

    def levels(self, root: int) -> list:
        if root not in self._levels:
            e = self.num_edges
            self._levels[root] = bfs_reference(self.src[:e], self.dst[:e],
                                               root, DEPTH, NUM_VERTICES)
        return self._levels[root]

    def expected_rows(self, root: int) -> dict:
        """Edge position -> BFS level for the depth-bounded query."""
        return {p: lvl for lvl, s in enumerate(self.levels(root)[:DEPTH + 1])
                for p in s}

    def distances(self, root: int) -> np.ndarray:
        """Path sums of ``w`` from ``root`` along the tree (inf where not
        reached within the depth bound)."""
        dist = np.full(NUM_VERTICES, np.inf)
        dist[root] = 0.0
        w = self.cols["w"].astype(np.float64)
        for s in self.levels(root)[:DEPTH + 1]:
            p = np.fromiter(s, np.int64, len(s))
            dist[self.dst[p]] = dist[self.src[p]] + w[p]
        return dist


def data_phase(pad_to: int = 1) -> Data:
    t0 = time.perf_counter()
    data = Data(pad_to)
    jax.block_until_ready(jax.tree_util.tree_leaves(data.ds.table))
    used = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
    say("data", f"vertices={NUM_VERTICES} edges={data.ds.table.num_rows} "
                f"payload_cols={PAYLOAD_COLS} bytes_in_use={used} "
                f"build_s={time.perf_counter() - t0:.3f}")
    return data


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------

def check_reach(data: Data, r, root: int) -> int:
    """Every row of one dressed reach answer against ``bfs_reference``:
    the same edges, each at its BFS level, with its stored columns."""
    require(not bool(np.any(np.asarray(r.overflow))),
            f"root {root}: overflow")
    n = int(r.count)
    want = data.expected_rows(root)
    pos = data.inv_id[np.asarray(r.values["id"])[:n]]
    require(sorted(pos.tolist()) == sorted(want),
            f"root {root}: {n} rows, reference has {len(want)}")
    depth = np.asarray(r.values["depth"])[:n]
    require(np.array_equal(depth, [want[p] for p in pos.tolist()]),
            f"root {root}: depth column differs from the BFS levels")
    for name in ("from", "to", "name", *payload_names(PAYLOAD_COLS)):
        got = np.asarray(r.values[name])[:n]
        require(np.array_equal(got, data.cols[name][pos]),
                f"root {root}: column {name} differs from the table")
    return n


def check_sssp(data: Data, r, root: int) -> int:
    """One shortest-path answer against the path sums along the tree: the
    same target rows, each row's ``value`` and every reached vertex's
    distance."""
    require(not bool(np.any(np.asarray(r.overflow))),
            f"sssp root {root}: overflow")
    n = int(r.count)
    to = np.asarray(r.values["to"])[:n]
    want_to = data.dst[list(data.expected_rows(root))]
    require(sorted(to.tolist()) == sorted(want_to.tolist()),
            f"sssp root {root}: row targets differ from the reference")
    dist = data.distances(root)
    require(np.allclose(np.asarray(r.values["value"])[:n], dist[to],
                        rtol=1e-5, atol=1e-5),
            f"sssp root {root}: row distances differ from the path sums")
    reached = np.isfinite(dist)
    require(np.allclose(np.asarray(r.vertex_values)[reached],
                        dist[reached], rtol=1e-5, atol=1e-5),
            f"sssp root {root}: vertex distances differ from the path sums")
    return n


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def served_phase(data: Data) -> None:
    session = ServingSession(data.ds, include_kernel=True)   # guards on
    sql = paper_listing(2, depth=DEPTH, payload_cols=PAYLOAD_COLS)
    rng = np.random.default_rng(SEED + 3)
    internal = np.unique(data.src)
    batches = [[0, *rng.choice(internal, 3).tolist(),
                *rng.integers(1, NUM_VERTICES, 4).tolist()]
               for _ in range(SUBMIT_BATCHES)]

    out, cold_s = _timed(lambda: serve(session, sql, batches[0]))
    rows = sum(check_reach(data, r, root)
               for root, r in zip(batches[0], out))
    warm = []
    for roots in batches[:1] * 3 + batches[1:]:
        out, dt = _timed(lambda: serve(session, sql, roots))
        warm.append(dt)
        rows += sum(check_reach(data, r, root) for root, r in zip(roots, out))
    say("served", f"submit batches of {len(batches[0])} roots: "
                  f"cold_s={cold_s:.3f} (plan+compile) "
                  f"warm_s_same_roots={[round(t, 4) for t in warm[:3]]} "
                  f"new_roots_s={[round(t, 4) for t in warm[3:]]} "
                  f"rows_checked={rows} "
                  f"engines={engines(session, sql, batches[0])}")

    roots = [0, *rng.choice(internal, COALESCED_ROOTS // 2 - 1).tolist(),
             *rng.integers(1, NUM_VERTICES, COALESCED_ROOTS // 2).tolist()]
    for label in ("cold", "warm"):
        tickets = [session.enqueue(sql, r) for r in roots]
        _, dt = _timed(session.flush)
        require(not session.last_report.truncated, "coalesced: truncated")
        rows = sum(check_reach(data, t.result(), root)
                   for root, t in zip(roots, tickets))
        say("served", f"enqueue/flush of {len(roots)} roots ({label}): "
                      f"flush_s={dt:.3f} rows_checked={rows}")

    sql_w = weighted_listing("shortest_path", root=0, depth=DEPTH,
                             weight_col="w")
    w_roots = [0, *rng.choice(internal, 3).tolist()]
    for label in ("cold", "warm"):
        out, dt = _timed(lambda: serve(session, sql_w, w_roots))
        rows = sum(check_sssp(data, r, root) for root, r in zip(w_roots, out))
        say("served", f"shortest_path over w, {len(w_roots)} roots "
                      f"({label}): s={dt:.3f} rows_checked={rows}")

    factors = {k: measured_kernel_factor(kernel=k) for k in KERNEL_NAMES}
    say("served", "kernel factors (kernel time / XLA time, measured here): "
                  + " ".join(f"{k}={v:.4g}" for k, v in factors.items()))


def serve(session, sql, roots) -> list:
    out = session.submit(sql, roots)
    rep = session.last_report
    require(not rep.truncated, f"roots {roots}: truncated ({rep})")
    return out


def engines(session, sql, roots) -> list:
    """The engines the session's buckets run for these roots."""
    entry = session.plan_for(sql, roots)
    return sorted({c.label for c in entry.bucket_choices})


# ---------------------------------------------------------------------------
# the kernels, compiled, at the deployment's widths
# ---------------------------------------------------------------------------

def kernels_phase(data: Data) -> None:
    from repro.kernels.frontier_expand import (frontier_expand_fused,
                                               frontier_expand_ref)
    from repro.kernels.frontier_pull import (frontier_pull_fused,
                                             frontier_pull_ref)
    from repro.kernels.late_gather import materialize
    from repro.kernels.spmm_segment import spmm_segment, spmm_segment_ref

    rng = np.random.default_rng(SEED + 4)
    ds = data.ds
    done = []

    targets = jnp.asarray(rng.integers(0, NUM_VERTICES, FRONTIER), jnp.int32)
    valid = jnp.asarray(rng.random(FRONTIER) < 0.9)
    cap = 2 * FRONTIER
    got = jax.block_until_ready(
        frontier_expand_fused(ds.csr, targets, valid, cap))
    want = frontier_expand_ref(ds.csr, targets, valid, cap)
    require(all(np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(got, want)), "frontier_expand != ref")
    require(not bool(got[2]), "frontier_expand: capacity too small")
    done.append(f"frontier_expand(F={FRONTIER},cap={cap},"
                f"edges_out={int(got[1])})")

    ds.ensure_reverse()
    ctx = ds.context("outbound")
    frontier = jnp.asarray(rng.random(NUM_VERTICES) < 0.25)
    visited = jnp.asarray(rng.random(NUM_VERTICES) < 0.5) | frontier
    got = frontier_pull_fused(ctx.rcsr, ctx.join_src, ctx.join_dst,
                              frontier, visited)
    want = frontier_pull_ref(ctx.rcsr, ctx.join_src, ctx.join_dst,
                             frontier, visited)
    require(np.array_equal(np.asarray(got), np.asarray(want)),
            "frontier_pull != ref")
    done.append(f"frontier_pull(V={NUM_VERTICES},next={int(jnp.sum(got))})")

    positions = jnp.asarray(
        rng.integers(0, ds.table.num_rows + 8, FRONTIER), jnp.int32)
    names = payload_names(PAYLOAD_COLS)
    got = materialize(ds.table.columns, positions, names, use_pallas=True)
    want = materialize(ds.table.columns, positions, names, use_pallas=False)
    require(all(np.array_equal(np.asarray(got[k]), np.asarray(want[k]))
                for k in names), "late_gather != ref")
    width = sum(ds.table.column(k).shape[1] for k in names)
    done.append(f"late_gather(P={FRONTIER},W={width})")

    x = jnp.asarray(rng.standard_normal((NUM_VERTICES, 1)), jnp.float32)
    src, dst = ds.table.column("from"), ds.table.column("to")
    w = ds.table.column("w")
    got = spmm_segment(x, src, dst, w, NUM_VERTICES, use_pallas=True)
    want = spmm_segment_ref(x, src, dst, w, NUM_VERTICES)
    require(np.allclose(np.asarray(got), np.asarray(want),
                        rtol=1e-5, atol=1e-5), "spmm_segment != ref")
    done.append(f"spmm_segment(V={NUM_VERTICES},E={src.shape[0]},D=1)")
    say("kernels", "compiled, equal to ref.py: " + " ".join(done))


# ---------------------------------------------------------------------------
# four chips: the sharded engine against one chip
# ---------------------------------------------------------------------------

def sharded_phase(data: Data, chips: int) -> None:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.distributed_bfs import make_distributed_pbfs
    from repro.launch.mesh import make_mesh

    root = 0
    want = data.expected_rows(root)
    widest = max(len(s) for s in data.levels(root))
    frontier = 1 << max(widest - 1, 1).bit_length()
    e_pad = data.ds.table.num_rows
    mesh = make_mesh((chips,), ("data",))
    fn = make_distributed_pbfs(
        mesh, ("data",), NUM_VERTICES,
        caps=EngineCaps(frontier=frontier, result=e_pad // chips),
        max_depth=DEPTH, num_payload_cols=PAYLOAD_COLS)
    sh = NamedSharding(mesh, P("data"))
    names = payload_names(PAYLOAD_COLS)
    payload = np.concatenate([data.cols[k] for k in names], axis=1)
    args = [jax.device_put(a, sh) for a in (data.src, data.dst, payload)]
    out, cold_s = _timed(lambda: jax.block_until_ready(
        fn(*args, jnp.int32(root))))
    out, warm_s = _timed(lambda: jax.block_until_ready(
        fn(*args, jnp.int32(root))))
    gpos, vals, counts, _, ovfs = out
    require(not bool(np.any(np.asarray(ovfs))), "sharded: overflow")

    per_shard = []
    devices = set()
    rows = []
    for s in sorted(gpos.addressable_shards, key=lambda s: s.index[0].start):
        p = np.asarray(s.data)
        live = p[p >= 0]
        per_shard.append(int(live.size))
        devices.add(s.device)
        rows.append(live)
    got = np.concatenate(rows)
    require(len(devices) == chips and min(per_shard) > 0,
            f"sharded: rows on {len(devices)} devices {per_shard}")
    require(sorted(got.tolist()) == sorted(want),
            "sharded rows != bfs_reference")
    require(per_shard == np.asarray(counts).ravel().tolist(),
            "sharded: counts disagree with the rows")
    v = np.asarray(vals)
    live = np.asarray(gpos) >= 0
    require(np.array_equal(v[live], payload[np.asarray(gpos)[live]]),
            "sharded: payload rows differ from the table")

    single = run_query(
        RecursiveQuery("precursive", DEPTH, PAYLOAD_COLS,
                       EngineCaps(frontier=frontier, result=e_pad)),
        data.ds, root)
    n = int(single.count)
    require(not bool(single.overflow) and sorted(
        np.asarray(single.positions)[:n].tolist()) == sorted(want),
        "single-chip precursive rows != bfs_reference")
    say("sharded", f"make_distributed_pbfs on {chips} chips, root {root}: "
                   f"rows={got.size} == single-chip precursive == "
                   f"bfs_reference; per_shard={per_shard} "
                   f"cold_s={cold_s:.3f} warm_s={warm_s:.4f}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded engine and what it is "
                         "compared with")
    args = ap.parse_args(argv)

    cache_dir = compile_cache.enable()
    cache = compile_cache.counter()
    t_start = time.perf_counter()
    device = device_phase(args.chips)
    data = data_phase(pad_to=args.chips)
    if args.chips == 1:
        served_phase(data)
        kernels_phase(data)
    else:
        sharded_phase(data, args.chips)
    say("cache", f"dir={cache_dir} persistent_cache_lookups="
                 f"{cache['lookups']} hits={cache['hits']} "
                 f"writes={cache['writes']} programs_compiled_or_loaded="
                 f"{cache['compiles']} compile_s={cache['compile_s']:.3f} "
                 f"total_s={time.perf_counter() - t_start:.3f} slowest="
                 + ",".join(f"{n}:{t:.1f}s" for t, n in cache["slowest"]))
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
