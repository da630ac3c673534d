"""End-to-end driver for the paper's engine: the PLANNER answering a SQL
``WITH RECURSIVE`` query without an engine name (cost-based selection over
all nine pipelines + EXPLAIN), the single-device depth sweep, vmap-BATCHED
multi-root serving (one XLA dispatch answering many users' roots),
direction-aware traversal (outbound / inbound / both), and the DISTRIBUTED
positional BFS over every device of the host — the pattern that runs
unchanged on the 512-chip production mesh.  On a CPU host the platform is
split into 8 placeholder devices (the flag shapes the CPU platform only);
on a 1- or 4-chip TPU host the mesh is the chips.

    PYTHONPATH=src python examples/bfs_traversal.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time                      # noqa: E402

import jax                       # noqa: E402
import jax.numpy as jnp          # noqa: E402
import numpy as np               # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P   # noqa: E402

from repro.core import EngineCaps                            # noqa: E402
from repro.core.distributed_bfs import make_distributed_pbfs  # noqa: E402
from repro.core.engine import (Dataset, RecursiveQuery,      # noqa: E402
                               plan_and_run, plan_repr, run_query,
                               run_query_batch)
from repro.data.treegen import TreeSpec, make_edge_table     # noqa: E402
from repro.launch.mesh import make_mesh                      # noqa: E402
from repro.planner import paper_listing, plan                # noqa: E402


def main():
    spec = TreeSpec(num_vertices=262_145, height=40, payload_cols=8, seed=1)
    table = make_edge_table(spec)
    ds = Dataset.prepare(table, spec.num_vertices)
    caps = EngineCaps(frontier=1 << 16, result=1 << 18)

    print("=== the planner: SQL in, engine choice out ===")
    sql = paper_listing(2, root=0, depth=10, payload_cols=8)
    print(sql)
    report = plan(sql, ds, caps=caps)
    print("ranked:", ", ".join(f"{c.label}~{c.cost.est_us:.0f}us"
                               for c in report.ranked[:4]), "...")
    r = jax.block_until_ready(plan_and_run(sql, ds, caps=caps))
    t0 = time.perf_counter()
    r = jax.block_until_ready(plan_and_run(sql, ds, caps=caps))
    print(f"chose {report.best.label}: {1e3*(time.perf_counter()-t0):7.2f} "
          f"ms  rows={int(r.count)}  depth column 0..",
          int(np.asarray(r.values['depth']).max()), sep="")
    filt = plan_and_run(sql + " WHERE depth <= 3", ds, caps=caps)
    print(f"with WHERE depth <= 3 (pushed into the recursion bound): "
          f"rows={int(filt.count)}")

    print("\n=== single-device PRecursive, depth sweep ===")
    for depth in (5, 10, 20, 40):
        q = RecursiveQuery("precursive", depth, 8, caps)
        r = jax.block_until_ready(run_query(q, ds, 0))
        t0 = time.perf_counter()
        r = jax.block_until_ready(run_query(q, ds, 0))
        print(f"depth {depth:3d}: {1e3*(time.perf_counter()-t0):7.2f} ms  "
              f"rows={int(r.count)} overflow={bool(r.overflow)}")

    print("\n=== batched multi-root serving (one dispatch, 16 users) ===")
    q = RecursiveQuery("precursive", 10, 8, caps)
    roots = jnp.arange(16, dtype=jnp.int32) * 1000
    rb = jax.block_until_ready(run_query_batch(q, ds, roots))   # compile
    t0 = time.perf_counter()
    rb = jax.block_until_ready(run_query_batch(q, ds, roots))
    dt = time.perf_counter() - t0
    print(f"16 roots in one jitted dispatch: {1e3*dt:7.2f} ms "
          f"({1e3*dt/16:6.2f} ms/root), rows per root: "
          f"{np.asarray(rb.count).tolist()}")

    print("\n=== direction-aware traversal (reverse CSR) ===")
    leaf = int(np.asarray(table.column('to'))[-1])
    for direction in ("outbound", "inbound", "both"):
        qd = RecursiveQuery("precursive", 10, 8, caps, direction=direction)
        r = jax.block_until_ready(run_query(qd, ds, leaf))
        print(f"{direction:9s} from vertex {leaf}: rows={int(r.count):6d} "
              f"levels={int(r.depth)} overflow={bool(r.overflow)} "
              f"max_row_depth={int(np.asarray(r.row_depths).max())}")

    print("\n=== the PRecursive plan, derived from the operator pipeline ===")
    print(plan_repr("precursive", 10, 8))

    n_dev = len(jax.devices())
    print(f"\n=== distributed PRecursive over a {n_dev}-device mesh ===")
    mesh = make_mesh((n_dev,), ("data",))
    fn = make_distributed_pbfs(mesh, ("data",), spec.num_vertices,
                               caps=EngineCaps(frontier=1 << 14,
                                               result=(1 << 18) // n_dev),
                               max_depth=20, num_payload_cols=8)
    sh = NamedSharding(mesh, P("data"))
    src = jax.device_put(np.asarray(table.column("from")), sh)
    dst = jax.device_put(np.asarray(table.column("to")), sh)
    pay = jax.device_put(
        np.concatenate([np.asarray(table.column("column1"))], axis=1), sh)
    out = jax.block_until_ready(fn(src, dst, pay, jnp.int32(0)))
    t0 = time.perf_counter()
    gpos, vals, counts, depths, ovfs = jax.block_until_ready(
        fn(src, dst, pay, jnp.int32(0)))
    rows = int(np.sum(np.asarray(counts)))
    print(f"20-hop traversal on {n_dev} shards: "
          f"{1e3*(time.perf_counter()-t0):7.2f} ms, rows={rows}")
    print("per-shard result counts:", np.asarray(counts).ravel().tolist())
    print("values materialized shard-locally; only vertex ids crossed the "
          "mesh (one all_gather per level).")


if __name__ == "__main__":
    main()
