"""Benchmark harness: one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--quick`` shrinks datasets for
CI-speed runs — it is the documented CI profile:

  PYTHONPATH=src python -m benchmarks.run --quick --json BENCH_bfs.json

``--json PATH`` additionally writes every emitted row as
``{name: {"us_per_call": float, "derived": str}}`` plus a ``"_meta"`` entry
(backend, host, git sha, timestamp, the quick/only profile) so the perf
trajectory can be tracked across PRs (one BENCH_bfs.json artifact per run).
``--history PATH`` appends one compact JSON line — the meta plus every
``us_per_call`` — to a history log (e.g. ``BENCH_history.jsonl``); the
drift report in ``scripts/perf_gate.py`` reads it.  Full sizes (no
``--quick``) reproduce the paper's relative results.
"""
from __future__ import annotations

import argparse
import json
import platform
import subprocess


def run_meta(args) -> dict:
    """The provenance stamp for one benchmark run.  Timestamps come from
    the caller (``--timestamp``, e.g. ``$(date -u +%Y-%m-%dT%H:%M:%SZ)``)
    so artifact regeneration is reproducible byte-for-byte; the git sha is
    best-effort (absent outside a checkout)."""
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10).stdout.strip() or None
    except Exception:
        sha = None
    import jax
    return {
        "backend": jax.default_backend(),
        "host": platform.node(),
        "git_sha": sha,
        "timestamp": args.timestamp,
        "quick": bool(args.quick),
        "only": args.only,
        "tier1_count": args.tier1_count,
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small datasets; the CI profile")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write emitted rows as JSON (e.g. BENCH_bfs.json)")
    ap.add_argument("--only", default=None,
                    help="comma list: exp1,exp2,exp3,claims,kern,planner,"
                         "serving,direction,weighted")
    ap.add_argument("--kernel", action="store_true",
                    help="benchmark the Pallas frontier_expand kernel via "
                         "CSRIndexJoin(expand_fn=) and let the planner "
                         "cost it as a physical alternative")
    ap.add_argument("--history", default=None, metavar="PATH",
                    help="append one JSON line (meta + every us_per_call) "
                         "to PATH (e.g. BENCH_history.jsonl)")
    ap.add_argument("--timestamp", default=None,
                    help="ISO timestamp to stamp into _meta/history "
                         "(callers pass it; omitted -> null)")
    ap.add_argument("--tier1-count", type=int, default=None,
                    help="tier-1 test count to record in _meta/history")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    compile_cache.enable()

    from . import (bench_util, exp1_bfs, exp2_payload, exp3_rewrite,
                   exp_claims, exp_direction, exp_planner, exp_serving,
                   exp_weighted, kernels_bench)

    bench_util.RESULTS.clear()     # fresh per invocation (notebook reuse)
    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")

    if not only or "exp1" in only:
        if args.quick:
            exp1_bfs.run(num_vertices=20_000, height=10, depths=(4, 8),
                         repeat=3)
        else:
            exp1_bfs.run()
    if not only or "exp2" in only:
        if args.quick:
            exp2_payload.run(num_vertices=20_000, height=10, depths=(4, 8),
                             payloads=(2, 16), repeat=3)
        else:
            exp2_payload.run()
    if not only or "exp3" in only:
        if args.quick:
            exp3_rewrite.run(num_vertices=20_000, height=10, depths=(4, 8),
                             payloads=(16,), repeat=3)
        else:
            exp3_rewrite.run()
    if not only or "claims" in only:
        if args.quick:
            exp_claims.run(num_vertices=50_000, height=500, depth=8,
                           repeat=3)
        else:
            exp_claims.run()
    if not only or "planner" in only:
        if args.quick:
            exp_planner.run(num_vertices=20_000, height=10, depths=(4, 8),
                            payloads=16, repeat=3,
                            include_kernel=args.kernel)
        else:
            exp_planner.run(include_kernel=args.kernel)
    if not only or "serving" in only:
        if args.quick:
            exp_serving.run(num_vertices=20_000, height=10, depth=4,
                            repeat=3)
        else:
            exp_serving.run()
    if not only or "direction" in only:
        if args.quick:
            exp_direction.run(num_vertices=20_000, height=10, depth=8,
                              repeat=3)
        else:
            exp_direction.run()
    if not only or "weighted" in only:
        if args.quick:
            exp_weighted.run(num_vertices=20_000, height=10, depth=8,
                             repeat=3)
        else:
            exp_weighted.run()
    if not only or "kern" in only:
        kernels_bench.run(repeat=3 if args.quick else 5)

    if args.json or args.history:
        rows = {name: {"us_per_call": us, "derived": derived}
                for name, us, derived in bench_util.RESULTS}
        meta = run_meta(args)
    if args.json:
        doc = dict(rows)
        doc["_meta"] = meta
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        print(f"# wrote {len(rows)} rows to {args.json}")
    if args.history:
        line = {"meta": meta,
                "rows": {name: round(r["us_per_call"], 3)
                         for name, r in rows.items()}}
        with open(args.history, "a") as f:
            f.write(json.dumps(line, sort_keys=True) + "\n")
        print(f"# appended {len(rows)} rows to {args.history}")


if __name__ == "__main__":
    main()
