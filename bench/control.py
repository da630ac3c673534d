"""The control of a cell's comparison: the reference, computed one step below
the configuration's float32 (bfloat16), put in the program's place.

    python3 bench/control.py --workload <name> --seeds 1,2,3

For each seed it makes the cell's data, draws the roots the window would
serve first, takes the control's answers for as many of them as a run
compares, and prints the readings of the same comparison a run makes with
their limits.  Every reading line should exceed a limit.  The benchmark's
own runs never run this.
"""
import argparse
import itertools
import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    from bench import harness, loadgen

    cell = harness.load_cell(args.workload, False)
    cfg, traffic = cell.config, cell.traffic
    gen = cell.part("generators", cfg["generator"])
    reference = cell.part("reference", cfg["name"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cols, v = gen.generate(cfg["params"], seed)
        host = harness.host_columns(cols)
        del cols
        pop = None
        if traffic["roots"]["kind"] != "fixed":
            pop = loadgen.population(traffic["roots"].get("population", {}),
                                     host["from"], v)
        roots = loadgen.Roots(traffic["roots"], seed, pop)
        n = int(traffic["check"]["answers"])
        drawn = list(itertools.islice(loadgen.requests(
            traffic["loop"], roots, loadgen.WINDOW), n))
        sample = [r for req in drawn for r in req][:n]
        ref = reference.Reference(host, v, cfg, traffic)
        readings, _ = ref.compare(sample, ref.control(sample))
        fails = [k for k, x in readings.items() if x > reference.LIMITS[k]]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "answers": len(sample), "readings": readings,
                          "limits": reference.LIMITS, "fails": fails,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
