"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout, on the machine that holds the chips the
cell asks for; exits non-zero, with no result line, where JAX finds no TPU
or fewer chips.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``, each number compared
with its limit.  See ``bench/harness.py``.
"""
import time

T_START = time.perf_counter()

import argparse                                   # noqa: E402
import os                                         # noqa: E402
import sys                                        # noqa: E402
from pathlib import Path                          # noqa: E402

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]
# the TPU runtime would log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-trace", default=None,
                    help="copy the traced run's .xplane.pb here")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    from bench import harness

    harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                t_start=T_START, save_trace=args.save_trace)
    return 0


if __name__ == "__main__":
    sys.exit(main())
