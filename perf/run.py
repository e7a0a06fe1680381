"""python3 perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, once: refuses to run without the cell's TPU chips,
loads the cell's files by name, builds tables and weights on the device from
the seed, drives the compared steps and the warm-up (set-up), measures for
``--seconds``, drains, runs the reference, and prints one JSON line last.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up counts from here: imports included

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None,
                    help="also write the trace's compact events here (for making a fixture)")
    args = ap.parse_args(argv)

    from perf import harness

    result = harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace), _T0,
                              dump_trace=args.dump_trace)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
