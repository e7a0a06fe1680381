"""Readings that the limits of ``perf/limits/<workload>.json`` are set from,
taken on the chip at the cell's own size, each through the harness's own
comparison (``compare.judge``) so that every line carries a verdict:

    python3 perf/limits_study.py --workload <name> --seeds 11,12,13 [--controls 3] [--out file]

For each seed: the program, one ``perf/run.py`` run with a short window. For
the first ``--controls`` seeds also, in the program's place: the control (the
reference in three bfloat16 passes, ``high``, one precision below the
``highest`` the configuration states; emulated, and as the chip's own
``high``), the one-pass arithmetic (the TPU's default, for the record), and
the program itself with each fault a one-chip training cell can have planted
in it (``--faults``, by default all of ``compare.FAULTS``; a state left
unchanged costs a second copy of the state on the device, which the pinned
cell's 12 GB do not leave room for: it reads 1 by construction). All of a
seed's runs are judged against one reference run. One JSON line per seed, each
entry with its verdict, each number beside its limit, and the gaps leaf by leaf.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONTROLS = (("control_high_3pass", 3), ("control_high_native", -3), ("default_1pass", 1))


def compare_faults():
    from perf import compare

    return compare.FAULTS


def _entry(verdict: dict) -> dict:
    leaves = {k: verdict["by_leaf"][k] for k in ("grad", "change")}
    return {"correct": verdict["correct"], "compared": verdict["compared"], "by_leaf": leaves}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=0,
                    help="how many of the seeds also get the controls and the faults")
    ap.add_argument("--faults", default=",".join(compare_faults()))
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from perf import compare, harness

    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    config = harness.load_config(cell["config"])
    out = open(args.out, "a") if args.out else None
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        kept: dict = {}
        run = harness.run_cell(args.workload, seed, args.seconds, False, time.perf_counter(),
                               overrides={"keep": kept})
        line = {"workload": args.workload, "seed": seed, "device": run["device"]["kind"],
                "reference_s": run["reference_s"], "steps": run["steps"],
                "program": _entry(run)}
        if n < args.controls:
            observed, sound = kept["observed"], kept["reference"]
            traffic = harness.load_traffic(cell["traffic"])
            entry = harness.load_module("entries", traffic["entry"]).Entry(config, traffic, seed)
            for name, passes in CONTROLS:
                other = compare.run_reference(config, entry, observed["lead"], observed["first"],
                                              seed, observed["keys"], passes=passes)
                other.update(lead=observed["lead"], first=observed["first"])
                after = compare.held_by(other, kept["after"]["keys"]) if kept["after"] else None
                v = compare.judge(config, entry, other, after, seed, args.workload, reference=sound)
                line[name] = _entry(v)
                del other, after
            for fault in filter(None, args.faults.split(",")):
                broken = harness.run_cell(args.workload, seed, args.seconds, False,
                                          time.perf_counter(),
                                          overrides={"fault": fault, "reference": sound})
                line[f"fault_{fault}"] = _entry(broken)
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
