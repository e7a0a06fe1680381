"""One control or one planted fault of a cell, in a process of its own, judged
by the cell's committed limits through the harness's own comparison:

    python3 perf/limits_one.py --workload <name> --seed <n> --control
    python3 perf/limits_one.py --workload <name> --seed <n> --fault half_batch

For a cell whose state is too large for ``perf/limits_study.py``, which holds
the program's and two references' snapshots in one process (30 GB of host
memory at ``sdar-ep8-bd4-seq4k``'s size, past the one-chip machine's 40 GiB
with a compile on top). ``--control`` runs the reference and then the first
control of the model's ``CONTROLS`` in the program's place over the same
batches from the seed: no program, two references, one after the other.
``--fault`` is one ``run_cell`` with the fault planted under the harness. One
JSON line: the verdict, each number beside its limit, the five worst leaves.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def control(workload: str, seed: int, rehearsal=None, root=None) -> dict:
    """The first control in the program's place, against the sound reference."""
    import numpy as np

    from perf import compare, harness

    root = root or harness.ROOT
    cell = harness.find_cell(harness.load_benchmark(root), workload)
    changed = rehearsal or {}
    cfg = dict(harness.load_config(cell["config"], root), **changed.get("config", {}))
    tr = dict(harness.load_traffic(cell["traffic"], root), **changed.get("traffic", {}))
    if rehearsal is None:
        harness.require_chips(cell["chips"])
    import jax

    jax.config.update("jax_default_matmul_precision", cfg["guarantees"]["matmul_precision"])
    from persia_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    entry = harness.load_module("entries", tr["entry"], root).Entry(cfg, tr, seed)
    gen = harness.load_module("generators", tr["generator"], root).make(cfg, tr, seed)
    first = [next(gen) for _ in range(entry.snapshot_after[-1])]
    keys = np.unique(np.concatenate([entry.keys(b).reshape(-1) for b in first]))
    sound = compare.run_reference(cfg, entry, [], first, seed, keys, root=root)
    name = harness.model_module("reference", cfg, root).CONTROLS[0]
    other = compare.run_reference(cfg, entry, [], first, seed, keys, control=name, root=root)
    other.update(lead=[], first=first)
    verdict = compare.judge(cfg, entry, other, None, seed, workload, root=root, reference=sound)
    return dict(verdict, control=name)


def fault(workload: str, seed: int, name: str, seconds: float = 3.0, rehearsal=None, root=None) -> dict:
    """The program with the fault planted, through ``run_cell``."""
    from perf import harness

    planted = dict(rehearsal or {}, fault=name)
    kw = {"rehearsal": planted} if rehearsal is not None else {"overrides": planted}
    return harness.run_cell(workload, seed, seconds, False, time.perf_counter(),
                            root=root or harness.ROOT, **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    which = ap.add_mutually_exclusive_group(required=True)
    which.add_argument("--control", action="store_true")
    which.add_argument("--fault")
    args = ap.parse_args(argv)
    t = time.perf_counter()
    v = control(args.workload, args.seed) if args.control else fault(args.workload, args.seed, args.fault)
    worst = sorted(v["by_leaf"]["grad"].items(), key=lambda kv: -kv[1])[:5]
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "what": v["control"] if args.control else f"fault_{args.fault}",
                      "correct": bool(v["correct"]), "compared": v["compared"],
                      "worst_grad_leaves": worst, "seconds": round(time.perf_counter() - t, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
