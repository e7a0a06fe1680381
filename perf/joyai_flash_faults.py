"""The ``joyai_flash_moe`` tower's own planted faults, for the limits' study
and the tests only: the program's arithmetic broken underneath the harness,
judged by the cell's committed limits through ``run_cell`` like
``perf/limits_one.py --fault`` judges the two every training cell shares:

    python3 perf/joyai_flash_faults.py --workload <name> --seed <n> --fault mtp_term_left_out
    python3 perf/joyai_flash_faults.py --workload <name> --seed <n> --fault rotation_left_out

``mtp_term_left_out``: the prediction module's term leaves the loss (its
coefficient 0: the module still runs, nothing trains on it).
``rotation_left_out``: no rotation of the query's and the shared key's columns
(latent attention without positions). Both are the tower's own arguments
(``mtp_weight``, ``rope_theta``), set on the tower the entry builds; nothing in
the timed path knows of a fault. One JSON line, as ``limits_one.py`` prints.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FAULTS = {"mtp_term_left_out": {"mtp_weight": 0.0}, "rotation_left_out": {"rope_theta": None}}


@contextlib.contextmanager
def planted(name: str):
    """While it is open, every tower built from a configuration carries the fault."""
    from persia_tpu.models.joyai_flash_moe import JoyAIFlashMoE

    sound = JoyAIFlashMoE.from_config.__func__

    def broken(cls, cfg, **kw):
        return dataclasses.replace(sound(cls, cfg, **kw), **FAULTS[name])

    JoyAIFlashMoE.from_config = classmethod(broken)
    try:
        yield
    finally:
        JoyAIFlashMoE.from_config = classmethod(sound)


def fault(workload: str, seed: int, name: str, seconds: float = 3.0, rehearsal=None, root=None) -> dict:
    """The program with the fault planted, through ``run_cell``."""
    from perf import harness

    with planted(name):
        kw = {"rehearsal": rehearsal} if rehearsal is not None else {}
        return harness.run_cell(workload, seed, seconds, False, time.perf_counter(),
                                root=root or harness.ROOT, **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    args = ap.parse_args(argv)
    t = time.perf_counter()
    v = fault(args.workload, args.seed, args.fault)
    worst = sorted(v["by_leaf"]["grad"].items(), key=lambda kv: -kv[1])[:5]
    print(json.dumps({"workload": args.workload, "seed": args.seed, "what": f"fault_{args.fault}",
                      "correct": bool(v["correct"]), "compared": v["compared"],
                      "worst_grad_leaves": worst, "seconds": round(time.perf_counter() - t, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
