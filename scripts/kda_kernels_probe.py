"""The delta rule's four kernels alone, on the chip, at the delta-rule cell's
widths: one pass of a KDA layer's group (8 heads of 128 x 128, one sequence of
16,384 positions packed from the cell's seven ragged documents), forward and
forward with backward (``jax.grad`` of ``sum(kda(...) ** 2)``), in ms by the
host's clock around ``block_until_ready``, then each kernel's own time a call
from a profiler trace of the same calls, and the gaps against the plain
recurrence that ``chip_smoke.phase_sequence_kernels`` prints.

    chiprun --chips 1 -- python3 scripts/kda_kernels_probe.py
    chiprun --chips 1 -- python3 scripts/kda_kernels_probe.py --beside .chipwork/parent/persia_tpu/ops/delta_rule.py

``--beside`` times other copies of ``ops/delta_rule.py`` (a parent's, a stub
of one part) in the same process after the tree's own, and prints how far
their outputs and gradients lie from the tree's. Without a chip it stops:
a CPU gives no time (``--rehearse`` runs a small shape through the interpreter
and prints no times, to try the script's own paths).
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

DOC_LENGTHS = (8195, 4101, 2057, 1041, 499, 246, 245)  # perf/traffic/pack16k-docs7-ragged-b1.json
KERNEL = re.compile(r"kda_(?:prepare|chunk)_(?:fwd|bwd)")
LENGTH, HEADS, REPEATS = 16384, 8, 10  # a pass of a KDA layer in the cell: 8 heads of its one sequence


def inputs(length: int, heads: int):
    rng = np.random.default_rng(0)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    shape = (1, length, heads, 128)
    docs = [d * length // sum(DOC_LENGTHS) for d in DOC_LENGTHS]
    docs[0] += length - sum(docs)
    lo = np.repeat(np.cumsum([0] + docs[:-1]), docs)[None].astype(np.int32)
    return [jnp.asarray(unit(rng.standard_normal(shape)) / np.sqrt(128), jnp.float32),
            jnp.asarray(unit(rng.standard_normal(shape)), jnp.float32),
            jnp.asarray(rng.standard_normal(shape), jnp.float32),
            jnp.asarray(-np.exp(rng.uniform(np.log(1e-3), np.log(1.6), shape)), jnp.float32),
            jnp.asarray(rng.uniform(0.1, 0.9, shape[:3]), jnp.float32)], jnp.asarray(lo)


def load(path: str):
    spec = importlib.util.spec_from_file_location("delta_rule_beside_" + re.sub(r"\W", "_", path), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def timed(f, args, repeats: int):
    out = jax.block_until_ready(f(*args))  # compiles
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e3, out


def kernel_ms(fs, args, calls: int) -> dict:
    """ms a call of each kernel, from the device's own events over ``calls``
    calls of every function of ``fs``."""
    from perf.trace_reduce import DEVICE_PLANE, OPS_LINE, read_events

    where = tempfile.mkdtemp(prefix="kda_probe_")
    try:
        with jax.profiler.trace(where):
            for f in fs:
                for _ in range(calls):
                    out = f(*args)
                jax.block_until_ready(out)
        total, count = {}, {}
        for path in glob.glob(os.path.join(where, "plugins", "profile", "*", "*.xplane.pb")):
            for plane, line, name, _start, dur in read_events(path):
                found = KERNEL.search(name) if DEVICE_PLANE.match(plane) and line == OPS_LINE else None
                if found:
                    total[found.group(0)] = total.get(found.group(0), 0.0) + dur * 1e-6
                    count[found.group(0)] = count.get(found.group(0), 0) + 1
        return {k: {"ms_a_call": total[k] / count[k], "calls": count[k] / calls} for k in sorted(total)}
    finally:
        shutil.rmtree(where, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--beside", action="append", default=[], help="another copy of ops/delta_rule.py to time")
    ap.add_argument("--rehearse", action="store_true", help="a small shape through the interpreter, no times")
    ns = ap.parse_args()

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not ns.rehearse:
        print("no chip: a CPU run gives no time (see --rehearse)", file=sys.stderr)
        return 1
    jax.config.update("jax_default_matmul_precision", "highest")  # as perf/run.py sets it
    length, heads, repeats = (256, 2, 1) if ns.rehearse else (LENGTH, HEADS, REPEATS)
    interpret = not on_chip
    device = jax.devices()[0]
    print(json.dumps({"platform": device.platform, "device_kind": device.device_kind, "length": length,
                      "heads": heads, "repeats": repeats, "interpret": interpret}), flush=True)

    import chip_smoke
    from persia_tpu.ops import delta_rule as own

    smoke = dict(length=256, heads=2) if ns.rehearse else {}
    gaps = chip_smoke.phase_sequence_kernels(interpret=interpret, **smoke)["delta_rule"]
    print(json.dumps({"against_the_recurrence": gaps}), flush=True)

    args, lo = inputs(length, heads)
    gap = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    first = None
    for name, module in [("tree", own)] + [(path, load(path)) for path in ns.beside]:
        rule = lambda *a, m=module: m.kda(*a, lo, interpret=interpret)
        fwd = jax.jit(rule)
        bwd = jax.jit(jax.grad(lambda *a: jnp.sum(rule(*a) ** 2), argnums=(0, 1, 2, 3, 4)))
        f_ms, o = timed(fwd, args, repeats)
        b_ms, grads = timed(bwd, args, repeats)
        line = {"module": name, "forward_ms": f_ms, "forward_with_backward_ms": b_ms}
        if on_chip:
            line["kernels"] = kernel_ms([fwd, bwd], args, repeats)
        else:
            line["forward_ms"] = line["forward_with_backward_ms"] = None  # the interpreter's: not a time
        if first is None:
            first = (o, grads)
        else:
            line["from_the_tree"] = {"o": gap(o, first[0]),
                                     **{n: gap(a, b) for n, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"),
                                                                       grads, first[1])}}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
