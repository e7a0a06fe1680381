"""The three interval-attention kernels alone, on the chip, at the latent
cells' shape: one sequence of 16,384 positions packed from the cells' seven
ragged documents, 32 query heads of 128 over 32 K/V heads, 64 further key
columns all heads share and 64 a query head beside them (``q_shared``,
head-major, as the rotated-latent tower hands them over), bfloat16, tiles of
512. For 1, 2, 4 and 8 query heads a grid step (``heads_a_step`` held to each
by its cap): the forward and the forward with backward in ms by the host's
clock around ``block_until_ready``, each kernel's ms a call from a profiler
trace of the same calls, and whether every output and gradient equals the one
head a step's bit for bit. Then the dead steps' own cost: with ``lo = arange``
a query reads itself alone, so the 32 tiles on the diagonal are live and the
other 496 of the triangle's 528 dead; the same live work on a grid of the 32
alone (a window of one position) is subtracted, and what is left is 496 dead
steps a row of heads. On a machine with one TPU chip, from the repository's root:

    python3 scripts/attention_heads_probe.py
    python3 scripts/attention_heads_probe.py --beside <a parent checkout>/persia_tpu/ops/flash_attention.py

``--beside`` times other copies of ``ops/flash_attention.py`` (a parent's) at
the same inputs in the same process, and says whether their outputs and
gradients equal the tree's bit for bit. Without a chip it stops: a CPU gives
no time (``--rehearse`` runs a small shape through the interpreter and prints
no times, to try the script's own paths).
"""

from __future__ import annotations

import argparse
import glob
import importlib
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
import time
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

DOC_LENGTHS = (8195, 4101, 2057, 1041, 499, 246, 245)  # perf/traffic/pack16k-docs7-ragged-b1.json
KERNEL = re.compile(r"interval_attention_(?:fwd|dq|dkv)")
LENGTH, HEADS, SHARED, REPEATS = 16384, 32, 64, 10
WHAT = ("o", "dq", "dk", "dv", "dq_shared", "dk_shared")


def inputs(length: int, heads: int):
    """(q, k, v, q_shared, k_shared), the cotangent, and the documents' ``lo``."""
    rng = np.random.default_rng(0)
    bf16 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    docs = [d * length // sum(DOC_LENGTHS) for d in DOC_LENGTHS]
    docs[0] += length - sum(docs)
    lo = np.repeat(np.cumsum([0] + docs[:-1]), docs)[None].astype(np.int32)
    args = (bf16(1, length, heads, 128), bf16(1, length, heads, 128), bf16(1, length, heads, 128),
            bf16(1, heads, length, SHARED), bf16(1, length, SHARED))
    return args, bf16(1, length, heads, 128), jnp.asarray(lo)


def load(path: str):
    spec = importlib.util.spec_from_file_location("flash_attention_beside_" + re.sub(r"\W", "_", path), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def functions(module, lo, ct, tile: int, interpret: bool, window=None):
    """The forward, and the forward with backward (every input's gradient), jitted."""
    def attend(q, k, v, qs, ks):
        return module.interval_attention(q, k, v, lo, window=window, tile=tile, interpret=interpret,
                                         k_shared=ks, q_shared=qs)

    def both(*args):
        o, vjp = jax.vjp(attend, *args)
        return (o,) + vjp(ct)

    return jax.jit(attend), jax.jit(both)


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

    where = tempfile.mkdtemp(prefix="attention_probe_")
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


def same(a, b) -> dict:
    return {n: bool(np.array_equal(np.asarray(x), np.asarray(y))) for n, x, y in zip(WHAT, a, b)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--beside", action="append", default=[], help="another copy of ops/flash_attention.py")
    ap.add_argument("--rehearse", action="store_true", help="a small shape through the interpreter, no times")
    ns = ap.parse_args()

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not ns.rehearse:
        print("no chip: a CPU run gives no time (see --rehearse)", file=sys.stderr)
        return 1
    jax.config.update("jax_default_matmul_precision", "highest")  # as perf/run.py sets it
    length, heads, tile, repeats = (256, 8, 64, 1) if ns.rehearse else (LENGTH, HEADS, 512, REPEATS)
    interpret = not on_chip
    device = jax.devices()[0]
    print(json.dumps({"platform": device.platform, "device_kind": device.device_kind, "length": length,
                      "heads": heads, "tile": tile, "repeats": repeats, "interpret": interpret}), flush=True)

    own = importlib.import_module("persia_tpu.ops.flash_attention")
    args, ct, lo = inputs(length, heads)
    diagonal = jnp.arange(length, dtype=jnp.int32)[None]
    n_tiles = length // tile
    grid = own.interval_visits(n_tiles, tile)

    def measure(module, lo, window=None):
        fwd, both = functions(module, lo, ct, tile, interpret, window)
        f_ms, _ = timed(fwd, args, repeats)
        b_ms, out = timed(both, args, repeats)
        line = {"forward_ms": f_ms, "forward_with_backward_ms": b_ms}
        if on_chip:
            line["kernels"] = kernel_ms([fwd, both], args, repeats)
        else:  # the interpreter's: not a time
            line["forward_ms"] = line["forward_with_backward_ms"] = None
        return line, out

    first = None
    for cap in (1, 2, 4, 8):
        with mock.patch.object(own, "_HEADS_A_STEP_MAX", cap):
            hs = own.heads_a_step(heads, heads, 128, tile, jnp.bfloat16, SHARED)
            line, out = measure(own, lo)
            line.update(heads_a_step=hs, grid_steps=heads // hs * grid)
            first = first or out
            line["same_as_one_head_a_step"] = same(out, first)
            print(json.dumps(line), flush=True)
            # the dead steps: a grid of the triangle with its diagonal alone live, and a
            # grid of the diagonal alone, at the same live work
            full, out_full = measure(own, diagonal)
            alone, out_alone = measure(own, diagonal, window=1)
            dead = {"heads_a_step": hs, "dead_steps_a_call": heads // hs * (grid - n_tiles),
                    "same_live_work": same(out_full, out_alone)}
            if on_chip:
                dead["us_a_dead_step"] = {
                    k: (full["kernels"][k]["ms_a_call"] - alone["kernels"][k]["ms_a_call"]) * 1e3
                    / dead["dead_steps_a_call"] for k in full["kernels"]}
                dead["ms_a_call"] = {k: [full["kernels"][k]["ms_a_call"], alone["kernels"][k]["ms_a_call"]]
                                     for k in full["kernels"]}
            print(json.dumps({"dead_steps": dead}), flush=True)
    for path in ns.beside:
        line, out = measure(load(path), lo)
        line.update(module=path, same_as_the_tree=same(out, first))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
