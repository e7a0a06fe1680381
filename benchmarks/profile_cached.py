"""Attribute the cached-tier stream time across pipeline stages, in situ.

Runs the exact bench.py BENCH_MODE=cached configuration (ctx + zipf batch
stream come from bench.py itself — no copy to drift) through
``train_stream`` with PERSIA_TRACE spans enabled and aggregates per-stage
busy time per step. Because the stream is pipelined across three threads,
per-thread busy-ms/step > wall-ms/step is possible; the WALL time is
bounded below by the busiest serial stage chain (feeder: prep; stager:
stage; main: dispatch; writeback: wb_flush + psgrad).

No device->host fetch happens inside the measured window
(fetch_final=False): a fetch makes the host wait for every step dispatched
so far, which the training loop itself never does.

Prints one JSON dict: wall ms/step, samples/sec, and per-span
{count/step, busy ms/step}.
"""

import json
import os
import sys
import time
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402

STEPS = int(os.environ.get("PROFILE_STEPS", "100"))
WARM = int(os.environ.get("PROFILE_WARM", "16"))


def main():
    from persia_tpu import tracing

    ctx = bench._cached_tier_ctx()
    make_batch = bench._zipf_batch_maker()
    batches = [make_batch() for _ in range(WARM + STEPS)]
    ctx.train_stream(batches[:WARM], fetch_final=False)  # warm cache + compile

    tracing.enable()
    tracing.clear()
    t0 = time.perf_counter()
    ctx.train_stream(batches[WARM:], fetch_final=False)
    wall = time.perf_counter() - t0
    tracing.enable(False)

    agg = defaultdict(lambda: [0, 0.0])
    for ev in tracing.spans_snapshot():
        agg[ev["name"]][0] += 1
        agg[ev["name"]][1] += ev["dur"] / 1e3  # us -> ms

    out = {
        "wall_ms_per_step": round(wall / STEPS * 1e3, 3),
        "samples_per_sec": round(STEPS * bench.BATCH_SIZE / wall, 1),
    }
    for name in sorted(agg):
        cnt, ms = agg[name]
        out[name] = {
            "per_step": round(cnt / STEPS, 2),
            "busy_ms_per_step": round(ms / STEPS, 3),
        }
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
