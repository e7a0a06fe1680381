"""Tiny presets the perf tests share: the same files and code paths as the
cells, cut to sizes a CPU test run can hold."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_MODEL = {"embedding_dim": 16, "bottom_mlp": [32, 16], "top_mlp": [64, 32, 1]}

REHEARSAL = {
    "fused_pinned": {
        "config": dict(TINY_MODEL, table_rows=[50, 3, 4000, 100000, 7, 200000]),
        "traffic": {"batch": 256, "rows_divisor": 1, "warmup_steps": 2},
    },
    "cached_stream": {
        # every table fits the pool, resident from the first step
        "config": dict(TINY_MODEL, table_rows=[50, 3, 4000, 100000, 7, 200000]),
        "traffic": {"batch": 256, "rows_divisor": 1, "cache_rows": 1 << 19, "ps_capacity": 1 << 20,
                    "dispatch_k": 4, "warmup_steps": 8},
    },
}

CELL_OF_ENTRY = {"fused_pinned": "tb-pinned-share16", "cached_stream": "tb-cached-resident"}


def host_tracer_over(events):
    """A recorded trace's events with one host event that spans every device
    event: the trace as a host tracer that covered the device's whole record
    would have left it (the recorded fixtures keep a few host events, which
    end before the device's record does)."""
    dev = [e for e in events if e[0].startswith("/device:")]
    t0, t1 = min(e[3] for e in dev), max(e[3] + e[4] for e in dev)
    return list(events) + [("/host:CPU", "main", "host_tracer_on", t0, t1 - t0)]
