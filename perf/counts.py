"""Operations and bytes the DLRM training step needs, as functions of a
configuration file. Copied in spirit from ``bench.py:_model_train_flops_per_sample``
(hard-wired there to the toy shape); here every size comes from the file.

Conventions: a multiply-add is 2 FLOPs; backward costs twice the forward;
gathers and the sparse update add bytes, not FLOPs (the usual model-FLOPs
convention).
"""

from __future__ import annotations

import json
import os
from typing import Dict

_HERE = os.path.dirname(os.path.abspath(__file__))


def n_vectors(config: dict) -> int:
    return len(config["table_rows"]) + 1


def forward_macs_per_sample(config: dict) -> int:
    """Multiply-adds of one sample's forward pass: both MLPs and the dot
    interaction as executed (the full (n, n) product of n = slots + 1 vectors)."""
    d = config["embedding_dim"]
    macs, fan = 0, config["num_dense"]
    for h in config["bottom_mlp"]:
        macs += fan * h
        fan = h
    n = n_vectors(config)
    macs += n * n * d
    fan = d + n * (n - 1) // 2
    for h in config["top_mlp"]:
        macs += fan * h
        fan = h
    return macs


def train_flops_per_sample(config: dict) -> float:
    """Forward plus backward (2x forward) FLOPs of one trained sample."""
    return 3.0 * 2.0 * forward_macs_per_sample(config)


def dense_param_count(config: dict) -> int:
    from perf.weights import dense_layer_sizes

    return sum(i * o + o for i, o in dense_layer_sizes(config))


def step_hbm_bytes(config: dict, batch: int) -> float:
    """Bytes the step's algorithm has to move through HBM for one batch:

    - every looked-up row read once for the forward (slots x batch x row),
    - the sparse Adagrad update on those rows: row and accumulator read and
      written (4 x row bytes), plus the gradient rows read once,
    - dense parameters, their gradient and Adam's two moments read and the
      parameters and moments written (7 x parameter bytes).

    Duplicates within a batch are counted as distinct rows (an upper bound on
    rows, so the roofline share can only be understated by it, never pass
    100%); activations are left out (they fit on chip at this batch)."""
    row = config["embedding_dim"] * 4
    rows = len(config["table_rows"]) * batch
    sparse = rows * row * (1 + 4 + 1)
    dense = dense_param_count(config) * 4 * 7
    return float(sparse + dense)


def step_floor_seconds(config: dict, batch: int, peaks: dict) -> Dict[str, float]:
    """Least time one chip could take for a step, and which peak bounds it."""
    t_flops = train_flops_per_sample(config) * batch / peaks["bf16_flops_per_s"]
    t_bytes = step_hbm_bytes(config, batch) / peaks["hbm_bytes_per_s"]
    return {
        "seconds": max(t_flops, t_bytes),
        "bound_by": "flops" if t_flops >= t_bytes else "hbm_bytes",
        "flops_s": t_flops,
        "bytes_s": t_bytes,
    }


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise SystemExit(
            f"no peaks listed for device_kind {device_kind!r}: add it to "
            "perf/peaks.json with its source before reporting a utilization"
        )
    return table[device_kind]
