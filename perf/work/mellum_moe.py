"""Operations and bytes the ``mellum_moe`` training step needs, as functions
of a configuration file and its traffic (``perf/counts.py`` hands over here),
and the same for its attention kernels, for their roofline share.

Conventions: a multiply-add is 2 FLOPs; backward costs twice the forward; the
recomputed forward does not count (a layer's, a head chunk's); gathers,
scatters and optimizers add bytes, not FLOPs. A sample is one packed sequence
of ``seq_len`` positions; every position runs the tower, the head and the loss.
"""

from __future__ import annotations

# a layer's products, an expert's, the held picks and the dense leaves are counted as the other
# tower's are: the two share the tower (models/moe_tower.py) and their configurations its keys
from perf.work.sdar_moe import (  # noqa: F401  (tests and readers take them from here)
    dense_param_count, expert_macs_per_pick, held_picks_per_position, layer_product_macs,
)


def positions(traffic: dict) -> int:
    return int(traffic["seq_len"])


def layer_kinds(config: dict) -> list:
    return list(config["layer_types"][:int(config["num_hidden_layers"])])


def live_pairs(config: dict, traffic: dict, kind: str) -> int:
    """(query, key) pairs of one packed sequence that a layer of ``kind``
    allows, a head: position j of a document (from 0) reads the j + 1 keys of
    its document up to itself, and on a sliding layer the last
    ``sliding_window`` of them at most. The documents' order does not matter."""
    w = int(config["sliding_window"])
    total = 0
    for n in traffic["doc_lengths"]:
        n = int(n)
        if kind == "full_attention" or n <= w:
            total += n * (n + 1) // 2
        else:
            total += w * (w + 1) // 2 + (n - w) * w
    return total


def attention_forward_flops(config: dict, traffic: dict) -> float:
    """q k^T and P v over the live pairs, every query head, one sequence, all layers."""
    pairs = sum(live_pairs(config, traffic, kind) for kind in layer_kinds(config))
    return 2.0 * 2 * config["head_dim"] * config["num_attention_heads"] * pairs


def train_flops_per_sample(config: dict, traffic: dict) -> float:
    """Forward plus backward (2x forward) model FLOPs of one sequence."""
    macs = layer_product_macs(config) + held_picks_per_position(config) * expert_macs_per_pick(config)
    layers = 2.0 * macs * positions(traffic) * config["num_hidden_layers"]
    head = 2.0 * positions(traffic) * config["hidden_size"] * config["vocab_size"]
    return 3.0 * (layers + attention_forward_flops(config, traffic) + head)


def step_hbm_bytes(config: dict, traffic: dict) -> float:
    """Bytes the step's algorithm has to move through HBM for one batch, as
    ``perf/work/sdar_moe.py`` counts them:

    - dense parameters, their gradient and Adam's two moments read, parameters
      and moments written (7 x 4 B a parameter),
    - every looked-up token row read once and the sparse Adagrad update on it
      (row and accumulator read and written, the gradient row read: 6 x row
      bytes, duplicates counted as distinct rows),
    - the activations that have to cross HBM whatever the schedule: the
      residual stream kept a layer for the backward (written, read), and the
      logits with their gradient (each written, read; in chunks here, the
      same bytes).
    """
    batch = int(traffic["batch"])
    d = config["hidden_size"]
    dense = dense_param_count(config) * 4 * 7
    rows = batch * positions(traffic) * d * 4 * 6
    stream = config["num_hidden_layers"] * batch * positions(traffic) * d * 4 * 2
    logits = batch * positions(traffic) * config["vocab_size"] * 4 * 4
    return float(dense + rows + stream + logits)


# ------------------------------------------------------------------ kernels

def attention_kernel_work(config: dict, traffic: dict) -> dict:
    """FLOPs and HBM bytes of one step's attention, forward and backward, all
    layers: the live pairs' products (backward twice the forward), and q, k, v
    and the output read or written once each way in bfloat16."""
    batch, layers = int(traffic["batch"]), config["num_hidden_layers"]
    hd = config["head_dim"]
    flops = 3.0 * attention_forward_flops(config, traffic) * batch
    width = (2 * config["num_attention_heads"] + 2 * config["num_key_value_heads"]) * hd
    return {"flops": flops, "bytes": float(3 * batch * positions(traffic) * width * 2 * layers)}
