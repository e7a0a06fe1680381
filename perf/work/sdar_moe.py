"""Operations and bytes the ``sdar_moe`` training step needs, as functions of
a configuration file and its traffic (``perf/counts.py`` hands over here), and
the same for its two kernels (the attention kernels and the grouped products
of the held experts), for their roofline shares.

Conventions: a multiply-add is 2 FLOPs; backward costs twice the forward; the
recomputed forward does not count; gathers, scatters and optimizers add bytes,
not FLOPs. A sample is one sequence of ``seq_len`` tokens: the tower runs
``2 * seq_len`` positions for it (the noised half and the clean one), the head
and the loss ``seq_len``.
"""

from __future__ import annotations


def positions(traffic: dict) -> int:
    return 2 * int(traffic["seq_len"])


def live_pairs(config: dict, traffic: dict) -> int:
    """(query, key) pairs of one sequence that the block-diffusion mask
    allows, a head: a noised query reads its own block and the clean blocks
    before it, a clean query the clean blocks up to its own, so with n = L / b
    blocks 2 * b^2 * (1 + ... + n) = L * (L + b)."""
    length, b = int(traffic["seq_len"]), int(config["block_length"])
    return length * (length + b)


def layer_product_macs(config: dict) -> int:
    """Multiply-adds a position of one layer's products outside the experts:
    q, k, v, o and the router at its published width."""
    d, hd = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d + d * config["router_width"]


def expert_macs_per_pick(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def held_picks_per_position(config: dict) -> float:
    """Picks a position on the experts held here, at their expectation under
    an even router: k * held / E."""
    return config["num_experts_per_tok"] * config["num_experts"] / config["router_width"]


def attention_forward_flops(config: dict, traffic: dict) -> float:
    """q k^T and P v over the live pairs, every query head, one sequence, one layer."""
    return 2.0 * 2 * config["head_dim"] * config["num_attention_heads"] * live_pairs(config, traffic)


def train_flops_per_sample(config: dict, traffic: dict) -> float:
    """Forward plus backward (2x forward) model FLOPs of one sequence."""
    macs = layer_product_macs(config) + held_picks_per_position(config) * expert_macs_per_pick(config)
    layer = 2.0 * macs * positions(traffic) + attention_forward_flops(config, traffic)
    head = 2.0 * int(traffic["seq_len"]) * config["hidden_size"] * config["vocab_size"]
    return 3.0 * (config["num_hidden_layers"] * layer + head)


def dense_param_count(config: dict) -> int:
    d, hd = config["hidden_size"], config["head_dim"]
    layer = (layer_product_macs(config) + config["num_experts"] * expert_macs_per_pick(config)
             + 2 * d + 2 * hd)  # the products, the held experts, two norms, the q and k norms
    return config["num_hidden_layers"] * layer + d + d * config["vocab_size"]


def step_hbm_bytes(config: dict, traffic: dict) -> float:
    """Bytes the step's algorithm has to move through HBM for one batch:

    - dense parameters, their gradient and Adam's two moments read, parameters
      and moments written (7 x 4 B a parameter),
    - every looked-up token row read once and the sparse Adagrad update on it
      (row and accumulator read and written, the gradient row read: 6 x row
      bytes, duplicates counted as distinct rows, as the click model's file does),
    - the activations that have to cross HBM whatever the schedule: the
      residual stream kept a layer for the backward (written, read), and the
      logits with their gradient (each written, read).
    """
    batch = int(traffic["batch"])
    d = config["hidden_size"]
    dense = dense_param_count(config) * 4 * 7
    rows = batch * positions(traffic) * d * 4 * 6
    stream = config["num_hidden_layers"] * batch * positions(traffic) * d * 4 * 2
    logits = batch * int(traffic["seq_len"]) * config["vocab_size"] * 4 * 4
    return float(dense + rows + stream + logits)


# ------------------------------------------------------------------ kernels

def attention_kernel_work(config: dict, traffic: dict) -> dict:
    """FLOPs and HBM bytes of one step's attention, forward and backward, all
    layers: the live pairs' products (backward twice the forward), and q, k, v
    and the output read or written once each way in bfloat16."""
    batch, layers = int(traffic["batch"]), config["num_hidden_layers"]
    hd = config["head_dim"]
    flops = 3.0 * attention_forward_flops(config, traffic) * batch * layers
    width = (2 * config["num_attention_heads"] + 2 * config["num_key_value_heads"]) * hd
    return {"flops": flops, "bytes": float(3 * batch * positions(traffic) * width * 2 * layers)}
