"""Operations and bytes the ``joyai_flash_moe`` training step needs, as
functions of a configuration file and its traffic (``perf/counts.py`` hands
over here), and the same for its latent attention's interval kernels, for
their roofline share.

Conventions, as the accepted towers' files: a multiply-add is 2 FLOPs;
backward costs twice the forward; the recomputed forward does not count;
gathers, scatters, norms, the rotation and optimizers add bytes, not FLOPs. A
block is a layer or the prediction module's layer: ``num_hidden_layers`` of
the one and ``num_nextn_predict_layers`` of the other, every one with latent
attention. The module's merge ``M`` and its pass through the head count as the
tower's own do. A sample is one packed sequence of ``seq_len`` positions.
"""

from __future__ import annotations

import numpy as np

from perf import joyai_flash_weights


def positions(traffic: dict) -> int:
    return int(traffic["seq_len"])


def modules(config: dict) -> int:
    return int(config["num_nextn_predict_layers"])


def blocks(config: dict) -> int:
    """Latent-attention blocks a step runs: the layers and the module's."""
    return int(config["num_hidden_layers"]) + modules(config)


def live_pairs(traffic: dict) -> int:
    """(query, key) pairs of one packed sequence that a latent block allows,
    a head: position j of a document (from 0) reads the j + 1 keys of its
    document up to itself. The documents' order does not matter."""
    return sum(int(n) * (int(n) + 1) // 2 for n in traffic["doc_lengths"])


def attention_product_macs(config: dict) -> int:
    """Multiply-adds a position of one block's attention products outside its
    kernels: the query's two, the latent's two, the output's."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    nope, rope, hd = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    q_rank, rank = config["q_lora_rank"], config["kv_lora_rank"]
    return d * q_rank + q_rank * h * (nope + rope) + d * (rank + rope) + rank * h * (nope + hd) + h * hd * d


def mlp_macs(config: dict, mlp: str) -> float:
    """A position's MLP: the dense SwiGLU, or the router at its published
    width, the shared expert and the held picks at an even router's expectation."""
    d = config["hidden_size"]
    if mlp == "dense":
        return 3.0 * d * config["intermediate_size"]
    expert = 3 * d * config["moe_intermediate_size"]
    held = config["num_experts_per_tok"] * config["n_routed_experts"] / config["router_width"]
    return d * config["router_width"] + config["n_shared_experts"] * expert + held * expert


def latent_attention_forward_flops(config: dict, traffic: dict) -> float:
    """Scores 192 wide and P v 128 wide over the live pairs, every head, one sequence, one block."""
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] + config["v_head_dim"]
    return 2.0 * width * config["num_attention_heads"] * live_pairs(traffic)


def train_flops_per_sample(config: dict, traffic: dict) -> float:
    """Forward plus backward (2x forward) model FLOPs of one sequence."""
    t, d = positions(traffic), config["hidden_size"]
    n, lead = int(config["num_hidden_layers"]), int(config["first_k_dense_replace"])
    macs = blocks(config) * attention_product_macs(config) + lead * mlp_macs(config, "dense") \
        + (n - lead + modules(config)) * mlp_macs(config, "shared_experts") \
        + modules(config) * 2 * d * d + (1 + modules(config)) * d * config["vocab_size"]
    return 3.0 * (2.0 * t * macs + blocks(config) * latent_attention_forward_flops(config, traffic))


def dense_param_count(config: dict) -> int:
    return int(sum(int(np.prod(s)) for s in joyai_flash_weights.leaf_shapes(config).values()))


def step_hbm_bytes(config: dict, traffic: dict) -> float:
    """Bytes the step's algorithm has to move through HBM for one batch, as
    the accepted towers' files count them: dense parameters, gradient and
    Adam's moments (7 x 4 B a parameter), every looked-up token row and its
    sparse update (6 x row bytes; the module's second read is of the gathered
    slot), the residual stream kept a block (written, read) and the logits of
    each pass through the head with their gradient (each written, read)."""
    batch, t, d = int(traffic["batch"]), positions(traffic), config["hidden_size"]
    dense = dense_param_count(config) * 4 * 7
    rows = batch * t * d * 4 * 6
    stream = blocks(config) * batch * t * d * 4 * 2
    logits = (1 + modules(config)) * batch * t * config["vocab_size"] * 4 * 4
    return float(dense + rows + stream + logits)


# ------------------------------------------------------------------ kernels

def latent_attention_kernel_work(config: dict, traffic: dict) -> dict:
    """FLOPs and HBM bytes of one step's latent attention, forward and
    backward, all blocks: the live pairs' products (backward twice the
    forward), and q (192 a head), k (128 a head and the 64 shared columns
    once), v and the output read or written once each way in bfloat16. The
    rotation runs outside the kernels: its bytes are the step's, not theirs."""
    batch, n = int(traffic["batch"]), blocks(config)
    h, nope, rope, hd = (config["num_attention_heads"], config["qk_nope_head_dim"],
                         config["qk_rope_head_dim"], config["v_head_dim"])
    flops = 3.0 * latent_attention_forward_flops(config, traffic) * batch * n
    width = h * (nope + rope) + h * nope + rope + 2 * h * hd
    return {"flops": flops, "bytes": float(3 * batch * positions(traffic) * width * 2 * n)}
