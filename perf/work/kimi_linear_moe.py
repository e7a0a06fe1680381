"""Operations and bytes the ``kimi_linear_moe`` training step needs, as
functions of a configuration file and its traffic (``perf/counts.py`` hands
over here), and the same for its two kernel families (the delta rule's four
kernels and the latent attention's interval kernels), for their roofline shares.

Conventions: a multiply-add is 2 FLOPs; backward costs twice the forward; the
recomputed forward does not count; gathers, scatters, convolutions, norms,
gates and optimizers add bytes, not FLOPs. Work is counted from what a layer
states and not from the form that computes it: the delta rule a position a
head reads the state with k, writes a rank-one delta and reads it with q
(three ``Dk x Dv`` multiply-adds); the chunked form's further products (the
pairs of a chunk, the triangular inverse) are not counted. A sample is one
packed sequence of ``seq_len`` positions.
"""

from __future__ import annotations

import numpy as np

from perf import kimi_linear_weights


def positions(traffic: dict) -> int:
    return int(traffic["seq_len"])


def layer_kinds(config: dict) -> list:
    return kimi_linear_weights.layer_kinds(config)


def live_pairs(traffic: dict) -> int:
    """(query, key) pairs of one packed sequence that a latent layer allows,
    a head: position j of a document (from 0) reads the j + 1 keys of its
    document up to itself, as ``perf/work/mellum_moe.py::live_pairs`` counts a
    full layer. The documents' order does not matter."""
    return sum(int(n) * (int(n) + 1) // 2 for n in traffic["doc_lengths"])


def state_macs_per_position(config: dict) -> int:
    """The delta rule a position, all heads: three passes over a Dk x Dv state."""
    d = config["linear_attn_config"]["head_dim"]
    return 3 * d * config["v_head_dim"] * config["linear_attn_config"]["num_heads"]


def attention_product_macs(config: dict, kind: str) -> int:
    """Multiply-adds a position of one layer's attention products outside its kernels."""
    d, h, hd = config["hidden_size"], config["num_attention_heads"], config["v_head_dim"]
    if kind == kimi_linear_weights.MLA:
        rank, rope, nope = config["kv_lora_rank"], config["qk_rope_head_dim"], config["qk_nope_head_dim"]
        return d * h * (nope + rope) + d * (rank + rope) + rank * h * (nope + hd) + h * hd * d
    r = config["linear_attn_config"]["head_dim"]
    return 3 * d * h * hd + 2 * (d * r + r * h * hd) + d * h + h * hd * d


def mlp_macs(config: dict, mlp: str) -> float:
    """A position's MLP: the dense SwiGLU, or the router at its published
    width, the shared expert and the held picks at an even router's expectation."""
    d = config["hidden_size"]
    if mlp == "dense":
        return 3.0 * d * config["intermediate_size"]
    expert = 3 * d * config["moe_intermediate_size"]
    held = config["num_experts_per_token"] * config["num_experts"] / config["router_width"]
    return d * config["router_width"] + config["num_shared_experts"] * expert + held * expert


def latent_attention_forward_flops(config: dict, traffic: dict) -> float:
    """Scores 192 wide and P v 128 wide over the live pairs, every head, one sequence, one layer."""
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] + config["v_head_dim"]
    return 2.0 * width * config["num_attention_heads"] * live_pairs(traffic)


def train_flops_per_sample(config: dict, traffic: dict) -> float:
    """Forward plus backward (2x forward) model FLOPs of one sequence."""
    t, total = positions(traffic), 0.0
    for kind, mlp in layer_kinds(config):
        total += 2.0 * t * (attention_product_macs(config, kind) + mlp_macs(config, mlp))
        total += (latent_attention_forward_flops(config, traffic) if kind == kimi_linear_weights.MLA
                  else 2.0 * t * state_macs_per_position(config))
    head = 2.0 * t * config["hidden_size"] * config["vocab_size"]
    return 3.0 * (total + head)


def dense_param_count(config: dict) -> int:
    return int(sum(int(np.prod(s)) for s in kimi_linear_weights.leaf_shapes(config).values()))


def step_hbm_bytes(config: dict, traffic: dict) -> float:
    """Bytes the step's algorithm has to move through HBM for one batch, as
    the accepted towers' files count them: dense parameters, gradient and
    Adam's moments (7 x 4 B a parameter), every looked-up token row and its
    sparse update (6 x row bytes), the residual stream kept a layer (written,
    read) and the logits with their gradient (each written, read)."""
    batch, t, d = int(traffic["batch"]), positions(traffic), config["hidden_size"]
    dense = dense_param_count(config) * 4 * 7
    rows = batch * t * d * 4 * 6
    stream = config["num_hidden_layers"] * batch * t * d * 4 * 2
    logits = batch * t * config["vocab_size"] * 4 * 4
    return float(dense + rows + stream + logits)


# ------------------------------------------------------------------ kernels

def _count(config: dict, kind: str) -> int:
    return sum(1 for k, _ in layer_kinds(config) if k == kind)


def kda_kernel_work(config: dict, traffic: dict) -> dict:
    """FLOPs and HBM bytes of one step's delta rule, forward and backward,
    all KDA layers: three passes over the state a position a head (backward
    twice that), and q, k, v, g (a head's 128 columns each), beta and o read or
    written once each way in float32, the width ``ops.delta_rule.kda`` takes."""
    batch, layers = int(traffic["batch"]), _count(config, kimi_linear_weights.KDA)
    lin = config["linear_attn_config"]
    flops = 3.0 * 2.0 * state_macs_per_position(config) * positions(traffic) * batch * layers
    width = lin["num_heads"] * (3 * lin["head_dim"] + 2 * config["v_head_dim"] + 1)
    return {"flops": flops, "bytes": float(2 * batch * positions(traffic) * width * 4 * layers)}


def latent_attention_kernel_work(config: dict, traffic: dict) -> dict:
    """FLOPs and HBM bytes of one step's latent attention, forward and
    backward, all MLA layers: the live pairs' products (backward twice the
    forward), and q (192 a head), k (128 a head and the 64 shared columns
    once), v and the output read or written once each way in bfloat16."""
    batch, layers = int(traffic["batch"]), _count(config, kimi_linear_weights.MLA)
    h, nope, rope, hd = (config["num_attention_heads"], config["qk_nope_head_dim"],
                         config["qk_rope_head_dim"], config["v_head_dim"])
    flops = 3.0 * latent_attention_forward_flops(config, traffic) * batch * layers
    width = h * (nope + rope) + h * nope + rope + 2 * h * hd
    return {"flops": flops, "bytes": float(3 * batch * positions(traffic) * width * 2 * layers)}
