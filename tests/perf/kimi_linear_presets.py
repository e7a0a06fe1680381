"""The tiny preset of the ``kimi-linear-ep32-pack16k`` cell for the CPU tests:
the cell's own files and code paths at the leading layer and one period
(kda/dense, kda, kda, mla, kda), 4 of 16 experts held (2 a token), hidden 128,
two heads, one sequence of 64 positions packed from five ragged documents;
the kernels run in the Pallas interpreter over tiles and chunks of 16."""

import perf_presets  # noqa: F401  (puts the repo's root on sys.path)

CELL = "kimi-linear-ep32-pack16k"

REHEARSAL = {
    "config": {
        "hidden_size": 128, "num_attention_heads": 2, "num_key_value_heads": 2, "kv_lora_rank": 64,
        "linear_attn_config": {"full_attn_layers": [4, 8], "head_dim": 128, "kda_layers": [1, 2, 3, 5, 6, 7],
                               "num_heads": 2, "short_conv_kernel_size": 4},
        "intermediate_size": 96, "moe_intermediate_size": 64, "num_experts": 4, "router_width": 16,
        "first_held_expert": 0, "num_experts_per_token": 2, "vocab_size": 97,
        "head_chunk": 32, "reference_query_block": 16, "reference_logit_block": 32,
        "reference_state_block": 16, "attention_tile": 16, "kda_chunk": 16,
        "interpret_kernels": True,  # no Mosaic on the CPU: the entry takes the interpreter from here
    },
    "traffic": {"batch": 1, "seq_len": 64, "doc_lengths": [29, 17, 11, 5, 2], "warmup_steps": 1},
}

# The limits a rehearsal is judged by: the cell's own file is set from chip
# readings at 16,384 positions a step; at 64 positions and a width of 128 one
# flipped pick is most of a percent of all picks, and the delta rule's
# bfloat16 operands show in every leaf below them.
REHEARSAL_LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.1, "grad_gap_median_leaf": 0.02,
                    "change_gap": 0.1, "expert_pick_mismatch_share": 0.05}
