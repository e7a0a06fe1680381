"""The tiny preset of the ``joyai-flash-ep32-pack16k-mtp1`` cell for the CPU
tests: the cell's own files and code paths at the leading layer, one expert
layer and the prediction module, 4 of 16 experts held (2 a token), hidden 128,
two heads, a query low rank of 48, one sequence of 64 positions packed from
five ragged documents; the kernels run in the Pallas interpreter over tiles of
16. And the catalog's row of the model, which the cell's file and the tower's
``from_config`` are held to."""

import perf_presets  # noqa: F401  (puts the repo's root on sys.path)

CELL = "joyai-flash-ep32-pack16k-mtp1"

# the catalog's row (model-configs guide): every number of its `config`
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 7168, "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "joyai_llm_flash", "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 8, "num_hidden_layers": 40, "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280,
}

REHEARSAL = {
    "config": {
        "hidden_size": 128, "num_attention_heads": 2, "num_key_value_heads": 2, "kv_lora_rank": 64,
        "q_lora_rank": 48, "intermediate_size": 96, "moe_intermediate_size": 64, "n_routed_experts": 4,
        "router_width": 16, "first_held_expert": 0, "num_experts_per_tok": 2, "num_hidden_layers": 2,
        "vocab_size": 97, "head_chunk": 32, "reference_query_block": 16, "reference_logit_block": 32, "reference_mlp_block": 32,
        "attention_tile": 16,
        # weights of deviation 0.15: at this width the scores then spread by 1, as the cell's do at 0.02
        "initial_deviation": 0.15,
        "interpret_kernels": True,  # no Mosaic on the CPU: the entry takes the interpreter from here
    },
    "traffic": {"batch": 1, "seq_len": 64, "doc_lengths": [29, 17, 11, 5, 2], "warmup_steps": 1},
}

# The limits a rehearsal is judged by: the cell's own file is set from chip
# readings at 16,384 positions a step; at 64 positions and a width of 128 one
# flipped pick is most of a percent of all picks and moves an expert's leaf by
# a tenth (the module's layer sees a gradient a hundredth of the tower's).
REHEARSAL_LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.2, "grad_gap_median_leaf": 0.02,
                    "change_gap": 0.1, "expert_pick_mismatch_share": 0.05, "mtp_loss_gap": 1e-3}
