"""The tiny preset of the ``mellum2-ep4-pack16k`` cell for the CPU tests: the
cell's own files and code paths at one period of four layers, 4 of 8 experts
held (2 a token), hidden 128, a window of 8, one sequence of 64 positions
packed from four documents; the attention kernels run in the Pallas
interpreter over tiles of 16."""

import perf_presets  # noqa: F401  (puts the repo's root on sys.path)

CELL = "mellum2-ep4-pack16k"

REHEARSAL = {
    "config": {
        "hidden_size": 128, "head_dim": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
        "moe_intermediate_size": 64, "num_experts": 4, "router_width": 8, "first_held_expert": 0,
        "num_experts_per_tok": 2, "vocab_size": 97, "sliding_window": 8,
        "head_chunk": 32, "reference_query_block": 16, "reference_logit_block": 32,
        "attention_tile": 16,
        "interpret_kernels": True,  # no Mosaic on the CPU: the entry takes the interpreter from here
    },
    "traffic": {"batch": 1, "seq_len": 64, "doc_lengths": [32, 16, 11, 5], "warmup_steps": 1},
}

# The limits a rehearsal is judged by: the cell's own file is set from chip
# readings at 16,384 positions a step; at 64 positions one flipped pick is
# most of a percent of all picks and moves an expert's leaf by percents.
REHEARSAL_LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.05, "grad_gap_median_leaf": 0.01,
                    "change_gap": 0.05, "expert_pick_mismatch_share": 0.03}
