"""The tiny preset of the ``sdar-ep8-bd4-seq4k`` cell for the CPU tests: the
cell's own files and code paths at 2 layers, 8 of 16 experts held (4 a
token), hidden 128, L 32; the attention kernels run in the Pallas interpreter."""

import perf_presets  # noqa: F401  (puts the repo's root on sys.path)

CELL = "sdar-ep8-bd4-seq4k"

REHEARSAL = {
    "config": {
        "hidden_size": 128, "head_dim": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
        "moe_intermediate_size": 64, "num_experts": 8, "router_width": 16, "first_held_expert": 4,
        "num_experts_per_tok": 4, "num_hidden_layers": 2, "vocab_size": 97, "block_length": 4,
        "reference_query_block": 16,
        "interpret_kernels": True,  # no Mosaic on the CPU: the entry takes the interpreter from here
    },
    "traffic": {"batch": 2, "seq_len": 32, "warmup_steps": 1},
}

# The limits a rehearsal is judged by: the cell's own file is set from chip
# readings at 16,384 positions a step; at 128 positions one flipped pick is
# most of a percent of all picks and moves an expert's leaf by percents.
REHEARSAL_LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.05, "grad_gap_median_leaf": 0.01,
                    "change_gap": 0.05, "expert_pick_mismatch_share": 0.02}
