"""Operations and bytes the DLRM training step needs, as functions of a
configuration file and its traffic (``perf/counts.py`` hands over to the
model's file here): FLOPs of the bottom and top MLPs and the dot
interaction, bytes of the rows gathered, the sparse update and the dense
optimizer's passes, with every size taken from the files.

Conventions: a multiply-add is 2 FLOPs; backward costs twice the forward;
gathers and the sparse update add bytes, not FLOPs (the usual model-FLOPs
convention).
"""

from __future__ import annotations


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


def train_flops_per_sample(config: dict, traffic: dict) -> float:
    """Forward plus backward (2x forward) FLOPs of one trained sample; one id
    a table a sample, so no length of the traffic enters."""
    return 3.0 * 2.0 * forward_macs_per_sample(config)


def dense_param_count(config: dict) -> int:
    from perf.weights import dense_layer_sizes

    return sum(i * o + o for i, o in dense_layer_sizes(config))


def step_hbm_bytes(config: dict, traffic: dict) -> float:
    """Bytes the step's algorithm has to move through HBM for one batch of the
    traffic:

    - every looked-up row read once for the forward (slots x batch x row),
    - the sparse Adagrad update on those rows: row and accumulator read and
      written (4 x row bytes), plus the gradient rows read once,
    - dense parameters, their gradient and Adam's two moments read and the
      parameters and moments written (7 x parameter bytes).

    Duplicates within a batch are counted as distinct rows (an upper bound on
    rows, so the roofline share can only be understated by it, never pass
    100%); activations are left out (they fit on chip at this batch)."""
    row = config["embedding_dim"] * 4
    rows = len(config["table_rows"]) * int(traffic["batch"])
    sparse = rows * row * (1 + 4 + 1)
    dense = dense_param_count(config) * 4 * 7
    return float(sparse + dense)
