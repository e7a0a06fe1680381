"""Packed text for the masked block-diffusion objective. A batch is ``batch``
sequences of ``seq_len`` tokens, drawn from a bounded power law (``zipf_a``)
with a fixed rotation over the configuration's vocabulary slice without its
last id, which is the mask id and never a label. Each block of
``block_length`` tokens draws one noise level t uniform on ``[t_min, 1]`` and
each of its tokens is masked with probability t.

``ids`` (B, 2L) is ``[xt | x0]``, the noised sequence then the clean one;
``labels`` (B, L) int32 is x0; ``weights`` (B, L) float32 is 1 / t of the
token's block where the token was masked, else 0. Everything comes from the
seed.
"""

from __future__ import annotations

from typing import Dict, Iterator

import numpy as np


def make(config: dict, traffic: dict, seed: int) -> Iterator[Dict[str, np.ndarray]]:
    batch, length = int(traffic["batch"]), int(traffic["seq_len"])
    block = int(config["block_length"])
    mask_id = int(config["vocab_size"]) - 1
    n = mask_id  # ids 0 .. mask_id - 1 are text
    a = float(traffic["zipf_a"])
    t_min = float(traffic["noise_min"])
    span = (n + 1.0) ** (1.0 - a) - 1.0  # inverse CDF of p(x) ~ x^-a on [1, n + 1)
    rng = np.random.Generator(np.random.PCG64([int(seed), 0xB10C]))
    rotation = int(rng.integers(0, 1 << 62)) % n
    while True:
        rank = np.floor((1.0 + rng.random((batch, length)) * span) ** (1.0 / (1.0 - a))).astype(np.int64) - 1
        x0 = (np.clip(rank, 0, n - 1) + rotation) % n
        t = t_min + (1.0 - t_min) * rng.random((batch, length // block))
        t = np.repeat(t, block, axis=1)
        masked = rng.random((batch, length)) < t
        xt = np.where(masked, mask_id, x0)
        yield {"ids": np.concatenate([xt, x0], axis=1),
               "labels": x0.astype(np.int32),
               "weights": np.where(masked, 1.0 / t, 0.0).astype(np.float32)}


def halve(b: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """The first half of a batch's sequences (the planted fault of ``perf/compare.py``)."""
    h = b["labels"].shape[0] // 2
    return {k: v[:h] for k, v in b.items()}
