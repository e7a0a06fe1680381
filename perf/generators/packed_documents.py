"""Packed documents for a causal tower. A batch is ``batch`` sequences of
``seq_len`` tokens, each packed from the documents of ``doc_lengths`` (which
sum to ``seq_len``) in an order drawn anew for every sequence; the tokens come
from a bounded power law (``zipf_a``) with a fixed rotation over the
configuration's vocabulary slice.

``ids`` (B, T); ``doc_lengths`` (B, n) int32, each sequence's documents in
their order (a zero is a document that is not there); ``labels`` (B, T) int32,
the next token (0 after the last); ``weights`` (B, T) float32, 0 at the last
position of a document, else 1. Where each position's document starts is the
program's and the reference's to work out from ``doc_lengths``, each for
itself. Everything comes from the seed.
"""

from __future__ import annotations

from typing import Dict, Iterator

import numpy as np


def _labels_and_weights(ids: np.ndarray, lengths: np.ndarray):
    labels = np.concatenate([ids[:, 1:], np.zeros_like(ids[:, :1])], axis=1).astype(np.int32)
    weights = np.ones(ids.shape, np.float32)
    ends = np.cumsum(lengths, axis=1) - 1  # a zero-length document ends where the one before it does
    np.put_along_axis(weights, np.clip(ends, 0, ids.shape[1] - 1), 0.0, axis=1)
    return labels, weights


def make(config: dict, traffic: dict, seed: int) -> Iterator[Dict[str, np.ndarray]]:
    batch, length = int(traffic["batch"]), int(traffic["seq_len"])
    docs = np.asarray(traffic["doc_lengths"], np.int32)
    if int(docs.sum()) != length:
        raise ValueError(f"doc_lengths sum to {int(docs.sum())}, not to seq_len {length}")
    n = int(config["vocab_size"])
    a = float(traffic["zipf_a"])
    span = (n + 1.0) ** (1.0 - a) - 1.0  # inverse CDF of p(x) ~ x^-a on [1, n + 1)
    rng = np.random.Generator(np.random.PCG64([int(seed), 0xD0C5]))
    rotation = int(rng.integers(0, 1 << 62)) % n
    while True:
        rank = np.floor((1.0 + rng.random((batch, length)) * span) ** (1.0 / (1.0 - a))).astype(np.int64) - 1
        ids = (np.clip(rank, 0, n - 1) + rotation) % n
        lengths = np.stack([docs[rng.permutation(len(docs))] for _ in range(batch)])
        labels, weights = _labels_and_weights(ids, lengths)
        yield {"ids": ids, "doc_lengths": lengths, "labels": labels, "weights": weights}


def halve(b: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """The first half of every sequence's positions, the document the cut
    falls in cut with it (the planted fault of ``perf/compare.py``; a batch
    of one sequence has no half of its sequences)."""
    half = b["ids"].shape[1] // 2
    ends = np.minimum(np.cumsum(b["doc_lengths"], axis=1), half)
    lengths = np.diff(ends, axis=1, prepend=0).astype(np.int32)
    ids = b["ids"][:, :half]
    labels, weights = _labels_and_weights(ids, lengths)
    return {"ids": ids, "doc_lengths": lengths, "labels": labels, "weights": weights}
