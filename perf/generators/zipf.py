"""Criteo-shaped training batches: one id per table per sample, ranks drawn
from a bounded power law (zipf exponent ``zipf_a``) over each table's rows,
13 normal dense features, coin-flip labels. Everything comes from the seed.

A fixed per-table rotation keeps each table's hot set stable and the tables
decorrelated; the law is bounded to the table (no wrap-around of an
unbounded tail), and every size is the traffic file's.
"""

from __future__ import annotations

from typing import Dict, Iterator, List

import numpy as np


def table_rows(config: dict, traffic: dict) -> List[int]:
    """Rows each table holds under this traffic: the published cardinality,
    or the chip's share of it (``rows_divisor``)."""
    div = int(traffic.get("rows_divisor", 1))
    return [-(-int(n) // div) for n in config["table_rows"]]


class ZipfBatches:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.batch = int(traffic["batch"])
        self.rows = np.asarray(table_rows(config, traffic), dtype=np.int64)
        self.n_slots = len(self.rows)
        self.n_dense = int(config["num_dense"])
        a = float(traffic["zipf_a"])
        self._inv_exp = 1.0 / (1.0 - a)
        # continuous inverse CDF of p(x) ~ x^-a on [1, N+1): floor(x) is the rank
        self._span = ((self.rows + 1.0) ** (1.0 - a) - 1.0)[:, None]
        self.rng = np.random.Generator(np.random.PCG64([int(seed), 0x5EED]))
        self.offsets = (self.rng.integers(0, 1 << 62, self.n_slots) % self.rows)[:, None]

    def _zipf(self) -> np.ndarray:
        """ids (S, B) for all tables."""
        u = self.rng.random((self.n_slots, self.batch))
        rank = np.floor((1.0 + u * self._span) ** self._inv_exp).astype(np.int64) - 1
        rank = np.minimum(np.maximum(rank, 0), self.rows[:, None] - 1)
        return (rank + self.offsets) % self.rows[:, None]

    def next_batch(self) -> Dict[str, np.ndarray]:
        ids = self._zipf()
        dense = self.rng.standard_normal((self.batch, self.n_dense), dtype=np.float32)
        labels = (self.rng.random((self.batch, 1)) < 0.5).astype(np.float32)
        return {"ids": ids, "dense": dense, "labels": labels}

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        while True:
            yield self.next_batch()


def halve(b: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """The first half of a batch's samples (the planted fault of ``perf/compare.py``)."""
    h = b["labels"].shape[0] // 2
    return {"ids": b["ids"][:, :h], "dense": b["dense"][:h], "labels": b["labels"][:h]}


def make(config: dict, traffic: dict, seed: int) -> Iterator[Dict[str, np.ndarray]]:
    return iter(ZipfBatches(config, traffic, seed))
