"""A causal mixture-of-experts transformer tower over packed documents (the
``mellum`` family): three layers of every four read a window of
``sliding_window`` keys and the fourth the whole document, the two kinds
under RoPE tables of their own (the full layers' by YaRN), trained on next
tokens. The tower itself is ``models/moe_tower.py``'s; here is what this
family adds to it.

The token rows come from the sparse plane as one raw slot, ``(B, T, hidden)``
float32; ``dense`` holds one int32 side input, ``(B, T)``: for each position
the index at which its document starts. A position's RoPE position is its
distance from that start, and it reads no key before it: query ``i`` reads the
keys ``[lo_i, i]``, ``lo_i`` the document's start on a full layer and the later
of that and ``i - sliding_window + 1`` on a sliding one
(``ops/flash_attention.py::interval_attention``).

Every position has a label, so head and loss never meet whole: the tower's
``train_loss`` (``moe_tower.NextTokenTower``, which ``build_fused_train_step``
takes where a model states one) runs them in chunks of ``head_chunk``
positions. ``apply`` gives the whole logits, for evaluation and for the tests
that hold ``train_loss`` to them.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from persia_tpu.models.moe_tower import NextTokenTower
from persia_tpu.ops.flash_attention import (
    BLOCK_DIFFUSION_TILE, interval_attention, interval_tile_counts, interval_visits,
)
from persia_tpu.ops.qk_prep import qk_prep_tile
from persia_tpu.tracing import record_event

SLIDING, FULL = "sliding", "full"


def rope_frequencies(head_dim: int, theta: float, yarn: Optional[dict] = None):
    """The ``head_dim // 2`` rotation frequencies and the factor on cos and
    sin. Default RoPE: ``theta ** (-2n / D)`` and 1. YaRN (``yarn``: factor,
    original_max_position_embeddings, beta_fast, beta_slow, attention_factor):
    the frequencies that turn fewer than ``beta_slow`` times over the original
    context are divided by the factor, those that turn more than
    ``beta_fast`` times are kept, a linear ramp over the dimensions between."""
    n = np.arange(head_dim // 2, dtype=np.float64)
    freq = theta ** (-2.0 * n / head_dim)
    if yarn is None:
        return freq.astype(np.float32), 1.0
    span = float(yarn["original_max_position_embeddings"])

    def dim_of(turns):  # the dimension whose frequency turns ``turns`` times over the span
        return head_dim * math.log(span / (2.0 * math.pi * turns)) / (2.0 * math.log(theta))

    low = max(math.floor(dim_of(yarn["beta_fast"])), 0)
    high = min(math.ceil(dim_of(yarn["beta_slow"])), head_dim - 1)
    ramp = np.clip((n - low) / max(high - low, 1e-3), 0.0, 1.0)
    freq = freq / yarn["factor"] * ramp + freq * (1.0 - ramp)
    return freq.astype(np.float32), float(yarn["attention_factor"])


# the published rope_parameters["full_attention"]
YARN = {"factor": 16.0, "original_max_position_embeddings": 8192, "beta_fast": 32.0,
        "beta_slow": 1.0, "attention_factor": 1.2772588722239782}


@dataclass(frozen=True)
class MellumMoE(NextTokenTower):
    vocab: int  # ids held here: the logits' width
    n_layers: int
    hidden: int = 2304
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    n_experts: int = 64  # the router's width, as published
    experts_per_token: int = 8
    expert_width: int = 896
    first_held: int = 0  # this chip's experts: first_held .. first_held + n_held - 1
    n_held: int = 64
    rms_eps: float = 1e-6
    layer_kinds: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL)  # one period, in its order
    sliding_window: int = 1024
    rope_theta: float = 5e5
    yarn: Optional[Tuple[Tuple[str, float], ...]] = tuple(sorted(YARN.items()))  # the full layers'
    head_chunk: int = 2048  # positions whose logits are live at once in ``train_loss``
    tile: int = BLOCK_DIFFUSION_TILE  # the attention kernels' (the CPU tests cut a short sequence)
    interpret: bool = False  # the Pallas interpreter for the attention kernels (CPU tests)

    @classmethod
    def from_config(cls, cfg: dict, **kw) -> "MellumMoE":
        """The tower of a published ``config.json`` (its keys as published),
        cut to a chip's share where the dict says so: ``num_hidden_layers``
        layers (the first entries of ``layer_types``), ``num_experts`` held
        from ``first_held_expert`` on of ``router_width`` routed (all of them
        where the two keys are absent), ``vocab_size`` ids. ``kw``: the
        model's own arguments that no config states."""
        n = int(cfg["num_hidden_layers"])
        rope = cfg["rope_parameters"]
        yarn = {k: float(v) for k, v in rope["full_attention"].items() if k in YARN}
        return cls(
            vocab=int(cfg["vocab_size"]), n_layers=n, hidden=int(cfg["hidden_size"]),
            n_heads=int(cfg["num_attention_heads"]), n_kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["head_dim"]), n_experts=int(cfg.get("router_width", cfg["num_experts"])),
            experts_per_token=int(cfg["num_experts_per_tok"]),
            expert_width=int(cfg["moe_intermediate_size"]),
            first_held=int(cfg.get("first_held_expert", 0)), n_held=int(cfg["num_experts"]),
            rms_eps=float(cfg["rms_norm_eps"]),
            layer_kinds=tuple(kind.split("_")[0] for kind in cfg["layer_types"][:n]),
            sliding_window=int(cfg["sliding_window"]),
            rope_theta=float(rope["sliding_attention"]["rope_theta"]),
            yarn=tuple(sorted(yarn.items())) if yarn else None, **kw)

    def counters(self):
        """Beside the picks: tile pairs the attention kernels visited and
        tile pairs that hold a pair some query reads, a head, by layer kind
        (row 0 sliding, row 1 full)."""
        return dict(super().counters(), attention_tiles=jnp.zeros((2, 2), jnp.int32))

    # --------------------------------------------------------------- forward

    def _hidden(self, variables, dense, emb):
        """The residual stream after the last layer and the step's counters."""
        (rows, _mask), = emb
        starts = dense[0].astype(jnp.int32)  # (B, T): where each position's document starts
        b, t, _ = rows.shape
        pos = (jnp.arange(t, dtype=jnp.int32)[None, :] - starts).astype(jnp.float32)
        rope = {}
        for kind, yarn in ((SLIDING, None), (FULL, self.yarn and dict(self.yarn))):
            freq, factor = rope_frequencies(self.head_dim, self.rope_theta, yarn)
            angle = pos[:, :, None] * jnp.asarray(freq)[None, None, :]
            rope[kind] = tuple(factor * jnp.concatenate([f(angle)] * 2, axis=-1)
                               for f in (jnp.cos, jnp.sin))
        window = {SLIDING: self.sliding_window, FULL: None}
        tile = min(self.tile, t)
        record_event("mellum_moe.paths", attention="pallas_interval", **self.expert_paths(b * t),
                     seq_len=t, window=self.sliding_window, tile=tile, head_chunk=self.head_chunk,
                     qk_prep="pallas_rows", qk_prep_tile=qk_prep_tile(t),
                     **{f"grid_{k}": interval_visits(t // tile, tile, w) for k, w in window.items()})

        def attend(kind, q, k, v):
            return interval_attention(q, k, v, starts, window=window[kind], tile=self.tile,
                                      interpret=self.interpret)

        h, picks = self.layers(variables["params"], rows, rope, attend)
        per_kind = jnp.stack([jnp.stack(interval_tile_counts(starts, window[k], self.tile))
                              * self.layer_kinds.count(k) * (self.n_layers // len(self.layer_kinds))
                              for k in (SLIDING, FULL)])
        stats = variables.get("batch_stats")
        if stats is not None:
            stats = {"expert_picks": stats["expert_picks"] + picks,
                     "attention_tiles": stats["attention_tiles"] + per_kind}
        return h, stats
