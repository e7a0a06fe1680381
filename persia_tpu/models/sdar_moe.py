"""A block-diffusion mixture-of-experts transformer tower over the sparse plane
(the ``sdar_moe`` family), trained with the masked block-diffusion objective.
The tower itself (RMSNorm, grouped-query attention with RoPE and per-head q/k
norms, the routed expert layer that is told which experts it holds, the
untied head, the scan) is ``models/moe_tower.py``'s; here is what block
diffusion adds to it.

The token rows come from the sparse plane as one raw slot: the ids of
``[xt | x0]`` (the noised sequence, then the clean one; 2L positions) give
``(B, 2L, hidden)`` float32 rows. The tower returns the logits of the noised
half, ``(B, L, vocab)``; ``loss`` and ``outputs`` are the model's own, which
``build_fused_train_step`` asks a model for before it falls back on the
click models' sigmoid cross-entropy.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from persia_tpu.models.moe_tower import MoETower
from persia_tpu.ops.flash_attention import block_diffusion_attention, block_diffusion_plan
from persia_tpu.ops.qk_prep import qk_prep_tile
from persia_tpu.tracing import record_event


@dataclass(frozen=True)
class SDARMoE(MoETower):
    vocab: int  # ids held here: the logits' width
    n_layers: int
    block_len: int
    hidden: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    n_experts: int = 128  # the router's width, as published
    experts_per_token: int = 8
    expert_width: int = 768
    first_held: int = 0  # this chip's experts: first_held .. first_held + n_held - 1
    n_held: int = 128
    rms_eps: float = 1e-6
    rope_theta: float = 1e6
    interpret: bool = False  # the Pallas interpreter for the attention kernels (CPU tests)

    def apply(self, variables, dense, emb, train: bool = True, mutable: Optional[Sequence[str]] = None):
        """Logits of the noised half, (B, L, vocab) float32. ``emb`` is the
        step's list of slot inputs: one raw slot, ``(rows (B, 2L, hidden),
        mask)``. With ``mutable=["batch_stats"]`` also the updated counter of
        picks by layer and held expert."""
        del dense, train
        (rows, _mask), = emb
        params = variables["params"]
        b, t, d = rows.shape
        seq_len = t // 2
        inv = self.rope_theta ** (-jnp.arange(0, self.head_dim, 2, dtype=jnp.float32) / self.head_dim)
        angle = (jnp.arange(t, dtype=jnp.float32) % seq_len)[:, None] * inv[None, :]
        cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
        sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
        # with what the attention kernels execute a head and sequence: visits by
        # the tile's kind, sub-tiles executed of the visited, pairs beside the live
        record_event("sdar_moe.paths", attention="pallas_block_mask",
                     seq_len=seq_len, block_len=self.block_len, **self.expert_paths(b * t),
                     qk_prep="pallas_rows", qk_prep_tile=qk_prep_tile(t),
                     **block_diffusion_plan(seq_len, self.block_len))

        def attend(kind, q, k, v):
            return block_diffusion_attention(q, k, v, seq_len, self.block_len,
                                             interpret=self.interpret)

        h, picks = self.layers(params, rows, {"attention": (cos, sin)}, attend)
        with jax.named_scope("lm_head"):
            logits = self.head(params, h[:, :seq_len])
        if mutable:
            stats = variables["batch_stats"]
            return logits, {"batch_stats": {"expert_picks": stats["expert_picks"] + picks}}
        return logits

    # ----------------------------------------------------- loss and outputs

    def loss(self, logits, labels):
        """The masked block-diffusion objective: ``labels`` = [x0 (B, L)
        int32, weight (B, L) float32 (1 / t of its block where the token was
        masked, else 0)]; the weighted cross-entropy summed, over B * L."""
        targets, weight = labels[0], labels[1]
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
        return jnp.sum(weight * (logz - picked)) / (targets.shape[0] * targets.shape[1])

    def outputs(self, logits):
        """What a step hands back beside the loss: the most likely id of
        each noised position, (B, L) int32."""
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
