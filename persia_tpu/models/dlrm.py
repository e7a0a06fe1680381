"""DLRM — the flagship benchmark model (BASELINE.json: DLRM on Criteo).

Standard DLRM architecture (bottom MLP over dense features, pairwise dot
interactions between the bottom output and per-slot pooled embeddings, top
MLP over [bottom | interactions]), built TPU-first: bf16 compute on the MXU,
f32 params, the interaction computed as one batched matmul
(``jnp.einsum('bnd,bmd->bnm')``) instead of per-pair dots.
"""

from __future__ import annotations

from typing import Any, List, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp


def _mlp(x, sizes, dt, final_relu=True):
    for i, h in enumerate(sizes):
        x = nn.Dense(h, dtype=dt)(x)
        if final_relu or i < len(sizes) - 1:
            x = nn.relu(x)
    return x


class DLRM(nn.Module):
    embedding_dim: int = 16
    bottom_mlp: Sequence[int] = (64, 32, 16)  # last must equal embedding_dim
    top_mlp: Sequence[int] = (256, 128)
    compute_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, non_id_features: List, embeddings: List, train: bool = True):
        dt = self.compute_dtype
        # the named scopes are what a device trace attributes time to
        with jax.named_scope("bottom_mlp"):
            dense = jnp.concatenate([f.astype(dt) for f in non_id_features], axis=1)
            bottom = _mlp(dense, self.bottom_mlp, dt)  # (B, d)

        with jax.named_scope("interaction"):
            embs = []
            for emb in embeddings:
                if isinstance(emb, tuple):  # raw slot → mean-pool into one vector
                    gathered, mask = emb
                    m = mask[..., None].astype(gathered.dtype)
                    denom = jnp.maximum(m.sum(axis=1), 1.0)
                    embs.append(((gathered * m).sum(axis=1) / denom).astype(dt))
                else:
                    embs.append(emb.astype(dt))

            # (B, n+1, d): bottom output joins the interaction like an embedding
            feats = jnp.stack([bottom] + embs, axis=1)
            inter = jnp.einsum("bnd,bmd->bnm", feats, feats)  # one MXU batched matmul
            n = feats.shape[1]
            iu, ju = jnp.triu_indices(n, k=1)
            inter_flat = inter[:, iu, ju]  # (B, n(n-1)/2)

        with jax.named_scope("top_mlp"):
            top_in = jnp.concatenate([bottom, inter_flat], axis=1)
            x = _mlp(top_in, self.top_mlp, dt)
            return nn.Dense(1, dtype=jnp.float32)(x)
