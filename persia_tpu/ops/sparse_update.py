"""Device-side sparse optimizer updates for HBM-resident embedding tables.

The reference applies sparse optimizers on the CPU parameter server with AVX2
kernels after the embedding worker has *accumulated gradients per sign*
(`embedding_worker_service/mod.rs:703-872` sums duplicate-id gradients, then
`embedding_parameter_service/mod.rs:359-427` runs `Optimizable::update` per
row). This module is the TPU counterpart for tables that live in HBM: the
same per-unique-row math (`persia_tpu/embedding/optim.py` — SGD / Adagrad
(±vectorwise-shared) / Adam), expressed as static-shape XLA:

1. sort ids, segment-sum duplicate gradients (the worker's per-sign
   accumulation),
2. gather the touched rows + optimizer state,
3. apply the optimizer math on the (N, dim) block,
4. scatter-add the deltas back (invalid tail rows contribute exact zeros).

Everything is functional and jit/grad/shard friendly; no dynamic shapes.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from persia_tpu.embedding.optim import (
    OPTIMIZER_ADAGRAD,
    OPTIMIZER_ADAM,
    OPTIMIZER_SGD,
    OptimizerConfig,
)


def init_sparse_state(cfg: OptimizerConfig, vocab: int, dim: int) -> Dict[str, jnp.ndarray]:
    """Per-table optimizer state arrays (the HBM layout of the reference's
    trailing `[emb | state]` block, `persia-embedding-holder/src/emb_entry.rs:16-76`)."""
    if cfg.kind == OPTIMIZER_SGD:
        return {}
    if cfg.kind == OPTIMIZER_ADAGRAD:
        width = 1 if cfg.vectorwise_shared else dim
        return {"acc": jnp.full((vocab, width), cfg.initialization, dtype=jnp.float32)}
    if cfg.kind == OPTIMIZER_ADAM:
        return {
            "m": jnp.zeros((vocab, dim), dtype=jnp.float32),
            "v": jnp.zeros((vocab, dim), dtype=jnp.float32),
        }
    raise ValueError(f"unknown optimizer kind {cfg.kind}")


_PAD_SENTINEL = np.iinfo(np.int32).max


def dedup_gradients(
    ids: jnp.ndarray, grads: jnp.ndarray, mask: jnp.ndarray = None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-sign gradient accumulation with static shapes.

    ids (N,) int, grads (N, D) → (uid (N,), gsum (N, D), valid (N,) bool).
    Row k < num_unique holds the k-th distinct id (ascending) and the sum of
    its gradients; rows past num_unique are garbage flagged invalid.
    ``mask`` (N,) bool marks live entries: masked-out entries (batch padding)
    are routed to an out-of-vocab sentinel that sorts last and is flagged
    invalid, so padding can never touch a real row — not even through
    weight decay, which applies to every *touched* row.
    """
    n = ids.shape[0]
    if mask is not None:
        ids = jnp.where(mask, ids, _PAD_SENTINEL)
        grads = grads * mask[..., None].astype(grads.dtype)
    order = jnp.argsort(ids)
    sids = ids[order]
    sg = grads[order]
    is_new = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), sids[1:] != sids[:-1]]
    )
    seg = jnp.cumsum(is_new) - 1  # (N,) segment index per sorted element
    gsum = jax.ops.segment_sum(sg, seg, num_segments=n)
    uid = jnp.zeros((n,), dtype=ids.dtype).at[seg].set(sids)
    valid = (jnp.arange(n) <= seg[-1]) & (uid != _PAD_SENTINEL)
    return uid, gsum, valid


def _apply_rows(
    cfg: OptimizerConfig,
    w: jnp.ndarray,
    st: Dict[str, jnp.ndarray],
    g: jnp.ndarray,
    batch_state: jnp.ndarray,
):
    """Optimizer math on a dense (N, D) block of touched rows — mirrors
    ``OptimizerConfig.update_dense`` bit-for-bit in f32."""
    w = w.astype(jnp.float32)
    g = g.astype(jnp.float32)
    # weight decay applies to SGD/Adagrad only — the reference's Adam branch
    # has no decay term (persia_tpu/embedding/optim.py update_dense,
    # mirroring persia-common/src/optim.rs adam_avx2)
    if cfg.weight_decay and cfg.kind in (OPTIMIZER_SGD, OPTIMIZER_ADAGRAD):
        g = g + cfg.weight_decay * w
    if cfg.kind == OPTIMIZER_SGD:
        return w - cfg.lr * g, {}
    if cfg.kind == OPTIMIZER_ADAGRAD:
        if cfg.vectorwise_shared:
            g2 = jnp.mean(g * g, axis=-1, keepdims=True)  # (N, 1)
            acc = st["acc"] * cfg.g_square_momentum + g2
            new_w = w - cfg.lr * g / jnp.sqrt(acc + cfg.eps)
        else:
            acc = st["acc"] * cfg.g_square_momentum + g * g
            new_w = w - cfg.lr * g / jnp.sqrt(acc + cfg.eps)
        return new_w, {"acc": acc}
    if cfg.kind == OPTIMIZER_ADAM:
        m = st["m"] * cfg.beta1 + (1.0 - cfg.beta1) * g
        v = st["v"] * cfg.beta2 + (1.0 - cfg.beta2) * g * g
        beta1_pow, beta2_pow = batch_state[0], batch_state[1]
        m_hat = m / (1.0 - beta1_pow)
        v_hat = v / (1.0 - beta2_pow)
        new_w = w - cfg.lr * m_hat / (jnp.sqrt(v_hat) + cfg.eps)
        return new_w, {"m": m, "v": v}
    raise ValueError(f"unknown optimizer kind {cfg.kind}")


def sparse_update(
    cfg: OptimizerConfig,
    table: jnp.ndarray,
    state: Dict[str, jnp.ndarray],
    ids: jnp.ndarray,
    grads: jnp.ndarray,
    batch_state: jnp.ndarray = None,
    mask: jnp.ndarray = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Apply one sparse optimizer step for the rows named by ``ids``.

    table (V, D) f32, state from ``init_sparse_state``, ids (N,) int,
    grads (N, D). Duplicate ids have their gradients summed first (reference
    worker semantics). ``batch_state`` = (beta1^t, beta2^t) f32[2] for Adam
    (the reference's per-feature-group accumulated beta powers,
    `persia-common/src/optim.rs:99-221`). ``mask`` (N,) bool marks live
    entries; masked-out (padding) entries touch no row at all.
    Rows only touched with zero effective delta are bit-identical unchanged.
    """
    if batch_state is None:
        batch_state = jnp.ones((2,), dtype=jnp.float32)
    ids = ids.astype(jnp.int32)
    # named scopes: a device trace names each of this update's operations by
    # the part it belongs to (PERF.md, "device ms by scope")
    with jax.named_scope("sparse_update"):
        with jax.named_scope("dedup"):
            uid, gsum, valid = dedup_gradients(ids, grads, mask)
        with jax.named_scope("row_update"):
            with jax.named_scope("gather_rows"):
                # OOB sentinel rows clamp-gather; their deltas are dropped
                w = table[uid]
                st_rows = {k: v[uid] for k, v in state.items()}
            new_w, new_st = _apply_rows(cfg, w, st_rows, gsum, batch_state)
            vcol = valid[:, None]
            with jax.named_scope("scatter_table"):
                table = table.at[uid].add(
                    jnp.where(vcol, new_w - w.astype(jnp.float32), 0.0).astype(table.dtype),
                    mode="drop",
                )
            out_state = {}
            for k, full in state.items():
                with jax.named_scope(f"scatter_{k}"):
                    delta = jnp.where(vcol, new_st[k] - st_rows[k], 0.0)
                    out_state[k] = full.at[uid].add(delta.astype(full.dtype), mode="drop")
    return table, out_state


def masked_flat_ids_grads(
    ids: jnp.ndarray, grads: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Flatten bag/single-id slots for ``sparse_update``: ids (B,) or (B, L)
    with -1 padding + per-position grads → (flat_ids, flat_grads (N, D),
    flat_mask). Padding keeps its -1 id but is masked out, so it touches no
    table row (not even through weight decay)."""
    mask = (ids >= 0).reshape(-1)
    flat_ids = ids.reshape(-1)
    flat_g = grads.reshape(-1, grads.shape[-1])
    return flat_ids, flat_g, mask
