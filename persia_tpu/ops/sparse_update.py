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
4. scatter-add the deltas back at strictly ascending, distinct indices.

Steps 2-4 run a chunk of rows a trip in a loop over the live rows only: the
invalid tail of the static N positions is dropped, not executed.

Everything is functional and jit/shard friendly; no dynamic shapes. The row
loop's trip count is dynamic, so the update is not reverse-differentiable
(nothing differentiates an optimizer step).
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
    ``mask`` (N,) bool marks live entries; without it every entry is live.
    Contract: with U distinct live ids, rows k < U hold the k-th distinct
    live id (strictly ascending) and the sum of its gradients, and are the
    only rows flagged valid. Masked-out entries (batch padding, ids that
    name no row) are folded into one out-of-vocab sentinel *before* the
    sort, so they sort last, land in row U flagged invalid and can never
    touch a real row — not even through weight decay, which applies to
    every *touched* row. Rows past the sentinel hold uid 0 and a zero sum.
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
    seg = jnp.cumsum(is_new) - 1  # (N,) segment index per sorted element, non-decreasing
    gsum = jax.ops.segment_sum(sg, seg, num_segments=n, indices_are_sorted=True)
    uid = jnp.zeros((n,), dtype=ids.dtype).at[seg].set(sids, indices_are_sorted=True)
    valid = (jnp.arange(n) <= seg[-1]) & (uid != _PAD_SENTINEL)
    return uid, gsum, valid


def scatter_indices(uid: jnp.ndarray, valid: jnp.ndarray, vocab: int) -> jnp.ndarray:
    """Row indices for the write-back of ``dedup_gradients``' rows into a
    (vocab, ·) array: the valid prefix keeps its ids (strictly ascending, in
    ``[0, vocab)`` when every live id is), the sentinel's row and the whole
    tail get ``vocab + position``. The result is strictly ascending and
    distinct over all N positions, and a ``mode="drop"`` scatter discards
    everything past the valid prefix."""
    n = uid.shape[0]
    if vocab + n > _PAD_SENTINEL:
        raise ValueError(f"vocab {vocab} + n {n} overflows the int32 row index")
    return jnp.where(valid, uid, vocab + jnp.arange(n, dtype=uid.dtype))


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
    `persia-common/src/optim.rs:99-221`).

    Contract: an entry is live when ``mask`` (N,) bool allows it (default:
    all) and ``0 <= id < V``. Masked-out (padding), negative and
    out-of-range entries touch no row at all. Each distinct live row is
    gathered, updated and written back exactly once, ``_CHUNK_ROWS`` rows at
    a time in a loop that ends with the last live row: the dead tail of the
    static N positions is never executed, and what a last, partly live
    chunk holds of it carries indices ``>= V`` that the scatters drop. Rows
    only touched with zero effective delta are bit-identical unchanged.
    """
    if batch_state is None:
        batch_state = jnp.ones((2,), dtype=jnp.float32)
    ids = ids.astype(jnp.int32)
    vocab = table.shape[0]
    live = (ids >= 0) & (ids < vocab)
    if mask is not None:
        live = live & mask
    # named scopes: a device trace names each of this update's operations by
    # the part it belongs to (PERF.md, "device ms by scope")
    with jax.named_scope("sparse_update"):
        with jax.named_scope("dedup"):
            uid, gsum, valid = dedup_gradients(ids, grads, live)
            sidx = scatter_indices(uid, valid, vocab)
            n_live = jnp.sum(valid, dtype=jnp.int32)
        with jax.named_scope("row_update"):
            table, state = _update_live_rows(cfg, table, state, sidx, gsum, n_live, batch_state)
    return table, state


# Rows a trip of the row-update loop handles. On the v5e a scatter costs
# 75-80 ns an update whether the update lands or is dropped, so the loop's
# trip count (the live rows, not the static N) is what the step pays for;
# 512 to 2048 rows a trip read within 0.3 ms of each other (PERF.md, PR 27).
_CHUNK_ROWS = 1024


def _update_live_rows(cfg, table, state, sidx, gsum, n_live, batch_state):
    """Gather, optimizer math and write-back for the first ``n_live`` of
    ``scatter_indices``' N positions, a chunk a trip. A chunk that would run
    past N starts at N - chunk instead and sends the positions an earlier
    trip already wrote out of range, so no row is added to twice."""
    n = sidx.shape[0]
    vocab = table.shape[0]
    chunk = min(_CHUNK_ROWS, n)

    def body(trip, carry):
        table, state = carry
        lo = trip * chunk
        start = jnp.minimum(lo, n - chunk)
        pos = start + jnp.arange(chunk, dtype=sidx.dtype)
        idx = jnp.where(pos >= lo, jax.lax.dynamic_slice(sidx, (start,), (chunk,)), vocab + pos)
        g = jax.lax.dynamic_slice(gsum, (start, 0), (chunk, gsum.shape[1]))
        with jax.named_scope("gather_rows"):
            # dropped positions gather row V-1; what they compute is dropped too
            rows = jnp.minimum(idx, vocab - 1)
            w = table[rows]
            st_rows = {k: v[rows] for k, v in state.items()}
        new_w, new_st = _apply_rows(cfg, w, st_rows, g, batch_state)
        with jax.named_scope("scatter_table"):
            table = _scatter_add_rows(table, idx, new_w - w.astype(jnp.float32))
        out_state = {}
        for k, full in state.items():
            with jax.named_scope(f"scatter_{k}"):
                out_state[k] = _scatter_add_rows(full, idx, new_st[k] - st_rows[k])
        return table, out_state

    return jax.lax.fori_loop(0, (n_live + chunk - 1) // chunk, body, (table, state))


def _scatter_add_rows(full: jnp.ndarray, idx: jnp.ndarray, delta: jnp.ndarray) -> jnp.ndarray:
    """``full[idx] += delta`` for a slice of ``scatter_indices``' output.
    The indices are distinct by construction and say so. They are ascending
    too and do NOT say so: on the v5e ``indices_are_sorted=True`` costs a
    third more outside a loop and a copy of the whole operand a trip inside
    one (PERF.md, PR 27)."""
    return full.at[idx].add(delta.astype(full.dtype), mode="drop", unique_indices=True)


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
