"""The fused cached-tier train/eval step builders (one jitted XLA
program per step: gather -> model fwd/bwd -> dense update -> on-device
sparse update -> eviction payload)."""


from __future__ import annotations

import ctypes
import os
import threading
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from persia_tpu.config import EmbeddingConfig
from persia_tpu.data import PersiaBatch
from persia_tpu.embedding.optim import OPTIMIZER_ADAM, OptimizerConfig
from persia_tpu.embedding.worker import (
    ProcessedBatch,
    ProcessedSlot,
    ShardedLookup,
    preprocess_batch,
)
from persia_tpu.logger import get_default_logger
from persia_tpu.utils import round_up_pow2 as _round_up_pow2
from persia_tpu.metrics import get_metrics
from persia_tpu.ops.sparse_update import sparse_update
from persia_tpu.tracing import span

logger = get_default_logger("persia_tpu.hbm_cache")

# ------------------------------------------------------------------ ctypes


from persia_tpu.embedding.hbm_cache.groups import (  # noqa: F401
    CacheGroup,
    CacheLayout,
    CachedTrainState,
    _apply_aux,
    _entry_to_state_cols,
    _gather_entry_rows,
    _model_emb_from_gathered,
    _restore_rows,
    _scatter_entry_block,
    _slot_group_of,
    _state_init_consts,
    _bucket,
)

def build_cached_train_step(
    model,
    dense_optimizer,
    sparse_cfg: OptimizerConfig,
    groups: Sequence[CacheGroup],
    loss_fn=None,
    donate: bool = True,
    ps_grad_dtype=jnp.float32,
    ps_grad_wire: Optional[str] = None,
    dynamic_loss_scale: bool = False,
    growth_interval: int = 2000,
    growth_factor: float = 2.0,
    backoff_factor: float = 0.5,
    max_scale: float = float(2 ** 24),
    sentinel_probe: bool = False,
    guard_clip_norm: Optional[float] = None,
):
    """Jitted ``step(state, batch, layout) -> (state, header)``.

    batch = {
      "dense": [(B,F) f32], "labels": [(B,1) f32],
      "stacked_rows": {group: (S, B, L) int32 cache rows for the group's
                       pooled slots (stack order = layout.stacked), pad = C
                       (the zero row)},
      "stacked_scale": {group: (S, B) f32} — omitted when no slot scales,
      "raw_rows": {slot: (B, L) int32} for sequence slots,
      "ps_emb": [ {"pooled": (B,D)} | {"distinct","index","mask"} ... ] —
                mixed-tier slots served by the worker/PS path
                (layout.ps names them, in order),
    }
    Miss scatters and the evict-payload read run as a separate fused tiny
    jit (``_apply_aux``) dispatched by the ctx around this step, so this —
    the expensive compile — sees only fixed-shape inputs. Returns
    ``(state, header, ps_gpacked)``: header = [loss, preds...]; ps_gpacked
    = flat f32 gradients of the ps_emb entries (empty when none) for the
    worker's gradient return.

    ``dynamic_loss_scale`` (same management as the hybrid path's
    build_train_step; ref GradScaler, persia/ctx.py:926-1005): the loss is
    scaled before backward, an on-device finite check over EVERY gradient
    (dense + cached + ps) gates the update — overflow skips the dense
    update AND the cached-row sparse update (scale *= backoff), a finite
    streak grows the scale. Header becomes [loss | scale | finite | preds],
    and ps_gpacked carries [grads... | scale | finite] so the write-back
    thread can unscale/skip without any extra device fetch. One documented
    divergence from the reference: the Adam beta powers (device AND PS)
    advance on overflow-skipped steps too — keeping the two tiers' powers
    in lockstep without a per-step device sync; the skipped step itself
    applies no gradient anywhere.

    ``ps_grad_wire``: the gradient-RETURN wire for PS-tier slots —
    "float32" / "bfloat16" (equivalent to ``ps_grad_dtype``, kept for
    callers that pass the dtype directly) or "int8": bytegrad-style
    per-slot absmax quantization with an error-feedback residual
    (``parallel/grad_sync.quantize_int8_ef``) — ~4× fewer d2h bytes than
    f32 on the wire that physically caps the ps-stream regime. The
    residual stays DEVICE-resident: the step reads it from
    ``batch["ps_gres"]`` (flat f32, zeros to reset) and returns the
    updated one, so what int8 could not represent this step re-enters the
    next step's wire instead of being lost. With int8 the step returns
    ``ps_gpacked = (q int8, scales f32 (S[+finite]), new_residual f32)``
    — grads are unscaled ON DEVICE under dynamic loss scaling (the
    scales tail then carries the finite flag), and an overflow step ships
    zeros and carries the residual through unchanged.

    ``sentinel_probe``: numerical-health probe for the stream sentinel
    (persia_tpu/health). Appends a fixed probe tail to the header —
    ``[dense_gnorm, group_gnorm x n_groups, ps_gnorm, finite, clipped]``
    (norms unscaled, pre-clip) — and arms the finite gate even without
    dynamic loss scaling: a non-finite gradient skips the dense update,
    masks every cached row, and ships a flagged/zeroed ps wire, exactly
    like an overflow step (device-side "skip-batch" rung; the ps wire
    then carries the ``[scale|finite]`` tail so the write-back thread can
    honor the skip). Healthy unclipped steps multiply by exactly 1.0
    everywhere, so arming the probe is bit-transparent. ``guard_clip_norm``
    (requires ``sentinel_probe``) rescales the whole update on device when
    the total grad norm exceeds it — the sentinel's "clip" rung.
    """
    from functools import partial

    from persia_tpu.parallel.train_step import default_loss_fn

    loss_fn = loss_fn or default_loss_fn
    by_name = {g.name: g for g in groups}
    if ps_grad_wire is not None:
        if ps_grad_wire not in ("float32", "bfloat16", "int8"):
            raise ValueError(
                f"ps_grad_wire must be float32/bfloat16/int8, got {ps_grad_wire!r}"
            )
        if ps_grad_wire == "bfloat16":
            ps_grad_dtype = jnp.bfloat16
    ps_int8 = ps_grad_wire == "int8"

    @partial(jax.jit, static_argnums=(2,), donate_argnums=(0,) if donate else ())
    def step(state: CachedTrainState, batch: Dict, layout: CacheLayout):
        tables, emb_state = dict(state.tables), dict(state.emb_state)

        # ONE gather per group for all its stacked pooled slots, plus one
        # per raw slot; differentiate w.r.t. the GATHERED arrays (like the
        # fused path) so cotangents stay gather-shaped instead of dense
        # table-shaped scatters. The named scopes of this step (gather, pool,
        # bottom_mlp, interaction, top_mlp, loss, grad_guard, dense_opt,
        # sparse_update/...) are the fused step's too: a device trace names
        # every operation by the part of the step it belongs to.
        with jax.named_scope("gather"):
            stacked_gathered = {
                gname: tables[gname][rows]  # (S, B, L, dim)
                for gname, rows in batch["stacked_rows"].items()
            }
            raw_gathered = {
                name: tables[_slot_group_of(groups, name)][rows]
                for name, rows in batch["raw_rows"].items()
            }
        from persia_tpu.parallel.train_step import (
            _embedding_model_inputs, _split_emb,
        )

        ps_diff, ps_static = _split_emb(batch.get("ps_emb", []))

        scale = (
            state.loss_scale.scale
            if dynamic_loss_scale
            else jnp.asarray(1.0, jnp.float32)
        )

        def loss_wrapper(params, stacked_in, raw_in, ps_in):
            with jax.named_scope("pool"):
                model_emb = _model_emb_from_gathered(
                    groups, batch, layout, stacked_in, raw_in,
                    pad_row=lambda gname: by_name[gname].rows,
                    ps_model_inputs=_embedding_model_inputs(ps_in, ps_static),
                )
            variables = {"params": params}
            if state.batch_stats:
                variables["batch_stats"] = state.batch_stats
                logits, updates = model.apply(
                    variables, batch["dense"], model_emb, train=True,
                    mutable=["batch_stats"],
                )
                new_stats = updates["batch_stats"]
            else:
                logits = model.apply(variables, batch["dense"], model_emb, train=True)
                new_stats = state.batch_stats
            with jax.named_scope("loss"):
                loss = loss_fn(logits, batch["labels"][0])
                return loss * scale.astype(loss.dtype), (loss, logits, new_stats)

        (_, (loss, logits, new_stats)), (param_grads, stacked_g, raw_g, ps_g) = (
            jax.value_and_grad(
                loss_wrapper, argnums=(0, 1, 2, 3), has_aux=True
            )(state.params, stacked_gathered, raw_gathered, ps_diff)
        )

        need_guard = dynamic_loss_scale or sentinel_probe
        with jax.named_scope("grad_guard"):
            if need_guard:
                leaves = (
                    jax.tree.leaves(param_grads)
                    + jax.tree.leaves(stacked_g) + jax.tree.leaves(raw_g)
                    + jax.tree.leaves(ps_g)
                )
                finite = jnp.all(
                    jnp.stack([jnp.all(jnp.isfinite(g)) for g in leaves])
                )
                inv = jnp.where(finite, 1.0 / scale, 0.0).astype(jnp.float32)
            else:
                finite = jnp.asarray(True)
                inv = jnp.asarray(1.0, jnp.float32)

            clip_f = jnp.asarray(1.0, jnp.float32)
            probe_tail = None
            if sentinel_probe:
                # Norms of the UNSCALED gradients (inv divides the loss scale
                # out; overflow steps report 0 and carry the finite flag).
                def _gnorm(parts):
                    parts = list(parts)
                    if not parts:
                        return jnp.asarray(0.0, jnp.float32)
                    return jnp.sqrt(
                        sum(jnp.sum(jnp.square(p.astype(jnp.float32)))
                            for p in parts)
                    )

                dense_gnorm = _gnorm(jax.tree.leaves(param_grads)) * inv
                group_gnorms = []
                for g in groups:
                    parts = []
                    if g.name in batch["stacked_rows"]:
                        parts.append(stacked_g[g.name])
                    for name in g.raw_slots:
                        if name in batch["raw_rows"]:
                            parts.append(raw_g[name])
                    group_gnorms.append(_gnorm(parts) * inv)
                ps_gnorm = _gnorm(jax.tree.leaves(ps_g)) * inv
                if guard_clip_norm is not None:
                    total = jnp.sqrt(
                        jnp.square(dense_gnorm) + jnp.square(ps_gnorm)
                        + sum(jnp.square(n) for n in group_gnorms)
                    )
                    clip_f = jnp.where(
                        total > guard_clip_norm,
                        guard_clip_norm / jnp.maximum(total, 1e-12),
                        1.0,
                    ).astype(jnp.float32)
                probe_tail = jnp.stack(
                    [dense_gnorm] + group_gnorms + [
                        ps_gnorm,
                        finite.astype(jnp.float32),
                        (clip_f < 1.0).astype(jnp.float32),
                    ]
                )
                inv = inv * clip_f

            if need_guard:
                param_grads = jax.tree.map(
                    lambda g: (g.astype(jnp.float32) * inv).astype(g.dtype),
                    param_grads,
                )

        import optax as _optax

        with jax.named_scope("dense_opt"):
            updates, new_opt_state = dense_optimizer.update(
                param_grads, state.opt_state, state.params
            )
            new_params = _optax.apply_updates(state.params, updates)
            if need_guard:
                # overflow / non-finite grads: dense update skipped entirely
                new_params = jax.tree.map(
                    lambda new, old: jnp.where(finite, new, old),
                    new_params, state.params,
                )
                new_opt_state = jax.tree.map(
                    lambda new, old: jnp.where(finite, new, old),
                    new_opt_state, state.opt_state,
                )

        # on-device sparse update of the cached rows — ONE duplicate-safe
        # scatter per group (dedup inside sparse_update merges the same row
        # appearing in several slots)
        batch_state = state.emb_batch_state * jnp.array(
            [sparse_cfg.beta1, sparse_cfg.beta2], dtype=jnp.float32
        )
        for g in groups:
            idp, gp, mp = [], [], []
            with jax.named_scope("sparse_prep"):
                if g.name in batch["stacked_rows"]:
                    rows = batch["stacked_rows"][g.name]
                    idp.append(rows.reshape(-1))
                    # unscale under dynamic loss scaling; on overflow every
                    # row is MASKED OUT below (sparse_update touches no row
                    # at all — exact skip for every optimizer incl. weight
                    # decay and Adam's state decay, at O(touched rows)); the
                    # grads are also selected to zero so inf*0 NaNs never
                    # enter the math
                    sg = stacked_g[g.name].astype(jnp.float32).reshape(-1, g.dim)
                    gp.append(jnp.where(finite, sg * inv, 0.0))
                    mp.append(((rows < g.rows) & finite).reshape(-1))
                for name in g.raw_slots:
                    if name not in batch["raw_rows"]:
                        continue
                    rows = batch["raw_rows"][name]
                    idp.append(rows.reshape(-1))
                    rg = raw_g[name].astype(jnp.float32).reshape(-1, g.dim)
                    gp.append(jnp.where(finite, rg * inv, 0.0))
                    mp.append(((rows < g.rows) & finite).reshape(-1))
                if not idp:
                    continue
                flat_ids = jnp.concatenate(idp) if len(idp) > 1 else idp[0]
                flat_g = jnp.concatenate(gp) if len(gp) > 1 else gp[0]
                flat_mask = jnp.concatenate(mp) if len(mp) > 1 else mp[0]
            tables[g.name], emb_state[g.name] = sparse_update(
                sparse_cfg,
                tables[g.name],
                emb_state[g.name],
                flat_ids,
                flat_g,
                batch_state,
                mask=flat_mask,
            )

        new_ls = state.loss_scale
        if dynamic_loss_scale:
            from persia_tpu.parallel.train_step import LossScaleState

            good = jnp.where(finite, state.loss_scale.good_steps + 1, 0)
            grown = good >= growth_interval
            new_scale = jnp.where(
                finite,
                jnp.where(grown, scale * growth_factor, scale),
                scale * backoff_factor,
            )
            new_scale = jnp.clip(new_scale, 1.0, max_scale)
            new_ls = LossScaleState(
                scale=new_scale, good_steps=jnp.where(grown, 0, good)
            )
        new_state = CachedTrainState(
            params=new_params,
            batch_stats=new_stats,
            opt_state=new_opt_state,
            tables=tables,
            emb_state=emb_state,
            emb_batch_state=batch_state,
            step=state.step + 1,
            loss_scale=new_ls,
        )
        head = [jnp.reshape(loss, (1,)).astype(jnp.float32)]
        if dynamic_loss_scale:
            head.append(jnp.reshape(scale, (1,)).astype(jnp.float32))
            head.append(jnp.reshape(finite, (1,)).astype(jnp.float32))
        head.append(jnp.reshape(jax.nn.sigmoid(logits), (-1,)).astype(jnp.float32))
        if probe_tail is not None:
            head.append(probe_tail)
        header = jnp.concatenate(head)
        # ps-tier gradients are an inherent d2h; a bf16 wire halves the
        # bytes on the return path (the reference ships scaled-f16 grad
        # wires, lib.rs:157-180) — the host casts back to f32 before the
        # worker's unscale/update. Under dynamic scaling the buffer's last
        # two entries are [scale | finite] (both exact in bf16: scale is a
        # power of two), so the write-back thread needs no extra fetch.
        # The int8 wire quarter-widths the same bytes: per-slot absmax
        # quantization with a device-resident error-feedback residual.
        if ps_int8:
            from persia_tpu.parallel.grad_sync import quantize_int8_ef

            flats = [jnp.reshape(g, (-1,)).astype(jnp.float32) for g in ps_g]
            total = sum(f.shape[0] for f in flats)
            res = batch.get("ps_gres")
            if res is None:
                res = jnp.zeros((total,), jnp.float32)
            qs, scs, new_res = [], [], []
            off = 0
            for f in flats:
                r = jax.lax.slice(res, (off,), (off + f.shape[0],))
                off += f.shape[0]
                # unscale ON the device (inv = 0 on overflow): the residual
                # must accumulate true-gradient error, not scaled error
                q, sc, _deq, nr = quantize_int8_ef(f * inv, r)
                if need_guard:
                    q = jnp.where(finite, q, jnp.zeros_like(q))
                    nr = jnp.where(finite, nr, r)
                qs.append(q)
                scs.append(sc)
                new_res.append(nr)
            q_packed = (
                jnp.concatenate(qs) if qs else jnp.zeros((0,), jnp.int8)
            )
            sc_parts = [jnp.stack(scs)] if scs else []
            if need_guard:
                sc_parts.append(
                    jnp.reshape(finite.astype(jnp.float32), (1,))
                )
            sc_packed = (
                jnp.concatenate(sc_parts) if sc_parts
                else jnp.zeros((0,), jnp.float32)
            )
            res_packed = (
                jnp.concatenate(new_res) if new_res
                else jnp.zeros((0,), jnp.float32)
            )
            return new_state, header, (q_packed, sc_packed, res_packed)
        ps_flat = [
            (jnp.reshape(g, (-1,)).astype(jnp.float32) * clip_f).astype(
                ps_grad_dtype
            )
            for g in ps_g
        ]
        if need_guard and ps_flat:
            ps_flat.append(
                jnp.stack([scale, finite.astype(jnp.float32)]).astype(ps_grad_dtype)
            )
        ps_gpacked = (
            jnp.concatenate(ps_flat) if ps_flat
            else jnp.zeros((0,), ps_grad_dtype)
        )
        return new_state, header, ps_gpacked

    return step


def build_cached_eval_step(model, groups: Sequence[CacheGroup]):
    """Jitted ``eval_step(state, batch, layout) -> preds``.

    Eval must not mutate the cache (no admits, no evictions, no directory
    churn — the ADVICE round-1 corruption bug): resident signs gather from
    the live cache tables; misses arrive as a host-side PS lookup
    (``miss_tables``: {group: (Mp, dim)}) with rows pre-assigned to C+1+j.
    Values come from a two-gather select (no table concat — concatenating
    would copy the multi-GB pool per eval batch). Mask rule here is
    ``rows != C`` (pad) since miss rows legitimately exceed C."""
    from functools import partial

    by_name = {g.name: g for g in groups}

    def _gather_ext(table, miss_table, rows, C):
        from_cache = table[jnp.minimum(rows, C)]
        miss_idx = jnp.maximum(rows - (C + 1), 0)
        from_miss = miss_table[miss_idx].astype(table.dtype)
        return jnp.where((rows > C)[..., None], from_miss, from_cache)

    @partial(jax.jit, static_argnums=(2,))
    def eval_step(state: CachedTrainState, batch: Dict, layout: CacheLayout):
        stacked_gathered = {}
        for gname, rows in batch["stacked_rows"].items():
            C = by_name[gname].rows
            stacked_gathered[gname] = _gather_ext(
                state.tables[gname], batch["miss_tables"][gname], rows, C
            )
        raw_gathered = {}
        for name, rows in batch["raw_rows"].items():
            gname = _slot_group_of(groups, name)
            C = by_name[gname].rows
            raw_gathered[name] = _gather_ext(
                state.tables[gname], batch["miss_tables"][gname], rows, C
            )
        from persia_tpu.parallel.train_step import (
            _embedding_model_inputs, _split_emb,
        )

        ps_diff, ps_static = _split_emb(batch.get("ps_emb", []))
        model_emb = _model_emb_from_gathered(
            groups, batch, layout, stacked_gathered, raw_gathered,
            pad_row=lambda gname: by_name[gname].rows,
            ps_model_inputs=_embedding_model_inputs(ps_diff, ps_static),
        )
        variables = {"params": state.params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        logits = model.apply(variables, batch["dense"], model_emb, train=False)
        return jax.nn.sigmoid(logits)

    return eval_step


# -------------------------------------------------------------- host tier


