"""Cache groups, device state layout, and the scatter/gather/aux device
helpers of the HBM cache tier."""


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


from persia_tpu.embedding.hbm_cache.common import _bucket  # noqa: F401
from persia_tpu.embedding.hbm_cache.directory import (  # noqa: F401
    native_uniform_init,
)

@flax.struct.dataclass
class CachedTrainState:
    params: object
    batch_stats: object
    opt_state: object
    tables: Dict[str, jnp.ndarray]  # group → (C+1, dim); row C is the zero pad row
    emb_state: Dict[str, Dict[str, jnp.ndarray]]  # group → optimizer state (C+1, ·)
    emb_batch_state: jnp.ndarray
    step: jnp.ndarray
    # dynamic mixed-precision loss scaling (None = static); same state the
    # hybrid TrainCtx carries (parallel/train_step.py LossScaleState)
    loss_scale: Optional[object] = None


@dataclass(frozen=True)
class CacheGroup:
    """One HBM row pool shared by all slots of one embedding dim."""

    name: str
    dim: int
    rows: int  # cache capacity C (the table itself has C+1 rows)
    state_dim: int
    pooled_slots: Tuple[str, ...]  # stacked: one gather/update for all of them
    raw_slots: Tuple[str, ...]  # sequence slots, per-slot (B, L) rows

    @property
    def slots(self) -> Tuple[str, ...]:
        return self.pooled_slots + self.raw_slots


def _lazy_pool(existing, prefix: str, workers: int = 8):
    """Idempotent daemon ThreadPoolExecutor creation (shared by the tier's
    chunking pool and the stream's fetch pool)."""
    if existing is None:
        from concurrent.futures import ThreadPoolExecutor

        existing = ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix=prefix
        )
    return existing


def make_cache_groups(
    cfg: EmbeddingConfig, rows_per_group: Dict[int, int],
    sparse_cfg: OptimizerConfig, exclude: Sequence[str] = (),
) -> Tuple[List[CacheGroup], Tuple[str, ...]]:
    """Group slots by dim (all same-dim slots share one row pool; cross-slot
    sign collisions are handled by the group-level dedup in
    ``CachedEmbeddingTier.prepare_batch``, so a prefix-bit-0 config cannot
    violate the directory's distinct-signs contract).

    Returns ``(groups, ps_slots)``: hash-stack slots (many table keys per
    id — uncacheable by construction) and any ``exclude``d names ride the
    pure worker/PS path inside the same ctx (the mixed-tier arrangement)."""
    unknown = set(exclude) - set(cfg.slots_config)
    if unknown:
        raise KeyError(
            f"exclude names not in embedding config: {sorted(unknown)}"
        )
    by_dim: Dict[int, Tuple[List[str], List[str]]] = {}
    ps_slots: List[str] = []
    for name, slot in cfg.slots_config.items():
        if slot.hash_stack_config.enabled or name in exclude:
            ps_slots.append(name)
            continue
        pooled, raw = by_dim.setdefault(slot.dim, ([], []))
        (pooled if slot.embedding_summation else raw).append(name)
    groups = []
    for dim in sorted(by_dim):
        pooled, raw = by_dim[dim]
        groups.append(
            CacheGroup(
                name=f"cache_d{dim}",
                dim=dim,
                rows=rows_per_group[dim],
                state_dim=sparse_cfg.state_dim(dim),
                pooled_slots=tuple(sorted(pooled)),
                raw_slots=tuple(sorted(raw)),
            )
        )
    return groups, tuple(sorted(ps_slots))


def init_cached_tables(
    groups: Sequence[CacheGroup], sparse_cfg: OptimizerConfig, dtype=jnp.float32
):
    """Zeroed row pools (+1 pad row at index C whose zeros absorb padding
    gathers). Content arrives via checkout scatters; initial values are
    irrelevant except the pad row, which the masked sparse update never
    touches."""
    from persia_tpu.ops.sparse_update import init_sparse_state

    tables, emb_state = {}, {}
    for g in groups:
        tables[g.name] = jnp.zeros((g.rows + 1, g.dim), dtype=dtype)
        emb_state[g.name] = init_sparse_state(sparse_cfg, g.rows + 1, g.dim)
    return tables, emb_state


def _entry_to_state_cols(state: Dict[str, jnp.ndarray], entry_tail):
    """Split the PS entry's state tail (M, state_dim) into sparse_update's
    per-key columns — PS entry layout is [emb | acc] (adagrad) or
    [emb | m | v] (adam), `persia_tpu/embedding/optim.py` init_state /
    update_dense."""
    out = {}
    off = 0
    for key in ("acc", "m", "v"):
        if key in state:
            w = state[key].shape[1]
            out[key] = entry_tail[:, off:off + w]
            off += w
    return out


# ----------------------------------------------------------- device step


def _model_emb_from_gathered(
    groups: Sequence[CacheGroup],
    batch: Dict,
    layout: "CacheLayout",
    stacked_gathered: Dict[str, jnp.ndarray],
    raw_gathered: Dict[str, jnp.ndarray],
    pad_row: Callable[[str], int],
    ps_model_inputs: Optional[List] = None,
):
    """Build the per-slot model input list (global sorted slot order) from
    the per-group stacked gather and per-slot raw gathers. ``pad_row(gname)``
    returns the row index whose gather must be masked out (the zero pad)."""
    slot_emb: Dict[str, object] = {}
    stacked_names = dict(layout.stacked)
    for gname, got in stacked_gathered.items():
        rows = batch["stacked_rows"][gname]  # (S, B, L)
        mask = rows != pad_row(gname)
        m = mask[..., None].astype(got.dtype)
        pooled = (got * m).sum(axis=2)  # (S, B, dim)
        scale = batch.get("stacked_scale", {}).get(gname)
        if scale is not None:
            pooled = pooled * scale[..., None].astype(pooled.dtype)
        for i, name in enumerate(stacked_names[gname]):
            slot_emb[name] = pooled[i]
    for name, got in raw_gathered.items():
        gname = _slot_group_of(groups, name)
        rows = batch["raw_rows"][name]
        slot_emb[name] = (got, rows != pad_row(gname))
    if ps_model_inputs is not None:
        # mixed-tier: worker/PS-served slots join the cached ones in the
        # same globally-sorted slot order the model expects
        for name, emb in zip(layout.ps, ps_model_inputs):
            slot_emb[name] = emb
    return [slot_emb[n] for n in sorted(slot_emb)]


def _slot_group_of(groups: Sequence[CacheGroup], slot: str) -> str:
    for g in groups:
        if slot in g.slots:
            return g.name
    raise KeyError(slot)


@dataclass(frozen=True)
class CacheLayout:
    """Static (hashable) description of which slots a batch carries —
    ``stacked``: ((group, (slot, ...)), ...) in stack order. Passed as a
    static jit argument so slot membership never rides in the traced pytree
    (it changes at most a handful of times per run)."""

    stacked: Tuple[Tuple[str, Tuple[str, ...]], ...]
    # mixed-tier: slot names served by the worker/PS path (hash-stack or
    # explicitly excluded), in the order their entries ride batch["ps_emb"]
    ps: Tuple[str, ...] = ()


# Tiny per-group device ops kept OUT of the main train step so that the
# variable miss/evict counts (pow2-bucketed) only ever recompile these
# trivial programs, never the model fwd/bwd. The main step's shapes are
# fixed per (B, L, slot-layout) and compile exactly once.


from functools import partial as _partial


def _scatter_entry_block(table, state: Dict[str, jnp.ndarray], rows, entries):
    """Shared body: scatter ``[emb | state]`` rows into the cache pools
    (out-of-range pad rows drop)."""
    dim = table.shape[1]
    table = table.at[rows].set(entries[:, :dim].astype(table.dtype), mode="drop")
    out_state = dict(state)
    cols = _entry_to_state_cols(out_state, entries[:, dim:])
    for key, vals in cols.items():
        out_state[key] = out_state[key].at[rows].set(
            vals.astype(out_state[key].dtype), mode="drop"
        )
    return table, out_state


@jax.jit
def _gather_entry_rows(table, state: Dict[str, jnp.ndarray], rows):
    """(K, dim + state_dim) ``[emb | state]`` of the given rows — the
    flush/publish read path (device gather, then ONE bounded d2h)."""
    parts = [table[rows]]
    for key in ("acc", "m", "v"):
        if key in state:
            parts.append(state[key][rows])
    return jnp.concatenate(parts, axis=1)


@_partial(jax.jit, donate_argnums=(0, 1))
def _restore_rows(table, state: Dict[str, jnp.ndarray], payload, src_idx, dst_rows):
    """Re-admit rows whose write-back is still in flight straight from the
    DEVICE-resident eviction payload (the hazard path must never wait on
    the write-back's device→host transfer)."""
    with jax.named_scope("restore"):
        return _scatter_entry_block(table, state, dst_rows, payload[src_idx])


@_partial(jax.jit, donate_argnums=(0, 1), static_argnums=(7, 8))
def _apply_aux(table, state: Dict[str, jnp.ndarray], ev_rows, m_rows,
               m_entries, c_rows, c_emb, state_consts, wb_bf16=False):
    """Fused per-group per-step aux program: read the eviction payload (from
    the PRE-scatter table — a missed row may reuse an evicted one), then
    scatter warm entries and cold seeds. One dispatch instead of three:
    these programs move a few thousand rows each, so the steady-state
    eviction regime pays per CALL, not per byte (a trivial chained dispatch
    measured ~0.19 ms on the v5e, PR 21 chip_smoke — the same before and
    after the process's first d2h). Absent pieces ride as 0-row arrays.

    Compile-cache tradeoff: fusing keys the jit on the COMBINATION of the
    three piece-size buckets (worst case the cross-product, vs the per-piece
    sum for split jits). In practice the regimes are disjoint — fill phase
    is cold-only, steady state is (warm, evict) in one or two stable buckets
    each with cold decaying — so observed combinations stay within a few
    dozen tiny programs."""
    with jax.named_scope("evict_gather"):
        parts = [table[ev_rows]]
        for key in ("acc", "m", "v"):
            if key in state:
                parts.append(state[key][ev_rows])
        payload = jnp.concatenate(parts, axis=1)
        if wb_bf16:
            # bf16 write-back wire (the reference ships f16 lookup/grad wires,
            # lib.rs:157-180): halves the d2h bytes that bound the eviction
            # steady state; opt-in because the default tier is bit-exact
            payload = payload.astype(jnp.bfloat16)
    with jax.named_scope("aux_scatter"):
        table, out_state = _scatter_entry_block(table, state, m_rows, m_entries)
        table = table.at[c_rows].set(c_emb.astype(table.dtype), mode="drop")
        for key, val in state_consts:
            st = out_state[key]
            fill = jnp.full((c_rows.shape[0], st.shape[1]), val, dtype=st.dtype)
            out_state[key] = st.at[c_rows].set(fill, mode="drop")
    return table, out_state, payload


@_partial(jax.jit, donate_argnums=(0, 1, 2), static_argnums=(9, 10))
def _apply_aux_ring(table, state: Dict[str, jnp.ndarray], ring, ring_pos,
                    ev_rows, m_rows, m_entries, c_rows, c_emb, state_consts,
                    wb_bf16=False):
    """``_apply_aux`` + one extra fused write: the eviction payload also
    lands in the group's standing DEVICE ring at ``ring_pos``. The stream's
    hazard restores then gather straight from the ring — ONE
    ``_restore_rows`` per group per step regardless of how many in-flight
    steps' payloads are referenced, where per-payload restores cost one
    degraded-latency dispatch EACH (measured 35 ms/step of a 129 ms wall at
    saturation). The per-step payload array is still returned for the
    write-back thread's bounded d2h fetch."""
    table, out_state, payload = _apply_aux(
        table, state, ev_rows, m_rows, m_entries, c_rows, c_emb,
        state_consts, wb_bf16,
    )
    with jax.named_scope("evict_gather"):
        ring = jax.lax.dynamic_update_slice(
            ring, payload.astype(ring.dtype), (ring_pos, 0)
        )
    return table, out_state, ring, payload


def _state_init_consts(cfg: OptimizerConfig):
    """(key, scalar) pairs for a fresh entry's optimizer-state tail —
    mirrors ``init_sparse_state`` / the PS's ``init_state``."""
    from persia_tpu.embedding.optim import OPTIMIZER_ADAGRAD

    if cfg.kind == OPTIMIZER_ADAGRAD:
        return (("acc", float(cfg.initialization)),)
    if cfg.kind == OPTIMIZER_ADAM:
        return (("m", 0.0), ("v", 0.0))
    return ()


# _bucket lives in hbm_cache.common (leaf module) — re-exported above for
# the step/stream/tier/ctx imports that predate the package split.


