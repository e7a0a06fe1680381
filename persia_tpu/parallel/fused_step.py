"""Fully-fused hybrid train step: embedding tables resident in HBM.

The reference's hot loop crosses process boundaries four times per step
(lookup RPC → h2d → step → d2h → gradient RPC, §3.2/3.3 of SURVEY.md)
because GPU memory cannot hold the tables. On TPU, Criteo-class tables fit
in (pooled) HBM, so the idiomatic fast path keeps them on device and fuses
the ENTIRE hybrid step into one XLA program:

    ids → gather → dense fwd/bwd → optax dense update → duplicate-safe
    sparse optimizer update (persia_tpu.ops.sparse_update)

Host↔device traffic per step collapses to the raw batch (int32 ids + dense
features + labels) in, one scalar loss out — no embedding or gradient ever
crosses the host↔device link. The host C++ PS tier
(`persia_tpu.embedding.native_store`) remains the capacity tier for vocab
that exceeds HBM; `persia_tpu.interop` moves rows between the two tiers.

Sharding: tables are row-sharded over the mesh "data" axis (GSPMD turns the
gathers/scatters into ICI collectives); batch leaves are sharded over "data";
dense params replicated (psum grads). Single-device jit needs no mesh.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax

from persia_tpu.embedding.optim import OptimizerConfig
from persia_tpu.ops.sparse_update import (
    init_sparse_state,
    masked_flat_ids_grads,
    sparse_update,
)
from persia_tpu.parallel.train_step import default_loss_fn
from persia_tpu.tracing import stage_span


@dataclass(frozen=True)
class FusedSlotSpec:
    """One HBM-resident slot (ref: SlotConfig,
    `persia-embedding-config/src/lib.rs:528-560`; LRU/eviction is the host
    tier's job — HBM slots are dense [0, vocab) keyed).

    ``init_method`` (a ``config.InitializationMethod``) selects the init
    distribution (uniform/gamma/poisson/normal/inverse_sqrt — the
    reference's InitializationMethod enum, lib.rs:79-98); ``None`` falls
    back to uniform over ``init_bounds``. HBM tables are dense-keyed and
    seeded from a PRNGKey, so parity with the host tiers' seeded-by-sign
    init is STATISTICAL, not bitwise (the key spaces differ by design)."""

    vocab: int
    dim: int
    pooled: bool = True  # embedding_summation; False → raw (B, L, D) + mask
    sqrt_scaling: bool = False
    init_bounds: Tuple[float, float] = (-0.01, 0.01)
    init_method: "object | None" = None


def _sample_init(key, shape, spec: "FusedSlotSpec", dtype):
    """Draw a table block from the slot's init distribution (traceable)."""
    m = spec.init_method
    if m is None:
        lo, hi = spec.init_bounds
        return jax.random.uniform(key, shape, dtype=dtype, minval=lo, maxval=hi)
    kind = m.kind
    if kind == "uniform":
        return jax.random.uniform(key, shape, dtype=dtype, minval=m.p0, maxval=m.p1)
    if kind == "inverse_sqrt":
        b = 1.0 / float(np.sqrt(shape[-1]))
        return jax.random.uniform(key, shape, dtype=dtype, minval=-b, maxval=b)
    if kind == "normal":
        return (m.p0 + m.p1 * jax.random.normal(key, shape)).astype(dtype)
    if kind == "gamma":
        return (jax.random.gamma(key, m.p0, shape) * m.p1).astype(dtype)
    if kind == "poisson":
        return jax.random.poisson(key, m.p0, shape).astype(dtype)
    raise ValueError(f"unknown init kind: {kind!r}")


@flax.struct.dataclass
class FusedTrainState:
    params: Any
    batch_stats: Any
    opt_state: Any
    tables: Dict[str, jnp.ndarray]
    emb_state: Dict[str, Dict[str, jnp.ndarray]]
    emb_batch_state: jnp.ndarray  # (beta1^t, beta2^t) for Adam
    step: jnp.ndarray


def create_fused_tables(
    rng,
    specs: Dict[str, FusedSlotSpec],
    sparse_cfg: OptimizerConfig,
    dtype=jnp.float32,
):
    """Seeded uniform tables + optimizer state (ref init:
    `emb_entry.rs:28-60` uniform from EmbeddingConfig.emb_initialization)."""
    tables, emb_state = {}, {}
    names = sorted(specs)
    keys = jax.random.split(rng, max(len(names), 1))
    for key, name in zip(keys, names):
        s = specs[name]
        tables[name] = _sample_init(key, (s.vocab, s.dim), s, dtype)
        emb_state[name] = init_sparse_state(sparse_cfg, s.vocab, s.dim)
    return tables, emb_state


def _model_inputs(
    specs: Dict[str, FusedSlotSpec],
    slot_order: Sequence[str],
    gathered: Dict[str, jnp.ndarray],
    ids: Dict[str, jnp.ndarray],
) -> List:
    """Build the per-slot model input list from gathered embeddings —
    pooling happens INSIDE the differentiated function so autodiff routes
    grads back to per-position rows."""
    out = []
    for name in slot_order:
        g = gathered[name]
        if g.ndim == 2:  # single-id slot; -1 padding → zero embedding
            i = ids[name]
            out.append(g * (i >= 0)[..., None].astype(g.dtype))
            continue
        i = ids[name]
        mask = i >= 0
        if specs[name].pooled:
            m = mask[..., None].astype(g.dtype)
            pooled = (g * m).sum(axis=1)
            if specs[name].sqrt_scaling:
                cnt = jnp.maximum(mask.sum(axis=1), 1).astype(pooled.dtype)
                pooled = pooled / jnp.sqrt(cnt)[..., None]
            out.append(pooled)
        else:
            out.append((g, mask))
    return out


def _gather_all(
    tables: Dict[str, jnp.ndarray], ids: Dict[str, jnp.ndarray]
) -> Dict[str, jnp.ndarray]:
    out = {}
    for name, i in ids.items():
        safe = jnp.where(i >= 0, i, 0).astype(jnp.int32)
        out[name] = jnp.take(tables[name], safe, axis=0)
    return out


# ---------------------------------------------------------------------------
# Stacked tables: all same-dim slots share one physical (sum(vocab), dim)
# table with per-slot row offsets, so the step issues ONE gather and ONE
# sparse-update scatter per dim-group instead of one per slot. This is the
# HBM analogue of the reference's single global key space partitioned by
# per-slot index prefixes (`embedding_worker_service/mod.rs:403-429`,
# `persia-embedding-config/src/lib.rs:600-650`) — offsets play the role of
# index prefixes.
# ---------------------------------------------------------------------------

_INT32_MAX = np.iinfo(np.int32).max


@dataclass(frozen=True)
class StackGroup:
    """One physical stacked table covering several same-dim slots."""

    name: str
    slots: Tuple[str, ...]
    offsets: Tuple[int, ...]  # row offset of each slot, aligned with ``slots``
    vocab: int
    dim: int


def group_stacked_specs(
    specs: Dict[str, FusedSlotSpec], slot_order: Sequence[str]
) -> List[StackGroup]:
    """Deterministically group slots by dim into stacked tables (splitting a
    group if its total rows would overflow int32 ids)."""
    by_dim: Dict[int, List[str]] = {}
    for name in slot_order:
        by_dim.setdefault(specs[name].dim, []).append(name)
    groups = []
    for dim in sorted(by_dim):
        names, offsets, total = [], [], 0
        part = 0
        for name in by_dim[dim]:
            v = specs[name].vocab
            if total + v > _INT32_MAX and names:
                groups.append(
                    StackGroup(f"__stack_d{dim}_{part}", tuple(names), tuple(offsets), total, dim)
                )
                names, offsets, total = [], [], 0
                part += 1
            names.append(name)
            offsets.append(total)
            total += v
        groups.append(
            StackGroup(f"__stack_d{dim}_{part}", tuple(names), tuple(offsets), total, dim)
        )
    return groups


def create_stacked_tables(
    rng,
    specs: Dict[str, FusedSlotSpec],
    groups: Sequence[StackGroup],
    sparse_cfg: OptimizerConfig,
    dtype=jnp.float32,
):
    """Stacked tables with each slot's row range drawing from its own
    init_bounds (ref init: `emb_entry.rs:28-60`).

    Filled one slot at a time into a donated group table (peak HBM = full
    table + one slot's rows, not 2x the table as a concat of parts would be
    — stacking exists precisely for the multi-GB case)."""
    tables, emb_state = {}, {}
    # key assignment matches create_fused_tables (sorted slot name) so a
    # given slot's seeded init is layout-independent
    all_names = sorted(n for g in groups for n in g.slots)
    keys = dict(zip(all_names, jax.random.split(rng, max(len(all_names), 1))))

    @partial(jax.jit, static_argnames=("shape", "spec"), donate_argnums=(0,))
    def _fill(tbl, key, off, shape, spec):
        part = _sample_init(key, shape, spec, tbl.dtype)
        return jax.lax.dynamic_update_slice(tbl, part, (off, 0))

    for g in groups:
        tbl = jnp.zeros((g.vocab, g.dim), dtype=dtype)
        for name, off in zip(g.slots, g.offsets):
            s = specs[name]
            tbl = _fill(tbl, keys[name], jnp.int32(off), (s.vocab, s.dim), s)
        tables[g.name] = tbl
        emb_state[g.name] = init_sparse_state(sparse_cfg, g.vocab, g.dim)
    return tables, emb_state


def _gather_all_stacked(
    tables: Dict[str, jnp.ndarray],
    ids: Dict[str, jnp.ndarray],
    groups: Sequence[StackGroup],
) -> Dict[str, jnp.ndarray]:
    """One ``take`` per dim-group; per-slot views are cheap slices.

    Ids are clamped to the slot's own [0, vocab) range before the offset is
    applied, matching the unstacked path's XLA gather-clamp semantics — an
    out-of-range id must never read a neighboring slot's rows."""
    out = {}
    for g in groups:
        parts = []
        ends = list(g.offsets[1:]) + [g.vocab]
        for name, off, end in zip(g.slots, g.offsets, ends):
            i = ids[name]
            clamped = jnp.minimum(i, end - off - 1)
            parts.append(jnp.where(i >= 0, clamped + off, 0).reshape(-1).astype(jnp.int32))
        flat = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        rows = jnp.take(tables[g.name], flat, axis=0)  # (sum(B·L), dim)
        pos = 0
        for name in g.slots:
            shape = ids[name].shape
            k = int(np.prod(shape))
            out[name] = jax.lax.slice(rows, (pos, 0), (pos + k, g.dim)).reshape(
                shape + (g.dim,)
            )
            pos += k
    return out


def stacked_slot_table(
    tables: Dict[str, jnp.ndarray], groups: Sequence[StackGroup], name: str
) -> jnp.ndarray:
    """Per-slot (vocab, dim) view of a stacked table (for checkpoints/tests)."""
    for g in groups:
        if name in g.slots:
            i = g.slots.index(name)
            end = g.offsets[i + 1] if i + 1 < len(g.slots) else g.vocab
            return tables[g.name][g.offsets[i]:end]
    raise KeyError(name)


def init_fused_state(
    model,
    rng,
    specs: Dict[str, FusedSlotSpec],
    sample_batch: Dict,
    dense_optimizer: optax.GradientTransformation,
    sparse_cfg: OptimizerConfig,
    slot_order: Optional[Sequence[str]] = None,
    stack: bool = False,
    table_dtype=jnp.float32,
) -> FusedTrainState:
    slot_order = list(slot_order or sorted(specs))
    rng_tbl, rng_model = jax.random.split(rng)
    if stack:
        groups = group_stacked_specs(specs, slot_order)
        tables, emb_state = create_stacked_tables(
            rng_tbl, specs, groups, sparse_cfg, dtype=table_dtype
        )
        gathered = _gather_all_stacked(tables, sample_batch["ids"], groups)
    else:
        tables, emb_state = create_fused_tables(rng_tbl, specs, sparse_cfg, dtype=table_dtype)
        gathered = _gather_all(tables, sample_batch["ids"])
    ids = sample_batch["ids"]
    model_emb = _model_inputs(specs, slot_order, gathered, ids)
    del gathered
    variables = model.init(rng_model, sample_batch["dense"], model_emb, train=False)
    params = variables["params"]
    return FusedTrainState(
        params=params,
        batch_stats=variables.get("batch_stats", {}),
        opt_state=dense_optimizer.init(params),
        tables=tables,
        emb_state=emb_state,
        emb_batch_state=jnp.ones((2,), dtype=jnp.float32),
        step=jnp.zeros((), dtype=jnp.int32),
    )


def build_fused_train_step(
    model,
    dense_optimizer: optax.GradientTransformation,
    sparse_cfg: OptimizerConfig,
    specs: Dict[str, FusedSlotSpec],
    slot_order: Optional[Sequence[str]] = None,
    loss_fn=default_loss_fn,
    donate: bool = True,
    jit: bool = True,
    stack: bool = False,
):
    """Returns jitted ``step(state, batch) -> (state, (loss, preds))``.

    batch = {"dense": [(B,F) f32...], "labels": [(B,1) f32...],
             "ids": {slot: (B,) or (B,L) int32, -1 = padding}}.
    ``donate=True`` donates the state buffers so multi-GB tables update
    in place instead of being copied each step. ``jit=False`` returns the
    raw traceable step for callers that wrap it (packed-I/O benches,
    shard_map composition). ``stack=True`` expects state built with
    ``init_fused_state(stack=True)``: same-dim slots share one physical
    table, so the step runs one gather + one sparse-update per dim-group
    instead of one per slot.
    """
    slot_order = list(slot_order or sorted(specs))
    groups = group_stacked_specs(specs, slot_order) if stack else None
    # a model may state its own loss, over all of the batch's labels, and what
    # a step hands back beside it (``models/sdar_moe.py``); the click models
    # state neither and keep ``loss_fn`` on the first label and the sigmoid.
    # A model whose logits must never stand whole states ``train_loss``
    # (``models/mellum_moe.py``): loss, outputs and counters in one call
    model_loss = getattr(model, "loss", None)
    model_outputs = getattr(model, "outputs", jax.nn.sigmoid)
    model_train_loss = getattr(model, "train_loss", None)

    def step(state: FusedTrainState, batch: Dict):
        ids = batch["ids"]
        # the named scopes are the cached step's (hbm_cache/step.py): one
        # table of device time by scope compares the two
        with jax.named_scope("gather"):
            gathered = (
                _gather_all_stacked(state.tables, ids, groups)
                if stack
                else _gather_all(state.tables, ids)
            )

        def loss_wrapper(params, gathered):
            with jax.named_scope("pool"):
                model_emb = _model_inputs(specs, slot_order, gathered, ids)
            variables = {"params": params}
            if model_train_loss is not None:
                if state.batch_stats:
                    variables["batch_stats"] = state.batch_stats
                loss, outs, new_stats = model_train_loss(
                    variables, batch["dense"], model_emb, batch["labels"])
                return loss, (outs, new_stats if state.batch_stats else state.batch_stats)
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
                if model_loss is not None:
                    loss = model_loss(logits, batch["labels"])
                else:
                    loss = loss_fn(logits, batch["labels"][0])
            return loss, (logits, new_stats)

        (loss, (logits, new_stats)), (param_grads, emb_grads) = jax.value_and_grad(
            loss_wrapper, argnums=(0, 1), has_aux=True
        )(state.params, gathered)

        with jax.named_scope("dense_opt"):
            updates, new_opt_state = dense_optimizer.update(
                param_grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)

        batch_state = state.emb_batch_state * jnp.array(
            [sparse_cfg.beta1, sparse_cfg.beta2], dtype=jnp.float32
        )
        new_tables, new_emb_state = {}, {}
        if stack:
            for grp in groups:
                idp, gp, mp = [], [], []
                with jax.named_scope("sparse_prep"):
                    for name, off in zip(grp.slots, grp.offsets):
                        i = ids[name]
                        # ids outside the slot's own [0, vocab) are masked
                        # out, matching the unstacked scatter's mode="drop"
                        # — they must not write a neighboring slot's rows
                        in_range = (i >= 0) & (i < specs[name].vocab)
                        fi, fg, fm = masked_flat_ids_grads(
                            jnp.where(in_range, i + off, -1),
                            emb_grads[name].astype(jnp.float32),
                        )
                        idp.append(fi)
                        gp.append(fg)
                        mp.append(fm)
                    flat_ids = jnp.concatenate(idp) if len(idp) > 1 else idp[0]
                    flat_g = jnp.concatenate(gp) if len(gp) > 1 else gp[0]
                    flat_mask = jnp.concatenate(mp) if len(mp) > 1 else mp[0]
                new_tables[grp.name], new_emb_state[grp.name] = sparse_update(
                    sparse_cfg,
                    state.tables[grp.name],
                    state.emb_state[grp.name],
                    flat_ids,
                    flat_g,
                    batch_state,
                    mask=flat_mask,
                )
        else:
            for name in slot_order:
                with jax.named_scope("sparse_prep"):
                    g = emb_grads[name].astype(jnp.float32)
                    flat_ids, flat_g, flat_mask = masked_flat_ids_grads(ids[name], g)
                new_tables[name], new_emb_state[name] = sparse_update(
                    sparse_cfg,
                    state.tables[name],
                    state.emb_state[name],
                    flat_ids,
                    flat_g,
                    batch_state,
                    mask=flat_mask,
                )

        new_state = FusedTrainState(
            params=new_params,
            batch_stats=new_stats,
            opt_state=new_opt_state,
            tables=new_tables,
            emb_state=new_emb_state,
            emb_batch_state=batch_state,
            step=state.step + 1,
        )
        return new_state, (loss, logits if model_train_loss is not None else model_outputs(logits))

    if not jit:
        return step
    return jax.jit(step, donate_argnums=(0,) if donate else ())


def build_fused_multi_step(
    model,
    dense_optimizer: optax.GradientTransformation,
    sparse_cfg: OptimizerConfig,
    specs: Dict[str, FusedSlotSpec],
    k: int,
    slot_order: Optional[Sequence[str]] = None,
    loss_fn=default_loss_fn,
    stack: bool = False,
):
    """K-step fused dispatch for the all-in-HBM path: ONE jitted program
    advances ``k`` consecutive batches — ``multi(state, batches) -> (state,
    (losses, preds_list))`` with ``batches`` a length-``k`` tuple of the
    single-step batch dict. The per-dispatch Python/header overhead that
    bounds small-step-time loops is paid once per K steps; the
    math is the single-step program iterated, so parity with
    ``build_fused_train_step`` is exact in program terms — but NOT bitwise:
    XLA compiles the step subgraph differently inside the larger program
    (cross-step/cluster fusion reorders float ops at the ~1 ulp level, and
    ``optimization_barrier`` between steps does not recover the standalone
    bits). Callers needing bit parity with the single-step loop must use
    k=1. The cached tier's stream applies the same idea to its hazard-free
    windows (hbm_cache/stream.py ``dispatch_k``) — there the K program IS
    bit-exact (pinned by test_stream_kstep_packing_bitwise_parity)."""
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    raw = build_fused_train_step(
        model, dense_optimizer, sparse_cfg, specs, slot_order,
        loss_fn=loss_fn, jit=False, stack=stack,
    )

    def multi(state: FusedTrainState, batches):
        losses, preds = [], []
        for b in batches:
            state, (loss, p) = raw(state, b)
            losses.append(loss)
            preds.append(p)
        return state, (jnp.stack(losses), preds)

    return jax.jit(multi, donate_argnums=(0,))


def build_fused_eval_step(model, specs, slot_order=None, stack: bool = False):
    slot_order = list(slot_order or sorted(specs))
    groups = group_stacked_specs(specs, slot_order) if stack else None

    def eval_step(state: FusedTrainState, batch: Dict):
        ids = batch["ids"]
        gathered = (
            _gather_all_stacked(state.tables, ids, groups)
            if stack
            else _gather_all(state.tables, ids)
        )
        model_emb = _model_inputs(specs, slot_order, gathered, ids)
        variables = {"params": state.params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        logits = model.apply(variables, batch["dense"], model_emb, train=False)
        return getattr(model, "outputs", jax.nn.sigmoid)(logits)

    return jax.jit(eval_step)


def shard_fused_state(state: FusedTrainState, mesh, table_axis: str = "data"):
    """Place tables row-sharded over ``table_axis`` and everything else
    replicated; GSPMD then partitions the step's gathers/scatters into ICI
    collectives (the TPU analogue of the reference's farmhash row sharding
    across PS replicas, `embedding_worker_service/mod.rs:342-345`)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P(table_axis, None))

    def place_tbl(x):
        return jax.device_put(x, row if x.shape[0] % mesh.shape[table_axis] == 0 else rep)

    return FusedTrainState(
        params=jax.tree.map(lambda x: jax.device_put(x, rep), state.params),
        batch_stats=jax.tree.map(lambda x: jax.device_put(x, rep), state.batch_stats),
        opt_state=jax.tree.map(lambda x: jax.device_put(x, rep), state.opt_state),
        tables={k: place_tbl(v) for k, v in state.tables.items()},
        emb_state={
            k: {sk: place_tbl(sv) for sk, sv in st.items()}
            for k, st in state.emb_state.items()
        },
        emb_batch_state=jax.device_put(state.emb_batch_state, rep),
        step=jax.device_put(state.step, rep),
    )


def pack_ids(ids_np: Dict[str, np.ndarray], slot_order: Sequence[str]):
    """Host-side helper: one contiguous int32 buffer for all slots' ids so
    staging is a single host→device transfer (one put per step instead of
    one per slot)."""
    flat = np.concatenate(
        [np.ascontiguousarray(ids_np[n], dtype=np.int32).reshape(-1) for n in slot_order]
    )
    shapes = [ids_np[n].shape for n in slot_order]
    return flat, shapes


def unpack_ids(flat_dev: jnp.ndarray, slot_order: Sequence[str], shapes) -> Dict[str, jnp.ndarray]:
    out = {}
    off = 0
    for name, shape in zip(slot_order, shapes):
        k = int(np.prod(shape))
        out[name] = jax.lax.slice(flat_dev, (off,), (off + k,)).reshape(shape)
        off += k
    return out


class FusedPipeline:
    """Stage-pipelined driver for the fused tier: a feeder thread runs the
    FEED stage (host batch conversion + h2d staging, double-buffered up to
    ``depth`` in flight) while the caller's thread runs the DENSE stage
    (the jitted single- or K-step program). Every table row is HBM-resident
    and the sparse update is fused INTO the dense program, so there are no
    feed/gradient hazards — a semaphore of ``depth`` permits only bounds
    how many staged batches (and therefore how much staging HBM) ride
    ahead of the dense stage. Batches enter the program in stream order,
    so with ``k == 1`` the result is the sequential ``step`` loop's bit
    for bit (pinned by test_stage_graph.py); ``k > 1`` packs the dense
    stage via ``build_fused_multi_step``, whose parity is numerical, not
    bitwise (see its docstring) — same trade as calling that program
    directly.

    ``run`` returns with every staged batch dispatched — callers may
    checkpoint (``FusedTrainCtx.dump_checkpoint``) immediately after with
    fence semantics. When the caller's dispatch raises, the feeder thread
    gives up within its poll interval and ``run`` re-raises.
    """

    def __init__(self, step, multi=None, depth: int = 2, k: int = 1):
        from persia_tpu.parallel.stage_graph import StageGraph

        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if k > 1 and multi is None:
            raise ValueError("k > 1 needs the multi-step program")
        self._step = step
        self._multi = multi
        self.depth = int(depth)
        # a full pack must fit in the window or feed and dense deadlock
        # waiting on each other
        self.k = max(1, min(int(k), self.depth))
        self.graph = StageGraph()

    def run(self, state, batches, stage=None):
        """Drive ``batches`` (iterable of fused batch dicts — or anything
        ``stage`` maps to one) through the pipeline. The iterable is
        consumed by the FEED thread, so host-side conversion inside a
        generator rides the feed lane too. Returns ``(state, losses)``
        with ``losses`` the per-step device scalars in stream order;
        :meth:`stats` reports overlap after the run."""
        import queue as _queue
        import threading
        import time as _time

        stage = jax.device_put if stage is None else stage
        graph = self.graph
        # at most ``depth`` batches between ``stage`` and their dispatch:
        # the feeder takes a permit before staging, the dispatcher hands
        # back a pack's permits once it went out. The queue itself is
        # unbounded (the permits are its bound), so the feeder's only park
        # is the permit wait, which polls ``abort``
        window = threading.Semaphore(self.depth)
        abort = threading.Event()
        q: "_queue.Queue" = _queue.Queue()
        errors: List[BaseException] = []
        SENTINEL = object()

        def admit() -> bool:
            while not abort.is_set():
                if window.acquire(timeout=0.05):
                    return True
            return False

        def feeder():
            try:
                for seq, b in enumerate(batches):
                    if not admit():
                        break
                    with graph.lane("feed"), stage_span("fused.stage", seq=seq):
                        staged = stage(b)
                    q.put((seq, staged))
            except BaseException as e:  # noqa: BLE001 — reraised on the caller
                errors.append(e)
            finally:
                q.put(SENTINEL)

        t0 = _time.perf_counter()
        th = threading.Thread(target=feeder, name="fused-pipe-feeder", daemon=True)
        th.start()
        losses: List[jnp.ndarray] = []
        pack: List[Tuple[int, Dict]] = []
        try:
            def flush():
                nonlocal state
                if not pack:
                    return
                seq = pack[0][0]
                if len(pack) > 1:
                    with self.graph.lane("dense", k=len(pack)), stage_span(
                        "fused.dispatch", seq=seq, k=len(pack)
                    ):
                        state, (ls, _preds) = self._multi(
                            state, tuple(b for _, b in pack)
                        )
                    losses.extend(ls[i] for i in range(len(pack)))
                else:
                    with self.graph.lane("dense"), stage_span("fused.dispatch", seq=seq):
                        state, (loss, _preds) = self._step(state, pack[0][1])
                    losses.append(loss)
                window.release(len(pack))
                pack.clear()

            while True:
                item = q.get()
                if item is SENTINEL:
                    break
                pack.append(item)
                if len(pack) >= self.k:
                    flush()
            flush()
            if errors:
                raise errors[0]
        finally:
            abort.set()
            th.join(timeout=5.0)
        self._wall_s = _time.perf_counter() - t0
        return state, losses

    def stats(self) -> Dict:
        """Lane stats of the last :meth:`run` (``StageGraph.stats``) plus
        the run's wall seconds."""
        out = self.graph.stats(getattr(self, "_wall_s", 0.0))
        out["wall_s"] = round(getattr(self, "_wall_s", 0.0), 6)
        return out


def build_fused_pipeline(
    model,
    dense_optimizer: optax.GradientTransformation,
    sparse_cfg: OptimizerConfig,
    specs: Dict[str, FusedSlotSpec],
    slot_order: Optional[Sequence[str]] = None,
    loss_fn=default_loss_fn,
    stack: bool = False,
    depth: int = 2,
    k: int = 1,
) -> FusedPipeline:
    """Convenience factory: builds the jitted single-step (and, when
    ``k > 1``, the K-step) program and wraps them in a
    :class:`FusedPipeline`. Reuse the returned pipeline across runs — each
    factory call retraces."""
    step = build_fused_train_step(
        model, dense_optimizer, sparse_cfg, specs, slot_order,
        loss_fn=loss_fn, stack=stack,
    )
    multi = None
    if k > 1:
        multi = build_fused_multi_step(
            model, dense_optimizer, sparse_cfg, specs, min(k, depth),
            slot_order, loss_fn=loss_fn, stack=stack,
        )
    return FusedPipeline(step, multi, depth=depth, k=k)
