"""Sharded train/eval steps with embedding-gradient return.

This is the TPU-native heart of the hybrid trainer. The reference's NN worker
runs torch forward/backward with DDP allreduce and scatters gradients back to
sparse tensors with ``index_add_`` (`persia/ctx.py:893-1005`). Here the whole
step — dense forward, loss, backward, dense-optimizer update, and the
embedding-input gradients — is ONE jitted XLA program:

- batch leaves are sharded over the mesh ``data`` axis; parameters are
  replicated, so XLA inserts the ICI psum for dense grads (replacing NCCL).
- raw (sequence) slots enter as (distinct_rows, index, mask); the gather
  ``distinct[index]`` happens inside the differentiated function, so autodiff
  produces the scatter-add back onto distinct rows (replacing torch
  index_add_, ref ctx.py:968-982) as an XLA scatter that is itself psum'd
  across the mesh.
- the returned per-slot embedding gradients go back to the embedding-worker
  tier (`EmbeddingWorker.update_gradient_batched`).

Batch pytree convention (built by ``persia_tpu.ctx.EmbeddingCtx.prepare_features``):

    batch = {
      "dense":  [ (B, F) f32/bf16 ... ],
      "labels": [ (B, 1) f32 ... ],
      "emb":    [ {"pooled": (B, D)}                                  # sum slot
                  | {"distinct": (P, D), "index": (B,L) i32,
                     "mask": (B,L) bool} ... ],                       # raw slot
    }
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax

from persia_tpu.parallel.mesh import batch_sharding, replicated


@flax.struct.dataclass
class LossScaleState:
    """Dynamic mixed-precision loss scaling (ref: the GradScaler management
    in persia/ctx.py:926-1005 — finite checks, skip-step on overflow, scale
    backoff/growth). On TPU the finite check is a fused on-device reduction,
    so it runs every step instead of every Nth."""

    scale: jnp.ndarray  # f32 scalar
    good_steps: jnp.ndarray  # i32 scalar


@flax.struct.dataclass
class TrainState:
    params: Any
    batch_stats: Any
    opt_state: Any
    step: jnp.ndarray
    loss_scale: Optional[LossScaleState] = None


def _embedding_model_inputs(emb_diff: List, emb_static: List) -> List:
    """Rebuild per-slot model inputs from (differentiable, static) halves."""
    out = []
    for diff, static in zip(emb_diff, emb_static):
        if static is None:  # pooled slot: diff IS the (B, dim) array
            out.append(diff)
        elif len(static) == 3:  # ("pool", index, counts) — raw statics are
            # 2-tuples; don't compare static[0] to a string (it may be a
            # numpy index array, where == broadcasts)
            # device-pooled sum slot: gather + sum (+ sqrt scaling) inside
            # the diff'ed function, so autodiff returns per-DISTINCT
            # gradients — the TPU-side replacement for worker sum pooling
            # (mod.rs:486-629); index pads point at zero rows past D
            _, index, pool_counts = static
            if index.dtype != jnp.int32:  # uint16 wire → device-side cast
                index = index.astype(jnp.int32)
            # accumulate in f32 even on a bf16 wire (the host pool summed
            # in f32 too); (B, L, dim) → (B, dim)
            pooled = diff[index].astype(jnp.float32).sum(axis=1)
            if pool_counts is not None:
                scale = jax.lax.rsqrt(
                    jnp.maximum(pool_counts[:, 0], 1).astype(jnp.float32)
                )
                pooled = pooled * scale[:, None]
            out.append(pooled)
        else:  # raw slot: gather inside the diff'ed function → autodiff scatter
            index, mask = static
            gathered = diff[index]  # (B, L, dim)
            out.append((gathered, mask))
    return out


def _split_emb(emb: List[Dict]) -> Tuple[List, List]:
    diff, static = [], []
    for e in emb:
        if "pooled" in e:
            diff.append(e["pooled"])
            static.append(None)
        elif "pool_index" in e:
            diff.append(e["distinct"])
            static.append(("pool", e["pool_index"], e.get("pool_counts")))
        else:
            diff.append(e["distinct"])
            static.append((e["index"], e["mask"]))
    return diff, static


def default_loss_fn(logits, labels):
    """Binary cross-entropy with logits (the reference example's BCELoss +
    in-model sigmoid, done the numerically stable way)."""
    return optax.sigmoid_binary_cross_entropy(logits, labels).mean()


def init_train_state(
    model,
    rng,
    sample_batch: Dict,
    optimizer: optax.GradientTransformation,
    loss_scale_init: Optional[float] = None,
) -> TrainState:
    emb_diff, emb_static = _split_emb(sample_batch["emb"])
    model_emb = _embedding_model_inputs(emb_diff, emb_static)
    variables = model.init(rng, sample_batch["dense"], model_emb, train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    return TrainState(
        params=params,
        batch_stats=batch_stats,
        opt_state=optimizer.init(params),
        step=jnp.zeros((), dtype=jnp.int32),
        loss_scale=(
            None
            if loss_scale_init is None
            else LossScaleState(
                scale=jnp.asarray(loss_scale_init, dtype=jnp.float32),
                good_steps=jnp.zeros((), dtype=jnp.int32),
            )
        ),
    )


def build_train_step(
    model,
    optimizer: optax.GradientTransformation,
    loss_fn: Callable = default_loss_fn,
    dynamic_loss_scale: bool = False,
    growth_interval: int = 2000,
    growth_factor: float = 2.0,
    backoff_factor: float = 0.5,
    max_scale: float = float(2 ** 24),
):
    """Returns jitted ``step(state, batch) -> (state, (header, gpacked))``.

    ``header`` is a small f32 array [loss | preds] — the cheap synchronous
    fetch (with ``dynamic_loss_scale``: [loss | scale_used | finite |
    preds]). ``gpacked`` is ONE flat array [emb_grad_0 | ...] in the
    embedding wire dtype (bf16 halves device→host bytes, matching the
    reference's f16 gradient wire) — the bulk transfer, fetched
    asynchronously by the BackwardEngine so it overlaps the next step
    (one fetch per step instead of one round-trip per array).
    ``unpack_step_output`` splits them
    using shapes derived from the batch. Emb grads align with
    ``batch['emb']``: (B, dim) for pooled slots, (P, dim) for raw slots
    (rows past the true distinct count are zero — the host slices them off
    before shipping to the worker).

    ``dynamic_loss_scale`` (ref: GradScaler management, persia/ctx.py:926-
    1005): the loss is multiplied by the running scale before backward; an
    on-device finite check over ALL gradients decides whether the dense
    update applies (overflow → skip step, scale *= backoff) and the scale
    grows by ``growth_factor`` after ``growth_interval`` consecutive finite
    steps. Embedding gradients ship SCALED; the header carries the scale so
    the worker's ``scale_factor`` division unscales them (non-finite slots
    are NaN-skipped there, mod.rs:716-744).
    """

    def step(state: TrainState, batch: Dict):
        emb_diff, emb_static = _split_emb(batch["emb"])
        scale = (
            state.loss_scale.scale
            if dynamic_loss_scale
            else jnp.asarray(1.0, jnp.float32)
        )

        def loss_wrapper(params, emb_diff):
            model_emb = _embedding_model_inputs(emb_diff, emb_static)
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
            loss = loss_fn(logits, batch["labels"][0])
            return loss * scale.astype(loss.dtype), (loss, logits, new_stats)

        (_, (loss, logits, new_stats)), (param_grads, emb_grads) = jax.value_and_grad(
            loss_wrapper, argnums=(0, 1), has_aux=True
        )(state.params, emb_diff)

        if dynamic_loss_scale:
            leaves = jax.tree.leaves(param_grads) + jax.tree.leaves(emb_grads)
            finite = jnp.all(
                jnp.stack([jnp.all(jnp.isfinite(g)) for g in leaves])
            )
            inv = jnp.where(finite, 1.0 / scale, 0.0).astype(jnp.float32)
            # unscale for the dense update; overflow zeros the grads and the
            # select below keeps params/opt_state untouched (skip-step)
            param_grads = jax.tree.map(
                lambda g: (g.astype(jnp.float32) * inv).astype(g.dtype), param_grads
            )
        else:
            finite = jnp.asarray(True)

        updates, opt_state_candidate = optimizer.update(
            param_grads, state.opt_state, state.params
        )
        params_candidate = optax.apply_updates(state.params, updates)
        if dynamic_loss_scale:
            new_params = jax.tree.map(
                lambda new, old: jnp.where(finite, new, old),
                params_candidate, state.params,
            )
            new_opt_state = jax.tree.map(
                lambda new, old: jnp.where(finite, new, old),
                opt_state_candidate, state.opt_state,
            )
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
        else:
            new_params, new_opt_state, new_ls = (
                params_candidate, opt_state_candidate, state.loss_scale,
            )
        new_state = TrainState(
            params=new_params,
            batch_stats=new_stats,
            opt_state=new_opt_state,
            step=state.step + 1,
            loss_scale=new_ls,
        )
        preds = jax.nn.sigmoid(logits)
        # Header (loss|preds) stays exact f32 — the cheap sync fetch; emb
        # grads ride the wire dtype in their own buffer so the bulk transfer
        # can be fetched asynchronously off the critical path.
        head = [jnp.reshape(loss, (1,)).astype(jnp.float32)]
        if dynamic_loss_scale:
            head.append(jnp.reshape(scale, (1,)).astype(jnp.float32))
            head.append(jnp.reshape(finite, (1,)).astype(jnp.float32))
        head.append(jnp.reshape(preds, (-1,)).astype(jnp.float32))
        header = jnp.concatenate(head)
        gflat = [jnp.reshape(g, (-1,)) for g in emb_grads]
        gpacked = jnp.concatenate(gflat) if gflat else jnp.zeros((0,), jnp.float32)
        return new_state, (header, gpacked)

    return jax.jit(step)


def _note_nonfinite_loss(loss: float) -> float:
    """Finite-guard on every host loss consumption: a NaN/Inf loss bumps
    the health counter + flight recorder instead of flowing silently into
    metrics/telemetry consumers."""
    if not np.isfinite(loss):
        from persia_tpu.metrics import get_metrics
        from persia_tpu.tracing import record_event

        get_metrics().counter(
            "persia_tpu_health_nonfinite_loss",
            "non-finite loss scalars observed at header decode",
        ).inc()
        record_event("health.anomaly", cause="nonfinite_loss", loss=repr(loss))
    return loss


def unpack_step_header(header: np.ndarray, batch: Dict):
    """Host view of the step's small output: (loss, preds). A sentinel
    probe tail (if any) rides after the preds and is ignored here — use
    :func:`unpack_step_probe` for it."""
    labels = batch["labels"][0]
    loss = _note_nonfinite_loss(float(header[0]))
    n = int(np.prod(labels.shape))
    preds = header[1:1 + n].reshape(labels.shape)
    return loss, preds


def unpack_step_header_dynamic(header: np.ndarray, batch: Dict):
    """Header view for a ``dynamic_loss_scale`` step:
    (loss, preds, scale_used, grads_finite)."""
    labels = batch["labels"][0]
    loss = _note_nonfinite_loss(float(header[0]))
    scale = float(header[1])
    finite = bool(header[2] > 0.5)
    n = int(np.prod(labels.shape))
    preds = header[3:3 + n].reshape(labels.shape)
    return loss, preds, scale, finite


def probe_tail_len(n_groups: int) -> int:
    """Floats appended to the header by ``sentinel_probe=True``:
    [dense_gnorm, group_gnorm x n_groups, ps_gnorm, finite, clipped]."""
    return n_groups + 4


def unpack_step_probe(
    header: np.ndarray, n_labels: int, n_groups: int, dynamic: bool = False
) -> Dict:
    """Decode the sentinel probe tail from a step header.

    All norms are unscaled (loss-scale divided out on device) and
    pre-clip; ``finite`` is the device-side skip gate, ``clipped``
    whether ``guard_clip_norm`` rescaled the update.
    """
    base = (3 if dynamic else 1) + int(n_labels)
    tail = np.asarray(header[base:base + probe_tail_len(n_groups)], np.float32)
    if tail.shape[0] != probe_tail_len(n_groups):
        raise ValueError(
            f"header carries no probe tail (got {tail.shape[0]} floats, "
            f"want {probe_tail_len(n_groups)}) — was the step built with "
            "sentinel_probe=True?"
        )
    dense = float(tail[0])
    groups = [float(v) for v in tail[1:1 + n_groups]]
    ps = float(tail[1 + n_groups])
    total = float(np.sqrt(dense * dense + ps * ps + sum(g * g for g in groups)))
    return {
        "dense_gnorm": dense,
        "group_gnorms": groups,
        "ps_gnorm": ps,
        "total_gnorm": total,
        "finite": float(tail[1 + n_groups + 1]),
        "clipped": float(tail[1 + n_groups + 2]),
    }


def unpack_step_grads(gpacked: np.ndarray, batch: Dict) -> List[np.ndarray]:
    """Split the bulk gradient buffer into per-slot arrays (shapes come from
    the same ``batch`` the step consumed; ``gpacked`` must already be host
    memory)."""
    grads = []
    off = 0
    for e in batch["emb"]:
        shape = e["pooled"].shape if "pooled" in e else e["distinct"].shape
        k = int(np.prod(shape))
        grads.append(np.ascontiguousarray(gpacked[off:off + k]).reshape(shape))
        off += k
    return grads


def unpack_step_output(header: np.ndarray, gpacked: np.ndarray, batch: Dict):
    """(loss, preds, emb_grads) from the step's two output buffers."""
    loss, preds = unpack_step_header(header, batch)
    return loss, preds, unpack_step_grads(gpacked, batch)


def build_eval_step(model):
    """Returns jitted ``eval_step(state, batch) -> preds`` (running-average
    batch norm, no mutation)."""

    def eval_step(state: TrainState, batch: Dict):
        emb_diff, emb_static = _split_emb(batch["emb"])
        model_emb = _embedding_model_inputs(emb_diff, emb_static)
        variables = {"params": state.params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        logits = model.apply(variables, batch["dense"], model_emb, train=False)
        return jax.nn.sigmoid(logits)

    return jax.jit(eval_step)


def _packed_put(batch: Dict) -> Dict:
    """Single-chip fast path: ship every float embedding leaf in ONE
    device_put (host-side concat, device-side lazy slices): one transfer per
    step instead of one host→device round-trip per leaf."""
    out: Dict = {
        "dense": [jnp.asarray(x) for x in batch["dense"]],
        "labels": [jnp.asarray(x) for x in batch["labels"]],
        "emb": [],
    }
    def _is_float(a) -> bool:
        d = np.asarray(a).dtype
        return np.issubdtype(d, np.floating) or d.name == "bfloat16"

    float_leaves = []  # (entry_idx, key, shape, size)
    entries: List[Dict] = [dict() for _ in batch["emb"]]
    for i, e in enumerate(batch["emb"]):
        for key, val in e.items():
            if _is_float(val):
                float_leaves.append((i, key, val.shape, val.size))
            else:
                entries[i][key] = jnp.asarray(val)
    if float_leaves:
        dt = batch["emb"][float_leaves[0][0]][float_leaves[0][1]].dtype
        flat = np.concatenate(
            [np.ascontiguousarray(batch["emb"][i][k]).reshape(-1)
             for i, k, _, _ in float_leaves]
        ).astype(dt, copy=False)
        dev = jax.device_put(flat)
        off = 0
        for i, k, shape, size in float_leaves:
            entries[i][k] = jax.lax.slice(dev, (off,), (off + size,)).reshape(shape)
            off += size
    out["emb"] = entries
    return out


def shard_device_batch(batch: Dict, mesh=None) -> Dict:
    """device_put the batch with DP shardings: batch-dim leaves over ``data``,
    raw-slot distinct rows replicated. Computation follows data: the jitted
    step picks these shardings up without explicit in_shardings.

    Mesh staging is PACKED like the single-chip path (round-1 Weak #8: the
    per-leaf device_put round-trips return on pods, where they matter most):
    one transfer per (sharding, dtype) group — batch-dim floats concat along
    axis 1 into (B, F_total), raw distinct rows concat along axis 0
    (replicated), int32 index matrices concat along axis 1 — then sliced
    back on device. Raw-slot masks are derived on device (``index != P-1``,
    the pad row) instead of shipping a bool matrix."""
    if mesh is None:
        return _packed_put(batch)
    bsh = batch_sharding(mesh)
    rep = replicated(mesh)

    # ---- group host leaves
    bdim_float: List[Tuple[str, int, np.ndarray]] = []  # ("dense"/"labels"/i, …)
    for j, x in enumerate(batch["dense"]):
        bdim_float.append(("dense", j, np.asarray(x)))
    for j, x in enumerate(batch["labels"]):
        bdim_float.append(("labels", j, np.asarray(x)))
    raw_distinct: List[Tuple[int, np.ndarray]] = []
    index_mats: List[Tuple[Tuple[str, int], np.ndarray]] = []
    for i, e in enumerate(batch["emb"]):
        if "pooled" in e:
            bdim_float.append(("emb", i, np.asarray(e["pooled"])))
        elif "pool_index" in e:
            raw_distinct.append((i, np.asarray(e["distinct"])))
            index_mats.append(
                (("idx", i), np.ascontiguousarray(e["pool_index"]))
            )
            if "pool_counts" in e:
                index_mats.append(
                    (("cnt", i), np.ascontiguousarray(e["pool_counts"], dtype=np.int32))
                )
        else:
            raw_distinct.append((i, np.asarray(e["distinct"])))
            index_mats.append(
                (("idx", i), np.ascontiguousarray(e["index"], dtype=np.int32))
            )

    def _packed_groups(leaves, axis, sharding):
        """One device_put per (dtype, off-axis width) group of 2-D leaves;
        other ranks ship individually (packing along one axis requires the
        other to match — NdarrayDataBase allows any ndim >= 1, and raw
        slots may carry different embedding dims)."""
        views: Dict = {}
        by_dtype: Dict = {}
        for key, arr in leaves:
            if arr.ndim != 2:
                views[key] = jax.device_put(arr, sharding)
                continue
            gk = (arr.dtype.name, arr.shape[1 - axis])
            by_dtype.setdefault(gk, []).append((key, arr))
        for group in by_dtype.values():
            packed = np.concatenate([a for _, a in group], axis=axis)
            dev = jax.device_put(packed, sharding)
            off = 0
            for key, a in group:
                w = a.shape[axis]
                if axis == 1:
                    views[key] = dev[:, off:off + w]
                else:
                    views[key] = dev[off:off + w]
                off += w
        return views

    fviews = _packed_groups([((k, j), a) for k, j, a in bdim_float], 1, bsh)
    dviews = _packed_groups(raw_distinct, 0, rep)
    iviews = _packed_groups(index_mats, 1, bsh)

    out: Dict = {
        "dense": [fviews[("dense", j)] for j in range(len(batch["dense"]))],
        "labels": [fviews[("labels", j)] for j in range(len(batch["labels"]))],
        "emb": [],
    }
    for i, e in enumerate(batch["emb"]):
        if "pooled" in e:
            out["emb"].append({"pooled": fviews[("emb", i)]})
        elif "pool_index" in e:
            entry = {"distinct": dviews[i], "pool_index": iviews[("idx", i)]}
            if "pool_counts" in e:
                entry["pool_counts"] = iviews[("cnt", i)]
            out["emb"].append(entry)
        else:
            idx = iviews[("idx", i)]
            p = e["distinct"].shape[0]
            out["emb"].append(
                {
                    "distinct": dviews[i],
                    "index": idx,
                    "mask": idx != (p - 1),  # pad row = P-1 (stage_embeddings)
                }
            )
    return out


def replicate_state(state: TrainState, mesh) -> TrainState:
    rep = replicated(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, rep), state)
