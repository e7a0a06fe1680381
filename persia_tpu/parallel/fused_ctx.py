"""TrainCtx-shaped wrapper around the fused all-in-HBM tier.

The fused tier (``parallel/fused_step.py``) is the idiomatic TPU answer to
the reference's async CPU-PS pipeline when the tables fit in HBM: gather →
model fwd/bwd → dense update → duplicate-safe sparse update, all ONE jitted
XLA program, host↔device traffic per step = the raw batch. This module
packages ``init_fused_state``/``build_fused_*`` behind the ``TrainCtx`` API
(train_step / eval_batch / dump_checkpoint / load_checkpoint, ref:
`persia/ctx.py` TrainCtx surface) so the example CLIs and user code can
switch tiers with one flag.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from persia_tpu.compile_cache import enable_compile_cache
from persia_tpu.data import PersiaBatch
from persia_tpu.logger import get_default_logger
from persia_tpu.parallel.fused_step import (
    FusedSlotSpec,
    FusedTrainState,
    build_fused_eval_step,
    build_fused_train_step,
    init_fused_state,
)
from persia_tpu.parallel.train_step import _note_nonfinite_loss
from persia_tpu.tracing import stage_span, wait_span

logger = get_default_logger("persia_tpu.fused_ctx")


def batch_to_fused(
    batch: PersiaBatch,
    specs: Optional[Dict[str, FusedSlotSpec]] = None,
    fold_ids: bool = False,
) -> Dict:
    """PersiaBatch → the fused step's dict batch.

    Single-id slots (every sample carries exactly one id) become (B,)
    int32; list-of-list slots become (B, Lmax) int32 padded with -1 (the
    step's pad sentinel). Static shapes matter on TPU: Lmax is the batch's
    own max, so callers with ragged streams should bucket batch shapes
    upstream.

    Fused tables are dense [0, vocab) while the rest of the framework
    passes open u64 hash signs, so when ``specs`` is given every slot's
    ids are range-checked against its vocab BEFORE the int32 cast (an
    id >= 2^31 would wrap negative and collide with the pad sentinel; an
    id in [vocab, 2^31) would alias XLA's clamped last row — both silent
    corruption). ``fold_ids=True`` folds by modulo instead of raising.
    """
    def _ranged(name: str, flat: np.ndarray) -> np.ndarray:
        if specs is None or not len(flat):
            return flat
        vocab = np.uint64(specs[name].vocab)
        if fold_ids:
            return flat % vocab
        bad = flat >= vocab
        if bad.any():
            raise ValueError(
                f"slot {name!r}: {int(bad.sum())} id(s) outside "
                f"[0, {int(vocab)}) (max {int(flat.max())}); hash-sign ids "
                f"must be folded first — pass fold_ids=True or fold upstream"
            )
        return flat

    ids = {}
    for f in batch.id_type_features:
        flat, counts = f.flat_counts()
        flat = _ranged(f.name, np.asarray(flat, dtype=np.uint64))
        if len(counts) and (counts == 1).all():  # one id per sample
            ids[f.name] = flat.astype(np.int32)
        else:
            b = len(counts)
            lmax = max(int(counts.max()), 1) if b else 1
            padded = np.full((b, lmax), -1, dtype=np.int32)
            off = 0
            for i, c in enumerate(counts):
                padded[i, :c] = flat[off:off + c]
                off += c
            ids[f.name] = padded
    # integers stay integers: a sequence model's target ids among the labels,
    # its per-position side inputs (where each position's document starts)
    # among the non-id features; everything else is float32
    def _staged(x) -> np.ndarray:
        return np.asarray(x, np.int32 if np.issubdtype(x.dtype, np.integer) else np.float32)

    out = {
        "dense": [_staged(d.data) for d in batch.non_id_type_features],
        "ids": ids,
    }
    if batch.labels:
        out["labels"] = [_staged(l.data) for l in batch.labels]
    return out


class FusedTrainCtx:
    """All-in-HBM training context (the "fused" tier as an API).

    State initializes lazily from the first batch (the model needs a sample
    to trace). ``train_step`` fetches the loss (one d2h per step — fine for
    examples; throughput loops pass ``fetch_metrics=False`` and read
    :meth:`last_metrics` when they need it).
    """

    def __init__(
        self,
        model,
        dense_optimizer: optax.GradientTransformation,
        embedding_optimizer,
        specs: Dict[str, FusedSlotSpec],
        loss_fn=None,
        stack: bool = True,
        table_dtype=jnp.float32,
        seed: int = 0,
        fold_ids: bool = False,
    ):
        self.model = model
        self.dense_optimizer = dense_optimizer
        self.sparse_cfg = embedding_optimizer.config
        self.specs = dict(specs)
        self.slot_order = sorted(self.specs)
        self.stack = stack
        self.table_dtype = table_dtype
        self.seed = seed
        self.fold_ids = fold_ids
        kw = {} if loss_fn is None else {"loss_fn": loss_fn}
        self._step = build_fused_train_step(
            model, dense_optimizer, self.sparse_cfg, self.specs,
            self.slot_order, stack=stack, **kw
        )
        self._eval = build_fused_eval_step(
            model, self.specs, self.slot_order, stack=stack
        )
        self.state: Optional[FusedTrainState] = None
        self._steps = 0  # train_step calls so far: the ``seq`` its spans share

    # lifecycle ------------------------------------------------------------

    def __enter__(self) -> "FusedTrainCtx":
        enable_compile_cache()
        return self

    def __exit__(self, *exc) -> None:
        return None

    def _ensure_state(self, fused_batch: Dict) -> None:
        if self.state is None:
            self.state = init_fused_state(
                self.model, jax.random.PRNGKey(self.seed), self.specs,
                fused_batch, self.dense_optimizer, self.sparse_cfg,
                slot_order=self.slot_order, stack=self.stack,
                table_dtype=self.table_dtype,
            )

    # training -------------------------------------------------------------

    def train_step(self, batch: PersiaBatch, fetch_metrics: bool = True) -> Dict:
        seq = self._steps
        self._steps += 1
        with stage_span("fused.stage", seq=seq):
            fb = batch_to_fused(batch, self.specs, self.fold_ids)
        self._ensure_state(fb)
        with stage_span("fused.dispatch", seq=seq):
            self.state, (loss, preds) = self._step(self.state, fb)
        self._last = (loss, preds)
        if not fetch_metrics:
            return {}
        with wait_span("fused.fetch", seq=seq):  # the d2h waits for the step
            return {"loss": _note_nonfinite_loss(float(loss)),
                    "preds": np.asarray(preds)}

    def last_metrics(self) -> Optional[Dict]:
        if getattr(self, "_last", None) is None:
            return None
        loss, preds = self._last
        return {"loss": _note_nonfinite_loss(float(loss)),
                "preds": np.asarray(preds)}

    def eval_batch(self, batch: PersiaBatch) -> np.ndarray:
        fb = batch_to_fused(batch, self.specs, self.fold_ids)
        self._ensure_state(fb)
        return np.asarray(self._eval(self.state, fb))

    # checkpoint -----------------------------------------------------------
    # One .npz of every state leaf keyed by its tree path + a JSON manifest
    # (ref capability: full-state dump/load, persia-model-manager). The
    # host tiers' directory checkpoints (checkpoint.py) cover the PS side;
    # fused state is pure device arrays so an archive is the natural form.

    def dump_checkpoint(self, path: str) -> None:
        assert self.state is not None, "no state to dump (train first)"
        import io

        from persia_tpu.jobstate import fsync_write_bytes

        os.makedirs(path, exist_ok=True)
        leaves = jax.tree_util.tree_leaves_with_path(self.state)
        arrays = {}
        manifest = []
        for i, (kp, leaf) in enumerate(leaves):
            arrays[f"a{i}"] = np.asarray(leaf)
            manifest.append(jax.tree_util.keystr(kp))
        # atomic + fsync'd publish (persia-lint DUR001): a crash mid-dump
        # must never leave a torn archive under the final name
        buf = io.BytesIO()
        np.savez(buf, **arrays)
        fsync_write_bytes(os.path.join(path, "fused_state.npz"), buf.getvalue())
        fsync_write_bytes(
            os.path.join(path, "fused_state.json"), json.dumps(manifest).encode()
        )
        logger.info("fused checkpoint written to %s (%d leaves)", path, len(manifest))

    def load_checkpoint(self, path: str) -> None:
        assert self.state is not None, (
            "load_checkpoint needs an initialized state shape — run one "
            "train_step/eval_batch first (the model traces from a sample)"
        )
        with open(os.path.join(path, "fused_state.json")) as f:
            manifest = json.load(f)
        data = np.load(os.path.join(path, "fused_state.npz"))
        leaves_now = jax.tree_util.tree_leaves_with_path(self.state)
        if [jax.tree_util.keystr(kp) for kp, _ in leaves_now] != manifest:
            raise ValueError(
                "checkpoint layout mismatch: model/spec/optimizer changed "
                "since the dump"
            )
        treedef = jax.tree_util.tree_structure(self.state)
        self.state = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(data[f"a{i}"]) for i in range(len(manifest))]
        )
