"""User-facing training/eval/inference contexts.

Parity target: ``persia/ctx.py`` — ``BaseCtx`` (common context wiring),
``DataCtx`` (data-loader side), ``EmbeddingCtx`` (feature prep + checkpoint),
``TrainCtx`` (training state machine), ``eval_ctx``/``InferCtx``.

TPU-first shape: instead of DLPack handoffs into torch autograd
(ref ctx.py:40-55), ``prepare_features`` stages numpy worker outputs into a
sharded device batch; the whole train step (forward, loss, backward, dense
update, embedding grads) is one jitted XLA program from
``persia_tpu.parallel.train_step``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
import optax

from persia_tpu.compile_cache import enable_compile_cache
from persia_tpu.config import EmbeddingConfig, HyperParameters, JobType
from persia_tpu.data import PersiaBatch
from persia_tpu.embedding.optim import SGD as SparseSGD
from persia_tpu.embedding.worker import (
    DevicePooledBatch,
    EmbeddingWorker,
    FeatureEmbeddingBatch,
    RawEmbeddingBatch,
    SumEmbeddingBatch,
)
from persia_tpu.logger import get_default_logger
from persia_tpu.utils import round_up_pow2 as _round_up_pow2
from persia_tpu.parallel.train_step import (
    TrainState,
    build_eval_step,
    build_train_step,
    init_train_state,
    replicate_state,
    shard_device_batch,
    unpack_step_grads,
    unpack_step_header,
    unpack_step_header_dynamic,
    unpack_step_output,
)

logger = get_default_logger("persia_tpu.ctx")


def _pad_bucket(n: int) -> int:
    """Padded-distinct bucket: pow2 below 512, then 512-quantum — the
    gradient buffer rides the (slow) device→host wire, so past the small
    sizes pow2's up-to-2x padding waste costs real link time. Production
    zipf streams concentrate distinct counts tightly, so the quantum still
    yields a near-constant step signature."""
    if n <= 512:
        return _round_up_pow2(n)
    return -(-n // 512) * 512


def stage_embeddings(
    emb_batches: Sequence[FeatureEmbeddingBatch],
    dtype=None,
) -> Tuple[List[Dict], List[Optional[int]]]:
    """Convert worker outputs into the device batch's ``emb`` entries.

    Raw and device-pooled slots: distinct rows are padded to a bucketed
    size (static shapes for jit — a bounded set of compiled programs
    instead of one per distinct-count) with zero rows absorbing padded
    index entries. Device-pooled slots share ONE bucket (the max) so the
    step signature stays stable across batches; their index pad keeps
    pointing at row D (a zero row), and pad gradients land on rows the
    host slices off. Returns (emb_entries, true_distinct_counts) — counts
    are None for host-pooled slots and are used to slice padding off the
    returned gradients.
    """
    entries: List[Dict] = []
    counts: List[Optional[int]] = []
    shared_p = 0
    for eb in emb_batches:
        if isinstance(eb, DevicePooledBatch):
            shared_p = max(shared_p, eb.distinct.shape[0] + 1)
    if shared_p:
        shared_p = _pad_bucket(shared_p)
    for eb in emb_batches:
        if isinstance(eb, SumEmbeddingBatch):
            pooled = eb.pooled if dtype is None else eb.pooled.astype(dtype)
            entries.append({"pooled": pooled})
            counts.append(None)
        elif isinstance(eb, DevicePooledBatch):
            d, dim = eb.distinct.shape
            padded = np.zeros(
                (shared_p, dim),
                dtype=eb.distinct.dtype if dtype is None else dtype,
            )
            padded[:d] = eb.distinct
            # uint16 indexes when the padded table allows: the index matrix
            # rides host→device every batch (cast back on device, fused free)
            idx_dtype = np.uint16 if shared_p <= 0xFFFF else np.int32
            entry = {
                "distinct": padded,
                "pool_index": np.ascontiguousarray(eb.index, dtype=idx_dtype),
            }
            if eb.sqrt_scaling:
                # 2-D int column (packs with the index matrices on the mesh
                # staging path); rsqrt happens on device in f32
                entry["pool_counts"] = eb.counts.reshape(-1, 1).astype(np.int32)
            entries.append(entry)
            counts.append(d)
        else:
            d, dim = eb.distinct.shape
            p = _round_up_pow2(d + 1)
            padded = np.zeros((p, dim),
                              dtype=eb.distinct.dtype if dtype is None else dtype)
            padded[:d] = eb.distinct
            index = np.where(eb.index == d, p - 1, eb.index).astype(np.int32)
            mask = eb.index != d
            entries.append({"distinct": padded, "index": index, "mask": mask})
            counts.append(d)
    return entries, counts


class BaseCtx:
    """Common wiring (ref: persia/ctx.py:208-243). ``worker`` is the embedding
    -worker tier handle: in-process ``EmbeddingWorker`` or an RPC client with
    the same surface."""

    def __init__(self, worker: EmbeddingWorker, embedding_config: EmbeddingConfig):
        self.worker = worker
        self.embedding_config = embedding_config

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class EmbeddingCtx(BaseCtx):
    """Feature preparation + checkpoint plumbing (ref: persia/ctx.py:345-652)."""

    def __init__(
        self,
        worker: EmbeddingWorker,
        embedding_config: EmbeddingConfig,
        mesh=None,
        wire_dtype: Optional[str] = None,
    ):
        super().__init__(worker, embedding_config)
        self.mesh = mesh
        # host↔device embedding/gradient dtype; "bfloat16" halves transfer
        # bytes (ref capability: f16 wire format with f32 master weights,
        # common/lib.rs:157-180 + backward.rs EmbeddingGradientBatch)
        self.wire_dtype = None if wire_dtype in (None, "float32") else np.dtype(wire_dtype)

    def prepare_features(
        self, batch: PersiaBatch, emb_batches: Sequence[FeatureEmbeddingBatch]
    ) -> Tuple[Dict, List[Optional[int]]]:
        """Build the sharded device batch from a ``PersiaBatch`` + worker
        lookup results (ref: _prepare_feature, ctx.py:75-199)."""
        entries, counts = stage_embeddings(emb_batches, dtype=self.wire_dtype)
        device_batch = {
            "dense": [f.data.astype(np.float32) for f in batch.non_id_type_features],
            "labels": [l.data.astype(np.float32) for l in batch.labels],
            "emb": entries,
        }
        return shard_device_batch(device_batch, self.mesh), counts

    def emb_grads_to_slot_grads(
        self,
        emb_batches: Sequence[FeatureEmbeddingBatch],
        emb_grads: Sequence,
        counts: Sequence[Optional[int]],
    ) -> Dict[str, np.ndarray]:
        """Strip padding and key device gradients by slot name for the
        worker's gradient path."""
        out = {}
        for eb, g, d in zip(emb_batches, emb_grads, counts):
            g = np.asarray(g, dtype=np.float32)
            out[eb.name] = g if d is None else g[:d]
        return out

    def dump_checkpoint(self, dst: str, blocking: bool = True) -> None:
        """Dense state + sharded embedding checkpoint under ``dst``
        (ref: ctx.dump_checkpoint, persia/ctx.py:1007-1034)."""
        import flax.serialization

        from persia_tpu.checkpoint import dump_dense

        if getattr(self, "state", None) is not None:
            dump_dense(flax.serialization.to_bytes(self.state), dst)
        self.worker.dump(dst, blocking=blocking)

    def load_checkpoint(self, src: str) -> None:
        """Restore dense state (requires ``self.state`` initialized with the
        right shapes) + embedding tables (ref: ctx.load_checkpoint,
        persia/ctx.py:1036-1064)."""
        import flax.serialization

        from persia_tpu.checkpoint import load_dense

        if getattr(self, "state", None) is not None:
            raw = load_dense(src, missing_ok=True)
            if raw is not None:
                self.state = flax.serialization.from_bytes(self.state, raw)
        self.worker.load(src)


class DataCtx(BaseCtx):
    """Data-loader role: push batches into the dataflow
    (ref: persia/ctx.py:274-342). In-process mode forwards straight to the
    worker's id buffer; the service mode sends over RPC (persia_tpu.service)."""

    def send_data(self, batch: PersiaBatch) -> int:
        if not self.worker.can_forward_batched():
            raise RuntimeError("embedding worker forward buffer full")
        return self.worker.put_forward_ids(batch)


class TrainCtx(EmbeddingCtx):
    """Synchronous training context — the M1 slice (lookup-direct path,
    ref forward_directly, forward.rs:782-831). The pipelined/bounded-staleness
    path lives in ``persia_tpu.data_loader.DataLoader``.

    Responsibilities (ref: persia/ctx.py:655-1064): hold the jitted train
    step + TrainState, register the sparse optimizer on the PS tier, convert
    device grads into worker gradient updates.
    """

    def __init__(
        self,
        model,
        dense_optimizer: optax.GradientTransformation,
        embedding_optimizer,
        worker: EmbeddingWorker,
        embedding_config: EmbeddingConfig,
        mesh=None,
        grad_scale: float = 1.0,
        loss_fn=None,
        wire_dtype: Optional[str] = None,
        dynamic_loss_scale: bool = False,
        loss_scale_init: float = float(2 ** 15),
        loss_scale_growth_interval: int = 2000,
        loss_scale_max: float = float(2 ** 24),
        resilience_policy=None,
        dense_sync: Optional[str] = None,
        dense_sync_block_size: int = 256,
    ):
        super().__init__(worker, embedding_config, mesh=mesh, wire_dtype=wire_dtype)
        self.model = model
        self.dense_optimizer = dense_optimizer
        self.embedding_optimizer = embedding_optimizer
        self.grad_scale = grad_scale
        # shared service/resilience.py policy: the DataLoader picks it up
        # for its recovery backoff + per-batch deadline budget, so trainer-
        # side retry behavior is configured in ONE place
        self.resilience_policy = resilience_policy
        # (device header, batch) of the latest fetch_metrics=False prepared
        # step — materialized by last_prepared_metrics()
        self._deferred_header = None
        # crash-consistent job state (persia_tpu.jobstate): once resume()
        # has been called (even on a cold start) every gradient batch is
        # tagged with a (manifest epoch, global step) journal id so the PS
        # apply-journal can dedupe a post-crash replay; snapshot_job()
        # advances the epoch at each fence
        self._job_epoch: Optional[int] = None
        self._global_step: int = 0
        self._resume_state_bytes: Optional[bytes] = None
        self.last_resume_info: Optional[Dict] = None
        # dynamic mixed-precision loss scaling (ref: GradScaler management,
        # persia/ctx.py:926-1005): on-device finite check every step,
        # skip-step + scale backoff on overflow, periodic growth
        self.dynamic_loss_scale = dynamic_loss_scale
        self._loss_scale_init = loss_scale_init if dynamic_loss_scale else None
        kwargs = {} if loss_fn is None else {"loss_fn": loss_fn}
        self._train_step_jit = build_train_step(
            model, dense_optimizer,
            dynamic_loss_scale=dynamic_loss_scale,
            growth_interval=loss_scale_growth_interval,
            max_scale=loss_scale_max,
            **kwargs,
        )
        # explicit dense-plane sync mode (persia_tpu.parallel.grad_sync
        # DENSE_SYNC_MODES): None keeps the default implicit-psum path; a
        # mode string swaps the jitted step for build_sync_train_step's
        # explicit-collective step (quantized ring and/or ZeRO-style sharded
        # optimizer update). The bytegrad mode's error-feedback residual is
        # carried on the ctx (not in TrainState), so it does NOT survive a
        # jobstate resume — ring modes carry theirs inside opt_state and do.
        self.dense_sync = dense_sync
        self.dense_sync_block_size = int(dense_sync_block_size)
        self._sync_step = None
        self._sync_algorithm = None
        self._sync_sharded = False
        self._sync_wrapped = False
        self._sync_residual = None
        self._dense_wire_bytes_per_step = 0
        self._wire_counter = None
        if dense_sync is not None:
            if mesh is None:
                raise ValueError("dense_sync requires a device mesh")
            if dynamic_loss_scale:
                raise ValueError(
                    "dense_sync and dynamic_loss_scale are mutually "
                    "exclusive: the explicit-collective step has no "
                    "loss-scale path"
                )
            from persia_tpu.parallel.grad_sync import (
                BlockInt8Ring,
                build_sync_train_step,
                sync_mode_algorithm,
            )

            algo, sharded = sync_mode_algorithm(
                dense_sync, block_size=self.dense_sync_block_size
            )
            self._sync_algorithm = algo
            self._sync_sharded = sharded
            self._sync_wrapped = sharded or isinstance(algo, BlockInt8Ring)
            self._sync_step = build_sync_train_step(
                model, dense_optimizer, mesh, algo,
                sharded_update=sharded, **kwargs,
            )
        self._eval_step = build_eval_step(model)
        self.state: Optional[TrainState] = None

    @property
    def sync_mode(self) -> str:
        """The dense-plane sync mode label this ctx runs (and records):
        an explicit ``dense_sync`` mode, else "implicit-psum" on a real DP
        mesh, else "local"."""
        if self.dense_sync is not None:
            return self.dense_sync
        if self.mesh is not None and int(self.mesh.shape["data"]) > 1:
            return "implicit-psum"
        return "local"

    def dense_wire_bytes_per_step(self) -> int:
        """Modeled per-replica dense collective bytes per step for this
        ctx's sync mode (0 before state init — the param count prices it)."""
        return self._dense_wire_bytes_per_step

    def _price_dense_sync(self, state) -> None:
        """Price the per-step dense collective once (param count is known
        after state init) so the hot path only adds a python-int counter
        bump — no host syncs (persia-lint JAX001)."""
        from persia_tpu.metrics import get_metrics
        from persia_tpu.parallel.grad_sync import (
            dense_param_count,
            dense_sync_wire_bytes,
        )

        n = int(self.mesh.shape["data"]) if self.mesh is not None else 1
        self._dense_wire_bytes_per_step = dense_sync_wire_bytes(
            self.sync_mode, dense_param_count(state.params), n,
            block_size=self.dense_sync_block_size,
        )
        self._wire_counter = get_metrics().counter(
            "persia_tpu_dense_wire_bytes",
            "modeled dense-plane collective bytes dispatched, by sync mode",
        )

    def _run_dense_step(self, state, device_batch):
        """Dispatch one jitted dense step through the selected sync mode.

        Explicit modes get a sync-stage span on the dispatch edge; every
        mode (implicit-psum included) bumps the wire-bytes counter with the
        precomputed per-step cost. The default path stays exactly
        ``self._train_step_jit`` — zero new overhead when ``dense_sync`` is
        unset and the mesh is single-device."""
        if self._sync_step is not None:
            from persia_tpu import tracing

            with tracing.span(
                "train.dense_sync", mode=self.dense_sync,
                wire_bytes=self._dense_wire_bytes_per_step,
            ):
                if self._sync_residual is not None:
                    state, out, self._sync_residual = self._sync_step(
                        state, device_batch, self._sync_residual
                    )
                else:
                    state, out = self._sync_step(state, device_batch)
        else:
            state, out = self._train_step_jit(state, device_batch)
        if self._wire_counter is not None and self._dense_wire_bytes_per_step:
            self._wire_counter.inc(
                self._dense_wire_bytes_per_step, mode=self.sync_mode
            )
        return state, out

    def _train_step(self, state, device_batch):
        """Run the jitted step and unpack its single-transfer output into the
        (state, metrics, emb_grads) host view."""
        state, (header, gpacked) = self._run_dense_step(state, device_batch)
        if self.dynamic_loss_scale:
            loss, preds, scale, finite = unpack_step_header_dynamic(
                np.asarray(header), device_batch
            )
            emb_grads = unpack_step_grads(np.asarray(gpacked), device_batch)
            metrics = {"loss": loss, "preds": preds,
                       "loss_scale": scale, "grads_finite": finite}
        else:
            loss, preds, emb_grads = unpack_step_output(
                np.asarray(header), np.asarray(gpacked), device_batch
            )
            metrics = {"loss": loss, "preds": preds}
        return state, metrics, emb_grads

    def __enter__(self):
        enable_compile_cache()
        # register the sparse optimizer on every PS replica
        # (ref: embedding_optimizer.apply(), persia/ctx.py:854-858)
        self.worker.register_optimizer(self.embedding_optimizer.config)
        return self

    def init_state(self, rng, sample_batch: Dict) -> TrainState:
        state = init_train_state(
            self.model, rng, sample_batch, self.dense_optimizer,
            loss_scale_init=self._loss_scale_init,
        )
        if self._sync_wrapped:
            # ring/sharded modes carry opt state in the init_sync_opt_state
            # wrapper (sharded moments + EF residual). Swap the template in
            # BEFORE the deferred overlay so a restored manifest's sharded
            # opt state lands on matching shapes.
            from persia_tpu.parallel.grad_sync import init_sync_opt_state

            state = state.replace(
                opt_state=init_sync_opt_state(
                    state.params, self.dense_optimizer, self.mesh,
                    self._sync_algorithm, self._sync_sharded,
                )
            )
        if self._resume_state_bytes is not None:
            # deferred resume: the manifest's dense/opt state overlays the
            # freshly initialized template (same model + optimizer shapes)
            import flax.serialization

            state = flax.serialization.from_bytes(
                state, self._resume_state_bytes
            )
            self._resume_state_bytes = None
        if self.mesh is not None:
            state = self._place_state(state)
        self.state = state
        if self._sync_residual is None and self.dense_sync == "bytegrad":
            from persia_tpu.parallel.grad_sync import init_residual

            self._sync_residual = init_residual(state.params)
        self._price_dense_sync(state)
        return state

    def _place_state(self, state: TrainState) -> TrainState:
        """Mesh placement for a (possibly host-resident) TrainState: the
        sync wrapper's lead-axis leaves shard over ``data``, everything else
        replicates."""
        if self._sync_wrapped:
            from persia_tpu.parallel.grad_sync import place_sync_state

            return place_sync_state(
                state, self.mesh, self._sync_algorithm, self._sync_sharded
            )
        return replicate_state(state, self.mesh)

    # -------------------------------------------------- crash-consistent jobs

    def _ps_replicas(self):
        router = getattr(self.worker, "lookup_router", None)
        if router is None:
            from persia_tpu.jobstate import ManifestError

            raise ManifestError(
                "job-state snapshots need direct PS replica handles (an "
                "in-process EmbeddingWorker over stores/StoreClients); a "
                "remote WorkerClient trainer should checkpoint via "
                "worker.dump instead"
            )
        return router.replicas

    def snapshot_job(self, job_state, loader=None, include_ps: bool = True,
                     extra_meta: Optional[Dict] = None, generators=None):
        """Step-fenced snapshot: drain the loader's in-flight gradients,
        then commit PS shards + dense/opt state + RNG streams as one
        manifest epoch (persia_tpu.jobstate). Returns the Manifest."""
        import flax.serialization

        from persia_tpu import jobstate

        mgr = jobstate.coerce_manager(job_state)
        if loader is not None:
            loader.flush()  # fence invariant: nothing in flight past here
        router = getattr(self.worker, "lookup_router", None)
        meta = {"kind": "train_ctx"}
        meta.update(extra_meta or {})
        manifest = jobstate.snapshot_job(
            mgr, self._global_step,
            state_bytes=(
                flax.serialization.to_bytes(self.state)
                if self.state is not None else None
            ),
            replicas=self._ps_replicas() if include_ps else None,
            batch_advances=(
                dict(getattr(router, "batch_advances", {})) if router else None
            ),
            components={
                "loader.json": {
                    "consumed_batches": self._global_step,
                    "staleness_outstanding": 0,  # fence = flushed
                },
            },
            meta=meta,
            generators=generators,
        )
        self._job_epoch = manifest.job_epoch
        return manifest

    def resume(self, job_state, restore_ps: bool = True, generators=None):
        """Rebuild the exact fence state from the newest good manifest (or
        arm journaling on a cold start). Returns the Manifest or None.

        ``restore_ps=True`` rewinds the PS to the fence — the replayed
        window re-applies and the run is bit-identical to a fault-free
        replay. ``restore_ps=False`` keeps the PS's post-crash state and
        relies on the apply-journal to skip already-applied batches
        (exactly-once, bounded staleness)."""
        from persia_tpu import jobstate

        mgr = jobstate.coerce_manager(job_state)
        router = getattr(self.worker, "lookup_router", None)
        manifest, info = jobstate.resume_job(
            mgr,
            replicas=(router.replicas if router is not None else None),
            rewind_ps=restore_ps,
            optimizer=self.embedding_optimizer.config,
            generators=generators,
        )
        self.last_resume_info = info
        if manifest is None:
            self._job_epoch = 0  # cold start: journal from step 0, epoch 0
            self._global_step = 0
            return None
        if manifest.has("dense.state"):
            self._resume_state_bytes = manifest.read_blob("dense.state")
            if self.state is not None:
                import flax.serialization

                self.state = flax.serialization.from_bytes(
                    self.state, self._resume_state_bytes
                )
                if self.mesh is not None:
                    self.state = self._place_state(self.state)
                self._resume_state_bytes = None
        router = getattr(self.worker, "lookup_router", None)
        if router is not None:
            # fences record CUMULATIVE advance counts; continue from them
            router.batch_advances = dict(info.get("batch_advances", {}))
        self._job_epoch = manifest.job_epoch
        self._global_step = manifest.step
        return manifest

    def _journal_id(self) -> Optional[int]:
        if self._job_epoch is None:
            return None
        from persia_tpu.jobstate import make_journal_id

        return make_journal_id(self._job_epoch, self._global_step)

    def train_step(self, batch: PersiaBatch) -> Dict:
        """One synchronous hybrid step: lookup → jitted step → gradient
        return. Returns host metrics {loss, preds}."""
        from persia_tpu import tracing

        # the step IS the trace edge on the synchronous path: the lookup
        # and gradient-update RPCs beneath inherit one trace_id, linking
        # this gradient batch to its journaled PS apply
        with tracing.span("train.step", step=self._global_step):
            return self._train_step_sync(batch)

    def _train_step_sync(self, batch: PersiaBatch) -> Dict:
        ref = self.worker.put_forward_ids(batch)
        emb_batches = self.worker.forward_batch_id(ref, train=True)
        try:
            device_batch, counts = self.prepare_features(batch, emb_batches)
            if self.state is None:
                self.init_state(jax.random.PRNGKey(0), device_batch)
            self.state, metrics, emb_grads = self._train_step(self.state, device_batch)
            slot_grads = self.emb_grads_to_slot_grads(emb_batches, emb_grads, counts)
        except Exception:
            # release the staleness slot + stashed layout (no silent buffer leak)
            self.worker.abort_gradient(ref)
            raise
        # emb grads ship scaled; the worker's scale_factor division unscales
        # (non-finite slots are NaN-skipped there, mod.rs:716-744). A static
        # grad_scale composes with the dynamic loss scale instead of being
        # silently discarded by it.
        scale = metrics.get("loss_scale", 1.0) * self.grad_scale
        jid = self._journal_id()
        if jid is not None:
            self.worker.update_gradient_batched(
                ref, slot_grads, scale_factor=scale, journal_id=jid
            )
        else:
            self.worker.update_gradient_batched(ref, slot_grads, scale_factor=scale)
        self._global_step += 1
        out = {
            "loss": float(metrics["loss"]),
            "preds": np.asarray(metrics["preds"]),
        }
        for k in ("loss_scale", "grads_finite"):
            if k in metrics:
                out[k] = metrics[k]
        return out

    def train_step_prepared(
        self, training_batch, loader, fetch_metrics: bool = True
    ) -> Optional[Dict]:
        """Pipelined step: consume a ``PersiaTrainingBatch`` from a
        ``DataLoader``; the embedding gradients return asynchronously through
        the loader's BackwardEngine (bounded staleness). The TPU step of batch
        N overlaps the lookup of batch N+k (ref: forward.rs pipeline +
        backward.rs).

        ``fetch_metrics=False`` (static loss scale only — the dynamic scale
        must be read every step) skips the per-step header fetch: that
        device→host read makes the host wait for the step it just
        dispatched, so metric-light loops fetch once at the end via
        :meth:`last_prepared_metrics`. Returns ``None`` in that mode."""
        device_batch = training_batch.device_batch
        if self.state is None:
            self.init_state(jax.random.PRNGKey(0), device_batch)
        defer = not fetch_metrics and not self.dynamic_loss_scale
        if not defer:
            self._deferred_header = None  # this step's metrics are fresher
        try:
            self.state, (header, gpacked) = self._run_dense_step(self.state, device_batch)
            # start the bulk gradient download without blocking; the
            # BackwardEngine thread materializes it, so the device→host
            # transfer overlaps the next step instead of serializing with it
            try:
                gpacked.copy_to_host_async()
            except AttributeError:
                pass
            if defer:
                # stash only the labels SHAPE: keeping the device_batch
                # would pin the whole batch's device buffers until the
                # deferred fetch
                self._deferred_header = (
                    header, tuple(device_batch["labels"][0].shape)
                )
                dyn_scale, scale, finite = None, self.grad_scale, None
            elif self.dynamic_loss_scale:
                loss, preds, dyn_scale, finite = unpack_step_header_dynamic(
                    np.asarray(header), device_batch
                )
                # static grad_scale composes with the dynamic loss scale
                scale = dyn_scale * self.grad_scale
            else:
                loss, preds = unpack_step_header(np.asarray(header), device_batch)
                dyn_scale, scale, finite = None, self.grad_scale, None
        except Exception:
            loader.mark_consumed(training_batch)
            raise
        loader.backward_packed(
            training_batch, gpacked, scale_factor=scale,
            journal_id=self._journal_id(),
        )
        self._global_step += 1
        if defer:
            return None
        out = {"loss": loss, "preds": np.asarray(preds)}
        if finite is not None:
            out["loss_scale"] = dyn_scale
            out["grads_finite"] = finite
        return out

    def last_prepared_metrics(self) -> Optional[Dict]:
        """Materialize the most recent ``fetch_metrics=False`` step's
        header (ONE device→host fetch, after the loop it was deferred out
        of)."""
        if self._deferred_header is None:
            return None
        header, label_shape = self._deferred_header
        self._deferred_header = None
        h = np.asarray(header)
        return {"loss": float(h[0]), "preds": h[1:].reshape(label_shape)}

    def eval_batch(self, batch: PersiaBatch) -> np.ndarray:
        emb_batches = self.worker.forward_directly(batch, train=False)
        device_batch, _ = self.prepare_features(batch, emb_batches)
        return np.asarray(self._eval_step(self.state, device_batch))


class InferCtx(EmbeddingCtx):
    """Inference: lookup-direct, zeros-on-miss, no buffers
    (ref: persia/ctx.py:1077-1133)."""

    def __init__(self, model, state: TrainState, worker, embedding_config, mesh=None):
        super().__init__(worker, embedding_config, mesh=mesh)
        enable_compile_cache()
        self.model = model
        self.state = state
        self._eval_step = build_eval_step(model)

    def predict(self, batch: PersiaBatch) -> np.ndarray:
        emb_batches = self.worker.forward_directly(batch, train=False)
        device_batch, _ = self.prepare_features(batch, emb_batches)
        return np.asarray(self._eval_step(self.state, device_batch))

    def predict_from_bytes(self, raw: bytes) -> np.ndarray:
        """(ref: get_embedding_from_bytes, persia/ctx.py:637-652)"""
        return self.predict(PersiaBatch.from_bytes(raw))
