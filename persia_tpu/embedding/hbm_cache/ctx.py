"""CachedTrainCtx: the TrainCtx-shaped user API of the HBM cache tier
(sync pipelined steps; the async stream lives in stream.py)."""


from __future__ import annotations

import ctypes
import os
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from persia_tpu.compile_cache import enable_compile_cache
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
from persia_tpu.tracing import accumulate, span, stage_span, wait_span

logger = get_default_logger("persia_tpu.hbm_cache")

# ------------------------------------------------------------------ ctypes


from persia_tpu.embedding.hbm_cache.directory import CacheDirectory  # noqa: F401
from persia_tpu.embedding.hbm_cache.groups import (  # noqa: F401
    CacheLayout,
    CachedTrainState,
    _apply_aux,
    _apply_aux_ring,
    _bucket,
    _lazy_pool,
    _model_emb_from_gathered,
    _restore_rows,
    _state_init_consts,
    init_cached_tables,
)
from persia_tpu.embedding.hbm_cache.step import (  # noqa: F401
    build_cached_eval_step,
    build_cached_train_step,
)
from persia_tpu.embedding.hbm_cache.tier import (  # noqa: F401
    CachedEmbeddingTier,
    _position_index,
)
from persia_tpu.embedding.hbm_cache.stream import run_train_stream

class CachedTrainCtx:
    """Training context for the HBM-cached hybrid tier — the TrainCtx-shaped
    API (train_step / eval_batch / dump_checkpoint / load_checkpoint) with
    on-device sparse updates and write-back tier migration.

    Pipelined by default: ``train_step`` dispatches the jitted step and
    defers the previous step's eviction write-back + metric fetch, so host
    preprocessing for step N+1 overlaps device compute of step N (the
    reference hides PS latency the same way with concurrent lookup workers,
    forward.rs:640-779). Call with ``fetch_metrics=False`` to keep the
    loop free of device syncs; ``drain()``/``last_metrics()`` at the end.
    """

    def __init__(
        self,
        model,
        dense_optimizer,
        embedding_optimizer,
        worker,
        embedding_config: EmbeddingConfig,
        cache_rows: "int | Dict[int, int]" = 1 << 20,
        loss_fn=None,
        table_dtype=jnp.float32,
        init_seed: Optional[int] = None,
        mesh=None,
        wb_wire_dtype: str = "float32",
        ps_slots: Sequence[str] = (),
        admit_touches: int = 1,
        aux_wire_dtype: str = "float32",
        ps_wire_dtype: str = "float32",
        dynamic_loss_scale: bool = False,
        loss_scale_init: float = float(2 ** 15),
        loss_scale_growth_interval: int = 2000,
        loss_scale_max: float = float(2 ** 24),
        wb_ring_rows: int = 1 << 20,
        health_probe: Optional[bool] = None,
        health_clip_norm: Optional[float] = None,
        health_scrub_at_fence: Optional[bool] = None,
        feed_threads: Optional[int] = None,
        feed_shards: Optional[int] = None,
    ):
        self.model = model
        self.dense_optimizer = dense_optimizer
        self.sparse_cfg = embedding_optimizer.config
        self.worker = worker
        self.embedding_config = embedding_config
        # DP mesh: batch-dim inputs shard over "data", cache pools + aux
        # scatters replicate; XLA reduces the sparse scatter deltas across
        # replicas exactly like replicated dense params (the capacity tier's
        # multi-chip story — the PS side is already sharded host-side)
        self.mesh = mesh
        if wb_wire_dtype not in ("float32", "bfloat16"):
            raise ValueError(f"wb_wire_dtype must be float32/bfloat16, got {wb_wire_dtype!r}")
        # bf16 eviction wire halves the d2h bytes that bound the eviction
        # steady state (the reference ships f16 wires); default stays f32
        # because the cached tier is otherwise bit-exact vs the pure-PS path
        self._wb_bf16 = wb_wire_dtype == "bfloat16"
        # standing per-group DEVICE eviction rings (stream restores gather
        # from here in ONE program per group; see _apply_aux_ring). Sized in
        # PADDED rows; the stream's allocator back-pressures when the
        # in-flight window would overrun.
        self.wb_ring_rows = int(wb_ring_rows)
        self._ev_rings: Dict[str, jnp.ndarray] = {}
        # live-migration bookkeeping (tiering): the constructor args a
        # fence-point re-registration rebuilds the tier/step from, the
        # explicit ps exclude set as it evolves, and the migration hooks
        self.cache_rows = cache_rows
        self._admit_touches = int(admit_touches)
        self._aux_wire_dtype = aux_wire_dtype
        self._loss_fn = loss_fn
        self._ps_wire_dtype = ps_wire_dtype
        self._ls_growth_interval = loss_scale_growth_interval
        self._ls_max = loss_scale_max
        self._ps_exclude: Set[str] = set(ps_slots)
        self._auto_tier = None
        self._pending_migration: Optional[Dict] = None
        # sharded feeder (round 14): feed_threads sizes the native walker
        # pool (None -> PERSIA_FEED_THREADS, pure throughput knob);
        # feed_shards pins the directory partition count (None ->
        # PERSIA_FEED_SHARDS, else 8 when threads > 1). The tier resolves
        # the defaults; the RESOLVED values are remembered here so the
        # fence-point migration rebuild reconstructs the same partition.
        self.tier = CachedEmbeddingTier(
            worker, self.sparse_cfg, cache_rows, embedding_config,
            init_seed=init_seed, ps_slots=ps_slots,
            admit_touches=admit_touches, aux_wire_dtype=aux_wire_dtype,
            feed_threads=feed_threads, feed_shards=feed_shards,
        )
        self._feed_threads = self.tier.feed_threads
        self._feed_shards = self.tier.feed_shards
        # feature groups containing cached slots: the PS-side Adam beta
        # powers of EVERY one of them mirror the device's per-step advance
        self._cached_groups = tuple(sorted({
            embedding_config.group_of(s)
            for g in self.tier.groups for s in g.slots
        }))
        self._state_consts = _state_init_consts(self.sparse_cfg)
        if ps_wire_dtype not in ("float32", "bfloat16", "int8"):
            raise ValueError(
                f"ps_wire_dtype must be float32/bfloat16/int8, got {ps_wire_dtype!r}"
            )
        self.dynamic_loss_scale = dynamic_loss_scale
        self._loss_scale_init = loss_scale_init
        # "int8" = bytegrad-style absmax quantization of the GRADIENT-RETURN
        # wire with a device-resident error-feedback residual (see
        # build_cached_train_step); the forward checkout wire stays bf16
        # (embedding VALUES do not tolerate int8 the way EF'd gradients do)
        self._ps_int8 = ps_wire_dtype == "int8"
        self._ps_residual: Dict[int, jnp.ndarray] = {}
        # numerical-health layer (persia_tpu/health): the on-device probe
        # tail + finite gate and the fence-point PS row scrubber. Defaults
        # follow PERSIA_HEALTH=1; explicit flags override the env.
        from persia_tpu.health import health_enabled

        self._health_probe = (
            health_enabled() if health_probe is None else bool(health_probe)
        )
        self._health_clip_norm = health_clip_norm
        self._health_scrub = (
            self._health_probe
            if health_scrub_at_fence is None
            else bool(health_scrub_at_fence)
        )
        self._step = build_cached_train_step(
            model, dense_optimizer, self.sparse_cfg, self.tier.groups,
            loss_fn=loss_fn,
            ps_grad_wire=ps_wire_dtype,
            dynamic_loss_scale=dynamic_loss_scale,
            growth_interval=loss_scale_growth_interval,
            max_scale=loss_scale_max,
            sentinel_probe=self._health_probe,
            guard_clip_norm=health_clip_norm,
        )
        self._eval = build_cached_eval_step(model, self.tier.groups)
        # forward-side ps wire: stage PS-tier entries in the same reduced
        # dtype the gradients return in (host->device rows are the other
        # half of the PS tier's link bill); int8 grad wire keeps bf16 here
        self._ps_stage_dtype = (
            np.dtype("bfloat16")
            if ps_wire_dtype in ("bfloat16", "int8") else None
        )
        self.table_dtype = table_dtype
        self.state: Optional[CachedTrainState] = None
        # concurrent device->host gradient/eviction fetch pool for the
        # stream's write-back thread: each fetch pays the full link
        # round-trip, so batched fetches MUST overlap (a serial loop is
        # latency x count)
        self._fetch_pool_obj = None
        # deferred write-back: (evict_meta, device payload, device header,
        # label shape) of the most recent dispatched step
        self._pending = None
        self._pending_signs: Set[int] = set()
        self._last_metrics: Optional[Dict] = None
        # (device header, label shape) of a fetch_final=False stream's last
        # step — materialized lazily by last_metrics()
        self._last_header_dev = None
        # per-group 0-row stand-ins for absent aux pieces (_group_empties)
        self._empties: Dict[str, Dict[str, jnp.ndarray]] = {}
        # K-step fused dispatch program (lazy; see _dispatch_packed) and
        # the most recent train_stream's dispatch/feeder accounting
        self._kstep_jit = None
        self._stream_stats: Optional[Dict] = None
        # the last stream's lanes and time accounting
        # (parallel/stage_graph.py). _stage_rebuild_hooks are copied onto
        # each stream's StageGraph and fire at a drained fence after a
        # tier migration (StageGraph.rebuild). ``state``/``_ev_rings`` are
        # written from one thread: the dispatcher (the caller of
        # train_stream or of the sync train_step).
        self._stage_graph = None
        self._stage_rebuild_hooks: List[Callable[[int], None]] = []
        # crash-consistent job state (persia_tpu.jobstate): manifest epoch
        # of the last committed fence (journal-id namespace), the global
        # step counter fences/journal ids run on, and a deferred resume
        # blob applied when init_state builds the state template
        self._job_epoch: Optional[int] = None
        self._global_step: int = 0
        self._resume_state_bytes: Optional[bytes] = None
        self.last_resume_info: Optional[Dict] = None

    def __enter__(self):
        enable_compile_cache()
        self.worker.register_optimizer(self.sparse_cfg)
        return self

    def __exit__(self, *exc):
        self.drain()
        return False

    # ------------------------------------------------------------- lifecycle

    def init_state(self, rng, sample_inputs: Dict, layout: CacheLayout) -> CachedTrainState:
        import optax

        tables, emb_state = init_cached_tables(
            self.tier.groups, self.sparse_cfg, dtype=self.table_dtype
        )
        by_name = {g.name: g for g in self.tier.groups}
        stacked_gathered = {
            gname: tables[gname][jnp.asarray(rows)]
            for gname, rows in sample_inputs["stacked_rows"].items()
        }
        raw_gathered = {
            name: tables[self.tier._slot_group[name].name][jnp.asarray(rows)]
            for name, rows in sample_inputs["raw_rows"].items()
        }
        ps_model_inputs = None
        if sample_inputs.get("ps_emb"):
            from persia_tpu.parallel.train_step import (
                _embedding_model_inputs, _split_emb,
            )

            ps_diff, ps_static = _split_emb(sample_inputs["ps_emb"])
            ps_model_inputs = _embedding_model_inputs(
                [jnp.asarray(d) for d in ps_diff], ps_static
            )
        model_emb = _model_emb_from_gathered(
            self.tier.groups,
            {
                k: (
                    {kk: jnp.asarray(vv) for kk, vv in v.items()}
                    if isinstance(v, dict) else v
                )
                for k, v in sample_inputs.items()
            },
            layout,
            stacked_gathered,
            raw_gathered,
            pad_row=lambda gname: by_name[gname].rows,
            ps_model_inputs=ps_model_inputs,
        )
        variables = self.model.init(
            rng, sample_inputs["dense"], model_emb, train=False
        )
        params = variables["params"]
        ls = None
        if self.dynamic_loss_scale:
            from persia_tpu.parallel.train_step import LossScaleState

            ls = LossScaleState(
                scale=jnp.asarray(self._loss_scale_init, jnp.float32),
                good_steps=jnp.zeros((), jnp.int32),
            )
        self.state = CachedTrainState(
            params=params,
            batch_stats=variables.get("batch_stats", {}),
            opt_state=self.dense_optimizer.init(params),
            tables=tables,
            emb_state=emb_state,
            emb_batch_state=jnp.ones((2,), dtype=jnp.float32),
            step=jnp.zeros((), dtype=jnp.int32),
            loss_scale=ls,
        )
        if self._resume_state_bytes is not None:
            # deferred resume (persia_tpu.jobstate): the manifest captured
            # the state at a post-flush fence (cold pools), so overlaying
            # it on the fresh template reproduces the fence exactly
            import flax.serialization

            self.state = flax.serialization.from_bytes(
                self.state, self._resume_state_bytes
            )
            self._resume_state_bytes = None
        rep = self._replicated()
        if rep is not None:
            self.state = jax.tree.map(
                lambda x: jax.device_put(x, rep), self.state
            )
        return self.state

    # ------------------------------------------------------------ train/eval

    def _sync_hazard_gate(self, gname: str, miss_signs: np.ndarray):
        if self._pending_signs and not self._pending_signs.isdisjoint(
            miss_signs.tolist()
        ):
            self._land_pending()  # after landing, the PS probe sees them warm
        return None

    def _fetch_pool(self):
        """Pool for CONCURRENT device→host fetches in the stream's
        write-back thread (each fetch pays a full link round-trip)."""
        self._fetch_pool_obj = _lazy_pool(self._fetch_pool_obj, "cache-fetch")
        return self._fetch_pool_obj

    def _replicated(self):
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P())

    def _stage(self, device_inputs, miss_aux, cold_aux, evict_aux):
        """Host→device staging with mesh shardings when a DP mesh is set:
        batch-dim leaves shard over ``data`` (dense/labels (B,·); stacked
        row/scale matrices on their middle axis), aux scatters replicate
        (they address the replicated cache pools).

        Every input here is a FRESH per-step host buffer (_BufRing hands
        out new arrays; see its docstring for the reuse-race history), so
        the asynchronous ``device_put``s need no completion barrier — the
        buffers stay alive via the queue items until consumed, and nothing
        rewrites them. A barrier here would serialize the feeder behind
        every transfer, so do not add one back without re-proving the
        buffers' lifetime story (chip_smoke.py's two-run bit-identity
        check is the on-chip test of it)."""
        if self.mesh is None:
            return (
                jax.device_put(device_inputs), jax.device_put(miss_aux),
                jax.device_put(cold_aux), jax.device_put(evict_aux),
            )
        from jax.sharding import NamedSharding, PartitionSpec as P

        bsh = NamedSharding(self.mesh, P("data"))
        mid = NamedSharding(self.mesh, P(None, "data"))
        rep = self._replicated()
        di = {
            "dense": [jax.device_put(x, bsh) for x in device_inputs["dense"]],
            "labels": [jax.device_put(x, bsh) for x in device_inputs["labels"]],
            "stacked_rows": {
                k: jax.device_put(v, mid)
                for k, v in device_inputs["stacked_rows"].items()
            },
            "raw_rows": {
                k: jax.device_put(v, bsh)
                for k, v in device_inputs["raw_rows"].items()
            },
        }
        if "stacked_scale" in device_inputs:
            di["stacked_scale"] = {
                k: jax.device_put(v, mid)
                for k, v in device_inputs["stacked_scale"].items()
            }
        if "ps_emb" in device_inputs:
            ps = []
            for e in device_inputs["ps_emb"]:
                if "pooled" in e:
                    ps.append({"pooled": jax.device_put(e["pooled"], bsh)})
                elif "pool_index" in e:  # device-pooled sum slot
                    entry = {
                        "distinct": jax.device_put(e["distinct"], rep),
                        "pool_index": jax.device_put(e["pool_index"], bsh),
                    }
                    if "pool_counts" in e:
                        entry["pool_counts"] = jax.device_put(e["pool_counts"], bsh)
                    ps.append(entry)
                else:
                    ps.append({
                        "distinct": jax.device_put(e["distinct"], rep),
                        "index": jax.device_put(e["index"], bsh),
                        "mask": jax.device_put(e["mask"], bsh),
                    })
            di["ps_emb"] = ps
        return (
            di,
            jax.device_put(miss_aux, rep),
            jax.device_put(cold_aux, rep),
            jax.device_put(evict_aux, rep),
        )

    def _group_empties(self, gname: str):
        """Cached 0-row device arrays standing in for absent aux pieces, so
        the fused ``_apply_aux`` keeps ONE dispatch per touched group."""
        em = self._empties.get(gname)
        if em is None:
            g = next(gr for gr in self.tier.groups if gr.name == gname)
            rep = self._replicated()
            put = (
                jax.device_put if rep is None
                else (lambda a: jax.device_put(a, rep))
            )
            aux_dt = self.tier.aux_np_dtype
            em = self._empties[gname] = {
                "rows": put(np.empty(0, dtype=np.int32)),
                "entries": put(
                    np.empty((0, g.dim + g.state_dim), dtype=aux_dt)
                ),
                "emb": put(np.empty((0, g.dim), dtype=aux_dt)),
            }
        return em

    def ring_rows(self, gname: str) -> int:
        """Standing-ring height for a group: per-step evictions are bounded
        by the group's own cache rows, so a ring a couple of cache-sizes
        tall covers any realistic in-flight window without allocating the
        global ceiling for tiny caches (a 100-row test cache does not need
        a 2^20-row ring)."""
        g = next(gr for gr in self.tier.groups if gr.name == gname)
        return min(self.wb_ring_rows, max(4096, 2 * g.rows))

    def _ev_ring(self, gname: str) -> jnp.ndarray:
        """The group's standing eviction ring (lazy; replicated on a mesh)."""
        ring = self._ev_rings.get(gname)
        if ring is None:
            g = next(gr for gr in self.tier.groups if gr.name == gname)
            dt = jnp.bfloat16 if self._wb_bf16 else jnp.float32
            ring = jnp.zeros(
                (self.ring_rows(gname), g.dim + g.state_dim), dtype=dt
            )
            rep = self._replicated()
            ring = jax.device_put(ring) if rep is None else jax.device_put(ring, rep)
            self._ev_rings[gname] = ring
        return ring

    def _apply_feed(self, miss_aux, cold_aux, evict_aux, evict_meta=None):
        """The FEED stage: ONE fused aux program per touched group
        (evict-payload read → ring write → warm scatter → cold scatter;
        ``_apply_aux``/``_apply_aux_ring``). Returns the per-group eviction
        payloads for the write-back thread's bounded d2h fetch."""
        evict_payload = {}
        touched = set(miss_aux) | set(cold_aux) | set(evict_aux)
        if not touched:
            return evict_payload
        tables = dict(self.state.tables)
        emb_state = dict(self.state.emb_state)
        for gname in sorted(touched):
            em = self._group_empties(gname)
            ev_rows = evict_aux.get(gname, em["rows"])
            m_rows, m_entries = miss_aux.get(
                gname, (em["rows"], em["entries"])
            )
            c_rows, c_emb = cold_aux.get(gname, (em["rows"], em["emb"]))
            ring_pos = -1
            if evict_meta and gname in evict_meta:
                ring_pos = evict_meta[gname][2]
            # one aux dispatch, named by the padded piece sizes that key its
            # program: a size not seen before compiles inside this span
            with stage_span(
                "ctx.apply_aux", group=gname, ev=ev_rows.shape[0],
                miss=m_rows.shape[0], cold=c_rows.shape[0], ring=ring_pos >= 0,
            ):
                if ring_pos >= 0:
                    (tables[gname], emb_state[gname],
                     self._ev_rings[gname], payload) = _apply_aux_ring(
                        tables[gname], emb_state[gname],
                        self._ev_ring(gname), jnp.int32(ring_pos),
                        ev_rows, m_rows, m_entries, c_rows, c_emb,
                        self._state_consts, self._wb_bf16,
                    )
                else:
                    tables[gname], emb_state[gname], payload = _apply_aux(
                        tables[gname], emb_state[gname], ev_rows,
                        m_rows, m_entries, c_rows, c_emb,
                        self._state_consts, self._wb_bf16,
                    )
            if gname in evict_aux:
                evict_payload[gname] = payload
        self.state = self.state.replace(tables=tables, emb_state=emb_state)
        return evict_payload

    def _dispatch(
        self, device_inputs, layout, miss_aux, cold_aux, restore_aux,
        evict_aux, evict_meta=None,
    ):
        """Dispatch the per-step device programs in order: the FEED stage
        (``_apply_feed``) + in-flight restores + the main step. Inputs must
        already be device arrays."""
        evict_payload = self._apply_feed(
            miss_aux, cold_aux, evict_aux, evict_meta
        )
        if restore_aux:
            tables = dict(self.state.tables)
            emb_state = dict(self.state.emb_state)
            n_restores = sum(len(r) for r in restore_aux.values())
            with span("ctx.restores", n=n_restores):
                for gname, restores in restore_aux.items():
                    for payload, src_idx, dst_rows in restores:
                        if payload is None:
                            # stream gate: gather from the group's standing
                            # eviction ring — the producing steps dispatch
                            # before this one (seq order), so their
                            # dynamic_update_slice writes precede this read
                            # in device program order
                            payload = self._ev_ring(gname)
                        tables[gname], emb_state[gname] = _restore_rows(
                            tables[gname], emb_state[gname], payload,
                            src_idx, dst_rows,
                        )
            self.state = self.state.replace(tables=tables, emb_state=emb_state)
        if self._ps_int8 and "ps_emb" in device_inputs:
            # thread the device-resident error-feedback residual through
            # the step; keyed by flat length so a bucketed-shape change
            # resets it to zeros (positions mean different signs then)
            total = 0
            for e in device_inputs["ps_emb"]:
                shape = (
                    e["pooled"].shape if "pooled" in e
                    else e["distinct"].shape
                )
                total += int(np.prod(shape))
            res = self._ps_residual.get(total)
            if res is None:
                z = np.zeros((total,), np.float32)
                rep = self._replicated()
                res = (
                    jax.device_put(z) if rep is None
                    else jax.device_put(z, rep)
                )
            device_inputs = dict(device_inputs)
            device_inputs["ps_gres"] = res
        with span("ctx.main_step"):
            self.state, header, ps_gpacked = self._step(
                self.state, device_inputs, layout
            )
        if self._ps_int8 and isinstance(ps_gpacked, tuple):
            q, scales, new_res = ps_gpacked
            if new_res.shape[0]:
                self._ps_residual[new_res.shape[0]] = new_res
            ps_gpacked = (q, scales)
        return header, evict_payload, ps_gpacked

    # ------------------------------------------------- K-step fused dispatch

    def _kstep_fn(self):
        """The jitted K-step program: for each packed step, apply its aux
        scatters (evict-payload read → ring write → warm/cold scatters),
        then run the main train step — K steps, ONE dispatch. Ordering
        inside the trace is exactly the single-step path's: step i's aux
        reads the post-step-(i-1) tables, so packing changes no math
        (tests pin stream-vs-sync bit parity through packs). Restores are
        excluded by the stream's packing predicate, which is what makes
        the unroll safe without any in-window hazard analysis."""
        if self._kstep_jit is None:
            def run(state, rings, steps, layout):
                rings = dict(rings)
                headers, payloads = [], []
                for di, aux in steps:
                    if aux:
                        tables = dict(state.tables)
                        emb_state = dict(state.emb_state)
                    step_payloads = {}
                    for gname in sorted(aux):
                        a = aux[gname]
                        ev_rows = a["ev"]
                        m_rows, m_entries = a["miss"]
                        c_rows, c_emb = a["cold"]
                        if "ring_pos" in a:
                            (tables[gname], emb_state[gname], rings[gname],
                             payload) = _apply_aux_ring(
                                tables[gname], emb_state[gname],
                                rings[gname], a["ring_pos"],
                                ev_rows, m_rows, m_entries, c_rows, c_emb,
                                self._state_consts, self._wb_bf16,
                            )
                        else:
                            tables[gname], emb_state[gname], payload = _apply_aux(
                                tables[gname], emb_state[gname], ev_rows,
                                m_rows, m_entries, c_rows, c_emb,
                                self._state_consts, self._wb_bf16,
                            )
                        step_payloads[gname] = payload
                    if aux:
                        state = state.replace(
                            tables=tables, emb_state=emb_state
                        )
                    state, header, _ps = self._step(state, di, layout)
                    headers.append(header)
                    payloads.append(step_payloads)
                return state, rings, headers, payloads

            self._kstep_jit = jax.jit(
                run, static_argnums=(3,), donate_argnums=(0, 1)
            )
        return self._kstep_jit

    def _dispatch_packed(self, items):
        """Dispatch K staged steps as one fused program. ``items``:
        ``[(di, layout, miss_aux, cold_aux, evict_aux, evict_meta), ...]``
        — already device-staged, hazard-free (no restore_aux, no ps_emb),
        one shared layout. Returns ``(headers, payloads)``: the per-step
        headers and per-step ``{group: eviction payload}`` dicts for the
        write-back thread's bounded d2h fetches."""
        layout = items[0][1]
        steps = []
        ring_names = set()
        for di, _lay, miss_aux, cold_aux, evict_aux, evict_meta in items:
            aux = {}
            for gname in sorted(set(miss_aux) | set(cold_aux) | set(evict_aux)):
                em = self._group_empties(gname)
                entry = {
                    "ev": evict_aux.get(gname, em["rows"]),
                    "miss": miss_aux.get(gname, (em["rows"], em["entries"])),
                    "cold": cold_aux.get(gname, (em["rows"], em["emb"])),
                }
                ring_pos = -1
                if evict_meta and gname in evict_meta:
                    ring_pos = evict_meta[gname][2]
                if ring_pos >= 0:
                    # traced scalar (not static): ring positions change
                    # every step and must not key the jit cache
                    entry["ring_pos"] = np.int32(ring_pos)
                    ring_names.add(gname)
                aux[gname] = entry
            steps.append((di, aux))
        rings = {gn: self._ev_ring(gn) for gn in sorted(ring_names)}
        state, rings_out, headers, payloads = self._kstep_fn()(
            self.state, rings, tuple(steps), layout
        )
        self.state = state
        self._ev_rings.update(rings_out)
        return headers, payloads

    def stream_stats(self) -> Optional[Dict]:
        """Dispatch/feeder accounting of the most recent ``train_stream``:
        ``dispatch_k``, ``packs``, ``packed_steps``, ``single_steps``,
        ``feeder_busy_s``, ``wall_s``, plus the dense-plane sync record
        (``sync_mode``, ``dense_wire_bytes_per_step``) that prices the
        dense collective, so a regression in either half is visible from
        these fields alone. ``stages`` and ``waits`` hold the stream's one time
        accounting (tracing.StageAccumulator): per work span ``{n, busy_s,
        max_s}``, per wait span ``{n, wait_s, max_s}``; the closing
        ``stream.drain`` waits, ``drain()``'s included, arrive after
        ``wall_s`` is taken."""
        return self._stream_stats

    @property
    def sync_mode(self) -> str:
        """Dense-plane sync label for records: the cached tier's dense half
        rides XLA's implicit psum on a DP mesh ("implicit-psum"), or no
        collective at all on one device ("local"). The explicit quantized /
        sharded modes live on the hybrid TrainCtx (``dense_sync=``); this
        property keeps the vocabulary shared so bench rows compare."""
        if self.mesh is not None and int(self.mesh.shape["data"]) > 1:
            return "implicit-psum"
        return "local"

    def dense_wire_bytes_per_step(self) -> int:
        """Modeled per-replica dense collective bytes/step
        (grad_sync.dense_sync_wire_bytes over the live dense param count);
        0 before state init or off-mesh."""
        if self.state is None or self.mesh is None:
            return 0
        from persia_tpu.parallel.grad_sync import (
            dense_param_count,
            dense_sync_wire_bytes,
        )

        return dense_sync_wire_bytes(
            self.sync_mode,
            dense_param_count(self.state.params),
            int(self.mesh.shape["data"]),
        )

    def _ps_forward(self, batch: PersiaBatch):
        """Forward the PS-tier slot subset through the worker's forward-ref
        machinery. Returns (ref, emb_batches, counts, entries) or None when
        the batch carries no ps slots. The ref's staleness slot is ALWAYS
        released on failure after the forward — any exception past
        put_forward_ids aborts before propagating."""
        if not self.tier.ps_slots:
            return None
        ps_feats = [
            f for f in batch.id_type_features if f.name in self.tier.ps_slots
        ]
        if not ps_feats:
            return None
        from persia_tpu.ctx import stage_embeddings

        ref = self.worker.put_forward_ids(PersiaBatch(ps_feats, requires_grad=False))
        try:
            embs = self.worker.forward_batch_id(ref, train=True)
            entries, counts = stage_embeddings(embs, dtype=self._ps_stage_dtype)
        except BaseException:
            self.worker.abort_gradient(ref)
            raise
        return ref, embs, counts, entries

    def _apply_ps_grads(self, ps_item, ps_gpacked, journal_step=None) -> None:
        """Unpack the step's packed ps-slot gradients (one layout
        convention: unpack_step_grads) and return them to the worker; the
        ref is released either by the update or by an abort on failure.
        ``journal_step`` tags the apply for the PS apply-journal when the
        ctx runs under a job-state manager (exactly-once resume)."""
        from persia_tpu.parallel.train_step import unpack_step_grads

        jid = None
        if journal_step is not None and self._job_epoch is not None:
            from persia_tpu.jobstate import make_journal_id

            jid = make_journal_id(self._job_epoch, journal_step)
        ref, embs, counts, entries = ps_item
        try:
            if isinstance(ps_gpacked, tuple):
                # int8 wire: (q int8, scales f32 per slot [+finite]); grads
                # were unscaled on device, so scale_factor stays 1.0
                from persia_tpu.parallel.grad_sync import dequantize_int8_np

                q = np.asarray(ps_gpacked[0])
                scales = np.asarray(ps_gpacked[1]).astype(np.float32)
                scale_factor = 1.0
                if self.dynamic_loss_scale or self._health_probe:
                    if not scales[-1] > 0.5:  # overflow/non-finite: skip-step
                        self.worker.abort_gradient(ref)
                        return
                    scales = scales[:-1]
                grads = [
                    dequantize_int8_np(g, s)
                    for g, s in zip(
                        unpack_step_grads(q, {"emb": entries}), scales
                    )
                ]
            else:
                gp = np.asarray(ps_gpacked)
                if gp.dtype != np.float32:  # bf16 ps-grad wire
                    gp = gp.astype(np.float32)
                scale_factor = 1.0
                if self.dynamic_loss_scale or self._health_probe:
                    # buffer tail = [scale | finite] (build_cached_train_step)
                    scale_factor = float(gp[-2])
                    if not gp[-1] > 0.5:  # overflow/non-finite: skip-step
                        self.worker.abort_gradient(ref)
                        return
                    gp = gp[:-2]
                grads = unpack_step_grads(gp, {"emb": entries})
            slot_grads = {
                eb.name: (g if d is None else g[:d])
                for eb, g, d in zip(embs, grads, counts)
            }
            if jid is not None:
                self.worker.update_gradient_batched(
                    ref, slot_grads, scale_factor=scale_factor, journal_id=jid
                )
            else:
                self.worker.update_gradient_batched(
                    ref, slot_grads, scale_factor=scale_factor
                )
        except BaseException:
            self.worker.abort_gradient(ref)
            raise

    def train_step(self, batch: PersiaBatch, fetch_metrics: bool = True):
        (device_inputs, layout, miss_aux, cold_aux, restore_aux, evict_aux,
         evict_meta) = self.tier.prepare_batch(
            batch, hazard_gate=self._sync_hazard_gate
        )
        # mixed-tier: worker/PS-served slots (hash-stack or excluded) flow
        # through the same forward-ref machinery the hybrid ctx uses; their
        # gradients come back as a step output
        ps_item = self._ps_forward(batch)
        try:
            if ps_item is not None:
                _ref, embs, _counts, entries = ps_item
                device_inputs["ps_emb"] = entries
                layout = CacheLayout(
                    stacked=layout.stacked,
                    ps=tuple(eb.name for eb in embs),
                )
            if self.state is None:
                self.init_state(jax.random.PRNGKey(0), device_inputs, layout)
            # explicit async host→device staging: passing numpy leaves
            # straight into jit makes the arg conversion a synchronous
            # per-leaf transfer inside the dispatch
            device_inputs, miss_aux, cold_aux, evict_aux = self._stage(
                device_inputs, miss_aux, cold_aux, evict_aux
            )
            header, evict_payload, ps_gpacked = self._dispatch(
                device_inputs, layout, miss_aux, cold_aux, restore_aux,
                evict_aux, evict_meta,
            )
        except Exception:
            # any failure after the forward must release the staleness slot
            # + stashed layout, or the worker buffers leak (same contract as
            # TrainCtx.train_step)
            if ps_item is not None:
                self.worker.abort_gradient(ps_item[0])
            raise
        if ps_item is not None:
            # the PS-tier gradient return is an inherent d2h (same as the
            # hybrid path); the helper aborts the ref itself on failure.
            # Ordering vs the deferred eviction write-back below is a
            # non-issue: the constructor rejects feature groups spanning
            # both tiers, so these gradients can never touch a sign an
            # eviction wrote back (same invariant the stream path's
            # _flush_ps documents).
            self._apply_ps_grads(
                ps_item, ps_gpacked, journal_step=self._global_step
            )
        prev = self._pending
        self._pending = (
            evict_meta, evict_payload, header, device_inputs["labels"][0].shape
        )
        self._pending_signs = {
            int(s)
            for ev_signs, k, _rp in evict_meta.values()
            for s in ev_signs[:k]
        }
        if prev is not None:
            self._write_back_only(prev)
        if self.sparse_cfg.kind == OPTIMIZER_ADAM:
            # PS-side Adam beta powers advance once per gradient batch,
            # mirroring the device's shared emb_batch_state for EVERY
            # feature group holding cached slots, so write-backs land in a
            # store whose future updates use consistent powers. PS-tier
            # slots' groups advance inside the worker's gradient batch
            # instead — the constructor guarantees the two tier's feature
            # groups are disjoint, so no group can be advanced twice.
            for grp in self._cached_groups:
                self.tier.router.advance_batch_state(grp)
        self._global_step += 1  # the job-state fence/journal step counter
        if fetch_metrics:
            return self._fetch_metrics()
        return None

    def _write_back_only(self, pending) -> None:
        evict_meta, evict_payload, _header, _shape = pending
        self.tier.write_back(evict_meta, evict_payload)

    def _land_pending(self) -> None:
        """Force the deferred write-back to the PS (hazard or boundary)."""
        if self._pending is not None:
            self._fetch_metrics()  # also materializes header once
            self._write_back_only(self._pending)
            self._pending = None
            self._pending_signs = set()

    def _parse_header(self, h: np.ndarray, label_shape) -> Dict:
        """Host view of the step header — the layout is owned by ONE pair
        of decoders (parallel/train_step.py unpack_step_header[_dynamic]);
        this adapter only supplies the label shape."""
        from types import SimpleNamespace

        from persia_tpu.parallel.train_step import (
            unpack_step_header,
            unpack_step_header_dynamic,
        )

        shaped = {"labels": [SimpleNamespace(shape=label_shape)]}
        if self.dynamic_loss_scale:
            loss, preds, scale, finite = unpack_step_header_dynamic(h, shaped)
            return {
                "loss": loss, "preds": preds,
                "loss_scale": scale, "grads_finite": finite,
            }
        loss, preds = unpack_step_header(h, shaped)
        return {"loss": loss, "preds": preds}

    def _fetch_metrics(self) -> Dict:
        if self._pending is None:
            return self._last_metrics or {}
        _meta, _payload, header, label_shape = self._pending
        self._last_metrics = self._parse_header(np.asarray(header), label_shape)
        self._last_header_dev = None  # fresher than any stashed stream header
        return self._last_metrics

    def drain(self) -> Optional[Dict]:
        """Land any deferred write-back and return the last step's metrics
        (materializing a ``fetch_final=False`` stream's stashed header if
        that is the freshest result)."""
        graph = self._stage_graph  # the last stream's accounting takes the wait
        with accumulate(graph.acc if graph else None), wait_span("stream.drain"):
            if self._pending is not None:
                self._fetch_metrics()
                self._land_pending()
            return self.last_metrics()

    # -------------------------------------------------------------- pipeline

    def last_metrics(self) -> Optional[Dict]:
        if self._pending:
            return self._fetch_metrics()
        if self._last_header_dev is not None:
            header, label_shape = self._last_header_dev
            self._last_metrics = self._parse_header(
                np.asarray(header), label_shape
            )
            self._last_header_dev = None
        return self._last_metrics


    def sentinel_spec(self) -> Dict:
        """Shape the health sentinel needs to decode the probe tail —
        ``StreamSentinel.from_ctx(ctx)`` consumes this."""
        return {
            "n_groups": len(self.tier.groups),
            "dynamic_loss_scale": self.dynamic_loss_scale,
            "probe": self._health_probe,
        }

    def train_stream(self, *args, **kwargs):
        """Asynchronous pipelined stream training — see
        ``persia_tpu.embedding.hbm_cache.stream.run_train_stream``."""
        return run_train_stream(self, *args, **kwargs)

    def register_stage_rebuild(self, fn) -> None:
        """Register a fence-point stage-graph rebuild hook: ``fn(step)``
        fires inside every subsequent stream's drained fence right after a
        tier migration re-registered the groups (StageGraph.rebuild) —
        the extension point for promoting a migrated group into
        ``FusedTrainCtx`` proper (ROADMAP direction 1)."""
        self._stage_rebuild_hooks.append(fn)

    def eval_batch(self, batch: PersiaBatch) -> np.ndarray:
        # eval misses consult the PS, so a deferred eviction must land first
        self._land_pending()
        inputs, layout = self.tier.prepare_eval_batch(batch)
        if self.tier.ps_slots:
            from persia_tpu.ctx import stage_embeddings

            ps_feats = [
                f for f in batch.id_type_features
                if f.name in self.tier.ps_slots
            ]
            if ps_feats:
                ps_sub = PersiaBatch(ps_feats, requires_grad=False)
                emb_batches = self.worker.forward_directly(ps_sub, train=False)
                entries, _ = stage_embeddings(emb_batches)
                inputs["ps_emb"] = entries
                layout = CacheLayout(
                    stacked=layout.stacked,
                    ps=tuple(eb.name for eb in emb_batches),
                )
        if self.state is None:
            raise RuntimeError("eval before any train_step/init_state")
        # eval stays simple under a mesh: everything replicated is correct
        # (no gradient reduction to get right) and eval is off the hot path
        rep = self._replicated()
        inputs = jax.device_put(inputs) if rep is None else jax.device_put(inputs, rep)
        return np.asarray(self._eval(self.state, inputs, layout))

    # ------------------------------------------------------------ checkpoint

    def publish(self) -> int:
        """Serving-freshness valve: write every resident row to the PS (and
        its incremental-update manager) WITHOUT evicting — hot signs that
        never leave the cache would otherwise ship no online-serving deltas
        between checkpoints. Call on the serving cadence; costs one
        device→host read of the resident rows. Returns rows published."""
        self._land_pending()
        if self.state is None:
            return 0
        return self.tier.publish(self.state.tables, self.state.emb_state)

    def flush(self) -> None:
        """Write every cached row back to the PS (checkpoint boundary); the
        cache restarts cold."""
        self._land_pending()
        if self.state is None:
            return
        self.tier.flush(self.state.tables, self.state.emb_state)
        # the directory is drained; zero the pools so stale rows can never be
        # mistaken for fresh checkouts
        tables, emb_state = init_cached_tables(
            self.tier.groups, self.sparse_cfg, dtype=self.table_dtype
        )
        self.state = self.state.replace(tables=tables, emb_state=emb_state)

    def dump_checkpoint(self, dst: str, blocking: bool = True) -> None:
        self.flush()
        self.worker.dump(dst, blocking=blocking)

    def load_checkpoint(self, src: str) -> None:
        self.flush()
        self.worker.load(src)

    # ------------------------------------------------------- live migration

    def attach_auto_tier(self, controller) -> None:
        """Attach a ``tiering.AutoTierController``: its profiler taps the
        tier's admit walk from the next batch on, and the stream's fences
        drive planning/migration (``_maybe_migrate_at_fence``)."""
        self._auto_tier = controller
        self.tier.profiler = controller.profiler

    def set_feed_threads(self, threads: int) -> None:
        """Resize the sharded feeder's native walker pool (no-op on an
        unsharded tier). Thread count never affects output bits."""
        self._feed_threads = max(1, int(threads))
        self.tier.set_feed_threads(self._feed_threads)

    @property
    def auto_tier(self):
        return self._auto_tier

    def request_migration(
        self,
        to_cached: Sequence[str] = (),
        to_ps: Sequence[str] = (),
        cache_rows: "int | Dict[int, int] | None" = None,
        feed_shards: "int | None" = None,
    ) -> None:
        """Queue a manual tier migration; it applies at the NEXT stream
        snapshot fence (feeder parked, hazard ledger drained, manifest
        committed) — the only point where the PS provably holds the single
        authoritative copy of every moving slot. ``feed_shards`` reshards
        the feed partition in the same rebuild (0 forces unsharded); the
        drained fence is the only safe point, since resident rows cannot
        survive a change of their shard row-ranges."""
        self._pending_migration = {
            "to_cached": tuple(to_cached), "to_ps": tuple(to_ps),
            "cache_rows": cache_rows, "feed_shards": feed_shards,
        }

    def apply_migration(
        self,
        to_cached: Sequence[str] = (),
        to_ps: Sequence[str] = (),
        cache_rows: "int | Dict[int, int] | None" = None,
        feed_shards: "int | None" = None,
    ) -> None:
        """Re-register slots between the cached and ps tiers. The cache
        MUST be cold (every directory drained — i.e. immediately after
        ``flush``/``_fence_capture``): with all rows flushed, the PS holds
        the only copy of every embedding and the move is pure metadata —
        rebuild the tier (directories, salts, groups), the step programs
        (their traces close over the group list), and the device pools.

        Bit-parity contract: a run migrated at fence F matches a run
        RESUMED from F's manifest directly into the final placement — both
        start from the identical flushed PS state and run identical device
        programs from F on (tests/test_tiering.py pins it)."""
        to_cached, to_ps = set(to_cached), set(to_ps)
        if to_cached & to_ps:
            raise ValueError(
                f"slots in both directions: {sorted(to_cached & to_ps)}"
            )
        slots_cfg = self.embedding_config.slots_config
        for s in to_cached | to_ps:
            if s not in slots_cfg:
                raise KeyError(f"unknown slot {s!r} (not in embedding config)")
        for s in to_cached:
            if slots_cfg[s].hash_stack_config.enabled:
                raise ValueError(
                    f"slot {s!r} is hash-stacked: it is served by the "
                    "worker/PS path and cannot move into the cache tier"
                )
        cached_now = {s for g in self.tier.groups for s in g.slots}
        to_cached &= set(self.tier.ps_slots)  # drop no-op moves
        to_ps &= cached_now
        if (not (to_cached or to_ps) and cache_rows is None
                and feed_shards is None):
            return
        self._land_pending()
        for g in self.tier.groups:
            n = len(self.tier.dirs[g.name])
            if n:
                raise RuntimeError(
                    f"apply_migration with a warm cache: group {g.name!r} "
                    f"still holds {n} resident rows — flush first (the "
                    "stream applies migrations only at drained fences)"
                )
        init_seed = self.tier.init_seed
        profiler = self.tier.profiler
        new_exclude = (self._ps_exclude | to_ps) - to_cached
        rows = self.cache_rows if cache_rows is None else cache_rows
        # the drained fence is the ONLY safe point to change the feed
        # partition (reshard): every directory is cold, so new shard
        # row-ranges cannot orphan resident rows
        if feed_shards is not None:
            self._feed_shards = feed_shards if feed_shards >= 1 else None
        # the tier constructor re-validates the mixed-tier invariants
        # (feature-group disjointness, prefix-bit partitioning) against the
        # NEW placement — an invalid plan fails loudly here, pre-mutation
        self.tier = CachedEmbeddingTier(
            self.worker, self.sparse_cfg, rows, self.embedding_config,
            init_seed=init_seed, ps_slots=sorted(new_exclude),
            admit_touches=self._admit_touches,
            aux_wire_dtype=self._aux_wire_dtype,
            feed_threads=self._feed_threads,
            feed_shards=self._feed_shards if self._feed_shards else 0,
        )
        self._feed_shards = self.tier.feed_shards
        self.tier.profiler = profiler
        # regrouping can move slots between group salts — keep the sharded
        # profiler's routing consistent with the NEW directories
        if profiler is not None and getattr(profiler, "shards", None):
            profiler.set_slot_salts(self.tier.profiler_slot_salts())
        self.cache_rows = rows
        self._ps_exclude = new_exclude
        self._cached_groups = tuple(sorted({
            self.embedding_config.group_of(s)
            for g in self.tier.groups for s in g.slots
        }))
        # step/eval traces close over the group list — rebuild them, and
        # drop every group-shaped device cache (rings, empties, K-step jit,
        # int8 residuals); all are rebuilt lazily against the new groups
        self._step = build_cached_train_step(
            self.model, self.dense_optimizer, self.sparse_cfg,
            self.tier.groups,
            loss_fn=self._loss_fn,
            ps_grad_wire=self._ps_wire_dtype,
            dynamic_loss_scale=self.dynamic_loss_scale,
            growth_interval=self._ls_growth_interval,
            max_scale=self._ls_max,
            sentinel_probe=self._health_probe,
            guard_clip_norm=self._health_clip_norm,
        )
        self._eval = build_cached_eval_step(self.model, self.tier.groups)
        self._kstep_jit = None
        self._empties = {}
        self._ev_rings = {}
        self._ps_residual = {}
        if self.state is not None:
            tables, emb_state = init_cached_tables(
                self.tier.groups, self.sparse_cfg, dtype=self.table_dtype
            )
            rep = self._replicated()
            if rep is not None:
                tables = {
                    k: jax.device_put(v, rep) for k, v in tables.items()
                }
                emb_state = {
                    k: jax.device_put(v, rep) for k, v in emb_state.items()
                }
            self.state = self.state.replace(tables=tables, emb_state=emb_state)
        logger.info(
            "tier migration applied: -> cached %s, -> ps %s (ps tier now %s)",
            sorted(to_cached), sorted(to_ps), sorted(self.tier.ps_slots),
        )

    def _maybe_migrate_at_fence(self, gstep: int) -> bool:
        """Stream fence hook (feeder parked, write-back drained, ledger
        empty, manifest committed): apply a queued ``request_migration``
        and/or run the auto-tier controller's planning round. Returns True
        when the tier was re-registered — the stream then resets its ring
        accounting and re-reads the group salts."""
        from persia_tpu.tracing import record_event
        migrated = False
        req = self._pending_migration
        if req is not None:
            self._pending_migration = None
            n = len(req["to_cached"]) + len(req["to_ps"])
            with span(
                "tiering.migration", step=gstep,
                to_cached=len(req["to_cached"]), to_ps=len(req["to_ps"]),
            ):
                self.apply_migration(**req)
            get_metrics().counter(
                "persia_tpu_tiering_migrations",
                "slots live-migrated between sparse tiers at a fence",
            ).inc(n)
            record_event(
                "tiering.migrate", step=gstep,
                moves={
                    **{s: "->cached" for s in req["to_cached"]},
                    **{s: "->ps" for s in req["to_ps"]},
                },
            )
            migrated = True
        if self._auto_tier is not None:
            migrated = bool(self._auto_tier.on_fence(self, gstep)) or migrated
        return migrated

    # ------------------------------------------------- crash-consistent jobs

    def _fence_capture(self, job_mgr, step: int, occupancy: Dict):
        """Commit one job-state epoch at a drained stream fence (or from
        ``snapshot_job`` on the sync path): flush every resident cached row
        to the PS (the pools restart cold — checkout round-trips full
        [emb | state] entries, so the training math is unchanged), then
        capture PS shards + the full CachedTrainState (dense params,
        optimizer state, the now-cold pools, Adam emb_batch_state) + the
        pre-flush directory/ring occupancy + loader cursor + RNG streams
        under one manifest (persia_tpu.jobstate)."""
        import flax.serialization

        from persia_tpu import jobstate

        if self.state is not None:
            self.tier.flush(self.state.tables, self.state.emb_state)
            tables, emb_state = init_cached_tables(
                self.tier.groups, self.sparse_cfg, dtype=self.table_dtype
            )
            self.state = self.state.replace(tables=tables, emb_state=emb_state)
        router = self.tier.router
        if self._health_scrub:
            # repair any non-finite PS rows (flushed cache rows included)
            # BEFORE they are captured into the manifest; journaled so a
            # retried fence is exactly-once per (epoch, step, replica)
            from persia_tpu.health.scrub import scrub_router

            scrub_router(router, self._job_epoch or 0, step)
        components = {
            "cache.json": occupancy,
            "loader.json": {"consumed_batches": step},
        }
        if self._auto_tier is not None:
            # profiler sketch + current placements ride the manifest so a
            # resumed job keeps its access history (and its tier layout)
            components["tiering.json"] = self._auto_tier.export_state()
        manifest = jobstate.snapshot_job(
            job_mgr, step,
            state_bytes=(
                flax.serialization.to_bytes(self.state)
                if self.state is not None else None
            ),
            replicas=router.replicas,
            batch_advances=dict(getattr(router, "batch_advances", {})),
            components=components,
            meta={"kind": "cached_ctx"},
        )
        self._job_epoch = manifest.job_epoch
        self._global_step = step
        return manifest

    def snapshot_job(self, job_state, extra_occupancy: Optional[Dict] = None):
        """Sync-path step-fenced snapshot: land the deferred write-back,
        then fence-capture at the current global step. (The stream path
        fences itself — ``train_stream(snapshot_every=, job_state=)``.)"""
        from persia_tpu import jobstate

        self._land_pending()
        occupancy = {
            "resident_rows": {
                g.name: len(self.tier.dirs[g.name]) for g in self.tier.groups
            },
            "pending_ledger_entries": 0,
        }
        occupancy.update(extra_occupancy or {})
        return self._fence_capture(
            jobstate.coerce_manager(job_state), self._global_step, occupancy
        )

    def resume(self, job_state, restore_ps: bool = True, generators=None):
        """Rebuild the exact mid-epoch fence state from the newest good
        manifest: PS shards rewound (default — bit-identical replay) or
        kept with journal dedupe (``restore_ps=False``, exactly-once), the
        CachedTrainState overlaid when ``init_state`` runs, Adam batch
        advances re-applied, RNG streams restored. Returns the Manifest
        (resume the stream with ``train_stream(batches_from(manifest.step),
        start_step=manifest.step, ...)``) or None on a cold start."""
        from persia_tpu import jobstate

        mgr = jobstate.coerce_manager(job_state)
        router = self.tier.router
        manifest, info = jobstate.resume_job(
            mgr,
            replicas=router.replicas,
            rewind_ps=restore_ps,
            optimizer=self.sparse_cfg,
            generators=generators,
        )
        self.last_resume_info = info
        if manifest is None:
            self._job_epoch = 0
            self._global_step = 0
            return None
        if self._auto_tier is not None and manifest.has("tiering.json"):
            from persia_tpu.embedding.tiering.planner import TIER_PS

            self._auto_tier.load_state(manifest.read_json("tiering.json"))
            # re-register to the SAVED placement BEFORE touching dense.state:
            # the manifest's cache pools (and the state template the bytes
            # deserialize against) were captured under it, and the profiler's
            # history only makes sense against the layout it scored
            want_ps = {
                s for s, t in self._auto_tier.placements.items()
                if t == TIER_PS
            }
            tracked = set(self._auto_tier.placements)
            have_ps = set(self.tier.ps_slots) & tracked
            cached_now = {s for g in self.tier.groups for s in g.slots}
            self.apply_migration(
                to_cached=sorted((have_ps - want_ps) & tracked),
                to_ps=sorted(want_ps & cached_now),
            )
        if manifest.has("dense.state"):
            self._resume_state_bytes = manifest.read_blob("dense.state")
            if self.state is not None:
                import flax.serialization

                state = flax.serialization.from_bytes(
                    self.state, self._resume_state_bytes
                )
                rep = self._replicated()
                if rep is not None:
                    state = jax.tree.map(
                        lambda x: jax.device_put(x, rep), state
                    )
                self.state = state
                self._resume_state_bytes = None
        router.batch_advances = dict(info.get("batch_advances", {}))
        self._job_epoch = manifest.job_epoch
        self._global_step = manifest.step
        return manifest
