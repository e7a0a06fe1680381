"""Entry ``CachedTrainCtx.train_stream``: every table's working set in one HBM
pool, the native parameter server on the host behind it; the feeder, the
staging and the K-step packs at work in every step, the aux scatters, the
eviction d2h and the PS write-back wherever the traffic misses.

Signs are ``(table + 1) << 40 | id`` with no index prefix, so the reference
needs nothing of the program to name a row. The dense parameters are the
harness's own, from the seed.

``resident_from_start`` (traffic): the pool holds every row of every table
from the first step, as a job that has run for days holds its hot slice: the
adapter admits every sign to the program's directory and fills the pool's
rows on the device in one jitted call from the seed (the counter hash of
``perf/weights.py``, as the pinned entry fills its tables). No step then
misses. Without it the pool starts empty and a sign never seen is born on the
parameter server by the configuration's ``row_birth`` rule (the PS seed is the
run's seed folded to 32 bits), which the reference implements from its text.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Dict, List

import numpy as np

from perf import weights
from perf.entries.fused_pinned import KEY_SHIFT, dense_snapshot, persia_batch, slot_names
from perf.generators.zipf import table_rows
from perf.reference.dlrm import splitmix_uniform_rows


PIPELINE_DEPTH = 1  # the in-order pipeline: packs carry their own aux
WORKER_THREADS = 16
ADMIT_CHUNK = 1 << 20  # signs admitted to the directory a call


class Entry:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.rows = table_rows(config, traffic)
        self.names = slot_names(len(self.rows))
        self.dim = int(config["embedding_dim"])
        self.ps_seed = (self.seed ^ (self.seed >> 32)) & 0x7FFFFFFF
        self.ctx = None
        self._stats_window = None
        self.h2d_bytes = 0
        self.dispatch_k = int(traffic["dispatch_k"])
        self.resident = bool(traffic.get("resident_from_start", False))
        self.snapshot_after = (self.dispatch_k,)  # one pack: read after it
        self.adam_start = traffic.get("adam_start")  # {"count", "nu"}: see the traffic file

    # ------------------------------------------------------------- building

    def build(self) -> None:
        import jax
        import jax.numpy as jnp
        import optax

        from persia_tpu.config import EmbeddingConfig, SlotConfig
        from persia_tpu.embedding.hbm_cache import CachedTrainCtx
        from persia_tpu.embedding.hbm_cache.groups import (
            CachedTrainState, init_cached_tables,
        )
        from persia_tpu.embedding.native_store import create_store
        from persia_tpu.embedding.optim import Adagrad
        from persia_tpu.embedding.worker import EmbeddingWorker
        from persia_tpu.models import DLRM

        cfg, tr = self.config, self.traffic
        so, do, g = cfg["sparse_optimizer"], cfg["dense_optimizer"], cfg["guarantees"]
        ecfg = EmbeddingConfig(
            slots_config={n: SlotConfig(dim=self.dim) for n in self.names},
            feature_index_prefix_bit=0,
        )
        emb_opt = Adagrad(lr=so["lr"], initialization=so["initial_accumulator"], eps=so["eps"])
        store = create_store(
            "native", capacity=int(tr["ps_capacity"]), num_internal_shards=64,
            optimizer=emb_opt.config, seed=self.ps_seed,
        )
        worker = EmbeddingWorker(ecfg, [store], num_threads=WORKER_THREADS)
        model = DLRM(
            embedding_dim=self.dim, bottom_mlp=tuple(cfg["bottom_mlp"]),
            top_mlp=tuple(cfg["top_mlp"][:-1]), compute_dtype=jnp.float32,
        )
        self.ctx = CachedTrainCtx(
            model=model,
            dense_optimizer=optax.adam(do["lr"], b1=do["b1"], b2=do["b2"], eps=do["eps"]),
            embedding_optimizer=emb_opt, worker=worker, embedding_config=ecfg,
            cache_rows=int(tr["cache_rows"]),
            wb_wire_dtype=g["write_back_wire_dtype"], aux_wire_dtype=g["aux_wire_dtype"],
            admit_touches=int(tr["admit_touches"]),
        ).__enter__()
        tier = self.ctx.tier
        if len(tier.groups) != 1:
            raise RuntimeError(f"expected one cache group, got {tier.groups}")
        self.group = tier.groups[0]
        # the seed goes in as an argument: a constant would key the compile cache
        self._seed_words = jnp.asarray(np.stack(weights.seed_words(self.seed)))
        dense = jax.jit(lambda words: weights.dense_params(cfg, words, jnp))(self._seed_words)
        params = {f"Dense_{i}": {"kernel": k, "bias": b} for i, (k, b) in enumerate(dense)}
        tables, emb_state = init_cached_tables(tier.groups, self.ctx.sparse_cfg, dtype=jnp.float32)
        if self.resident:
            tables[self.group.name] = self._fill_pool(tables[self.group.name])
        opt_state = self.ctx.dense_optimizer.init(params)
        if self.adam_start:
            adam = opt_state[0]._replace(
                count=jnp.asarray(int(self.adam_start["count"]), jnp.int32),
                nu=jax.tree.map(lambda x: jnp.full_like(x, float(self.adam_start["nu"])),
                                opt_state[0].nu))
            opt_state = (adam,) + tuple(opt_state[1:])
        self.ctx.state = CachedTrainState(
            params=params, batch_stats={},
            opt_state=opt_state,
            tables=tables, emb_state=emb_state,
            emb_batch_state=jnp.ones((2,), jnp.float32),
            step=jnp.zeros((), jnp.int32), loss_scale=None,
        )
        self._gather = jax.jit(lambda t, a, idx: (t[idx], a[idx]))

    def _fill_pool(self, table):
        """Admit every sign of every table to the directory and write its row,
        made on the device from the seed, at the pool row the directory gave."""
        import jax
        import jax.numpy as jnp

        d = self.ctx.tier.dirs[self.group.name]
        total = int(sum(self.rows))
        pool_row = np.empty(total, np.int32)
        at = 0
        for t, n in enumerate(self.rows):
            for lo in range(0, n, ADMIT_CHUNK):
                ids = np.arange(lo, min(n, lo + ADMIT_CHUNK), dtype=np.uint64)
                rows, miss, ev_signs, _ev_rows = d.admit((np.uint64(t + 1) << np.uint64(KEY_SHIFT)) | ids)
                if len(miss) != len(ids) or len(ev_signs):
                    raise RuntimeError("the pool does not hold the traffic's rows whole")
                pool_row[at:at + len(ids)] = rows
                at += len(ids)
        offs = jnp.asarray(np.concatenate([[0], np.cumsum(self.rows)[:-1]]), jnp.int32)
        dim = self.dim

        @partial(jax.jit, donate_argnums=(0,))
        def fill(table, pool_row, words):
            r = jnp.arange(total, dtype=jnp.int32)
            slot = jnp.searchsorted(offs, r, side="right").astype(jnp.int32) - 1
            return table.at[pool_row].set(weights.table_rows_init(words, slot, r - offs[slot], dim, jnp))

        return fill(table, jax.device_put(pool_row), self._seed_words)

    # ----------------------------------------------------------- conversions

    def keys(self, b: Dict[str, np.ndarray]) -> np.ndarray:
        s = np.arange(1, len(self.rows) + 1, dtype=np.uint64)[:, None]
        return (s << np.uint64(KEY_SHIFT)) | b["ids"].astype(np.uint64)

    def to_program_batch(self, b: Dict[str, np.ndarray]):
        return persia_batch(self.names, self.keys(b), b)

    def row_birth(self, keys: np.ndarray) -> np.ndarray:
        keys = np.asarray(keys, np.uint64)
        if not self.resident:
            return splitmix_uniform_rows(keys, self.ps_seed, self.dim)
        slot = (keys >> np.uint64(KEY_SHIFT)).astype(np.int64) - 1
        ids = (keys & np.uint64((1 << KEY_SHIFT) - 1)).astype(np.int64)
        return weights.table_rows_init(self.seed, slot, ids, self.dim)

    # ------------------------------------------------------------- stepping

    def _stream(self, batches, dispatch_k: int) -> None:
        self.ctx.train_stream(batches, fetch_final=False, dispatch_k=dispatch_k,
                              pipeline_depth=PIPELINE_DEPTH)
        self.ctx.drain()

    def compared_run(self, batches: List[Dict[str, np.ndarray]]):
        """``dispatch_k`` set-up steps through the window's own call and
        program: one stream, one K-step pack, drained. Returns the steps'
        losses, read from the headers the pack returned, or None where the
        stream did not dispatch them as one pack."""
        ctx, headers = self.ctx, []
        inner = ctx._dispatch_packed

        def tapped(items):
            out = inner(items)
            headers.extend(out[0])
            return out

        ctx._dispatch_packed = tapped
        staged = [self.to_program_batch(b) for b in batches]
        try:
            self._stream(staged, self.dispatch_k)
        finally:
            ctx._dispatch_packed = inner
        stats = ctx.stream_stats() or {}
        if len(headers) != len(batches) or stats.get("single_steps"):
            return None
        shape = staged[0].labels[0].data.shape
        return [float(ctx._parse_header(np.asarray(h), shape)["loss"]) for h in headers]

    def held_rows(self, keys: np.ndarray):
        """[row | accumulator] of ``keys`` as cache and PS together hold them
        now: the pool's row where the sign is resident, else the PS entry.
        ``found`` is False where neither holds the sign."""
        import jax

        keys = np.ascontiguousarray(keys, np.uint64)
        d = self.ctx.tier.dirs[self.group.name]
        pool_row = np.asarray(d.probe(keys), np.int64)
        resident = pool_row >= 0
        st, g = self.ctx.state, self.group.name
        rows = np.zeros((len(keys), self.dim), np.float32)
        acc = np.zeros((len(keys), self.dim), np.float32)
        if resident.any():
            r, a = self._gather(st.tables[g], st.emb_state[g]["acc"],
                                jax.device_put(pool_row[resident].astype(np.int32)))
            rows[resident], acc[resident] = np.asarray(r), np.asarray(a)
        found = resident.copy()
        if (~resident).any():
            warm, vals = self.ctx.tier.router.probe_entries(keys[~resident], self.dim)
            vals = np.asarray(vals)
            rows[~resident], acc[~resident] = vals[:, :self.dim], vals[:, self.dim:]
            found[~resident] = np.asarray(warm, bool)
        return rows, acc, found

    def snapshot(self, keys: np.ndarray) -> dict:
        rows, acc, found = self.held_rows(keys)
        birth = ~found  # a sign not yet touched: its birth row, accumulator as configured
        if birth.any():
            rows[birth] = self.row_birth(np.asarray(keys, np.uint64)[birth])
            acc[birth] = self.config["sparse_optimizer"]["initial_accumulator"]
        return dict(dense_snapshot(self.ctx.state), rows=rows, acc=acc)

    def warm_up(self, stream) -> int:
        """``warmup_steps`` in packs, then a tail short of a pack, which the
        stream dispatches step by step: a window's last steps go that way too,
        and so does a pack the dispatcher gave up waiting on."""
        n = int(self.traffic["warmup_steps"]) + self.dispatch_k - 1
        self._stream((self.to_program_batch(next(stream)) for _ in range(n)), self.dispatch_k)
        return n

    def run_window(self, stream, seconds: float) -> dict:
        """One ``train_stream`` call over batches drawn until ``seconds`` have
        passed; the window closes when the stream is drained (device step
        done, eviction payloads fetched, PS write-back landed)."""
        count = [0]
        t0 = time.perf_counter()

        def batches():
            while time.perf_counter() - t0 < seconds:
                yield self.to_program_batch(next(stream))
                count[0] += 1

        self._stream(batches(), self.dispatch_k)
        t1 = time.perf_counter()
        self._stats_window = dict(self.ctx.stream_stats() or {})
        return {"steps": count[0], "samples": count[0] * int(self.traffic["batch"]),
                "t0": t0, "t1": t1, "last_loss": float(self.ctx.last_metrics()["loss"])}

    def install_probes(self) -> None:
        """Count the bytes that go host to device, around the program's
        ``_stage`` (traced runs only)."""
        import jax

        stage = self.ctx._stage

        def staged(*args):
            self.h2d_bytes += sum(
                int(x.nbytes) for x in jax.tree_util.tree_leaves(args) if hasattr(x, "nbytes"))
            return stage(*args)

        self.ctx._stage = staged

    def counters(self) -> dict:
        """Counts over the window alone."""
        return {"h2d_bytes": self.h2d_bytes, "stream_stats": self._stats_window or {}}

    def step_programs(self) -> Dict[str, int]:
        """Device programs that are training steps, by the name the trace
        gives them, with the steps each holds (``jit_run`` is the K-step pack)."""
        return {"jit_step": 1, "jit_run": self.dispatch_k}

    def free(self) -> None:
        import gc

        self.ctx.state = None
        self.ctx._ev_rings.clear()
        self.ctx = None
        gc.collect()
