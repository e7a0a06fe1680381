"""Entry ``FusedTrainCtx.train_step``: every table pinned whole in HBM, one
program a step, no host PS, no feeder, no aux programs.

The adapter builds the context, fills its state from the seed in one jitted
call (tables by the counter hash of ``perf/weights.py``, so the reference can
compute the same rows), and drives the context's own ``train_step`` for the
compared steps, the warm-up and the window.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Dict, List

import numpy as np

from perf import weights
from perf.generators.zipf import table_rows

KEY_SHIFT = 40  # reference key = table << 40 | id
RUN_AHEAD = 2  # steps the host may be ahead of the device in the window


def slot_names(n: int) -> List[str]:
    return [f"cat_{i:02d}" for i in range(n)]  # zero-padded: sorted == numeric


def persia_batch(names: List[str], ids: np.ndarray, b: Dict[str, np.ndarray]):
    """The generator's batch as the program's ``PersiaBatch``: one single-id
    feature a table, ``ids`` (S, B) uint64 being what the program keys rows by."""
    from persia_tpu.data import (
        IDTypeFeatureWithSingleID, Label, NonIDTypeFeature, PersiaBatch,
    )

    return PersiaBatch(
        [IDTypeFeatureWithSingleID(n, ids[i]) for i, n in enumerate(names)],
        non_id_type_features=[NonIDTypeFeature(b["dense"])],
        labels=[Label(b["labels"])], requires_grad=True,
    )


def dense_snapshot(state) -> dict:
    """Host copies of the dense parameters and Adam's first moment, layer by
    layer, as [(kernel, bias), ...]."""
    def layers(tree):
        return [(np.asarray(tree[f"Dense_{i}"]["kernel"]), np.asarray(tree[f"Dense_{i}"]["bias"]))
                for i in range(len(tree))]

    return {"dense": layers(state.params), "adam_mu": layers(state.opt_state[0].mu)}


class Entry:
    snapshot_after = (1, 3)  # one program a step: read after the first and the third

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.rows = table_rows(config, traffic)
        self.names = slot_names(len(self.rows))
        self.dim = int(config["embedding_dim"])
        self.ctx = None
        self.h2d_bytes = 0

    # ------------------------------------------------------------- building

    def build(self) -> None:
        import jax
        import jax.numpy as jnp
        import optax

        from persia_tpu.embedding.optim import Adagrad
        from persia_tpu.models import DLRM
        from persia_tpu.ops.sparse_update import init_sparse_state
        from persia_tpu.parallel.fused_ctx import FusedTrainCtx
        from persia_tpu.parallel.fused_step import (
            FusedSlotSpec, FusedTrainState, group_stacked_specs,
        )

        cfg = self.config
        so, do = cfg["sparse_optimizer"], cfg["dense_optimizer"]
        model = DLRM(
            embedding_dim=self.dim, bottom_mlp=tuple(cfg["bottom_mlp"]),
            top_mlp=tuple(cfg["top_mlp"][:-1]), compute_dtype=jnp.float32,
        )
        specs = {n: FusedSlotSpec(vocab=r, dim=self.dim) for n, r in zip(self.names, self.rows)}
        emb_opt = Adagrad(lr=so["lr"], initialization=so["initial_accumulator"], eps=so["eps"])
        self.ctx = FusedTrainCtx(
            model, optax.adam(do["lr"], b1=do["b1"], b2=do["b2"], eps=do["eps"]),
            emb_opt, specs, stack=True,
        ).__enter__()
        groups = group_stacked_specs(specs, self.ctx.slot_order)
        if len(groups) != 1 or list(groups[0].slots) != self.names:
            raise RuntimeError(f"expected one stacked table in slot order, got {groups}")
        self.group = groups[0]
        self.offsets = np.asarray(self.group.offsets, np.int64)
        total, dim = self.group.vocab, self.dim
        offs = jnp.asarray(self.offsets, jnp.int32)
        # the seed goes in as an argument: a constant would key the compile cache
        words = jnp.asarray(np.stack(weights.seed_words(self.seed)))

        @jax.jit
        def make_table(words):
            r = jnp.arange(total, dtype=jnp.int32)
            slot = jnp.searchsorted(offs, r, side="right").astype(jnp.int32) - 1
            return weights.table_rows_init(words, slot, r - offs[slot], dim, jnp)

        dense = jax.jit(lambda words: weights.dense_params(cfg, words, jnp))(words)
        params = {f"Dense_{i}": {"kernel": k, "bias": b} for i, (k, b) in enumerate(dense)}
        gname = self.group.name
        self.ctx.state = FusedTrainState(
            params=params, batch_stats={},
            opt_state=self.ctx.dense_optimizer.init(params),
            tables={gname: make_table(words)},
            emb_state={gname: init_sparse_state(emb_opt.config, total, dim)},
            emb_batch_state=jnp.ones((2,), jnp.float32),
            step=jnp.zeros((), jnp.int32),
        )
        self._gather = jax.jit(lambda t, a, idx: (t[idx], a[idx]))

    # ----------------------------------------------------------- conversions

    def to_program_batch(self, b: Dict[str, np.ndarray]):
        return persia_batch(self.names, b["ids"].astype(np.uint64), b)

    def keys(self, b: Dict[str, np.ndarray]) -> np.ndarray:
        s = np.arange(len(self.rows), dtype=np.uint64)[:, None]
        return (s << np.uint64(KEY_SHIFT)) | b["ids"].astype(np.uint64)

    def row_birth(self, keys: np.ndarray) -> np.ndarray:
        keys = np.asarray(keys, np.uint64)
        slot = (keys >> np.uint64(KEY_SHIFT)).astype(np.int64)
        ids = (keys & np.uint64((1 << KEY_SHIFT) - 1)).astype(np.int64)
        return weights.table_rows_init(self.seed, slot, ids, self.dim)

    # ------------------------------------------------------------- stepping

    def compared_run(self, batches: List[Dict[str, np.ndarray]]) -> List[float]:
        """Set-up steps through the window's own call; returns their losses."""
        return [float(self.ctx.train_step(self.to_program_batch(b))["loss"]) for b in batches]

    def snapshot(self, keys: np.ndarray) -> dict:
        """Dense parameters, Adam's first moment, and the rows and Adagrad
        accumulators of ``keys``, as the program holds them now (host copies)."""
        import jax

        st = self.ctx.state
        keys = np.asarray(keys, np.uint64)
        slot = (keys >> np.uint64(KEY_SHIFT)).astype(np.int64)
        idx = (keys & np.uint64((1 << KEY_SHIFT) - 1)).astype(np.int64) + self.offsets[slot]
        g = self.group.name
        rows, acc = self._gather(st.tables[g], st.emb_state[g]["acc"],
                                 jax.device_put(idx.astype(np.int32)))
        return dict(dense_snapshot(st), rows=np.asarray(rows), acc=np.asarray(acc))

    def warm_up(self, stream) -> int:
        import jax

        n = int(self.traffic["warmup_steps"])
        for _ in range(n):
            self.ctx.train_step(self.to_program_batch(next(stream)), fetch_metrics=False)
        jax.block_until_ready(self.ctx.state.step)
        return n

    def run_window(self, stream, seconds: float) -> dict:
        """Train on the stream until ``seconds`` have passed, then wait for
        the device: every step started counts, and so does the wait."""
        import jax

        done: deque = deque()
        t0 = time.perf_counter()
        steps = 0
        while time.perf_counter() - t0 < seconds:
            b = next(stream)
            self.ctx.train_step(self.to_program_batch(b), fetch_metrics=False)
            # what batch_to_fused stages: int32 ids, float32 dense and labels
            self.h2d_bytes += b["ids"].size * 4 + b["dense"].nbytes + b["labels"].nbytes
            done.append(self.ctx._last[0])
            steps += 1
            if len(done) > RUN_AHEAD:
                jax.block_until_ready(done.popleft())  # completion only, no transfer
        jax.block_until_ready(self.ctx.state.step)
        t1 = time.perf_counter()
        return {"steps": steps, "samples": steps * int(self.traffic["batch"]),
                "t0": t0, "t1": t1, "last_loss": float(done[-1])}

    def install_probes(self) -> None:
        """Nothing to wrap: the staged bytes are counted from shapes as the
        window runs."""

    def counters(self) -> dict:
        return {"h2d_bytes": self.h2d_bytes}

    def step_programs(self) -> Dict[str, int]:
        """Device programs that are training steps, by the name the trace
        gives them, with the steps each holds."""
        return {"jit_step": 1}

    def free(self) -> None:
        import gc

        self.ctx.state = None
        self.ctx = None
        gc.collect()
