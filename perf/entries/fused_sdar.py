"""Entry ``FusedTrainCtx.train_step`` with the ``sdar_moe`` tower: the token
table pinned whole in HBM as one raw slot, one program a step, the same call
as ``fused_pinned`` drives for the click model.

The adapter builds the context, fills its state from the seed in one jitted
call (``perf/sdar_weights.py``: the values the reference makes for itself),
and drives the context's own ``train_step`` for the compared step, the
warm-up and the window. A snapshot names the dense leaves as the reference
does (``L<l>.<leaf>``, ``norm_f``, ``head``) and carries the step's counter of
picks by layer and held expert.
"""

from __future__ import annotations

import sys
import time
from collections import deque
from typing import Dict, List

import numpy as np

from perf import sdar_weights, weights

SLOT = "tokens"
RUN_AHEAD = 2  # steps the host may be ahead of the device in the window


class Entry:
    # two compared steps, read once after the second: Adam's first step is
    # lr x sign(g) whatever b1, b2 and eps are, the second shows them. One read,
    # because a snapshot is every dense leaf and Adam's first moment: 4.9 GB of
    # host memory at the cell's size, here and in the reference
    snapshot_after = (2,)

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.vocab, self.dim = int(config["vocab_size"]), int(config["hidden_size"])
        self.ctx = None
        self.h2d_bytes = 0
        self._picks0 = self._steps0 = None
        self._said_paths = False

    # ------------------------------------------------------------- building

    def build(self) -> None:
        import jax
        import jax.numpy as jnp
        import optax

        from persia_tpu.embedding.optim import Adagrad
        from persia_tpu.models import SDARMoE
        from persia_tpu.ops.sparse_update import init_sparse_state
        from persia_tpu.parallel.fused_ctx import FusedTrainCtx
        from persia_tpu.parallel.fused_step import (
            FusedSlotSpec, FusedTrainState, group_stacked_specs,
        )

        cfg = self.config
        so, do = cfg["sparse_optimizer"], cfg["dense_optimizer"]
        model = SDARMoE(
            vocab=self.vocab, n_layers=int(cfg["num_hidden_layers"]),
            block_len=int(cfg["block_length"]), hidden=self.dim,
            n_heads=int(cfg["num_attention_heads"]), n_kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["head_dim"]), n_experts=int(cfg["router_width"]),
            experts_per_token=int(cfg["num_experts_per_tok"]),
            expert_width=int(cfg["moe_intermediate_size"]),
            first_held=int(cfg["first_held_expert"]), n_held=int(cfg["num_experts"]),
            rms_eps=float(cfg["rms_norm_eps"]), rope_theta=float(cfg["rope_theta"]),
            # a rehearsal's preset asks for the Pallas interpreter; the cell's file does not
            interpret=bool(cfg.get("interpret_kernels", False)),
        )
        specs = {SLOT: FusedSlotSpec(vocab=self.vocab, dim=self.dim, pooled=False)}
        emb_opt = Adagrad(lr=so["lr"], initialization=so["initial_accumulator"], eps=so["eps"])
        self.ctx = FusedTrainCtx(
            model, optax.adam(do["lr"], b1=do["b1"], b2=do["b2"], eps=do["eps"]),
            emb_opt, specs, stack=True,
        ).__enter__()
        (self.group,) = group_stacked_specs(specs, self.ctx.slot_order)
        n_layers, vocab = int(cfg["num_hidden_layers"]), self.vocab
        # the seed goes in as an argument: a constant would key the compile cache
        words = jnp.asarray(np.stack(weights.seed_words(self.seed)))

        @jax.jit
        def make_state(words):
            table = sdar_weights.token_rows(cfg, words, jnp.arange(vocab, dtype=jnp.int32), jnp)
            return sdar_weights.dense_tree(cfg, words, jnp), table

        params, table = make_state(words)
        gname = self.group.name
        self.ctx.state = FusedTrainState(
            params=params,
            batch_stats={"expert_picks": jnp.zeros((n_layers, int(cfg["num_experts"])), jnp.int32)},
            opt_state=self.ctx.dense_optimizer.init(params),
            tables={gname: table},
            emb_state={gname: init_sparse_state(emb_opt.config, vocab, self.dim)},
            emb_batch_state=jnp.ones((2,), jnp.float32),
            step=jnp.zeros((), jnp.int32),
        )
        self._gather = jax.jit(lambda t, a, idx: (t[idx], a[idx]))

    # ----------------------------------------------------------- conversions

    def to_program_batch(self, b: Dict[str, np.ndarray]):
        from persia_tpu.data import IDTypeFeature, Label, PersiaBatch

        ids = b["ids"]
        tokens = IDTypeFeature.from_flat(SLOT, np.ascontiguousarray(ids, np.uint64).reshape(-1),
                                         np.full(ids.shape[0], ids.shape[1], np.int64))
        return PersiaBatch([tokens], labels=[Label(b["labels"]), Label(b["weights"])],
                           requires_grad=True)

    def keys(self, b: Dict[str, np.ndarray]) -> np.ndarray:
        return b["ids"].astype(np.uint64)  # one slot: a row's key is its id

    def row_birth(self, keys: np.ndarray) -> np.ndarray:
        return sdar_weights.token_rows(self.config, self.seed, np.asarray(keys, np.uint64).astype(np.int64))

    # ------------------------------------------------------------- stepping

    def compared_run(self, batches: List[Dict[str, np.ndarray]]) -> List[float]:
        """Set-up steps through the window's own call; returns their losses."""
        losses = [float(self.ctx.train_step(self.to_program_batch(b))["loss"]) for b in batches]
        if not self._said_paths:
            self._said_paths = True
            self._say_paths()
        return losses

    def _say_paths(self) -> None:
        """Which attention, expert and row-write path the compiled step took."""
        from persia_tpu import tracing

        for e in tracing.flight_snapshot():
            if e["kind"] in ("sdar_moe.paths", "sparse_update.row_write"):
                attrs = " ".join(f"{k}={v}" for k, v in sorted(e["attrs"].items()))
                print("flight", e["kind"], attrs, file=sys.stderr)

    def snapshot(self, keys: np.ndarray) -> dict:
        """Dense parameters and Adam's first moment by leaf, the rows and
        Adagrad accumulators of ``keys``, and the counter of picks, as the
        program holds them now (host copies)."""
        import jax

        st = self.ctx.state
        idx = np.asarray(keys, np.uint64).astype(np.int32)
        g = self.group.name
        rows, acc = self._gather(st.tables[g], st.emb_state[g]["acc"], jax.device_put(idx))
        # before the first step Adam's first moment is zeros: no 2.4 GB copy of them
        mu = (sdar_weights.leaves_by_name(st.opt_state[0].mu) if self.ctx._steps
              else sdar_weights.zeros_by_name(self.config))
        return {"dense": sdar_weights.leaves_by_name(st.params), "adam_mu": mu,
                "rows": np.asarray(rows), "acc": np.asarray(acc),
                "expert_picks": np.asarray(st.batch_stats["expert_picks"])}

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

        self._picks0 = np.asarray(self.ctx.state.batch_stats["expert_picks"])
        self._steps0 = self.ctx._steps
        done: deque = deque()
        t0 = time.perf_counter()
        steps = 0
        while time.perf_counter() - t0 < seconds:
            b = next(stream)
            self.ctx.train_step(self.to_program_batch(b), fetch_metrics=False)
            # what batch_to_fused stages: int32 ids and labels, float32 weights
            self.h2d_bytes += b["ids"].size * 4 + b["labels"].nbytes + b["weights"].nbytes
            done.append(self.ctx._last[0])
            steps += 1
            if len(done) > RUN_AHEAD:
                jax.block_until_ready(done.popleft())  # completion only, no transfer
        jax.block_until_ready(self.ctx.state.step)
        t1 = time.perf_counter()
        return {"steps": steps, "samples": steps * int(self.traffic["batch"]),
                "t0": t0, "t1": t1, "last_loss": float(done[-1])}

    def install_probes(self) -> None:
        """Nothing to wrap: the staged bytes are counted from shapes and the
        picks on the device, as the window runs."""

    def counters(self) -> dict:
        """``expert_picks``: the window's picks by layer and held expert,
        from the counter the step keeps on the device (read once, here)."""
        out = {"h2d_bytes": self.h2d_bytes}
        if self._picks0 is not None:
            picks = np.asarray(self.ctx.state.batch_stats["expert_picks"]) - self._picks0
            out["expert_picks"] = picks.tolist()
            # this share's load a layer over the even router's, and its fullest expert over its mean
            cfg, tr = self.config, self.traffic
            even = (self.ctx._steps - self._steps0) * int(tr["batch"]) * 2 * int(tr["seq_len"]) * int(
                cfg["num_experts_per_tok"]) * int(cfg["num_experts"]) / int(cfg["router_width"])
            print("window picks over even", " ".join(f"{x / even:.4f}" for x in picks.sum(axis=1)),
                  "fullest over mean", " ".join(f"{x:.2f}" for x in picks.max(axis=1) / picks.mean(axis=1)),
                  file=sys.stderr)
        return out

    def step_programs(self) -> Dict[str, int]:
        return {"jit_step": 1}

    def free(self) -> None:
        import gc

        self.ctx.state = None
        self.ctx = None
        gc.collect()
