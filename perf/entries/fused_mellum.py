"""Entry ``FusedTrainCtx.train_step`` with the ``mellum_moe`` tower: the token
table pinned whole in HBM as one raw slot, each position's document start as
an int32 side input, one program a step: the same call as ``fused_pinned``
drives for the click model and ``fused_sdar`` for the block-diffusion tower,
whose adapter this one is (its snapshot, compared steps, warm-up and window),
with another model, another batch and the counters this tower keeps.
"""

from __future__ import annotations

import sys
from typing import Dict

import numpy as np

from perf import mellum_weights, weights
from perf.entries import fused_sdar

SLOT = fused_sdar.SLOT


class Entry(fused_sdar.Entry):
    def __init__(self, config: dict, traffic: dict, seed: int):
        super().__init__(config, traffic, seed)
        self._tiles0 = None

    # ------------------------------------------------------------- building

    def build(self) -> None:
        import jax
        import jax.numpy as jnp
        import optax

        from persia_tpu.embedding.optim import Adagrad
        from persia_tpu.models import MellumMoE
        from persia_tpu.ops.sparse_update import init_sparse_state
        from persia_tpu.parallel.fused_ctx import FusedTrainCtx
        from persia_tpu.parallel.fused_step import (
            FusedSlotSpec, FusedTrainState, group_stacked_specs,
        )

        cfg = self.config
        so, do = cfg["sparse_optimizer"], cfg["dense_optimizer"]
        # a rehearsal's preset cuts the tile and asks for the Pallas interpreter; the cell's file does neither
        model = MellumMoE.from_config(
            cfg, head_chunk=int(cfg["head_chunk"]), interpret=bool(cfg.get("interpret_kernels", False)),
            **({"tile": int(cfg["attention_tile"])} if "attention_tile" in cfg else {}))
        specs = {SLOT: FusedSlotSpec(vocab=self.vocab, dim=self.dim, pooled=False)}
        emb_opt = Adagrad(lr=so["lr"], initialization=so["initial_accumulator"], eps=so["eps"])
        self.ctx = FusedTrainCtx(
            model, optax.adam(do["lr"], b1=do["b1"], b2=do["b2"], eps=do["eps"]),
            emb_opt, specs, stack=True,
        ).__enter__()
        (self.group,) = group_stacked_specs(specs, self.ctx.slot_order)
        vocab = self.vocab
        # the seed goes in as an argument: a constant would key the compile cache
        words = jnp.asarray(np.stack(weights.seed_words(self.seed)))

        @jax.jit
        def make_state(words):
            table = mellum_weights.token_rows(cfg, words, jnp.arange(vocab, dtype=jnp.int32), jnp)
            return mellum_weights.dense_tree(cfg, words, jnp), table

        params, table = make_state(words)
        gname = self.group.name
        self.ctx.state = FusedTrainState(
            params=params,
            batch_stats=model.counters(),
            opt_state=self.ctx.dense_optimizer.init(params),
            tables={gname: table},
            emb_state={gname: init_sparse_state(emb_opt.config, vocab, self.dim)},
            emb_batch_state=jnp.ones((2,), jnp.float32),
            step=jnp.zeros((), jnp.int32),
        )
        self._gather = jax.jit(lambda t, a, idx: (t[idx], a[idx]))

    # ----------------------------------------------------------- conversions

    def to_program_batch(self, b: Dict[str, np.ndarray]):
        from persia_tpu.data import IDTypeFeature, Label, PersiaBatch, document_starts

        ids = b["ids"]
        tokens = IDTypeFeature.from_flat(SLOT, np.ascontiguousarray(ids, np.uint64).reshape(-1),
                                         np.full(ids.shape[0], ids.shape[1], np.int64))
        return PersiaBatch([tokens], [document_starts(b["doc_lengths"], ids.shape[1])],
                           labels=[Label(b["labels"]), Label(b["weights"])], requires_grad=True)

    # ------------------------------------------------------------- stepping

    def _say_paths(self) -> None:
        """Which attention, expert and row-write path the compiled step took."""
        from persia_tpu import tracing

        for e in tracing.flight_snapshot():
            if e["kind"] in ("mellum_moe.paths", "sparse_update.row_write"):
                attrs = " ".join(f"{k}={v}" for k, v in sorted(e["attrs"].items()))
                print("flight", e["kind"], attrs, file=sys.stderr)

    def run_window(self, stream, seconds: float) -> dict:
        self._tiles0 = np.asarray(self.ctx.state.batch_stats["attention_tiles"])
        out = super().run_window(stream, seconds)
        # the side input batch_to_fused stages beside what the adapter's window counts: int32 starts
        self.h2d_bytes += out["steps"] * int(self.traffic["batch"]) * int(self.traffic["seq_len"]) * 4
        return out

    def counters(self) -> dict:
        """``expert_picks``: the window's picks by layer and held expert;
        ``attention_tiles``: tile pairs the attention kernels visited and tile
        pairs that hold a live pair, a head, by layer kind (sliding, full).
        Both from counters the step keeps on the device, read once, here."""
        out = {"h2d_bytes": self.h2d_bytes}
        if self._picks0 is None:
            return out
        stats = self.ctx.state.batch_stats
        picks = np.asarray(stats["expert_picks"]) - self._picks0
        tiles = np.asarray(stats["attention_tiles"]) - self._tiles0
        out.update(expert_picks=picks.tolist(), attention_tiles=tiles.tolist())
        cfg, tr = self.config, self.traffic
        even = (self.ctx._steps - self._steps0) * int(tr["batch"]) * int(tr["seq_len"]) * int(
            cfg["num_experts_per_tok"]) * int(cfg["num_experts"]) / int(cfg["router_width"])
        print("window picks over even", " ".join(f"{x / even:.4f}" for x in picks.sum(axis=1)),
              "fullest over mean", " ".join(f"{x:.2f}" for x in picks.max(axis=1) / picks.mean(axis=1)),
              "tiles visited, live (sliding | full)", tiles.tolist(), file=sys.stderr)
        return out
