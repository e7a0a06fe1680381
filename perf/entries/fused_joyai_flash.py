"""Entry ``FusedTrainCtx.train_step`` with the ``joyai_flash_moe`` tower: the
token table pinned whole in HBM as one raw slot (read once a step: the
prediction module takes the gathered slot shifted by a position), each
position's document start as an int32 side input, one program a step. An
adapter of ``fused_mellum``'s as ``fused_kimi_linear`` is, whose batch, window
and staged bytes it keeps (the three towers train on the same generator):
another model, its leaves, and the counters this tower keeps.
"""

from __future__ import annotations

import sys

import numpy as np

from perf import joyai_flash_weights, weights
from perf.entries import fused_mellum
# here, not in ``build``: a program without this tower fails as the cell's files are loaded
from persia_tpu.models.joyai_flash_moe import JoyAIFlashMoE

SLOT = fused_mellum.SLOT


class Entry(fused_mellum.Entry):
    def __init__(self, config: dict, traffic: dict, seed: int):
        super().__init__(config, traffic, seed)
        self._objective0 = None

    # ------------------------------------------------------------- building

    def build(self) -> None:
        import jax
        import jax.numpy as jnp
        import optax

        from persia_tpu.embedding.optim import Adagrad
        from persia_tpu.ops.sparse_update import init_sparse_state
        from persia_tpu.parallel.fused_ctx import FusedTrainCtx
        from persia_tpu.parallel.fused_step import (
            FusedSlotSpec, FusedTrainState, group_stacked_specs,
        )

        cfg = self.config
        so, do = cfg["sparse_optimizer"], cfg["dense_optimizer"]
        # a rehearsal's preset cuts the tile and asks for the Pallas interpreter; the cell's file does neither
        model = JoyAIFlashMoE.from_config(
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
            table = joyai_flash_weights.token_rows(cfg, words, jnp.arange(vocab, dtype=jnp.int32), jnp)
            return joyai_flash_weights.dense_tree(cfg, words, jnp), table

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

    def row_birth(self, keys: np.ndarray) -> np.ndarray:
        return joyai_flash_weights.token_rows(self.config, self.seed, np.asarray(keys, np.uint64).astype(np.int64))

    # ------------------------------------------------------------- stepping

    def _say_paths(self) -> None:
        """Which attention, rotation, module, expert and row-write path the compiled step took."""
        from persia_tpu import tracing

        for e in tracing.flight_snapshot():
            if e["kind"] in ("joyai_flash.paths", "sparse_update.row_write"):
                attrs = " ".join(f"{k}={v}" for k, v in sorted(e["attrs"].items()))
                print("flight", e["kind"], attrs, file=sys.stderr)

    def snapshot(self, keys: np.ndarray) -> dict:
        """Dense parameters and Adam's first moment by leaf, the rows and
        Adagrad accumulators of ``keys``, the counter of picks and the
        objectives' running sums, as the program holds them now (host copies)."""
        import jax

        st = self.ctx.state
        idx = np.asarray(keys, np.uint64).astype(np.int32)
        g = self.group.name
        rows, acc = self._gather(st.tables[g], st.emb_state[g]["acc"], jax.device_put(idx))
        by_name = lambda tree: joyai_flash_weights.leaves_by_name(tree, self.config)
        # before the first step Adam's first moment is zeros: no 2.1 GB copy of them
        mu = by_name(st.opt_state[0].mu) if self.ctx._steps else joyai_flash_weights.zeros_by_name(self.config)
        return {"dense": by_name(st.params), "adam_mu": mu, "rows": np.asarray(rows), "acc": np.asarray(acc),
                "expert_picks": np.asarray(st.batch_stats["expert_picks"]),
                "objective": np.asarray(st.batch_stats["objective"])}

    def run_window(self, stream, seconds: float) -> dict:
        self._objective0 = np.asarray(self.ctx.state.batch_stats["objective"], np.float64)
        return super().run_window(stream, seconds)

    def counters(self) -> dict:
        """``expert_picks``: the window's picks by expert layer (layers 1 to 5,
        then the module's) and held expert; ``attention_tiles``: tile pairs the
        latent blocks' kernels visited and tile pairs that hold a live pair, a
        head (row 1); ``held_picks_over_even``: each expert layer's picks on
        this share over what an even router sends it; ``objective``: the
        window's ``sum w``, ``sum w2``, ``sum w CE`` and ``sum w2 CE2``. From
        counters the step keeps on the device, read once, here."""
        out = {"h2d_bytes": self.h2d_bytes}
        if self._picks0 is None:
            return out
        stats = self.ctx.state.batch_stats
        picks = np.asarray(stats["expert_picks"]) - self._picks0
        tiles = np.asarray(stats["attention_tiles"]) - self._tiles0
        sums = np.asarray(stats["objective"], np.float64) - self._objective0
        cfg, tr = self.config, self.traffic
        even = (self.ctx._steps - self._steps0) * int(tr["batch"]) * int(tr["seq_len"]) * int(
            cfg["num_experts_per_tok"]) * int(cfg["n_routed_experts"]) / int(cfg["router_width"])
        over_even = (picks.sum(axis=1) / even).tolist()
        out.update(expert_picks=picks.tolist(), attention_tiles=tiles.tolist(), held_picks_over_even=over_even,
                   objective=sums.tolist())
        half = len(sums) // 2
        print("window picks over even", " ".join(f"{x:.4f}" for x in over_even),
              "fullest over mean", " ".join(f"{x:.2f}" for x in picks.max(axis=1) / np.maximum(picks.mean(axis=1), 1)),
              "latent tiles visited, live", tiles[1].tolist(),
              "mean loss by objective", " ".join(f"{x:.5f}" for x in sums[half:] / np.maximum(sums[:half], 1)),
              file=sys.stderr)
        return out
