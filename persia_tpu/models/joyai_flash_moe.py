"""A causal tower whose every layer is multi-head latent attention with a
low-rank query and a rotated shared key, trained with a multi-token-prediction
module, over packed documents (the ``joyai_llm_flash`` family; the layers' law
is the DeepSeek-V3 family's, arXiv:2412.19437). The tower itself (the scan, the
expert layer that is told which experts it holds, the chunked head) is
``models/moe_tower.py``'s, and so is the latent attention, which the
``kimi_linear`` family calls without positions; here is what this family
states and adds:

- **every layer is latent attention** (scores 192 wide: a head's own 128 key
  columns beside 64 that all heads share; values 128 wide), its query through
  ``wq_a``, an RMS norm and ``wq_b``; the last 64 columns of each head's query
  and the shared key columns are rotated by the position inside the document
  (positions restart where a document starts), on interleaved pairs;
- a leading layer with a dense SwiGLU, then layers with one shared expert
  beside sigmoid-routed ones, every scanned layer's leaves alike under one
  scan;
- **the prediction module** (depth 1) after the scan: position ``i``'s merged
  stream is ``[rms(e_i) * w_e ; rms(u_i) * w_h] M``, ``u`` the tower's normed
  last stream and ``e_i`` the token row of ``x_{i+1}``: the tower's own
  gathered slot shifted by one position, zero where ``i`` is its document's
  last (the sparse plane is read once; a row's gradient is the sum of what the
  tower's foot and the module give it). One whole layer of the scanned
  layers' form with leaves of its own follows, and a second pass through the
  tower's head predicts ``x_{i+2}``: ``loss = L_main + mtp_weight * L_mtp``.

The token rows come from the sparse plane as one raw slot, ``(B, T, hidden)``
float32; ``dense`` holds one int32 side input, ``(B, T)``: for each position
the index at which its document starts. A position reads no key and no token
of another document. The rotation is one Pallas pass each way
(``ops/qk_prep.py::mla_rope``, ``attention/rope`` in a trace), which writes the
rotated query columns head-major for the attention kernels.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from persia_tpu.models.mellum_moe import rope_frequencies
from persia_tpu.models.moe_tower import NextTokenTower, _mm, _rms, latent_attention, latent_attention_grid
from persia_tpu.ops.flash_attention import BLOCK_DIFFUSION_TILE, interval_tile_counts, interval_visits
from persia_tpu.tracing import record_event

MLA = "mla"


def rope_tables(starts, width: int, theta: float):
    """``(cos, sin)``, each (B, T, width) float32: the angle ``p_i theta **
    (-2m / width)`` of position ``i``'s place in its document, ``p_i = i -
    starts_i``, at both columns ``2m`` and ``2m + 1`` of pair ``m``."""
    freq, _ = rope_frequencies(width, theta)
    pos = (jnp.arange(starts.shape[1], dtype=jnp.int32)[None, :] - starts).astype(jnp.float32)
    angle = pos[:, :, None] * jnp.asarray(np.repeat(freq, 2))[None, None, :]
    return jnp.cos(angle), jnp.sin(angle)


def shifted(x):
    """``x_{i+1}`` at position ``i`` along axis 1, zero at the last."""
    return jnp.concatenate([x[:, 1:], jnp.zeros_like(x[:, :1])], axis=1)


@dataclass(frozen=True)
class JoyAIFlashMoE(NextTokenTower):
    vocab: int  # ids held here: the logits' width
    n_layers: int  # the leading layers and the scanned ones; the prediction module stands beside them
    hidden: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 32
    head_dim: int = 128  # a head's own key columns and its values
    rope_head_dim: int = 64  # the rotated columns: of every head's query, and the key columns all heads share
    q_lora_rank: Optional[int] = 1536  # None: a full-rank query
    kv_lora_rank: int = 512
    rope_theta: Optional[float] = 32e6  # None: no rotation (NoPE)
    dense_width: int = 7168
    n_experts: int = 256  # the router's width, as published
    experts_per_token: int = 8
    expert_width: int = 768
    routed_scaling: float = 2.5
    first_held: int = 0
    n_held: int = 256
    rms_eps: float = 1e-6
    mtp_depth: int = 1  # prediction modules after the scan: 0 or 1
    mtp_weight: float = 0.1  # the module's term in the loss
    layer_kinds: Tuple[str, ...] = (MLA,)
    kind_leaves: bool = False
    leading_kinds: Tuple[str, ...] = (MLA,)
    mlp: str = "shared_experts"
    router_law: str = "sigmoid"
    # a chunk of picks holds four times what an even router sends the held experts (16,384 picks for
    # 16,384 tokens): with latent attention in every layer a layer's load on one of 32 shares reads 0.03
    # to 4.1 of even by the seed, a trip of the loop over chunks costs its 8 ms whatever it holds, and at
    # the ``kimi_linear`` tower's twice even a step took 6 to 8 trips by the seed: a rate that spread by
    # 1.0-1.7% over seeds where the benchmark admits a cell under 1% (``PERF.md`` section 6, PR 42)
    pick_room: float = 4.0
    head_chunk: int = 2048
    tile: int = BLOCK_DIFFUSION_TILE  # the attention kernels' (the CPU tests cut a short sequence)
    interpret: bool = False

    @classmethod
    def from_config(cls, cfg: dict, **kw) -> "JoyAIFlashMoE":
        """The tower of a published ``config.json`` (its keys as published),
        cut to a chip's share where the dict says so: ``num_hidden_layers``
        layers from layer 0 on (``first_k_dense_replace`` leading ones),
        ``n_routed_experts`` held from ``first_held_expert`` on of
        ``router_width`` routed, ``vocab_size`` ids; the prediction module
        whole. ``mtp_loss_weight`` where the dict states one."""
        if cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "noaux_tc" or not cfg["norm_topk_prob"] \
                or cfg["n_group"] != 1 or cfg["topk_group"] != 1 or cfg["n_shared_experts"] != 1:
            raise ValueError("sigmoid scores with a selection bias (noaux_tc), renormalised, in one group "
                             "(n_group 1, topk_group 1), beside one shared expert, is what this tower runs")
        if cfg["rope_scaling"] is not None or not cfg["rope_interleave"]:
            raise ValueError("a rotation on interleaved pairs with no rope_scaling is what this tower runs")
        if int(cfg["num_nextn_predict_layers"]) > 1:
            raise ValueError("a prediction module of depth 1 (num_nextn_predict_layers 0 or 1) is what this tower runs")
        if cfg["qk_nope_head_dim"] != cfg["v_head_dim"] or cfg["moe_layer_freq"] != 1 \
                or cfg.get("attention_bias") or cfg.get("tie_word_embeddings"):
            raise ValueError("one width for a head's own key columns and its values, an expert layer every "
                             "layer after the leading ones, no bias and an untied head is what this tower runs")
        lead = int(cfg["first_k_dense_replace"])
        return cls(
            vocab=int(cfg["vocab_size"]), n_layers=int(cfg["num_hidden_layers"]), hidden=int(cfg["hidden_size"]),
            n_heads=int(cfg["num_attention_heads"]), n_kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["v_head_dim"]), rope_head_dim=int(cfg["qk_rope_head_dim"]),
            q_lora_rank=None if cfg["q_lora_rank"] is None else int(cfg["q_lora_rank"]),
            kv_lora_rank=int(cfg["kv_lora_rank"]), rope_theta=float(cfg["rope_theta"]),
            dense_width=int(cfg["intermediate_size"]),
            n_experts=int(cfg.get("router_width", cfg["n_routed_experts"])),
            experts_per_token=int(cfg["num_experts_per_tok"]), expert_width=int(cfg["moe_intermediate_size"]),
            routed_scaling=float(cfg["routed_scaling_factor"]),
            first_held=int(cfg.get("first_held_expert", 0)), n_held=int(cfg["n_routed_experts"]),
            rms_eps=float(cfg["rms_norm_eps"]), mtp_depth=int(cfg["num_nextn_predict_layers"]),
            mtp_weight=float(cfg.get("mtp_loss_weight", 0.1)), leading_kinds=(MLA,) * lead, **kw)

    # ------------------------------------------------------------ parameters

    @property
    def after_kinds(self) -> Tuple[str, ...]:
        return (MLA,) * self.mtp_depth  # the module's layer

    def attention_shapes(self, kind):
        d, h, hd, r = self.hidden, self.n_heads, self.head_dim, self.rope_head_dim
        query = ({"wq": (d, h * (hd + r))} if self.q_lora_rank is None else
                 {"wq_a": (d, self.q_lora_rank), "q_norm": (self.q_lora_rank,),
                  "wq_b": (self.q_lora_rank, h * (hd + r))})
        return {**query, "wkv_a": (d, self.kv_lora_rank + r), "kv_norm": (self.kv_lora_rank,),
                "wkv_b": (self.kv_lora_rank, h * 2 * hd), "wo": (h * hd, d)}

    def param_shapes(self):
        """The tower's leaves and, under ``mtp``, the module's own beside its
        layer's (``after``): the two norms and the product that merge the
        next token's row with the tower's stream, and the norm before the
        shared head."""
        out = super().param_shapes()
        if self.mtp_depth:
            d = self.hidden
            out["mtp"] = {"norm_e": (d,), "norm_h": (d,), "merge": (2 * d, d), "norm_s": (d,)}
        return out

    def counters(self):
        """The picks by expert layer (the scanned layers, then the module's)
        and held expert; tile pairs the latent attention's kernels visited and
        tile pairs that hold a live pair, a head (row 1; row 0 stays 0: the
        accepted readers of this counter take the ``mellum`` family's two
        rows); the routers' selection bias by expert layer, a buffer of zeros
        that nothing here moves; and ``objective``: the running sums of the
        objectives' weights (``sum w``, ``sum w2``) and then of their weighted
        cross-entropies."""
        blocks = self.n_scanned + self.mtp_depth
        return dict(super().counters(), attention_tiles=jnp.zeros((2, 2), jnp.int32),
                    router_bias=jnp.zeros((blocks, self.n_experts), jnp.float32),
                    objective=jnp.zeros((2 * (1 + self.mtp_depth),), jnp.float32))

    # ------------------------------------------------------------- attention

    def attention(self, kind, p, a, side, attend):
        starts, rope = side
        return latent_attention(p, a, starts, n_heads=self.n_heads, head_dim=self.head_dim,
                                rope_head_dim=self.rope_head_dim, kv_lora_rank=self.kv_lora_rank,
                                eps=self.rms_eps, tile=self.tile, interpret=self.interpret, rope=rope)

    def _side(self, starts):
        rope = None if self.rope_theta is None else rope_tables(starts, self.rope_head_dim, self.rope_theta)
        return {MLA: (starts, rope)}

    # --------------------------------------------------------------- forward

    def _hidden(self, variables, dense, emb):
        """The residual stream after the last scanned layer and the step's
        counters (the module's are ``objectives``' to add)."""
        return self._tower(variables, dense, emb)[:2]

    def _tower(self, variables, dense, emb):
        """``_hidden``'s two, and what the module reads beside them: the
        gathered rows, the documents' starts and the attention's side input."""
        (rows, _mask), = emb
        starts = dense[0].astype(jnp.int32)  # (B, T): where each position's document starts
        b, t, _ = rows.shape
        tile = min(self.tile, t)
        record_event("joyai_flash.paths", latent_attention="pallas_interval_two_products",
                     q_low_rank=self.q_lora_rank, rope="pallas_pass" if self.rope_theta is not None else "none",
                     rope_pairs="interleaved", mtp_depth=self.mtp_depth, mtp_embedding="shifted_slot",
                     head_passes=1 + self.mtp_depth, tile=tile, seq_len=t,
                     grid_mla=interval_visits(t // tile, tile, None), head_chunk=self.head_chunk,
                     **latent_attention_grid(b, t, n_heads=self.n_heads, head_dim=self.head_dim,
                                             rope_head_dim=self.rope_head_dim, tile=tile),
                     **self.expert_paths(b * t))
        stats = variables.get("batch_stats")
        side = self._side(starts)
        h, picks = self.layers(variables["params"], rows, side,
                               buffers={"router_bias": self._router_bias(stats)[:self.n_scanned]})
        if stats is not None:
            stats = dict(stats, expert_picks=stats["expert_picks"].at[:self.n_scanned].add(picks),
                         attention_tiles=self._count_tiles(stats, starts, self.n_layers))
        return h, stats, rows.astype(jnp.float32), starts, side

    def _router_bias(self, stats):
        return (stats or self.counters())["router_bias"]

    def _count_tiles(self, stats, starts, blocks):
        tiles = jnp.stack(interval_tile_counts(starts, None, self.tile)) * blocks
        return stats["attention_tiles"].at[1].add(tiles)

    def objectives(self, variables, dense, emb, labels):
        """Two passes through the one head: the tower's normed stream against
        the next token, and the module's stream against the one after it,
        ``w2_i = w_i w_{i+1}`` (zero where ``x_{i+1}`` or ``x_{i+2}`` is not in
        ``i``'s document), ``mtp_weight`` its coefficient. The tower's
        ``rms(h) * wf`` is computed once and feeds both."""
        h, stats, rows, starts, side = self._tower(variables, dense, emb)
        params = variables["params"]
        targets, weight = labels[0], labels[1].astype(jnp.float32)
        with jax.named_scope("lm_head"):
            u = _rms(h, params["norm_f"], self.rms_eps)
        passes = [("lm_head", u, None, targets, weight, 1.0)]
        if self.mtp_depth:
            g, picks = self._module(params, u, rows, starts, side, self._router_bias(stats)[self.n_scanned])
            passes.append(("mtp/lm_head", g, params["mtp"]["norm_s"], shifted(targets), weight * shifted(weight),
                           self.mtp_weight))
            if stats is not None:
                stats = dict(stats, expert_picks=stats["expert_picks"].at[self.n_scanned].add(picks),
                             attention_tiles=self._count_tiles(stats, starts, 1))
        return passes, stats

    def _module(self, params, u, rows, starts, side, router_bias):
        """The module's stream (B, T, hidden) and its layer's picks: the next
        token's row (the gathered slot shifted by one position, zero where the
        document ends) and the tower's normed stream, each normed, side by
        side through ``merge``, then one whole layer."""
        mtp, eps = params["mtp"], self.rms_eps
        at = jnp.arange(1, rows.shape[1], dtype=jnp.int32)[None, :]
        # position i + 1 carries i's document on where it is no document's start
        goes_on = jnp.concatenate([starts[:, 1:] != at, jnp.zeros_like(starts[:, :1], bool)], axis=1)
        with jax.named_scope("mtp/merge"):
            e = jnp.where(goes_on[..., None], shifted(rows), 0.0)
            z = jnp.concatenate([_rms(e, mtp["norm_e"], eps), _rms(u, mtp["norm_h"], eps)], axis=-1)
            g = _mm(z, mtp["merge"])
        with jax.named_scope("mtp"):
            return self.layer_after(0, params, g, side, buffers={"router_bias": router_bias})
