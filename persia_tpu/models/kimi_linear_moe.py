"""A causal tower of gated delta-rule and latent-attention layers over packed
documents (the ``kimi_linear`` family): three layers of every four are Kimi
Delta Attention (KDA: a short convolution on q, k and v, a decay a channel, a
128 x 128 state a head and no keys; ``ops/delta_rule.py``), the fourth is
multi-head latent attention without positions (MLA, NoPE: scores 192 wide, a
head's own 128 key columns beside 64 that all heads share, values 128 wide;
``moe_tower.latent_attention``, which the ``joyai_llm_flash`` family shares, over
``ops/flash_attention.py::interval_attention`` with ``k_shared``). A leading
KDA layer has a dense SwiGLU; every later layer a shared expert beside
sigmoid-routed ones. The tower itself (the scan, the expert layer that is told
which experts it holds, the head) is ``models/moe_tower.py``'s; here is what
this family states and adds; the chunked head and loss are
``moe_tower.NextTokenTower``'s, as the ``mellum`` family's are: the two train
the same objective on the same batches.

The token rows come from the sparse plane as one raw slot, ``(B, T, hidden)``
float32; ``dense`` holds one int32 side input, ``(B, T)``: for each position
the index at which its document starts. A position reads no state, key or
convolution tap of another document.

Left to XLA in a KDA layer: the projections, the convolution (four shifted
multiply-adds under the documents' mask), the l2 norms, decays and gates. The
delta rule itself, forward and backward, is ``ops/delta_rule.py``'s four Pallas
kernels: a chunk's operands (``kda_prepare_fwd``, ``kda_prepare_bwd``) and the
scan over chunks (``kda_chunk_fwd``, ``kda_chunk_bwd``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from persia_tpu.models.moe_tower import NextTokenTower, _mm, _rms, latent_attention, latent_attention_grid
from persia_tpu.ops.delta_rule import KDA_CHUNK, kda, kda_chunk, log_decay_floor
from persia_tpu.ops.flash_attention import (
    ATTENTION_OUT, BLOCK_DIFFUSION_TILE, interval_tile_counts, interval_visits,
)
from persia_tpu.tracing import record_event

KDA, MLA = "kda", "mla"
L2_EPS = 1e-6


def short_convolution(x, taps, starts):
    """``silu(sum_t taps[t] * x[i - t])`` over the taps that lie in position
    ``i``'s document: x (B, T, C) float32, taps (K, C), starts (B, T) int32."""
    t = x.shape[1]
    at = jnp.arange(t, dtype=jnp.int32)[None, :]
    out = taps[0] * x
    for back in range(1, taps.shape[0]):
        inside = (at - back >= starts)[..., None]
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :t]
        out = out + jnp.where(inside, taps[back] * shifted, 0.0)
    return jax.nn.silu(out)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


@dataclass(frozen=True)
class KimiLinearMoE(NextTokenTower):
    vocab: int  # ids held here: the logits' width
    n_layers: int  # the leading layer and whole periods after it
    hidden: int = 2304
    n_heads: int = 32
    n_kv_heads: int = 32
    head_dim: int = 128  # KDA's key and value width a head, MLA's own key columns and its values
    rope_head_dim: int = 64  # MLA's key columns that all heads share (no rotation is applied: NoPE)
    kv_lora_rank: int = 512
    low_rank: int = 128  # the decay's and the output gate's inner width
    conv_taps: int = 4
    dense_width: int = 9216
    n_experts: int = 256  # the router's width, as published
    experts_per_token: int = 8
    expert_width: int = 1024
    routed_scaling: float = 2.446
    first_held: int = 0
    n_held: int = 256
    rms_eps: float = 1e-5
    layer_kinds: Tuple[str, ...] = (KDA, KDA, MLA, KDA)  # one period after the leading layer
    kind_leaves: bool = True
    leading_kinds: Tuple[str, ...] = (KDA,)
    mlp: str = "shared_experts"
    router_law: str = "sigmoid"
    # a chunk of picks holds twice what an even router sends the held experts (8,192 picks for 16,384
    # tokens), where the tower's own is an eighth over it: over 32 shares a layer's load on one share
    # follows which token ids took it and reads 0.65-1.95 of even by the seed, and a second trip of
    # the loop over chunks costs more than the picks in it (``PERF.md`` section 7 row 5)
    pick_room: float = 2.0
    head_chunk: int = 2048
    tile: int = BLOCK_DIFFUSION_TILE  # the attention kernels' (the CPU tests cut a short sequence)
    kda_chunk: int = KDA_CHUNK  # the delta rule's scan
    kda_heads: int = 8  # heads a pass of a KDA layer's own part (see ``_kda``)
    interpret: bool = False

    @classmethod
    def from_config(cls, cfg: dict, **kw) -> "KimiLinearMoE":
        """The tower of a published ``config.json`` (its keys as published),
        cut to a chip's share where the dict says so: ``num_hidden_layers``
        layers from layer 1 on (``first_k_dense_replace`` leading ones, then
        whole periods of ``linear_attn_config``'s pattern), ``num_experts``
        held from ``first_held_expert`` on of ``router_width`` routed,
        ``vocab_size`` ids."""
        n, lin = int(cfg["num_hidden_layers"]), cfg["linear_attn_config"]
        lead = int(cfg["first_k_dense_replace"])
        kinds = [KDA if l in lin["kda_layers"] else MLA for l in range(1, n + 1)]
        if lin["num_heads"] != cfg["num_attention_heads"] or lin["head_dim"] != cfg["v_head_dim"] \
                or cfg["qk_nope_head_dim"] != cfg["v_head_dim"] or cfg["q_lora_rank"] is not None:
            raise ValueError("one head count and one 128-wide value for both attentions, and no q_lora, "
                             "is what this tower runs")
        if cfg["moe_router_activation_func"] != "sigmoid" or not cfg["moe_renormalize"] \
                or cfg["num_expert_group"] != 1 or cfg["num_shared_experts"] != 1:
            raise ValueError("sigmoid routing, renormalised, in one group, beside one shared expert, "
                             "is what this tower runs")
        period = int(lin["full_attn_layers"][0])  # the pattern's first latent layer closes its first period
        if kinds[lead:] != kinds[lead:lead + period] * ((n - lead) // period):
            raise ValueError(f"layers {lead + 1}..{n} are no whole periods of {kinds[lead:lead + period]}")
        return cls(
            vocab=int(cfg["vocab_size"]), n_layers=n, hidden=int(cfg["hidden_size"]),
            n_heads=int(cfg["num_attention_heads"]), n_kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["v_head_dim"]), rope_head_dim=int(cfg["qk_rope_head_dim"]),
            kv_lora_rank=int(cfg["kv_lora_rank"]), low_rank=int(lin["head_dim"]),
            conv_taps=int(lin["short_conv_kernel_size"]), dense_width=int(cfg["intermediate_size"]),
            n_experts=int(cfg.get("router_width", cfg["num_experts"])),
            experts_per_token=int(cfg["num_experts_per_token"]),
            expert_width=int(cfg["moe_intermediate_size"]),
            routed_scaling=float(cfg["routed_scaling_factor"]),
            first_held=int(cfg.get("first_held_expert", 0)), n_held=int(cfg["num_experts"]),
            rms_eps=float(cfg["rms_norm_eps"]),
            layer_kinds=tuple(kinds[lead:lead + period]), leading_kinds=tuple(kinds[:lead]), **kw)

    # ------------------------------------------------------------ parameters

    def attention_shapes(self, kind):
        d, h, hd, r = self.hidden, self.n_heads, self.head_dim, self.low_rank
        if kind == MLA:
            return {"wq": (d, h * (hd + self.rope_head_dim)),
                    "wkv_a": (d, self.kv_lora_rank + self.rope_head_dim), "kv_norm": (self.kv_lora_rank,),
                    "wkv_b": (self.kv_lora_rank, h * 2 * hd), "wo": (h * hd, d)}
        return {"wq": (d, h * hd), "wk": (d, h * hd), "wv": (d, h * hd),
                "conv_q": (self.conv_taps, h * hd), "conv_k": (self.conv_taps, h * hd),
                "conv_v": (self.conv_taps, h * hd),
                "wf_a": (d, r), "wf_b": (r, h * hd), "a_log": (h,), "dt_bias": (h * hd,),
                "wb": (d, h), "o_norm": (hd,), "wg_a": (d, r), "wg_b": (r, h * hd), "wo": (h * hd, d)}

    def _kind_counts(self):
        return {kind: self.layer_kinds.count(kind) * self.n_scanned // len(self.layer_kinds)
                for kind in set(self.layer_kinds)}

    def counters(self):
        """The picks by expert layer and held expert; tile pairs the latent
        attention's kernels visited and tile pairs that hold a live pair, a
        head (row 1; row 0 stays 0: the accepted readers of this counter take
        the ``mellum`` family's two rows); and the routers' selection bias by kind of
        layer, a buffer of zeros that nothing here moves."""
        return dict(expert_picks=jnp.zeros((self.n_scanned, self.n_held), jnp.int32),
                    attention_tiles=jnp.zeros((2, 2), jnp.int32),
                    router_bias={kind: jnp.zeros((count, self.n_experts), jnp.float32)
                                 for kind, count in self._kind_counts().items()})

    # ------------------------------------------------------------- attention

    def attention(self, kind, p, a, side, attend):
        return (self._mla if kind == MLA else self._kda)(p, a, side)

    def _kda(self, p, a, starts):
        """Everything between the projections and the output projection is a
        head's own, so it runs ``kda_heads`` heads at a time under one
        ``lax.map`` whose body is recomputed in the backward: the convolution's,
        the norms' and the chunked form's intermediates (a score of arrays of a
        projection's size) stand for one group of heads only. The layer keeps
        the gated output (bfloat16, what the output projection takes), as the
        tower keeps an attention kernel's."""
        b, t, _ = a.shape
        h, hd = self.n_heads, self.head_dim
        hg = min(self.kda_heads, h)
        if h % hg:
            raise ValueError(f"{h} heads are no whole groups of {hg}")
        by_group = lambda x: jnp.moveaxis(x.reshape(*x.shape[:-1], h // hg, -1), -2, 0)  # columns by head
        low_rank = lambda u, w: _mm(_mm(a, p[u]), p[w])
        xs = {name: by_group(x) for name, x in dict(
            q=_mm(a, p["wq"]), k=_mm(a, p["wk"]), v=_mm(a, p["wv"]), decay=low_rank("wf_a", "wf_b"),
            gate=low_rank("wg_a", "wg_b"), beta=_mm(a, p["wb"]), conv_q=p["conv_q"], conv_k=p["conv_k"],
            conv_v=p["conv_v"], a_log=p["a_log"], dt_bias=p["dt_bias"]).items()}
        heads = lambda x: x.reshape(b, t, hg, -1)
        floor = log_decay_floor(kda_chunk(t, self.kda_chunk))

        @jax.checkpoint
        def group(x):
            q, k, v = (heads(short_convolution(x[n], x[f"conv_{n}"], starts)) for n in "qkv")
            q, k = _l2norm(q) * hd ** -0.5, _l2norm(k)
            g = -jnp.exp(x["a_log"])[:, None] * heads(jax.nn.softplus(x["decay"] + x["dt_bias"]))
            g = jnp.maximum(g, floor)  # the range in which the chunked form is exact (-2.5 at 64)
            o = kda(q, k, v, g, jax.nn.sigmoid(x["beta"]), starts, chunk=self.kda_chunk,
                    interpret=self.interpret)
            y = _rms(o, p["o_norm"], self.rms_eps) * jax.nn.sigmoid(heads(x["gate"]))
            return y.astype(jnp.bfloat16).reshape(b, t, hg * hd)

        y = jnp.moveaxis(jax.lax.map(group, xs), 0, 2).reshape(b, t, h * hd)
        return _mm(checkpoint_name(y, ATTENTION_OUT), p["wo"])

    def _mla(self, p, a, starts):
        return latent_attention(p, a, starts, n_heads=self.n_heads, head_dim=self.head_dim,
                                rope_head_dim=self.rope_head_dim, kv_lora_rank=self.kv_lora_rank,
                                eps=self.rms_eps, tile=self.tile, interpret=self.interpret)

    # --------------------------------------------------------------- forward

    def _hidden(self, variables, dense, emb):
        """The residual stream after the last layer and the step's counters."""
        (rows, _mask), = emb
        starts = dense[0].astype(jnp.int32)  # (B, T): where each position's document starts
        b, t, _ = rows.shape
        tile = min(self.tile, t)
        record_event("kimi_linear.paths", kda="pallas_chunk_scan", kda_chunk=kda_chunk(t, self.kda_chunk),
                     kda_prepare="pallas", kda_backward_keeps="chunk_states_float32+chunk_inverse_float32",
                     kda_heads_a_pass=min(self.kda_heads, self.n_heads),
                     convolution="xla", norms_and_gates="xla",
                     latent_attention="pallas_interval_two_products", tile=tile, seq_len=t,
                     grid_mla=interval_visits(t // tile, tile, None), head_chunk=self.head_chunk,
                     **latent_attention_grid(b, t, n_heads=self.n_heads, head_dim=self.head_dim,
                                             rope_head_dim=self.rope_head_dim, tile=tile),
                     **self.expert_paths(b * t))
        stats = variables.get("batch_stats")
        buffers = (stats or self.counters())["router_bias"]
        h, picks = self.layers(variables["params"], rows, {KDA: starts, MLA: starts},
                               buffers={kind: {"router_bias": x} for kind, x in buffers.items()})
        if stats is not None:
            tiles = jnp.stack(interval_tile_counts(starts, None, self.tile)) * self._kind_counts().get(MLA, 0)
            stats = dict(stats, expert_picks=stats["expert_picks"] + picks,
                         attention_tiles=stats["attention_tiles"].at[1].add(tiles))
        return h, stats
