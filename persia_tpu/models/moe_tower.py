"""What the sequence towers share: a mixture-of-experts tower over the sparse
plane (RMSNorm, an attention a kind of layer, an expert layer that is told
which experts it holds, an untied head), as one ``lax.scan`` over stacked
layers. A tower states what its layers are: the kinds of layer a period
holds and whether they hold different leaves, a leading layer before the
scan, the MLP (routed experts; routed and a shared one; dense) and the
router's law (softmax then the k largest renormalised; sigmoid with a
selection bias and a scaling factor). ``models/sdar_moe.py`` (block
diffusion) and ``models/mellum_moe.py`` (causal, window and full layers over
packed documents) keep the tower's own attention (grouped-query softmax
attention with RoPE and per-head q/k norms) and state a RoPE table a kind,
which attention kernel runs, which positions have logits and the loss;
``models/kimi_linear_moe.py`` brings the gated delta rule beside latent
attention, a leading dense layer, the shared expert and the sigmoid law;
``models/joyai_flash_moe.py`` has latent attention in every layer, with a
low-rank query and rotated columns, and a prediction module after the scan
(a block with leaves of its own: ``after_kinds``) whose objective is a second
pass through the head. Latent attention is one function here for both
(``latent_attention``).

**The expert layer is told which experts it holds** (``first_held``,
``n_held``): it routes over all ``n_experts`` with the published
``experts_per_token``, and computes its own experts' part of the result for
the tokens routed to them; what the absent experts would add is another
chip's to add. On one chip it runs without that exchange. The parts all the
shares give sum to the whole layer, a shared expert counted once
(``tests/test_sdar_moe.py``, ``tests/test_mellum_moe.py``,
``tests/test_kimi_linear_moe.py``).

Arithmetic: every matrix product takes bfloat16 operands and accumulates in
float32, forward and backward (``_mm``, ``ops.grouped_matmul``, the attention
kernels); parameters, residual stream, norms, RoPE, softmax, router
probabilities and loss are float32. q and k go from their projections to the
attention kernels through one pass in the projections' own layout
(``ops.qk_prep.qk_norm_rope``: per-head norm, RoPE, the cast to bfloat16);
latent attention's rotated columns through ``ops.qk_prep.mla_rope``.

Not a flax module: the layers are one ``lax.scan`` over stacked parameters
with each layer recomputed in the backward, which flax's lifted transforms
would only wrap. ``init`` and ``apply`` keep flax's calling convention, which
is all the fused step asks of a model.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from persia_tpu.ops.flash_attention import (
    ATTENTION_LSE, ATTENTION_OUT, attention_grid_steps, heads_a_step, interval_attention, interval_visits,
)
from persia_tpu.ops.grouped_matmul import grouped_matmul, grouped_outer, grouped_tiles
from persia_tpu.ops.qk_prep import mla_rope, qk_norm_rope


@jax.custom_vjp
def _mm(a, w):
    """a (..., K) x w (K, N) -> (..., N): bfloat16 operands, float32 sums,
    float32 results; the same for both gradients (the input's in its dtype)."""
    return _dot(a.astype(jnp.bfloat16), w.astype(jnp.bfloat16))


def _dot(a, b):
    # one pass whatever the process's default matmul precision: the operands are bfloat16
    return jnp.dot(a, b, precision=jax.lax.Precision.DEFAULT, preferred_element_type=jnp.float32)


def _mm_fwd(a, w):
    like = jnp.zeros((0,), a.dtype)  # the input's dtype, which its gradient has to take
    a, w = a.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    return _dot(a, w), (a, w, like)


def _mm_bwd(res, g):
    a, w, like = res
    g = g.astype(jnp.bfloat16)
    da = _dot(g, w.T)
    dw = _dot(a.reshape(-1, a.shape[-1]).T, g.reshape(-1, g.shape[-1]))
    return da.astype(like.dtype), dw


_mm.defvjp(_mm_fwd, _mm_bwd)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _chunk(m, flat_w, order, starts, lo, size, k):
    """The picks ``lo .. lo + size`` of the sorted order: their sizes by held
    expert, their place among all picks, whether each is on a held expert, its
    token, its routing weight (0 where it is not), and its token's row."""
    sizes = jnp.clip(starts[1:] - lo, 0, size) - jnp.clip(starts[:-1] - lo, 0, size)
    at = jax.lax.dynamic_slice(order, (lo,), (size,))  # ``order`` is padded to whole chunks
    live = (lo + jnp.arange(size, dtype=jnp.int32)) < starts[-1]
    token = at // k
    return sizes, at, live, token, jnp.where(live, flat_w[at], 0.0), m[token].astype(jnp.bfloat16)


def _swiglu(rows, gate, up, sizes, interpret):
    with jax.named_scope("experts"):
        g = grouped_matmul(rows, gate, sizes, interpret=interpret)
        u = grouped_matmul(rows, up, sizes, interpret=interpret)
    return g, u, (jax.nn.silu(g) * u).astype(jnp.bfloat16)


@partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _held_experts(m, flat_w, gate, up, down, order, starts, size, k, interpret):
    """sum over the picks on held experts of weight x down_e(silu(gate_e x) *
    up_e x), by token: m (N, d) -> (N, d). ``order`` holds the picks sorted by
    held expert (pick i is token ``i // k``), ``starts`` (held + 1,) where each
    expert's begin, ``flat_w`` (N * k,) the routing weights. The loop's trip
    count is the live chunks', so autodiff cannot reverse it, and the grouped
    products are Pallas kernels, which have no derivative of their own: the
    backward below is written out, with the same products (bfloat16 operands,
    float32 sums) in the same chunks, recomputing a chunk's forward. (While
    the products were the compiler's ``lax.ragged_dot``, autodiff also gave
    their gradients rounded to bfloat16 and wanted the weights transposed as
    arrays of their own; ``ops.grouped_matmul`` contracts the weights over
    either axis and returns float32.) Every product gives 0 for a row of no
    group, which ``dw`` in the backward leans on: 0 x an unwritten row could
    be NaN."""
    return _held_experts_fwd(m, flat_w, gate, up, down, order, starts, size, k, interpret)[0]


def _held_experts_fwd(m, flat_w, gate, up, down, order, starts, size, k, interpret):
    gate_b, up_b, down_b = (x.astype(jnp.bfloat16) for x in (gate, up, down))

    def body(c, y):
        with jax.named_scope("dispatch"):
            sizes, _at, live, token, w, rows = _chunk(m, flat_w, order, starts, c * size, size, k)
        _g, _u, mid = _swiglu(rows, gate_b, up_b, sizes, interpret)
        with jax.named_scope("experts"):
            out = grouped_matmul(mid, down_b, sizes, interpret=interpret)
        with jax.named_scope("combine"):
            return y.at[token].add(jnp.where(live[:, None], out, 0.0) * w[:, None])

    y = jax.lax.fori_loop(0, (starts[-1] + size - 1) // size, body, jnp.zeros(m.shape, jnp.float32))
    return y, (m, flat_w, gate_b, up_b, down_b, order, starts)


def _held_experts_bwd(size, k, interpret, res, dy):
    m, flat_w, gate_b, up_b, down_b, order, starts = res
    # the products with the transposed weights contract over the weights' last
    # axis in the kernel: no transposed copy of a leaf is made
    product = partial(grouped_matmul, interpret=interpret)
    by_transposed = partial(grouped_matmul, transposed=True, interpret=interpret)
    outer = partial(grouped_outer, interpret=interpret)

    def body(c, carry):
        dm, dw, d_gate, d_up, d_down = carry
        with jax.named_scope("dispatch"):
            sizes, at, live, token, w, rows = _chunk(m, flat_w, order, starts, c * size, size, k)
        g, u, mid = _swiglu(rows, gate_b, up_b, sizes, interpret)
        with jax.named_scope("combine"):
            dyt = jnp.where(live[:, None], dy[token], 0.0)
        with jax.named_scope("experts"):
            out = product(mid, down_b, sizes)
            d_out = (dyt * w[:, None]).astype(jnp.bfloat16)
            d_mid = by_transposed(d_out, down_b, sizes)
            sig = jax.nn.sigmoid(g)
            dg = (d_mid * u * (sig * (1.0 + g * (1.0 - sig)))).astype(jnp.bfloat16)
            du = (d_mid * (g * sig)).astype(jnp.bfloat16)
            d_rows = by_transposed(dg, gate_b, sizes) + by_transposed(du, up_b, sizes)
            d_gate = d_gate + outer(rows, dg, sizes)
            d_up = d_up + outer(rows, du, sizes)
            d_down = d_down + outer(mid, d_out, sizes)
        with jax.named_scope("dispatch"):
            dm = dm.at[token].add(jnp.where(live[:, None], d_rows, 0.0))
            dw = dw.at[at].add(jnp.sum(dyt * out, axis=-1))
        return dm, dw, d_gate, d_up, d_down

    zeros = lambda x: jnp.zeros(x.shape, jnp.float32)
    dm, dw, d_gate, d_up, d_down = jax.lax.fori_loop(
        0, (starts[-1] + size - 1) // size, body,
        (zeros(m), zeros(flat_w), zeros(gate_b), zeros(up_b), zeros(down_b)))
    return dm, dw, d_gate, d_up, d_down, None, None


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def _swiglu_mlp(m, gate, up, down):
    """down(silu(gate m) * up m) for every token: a dense MLP, a shared expert."""
    return _mm(jax.nn.silu(_mm(m, gate)) * _mm(m, up), down)


def latent_attention(p, a, starts, *, n_heads, head_dim, rope_head_dim, kv_lora_rank, eps, tile,
                     interpret, rope=None):
    """Multi-head latent attention over packed documents, for every tower that
    holds it: what the layer adds to the residual stream for its normed input
    ``a`` (B, T, hidden); query ``i`` reads the keys ``starts_i .. i``. A
    head's score is ``head_dim + rope_head_dim`` wide: its own ``head_dim``
    key columns (with its values, from the ``kv_lora_rank``-wide normed latent
    through ``wkv_b``) beside ``rope_head_dim`` that all heads share (the last
    columns of ``wkv_a``); values are ``head_dim`` wide. The query is full
    rank (``wq``) or low rank (``wq_a``, the norm ``q_norm``, ``wq_b``) by the
    leaves ``p`` holds. ``rope``: None (no positions: NoPE), or ``(cos, sin)``
    (B, T, rope_head_dim) of each position's angle a column, by which the last
    ``rope_head_dim`` columns of every head's query and the shared key columns
    are rotated as interleaved pairs in float32, before the cast to bfloat16
    the kernels take (``ops/flash_attention.py::interval_attention`` with
    ``k_shared``). With a rotation the query is two products over the leaf's
    column groups (a head's own columns, then its rotated ones: the leaf keeps
    its layout) and the rotated columns go through one pass,
    ``ops/qk_prep.py::mla_rope``, straight into the head-major operand the
    kernels read; the shared key columns through the same pass as one head."""
    b, t, _ = a.shape
    h, hd, r = n_heads, head_dim, rope_head_dim
    if "wq_a" in p:
        x, wq = _rms(_mm(a, p["wq_a"]), p["q_norm"], eps), p["wq_b"]
    else:
        x, wq = a, p["wq"]
    if rope is None:
        q, q_shared = _mm(x, wq).astype(jnp.bfloat16).reshape(b, t, h, hd + r), None
    else:
        # the query as two products over the leaf's column groups: a head's own
        # columns straight to bfloat16, its rotated ones in float32 to the pass
        wq = wq.reshape(-1, h, hd + r)
        q = _mm(x, wq[..., :hd].reshape(-1, h * hd)).astype(jnp.bfloat16).reshape(b, t, h, hd)
        turned = _mm(x, wq[..., hd:].reshape(-1, h * r))
    kv_a = _mm(a, p["wkv_a"])
    latent = _rms(kv_a[..., :kv_lora_rank], p["kv_norm"], eps)
    shared = kv_a[..., kv_lora_rank:]
    if rope is not None:
        with jax.named_scope("rope"):
            cos, sin = rope
            q_shared = mla_rope(turned, cos, sin, h, interpret)
            shared = mla_rope(shared, cos, sin, 1, interpret).reshape(b, t, r)
    shared = shared.astype(jnp.bfloat16)
    kv = _mm(latent, p["wkv_b"]).astype(jnp.bfloat16).reshape(b, t, h, 2 * hd)
    o = interval_attention(q, kv[..., :hd], kv[..., hd:], starts, tile=tile, interpret=interpret,
                           k_shared=shared, q_shared=q_shared)
    return _mm(o.reshape(b, t, h * hd), p["wo"])


def latent_attention_grid(batch, length, *, n_heads, head_dim, rope_head_dim, tile) -> Dict[str, int]:
    """What a tower's ``*.paths`` event says of ``latent_attention``'s kernels
    over ``batch`` sequences of ``length``: the query heads a grid step and the
    grid steps of one kernel's call (a K/V head a query head, the
    ``rope_head_dim`` key columns all share beside, the triangle's grid)."""
    grid = dict(hq=n_heads, hkv=n_heads, d=head_dim, tile=tile, shared=rope_head_dim)
    visits = interval_visits(length // tile, tile, None)
    return {"attention_heads_a_step": heads_a_step(**grid),
            "attention_grid_steps": attention_grid_steps(batch, visits=visits, **grid)}


class MoETower:
    """The tower's parameters, layers and scan, for a frozen dataclass that
    holds its sizes (``vocab``, ``n_layers``, ``hidden``, ``n_heads``,
    ``n_kv_heads``, ``head_dim``, ``n_experts``, ``experts_per_token``,
    ``expert_width``, ``first_held``, ``n_held``, ``rms_eps``, ``interpret``
    for the Pallas kernels) and states what its layers are:

    - ``layer_kinds``, the kinds of layer of one period in their order: the
      scanned layers are the period repeated. A kind has an attention of its
      own (``attention``) and what that takes beside the layer's leaves (a
      RoPE table, the documents' starts).
    - ``kind_leaves``: False where every kind holds the same leaves (they are
      stacked along one layer axis under ``layers``), True where the kinds'
      attentions hold different ones (``attention_shapes``): each kind's
      layers are then stacked under ``layers[kind]``. Either way a period is
      one scan body.
    - ``leading_kinds``: layers before the scan, each with leaves of its own
      under ``lead``, whose MLP is dense (a SwiGLU of ``dense_width``, no
      router).
    - ``after_kinds``: blocks after the scan, each a whole layer of the
      scanned layers' form with leaves of its own under ``after``, its own row
      of ``expert_picks`` after the scanned layers' and its own selection
      bias. The tower does not run them in ``layers``: a tower that states
      one says what enters it (``layer_after``; a prediction module's merged
      stream in ``models/joyai_flash_moe.py``).
    - ``mlp``: what follows a scanned layer's attention: ``"experts"`` (the
      held routed experts' part) or ``"shared_experts"`` (that and an expert
      every token takes, computed here whole).
    - ``router_law``: ``"softmax"`` (softmax over all experts, the k largest,
      renormalised) or ``"sigmoid"`` (sigmoid scores, the k largest of score
      plus a selection bias that no gradient reaches, the picked scores
      renormalised and times ``routed_scaling``)."""

    layer_kinds: Tuple[str, ...] = ("attention",)
    kind_leaves: bool = False
    leading_kinds: Tuple[str, ...] = ()
    after_kinds: Tuple[str, ...] = ()
    mlp: str = "experts"
    router_law: str = "softmax"
    pick_room: float = 1.125

    # ------------------------------------------------------------ parameters

    def attention_shapes(self, kind: str) -> Dict[str, Tuple[int, ...]]:
        """The leaves of one layer's attention of ``kind``."""
        d, hd = self.hidden, self.head_dim
        return {"wq": (d, self.n_heads * hd), "wk": (d, self.n_kv_heads * hd),
                "wv": (d, self.n_kv_heads * hd), "q_norm": (hd,), "k_norm": (hd,),
                "wo": (self.n_heads * hd, d)}

    def mlp_shapes(self, mlp: str) -> Dict[str, Tuple[int, ...]]:
        """The leaves of one layer's MLP: the router and the held experts, the
        shared expert beside them, or a dense SwiGLU."""
        d, e, f = self.hidden, self.n_held, self.expert_width
        if mlp == "dense":
            w = self.dense_width
            return {"dense_gate": (d, w), "dense_up": (d, w), "dense_down": (w, d)}
        out = {"router": (d, self.n_experts), "gate": (e, d, f), "up": (e, d, f), "down": (e, f, d)}
        if mlp == "shared_experts":
            out.update(shared_gate=(d, f), shared_up=(d, f), shared_down=(f, d))
        return out

    def layer_shapes(self, kind: str, mlp: str) -> Dict[str, Tuple[int, ...]]:
        d = self.hidden
        return {"norm1": (d,), **self.attention_shapes(kind), "norm2": (d,), **self.mlp_shapes(mlp)}

    @property
    def n_scanned(self) -> int:
        return self.n_layers - len(self.leading_kinds)

    def param_shapes(self) -> Dict[str, Any]:
        """Every dense leaf; those under ``layers`` carry a leading layer axis
        (of all scanned layers, or of a kind's where ``kind_leaves``)."""
        kinds, n = self.layer_kinds, self.n_scanned
        stacked = lambda shapes, count: {k: (count, *s) for k, s in shapes.items()}
        if self.kind_leaves:
            layers = {kind: stacked(self.layer_shapes(kind, self.mlp), kinds.count(kind) * n // len(kinds))
                      for kind in set(kinds)}
        else:
            layers = stacked(self.layer_shapes(kinds[0], self.mlp), n)
        out = {"layers": layers, "norm_f": (self.hidden,), "head": (self.hidden, self.vocab)}
        if self.leading_kinds:
            out["lead"] = tuple(self.layer_shapes(kind, "dense") for kind in self.leading_kinds)
        if self.after_kinds:
            out["after"] = tuple(self.layer_shapes(kind, self.mlp) for kind in self.after_kinds)
        return out

    def counters(self) -> Dict[str, Any]:
        """What the step counts on the device: the picks by expert layer (the
        scanned layers, then the blocks after the scan) and held expert."""
        return {"expert_picks": jnp.zeros((self.n_scanned + len(self.after_kinds), self.n_held), jnp.int32)}

    def init(self, rng, dense, emb, train: bool = False) -> Dict[str, Any]:
        """Normal(0, 0.02) products, unit norms, and the step's counters."""
        shapes = self.param_shapes()
        leaves, tree = jax.tree.flatten_with_path(shapes, is_leaf=lambda s: isinstance(s, tuple) and (
            not s or isinstance(s[0], int)))
        keys = jax.random.split(rng, len(leaves))
        params = jax.tree.unflatten(tree, [
            jnp.ones(shape, jnp.float32) if "norm" in path[-1].key
            else 0.02 * jax.random.normal(key, shape, jnp.float32)
            for (path, shape), key in zip(leaves, keys)])
        return {"params": params, "batch_stats": self.counters()}

    # ---------------------------------------------------------------- layers

    def layers(self, params, rows, side, attend=None, buffers=None):
        """The residual stream after the last layer, (B, T, hidden) float32,
        and the picks by scanned layer and held expert. ``side[kind]`` is what
        the kind's attention takes beside the leaves (the default's: its RoPE
        ``(cos, sin)``), ``attend(kind, q, k, v)`` the default attention's
        kernel over bfloat16 q (B, T, Hq, D), k and v (B, T, Hkv, D).
        ``buffers``: leaves that are no parameters, which a layer finds beside
        its own: ``{kind: {leaf: stacked}}`` as a ``kind_leaves`` tower's
        ``params["layers"]``, ``{leaf: stacked}`` where the kinds hold the same.

        The leading layers, then one scan over the periods, a period's layers
        written out in its body. Each layer is recomputed in the backward, but
        for its attention kernel's output and row statistics, which are kept
        (150 MB a layer at the benchmark's sequence cells against 12 ms of the
        forward kernel)."""
        kinds, lead = self.layer_kinds, self.leading_kinds
        if self.n_scanned % len(kinds):
            raise ValueError(f"{self.n_scanned} layers are no whole periods of {kinds}")
        make = partial(self._recomputed_layer, side=side, attend=attend)
        h = rows.astype(jnp.float32)
        for kind, p in zip(lead, params.get("lead", ())):
            h, _ = make(kind, "dense")(p, h)
        layer = {k: make(k, self.mlp) for k in set(kinds)}
        stacked = params["layers"]
        if buffers is not None and self.kind_leaves:  # by kind, as the leaves of a tower whose kinds hold their own
            stacked = {kind: dict(leaves, **buffers.get(kind, {})) for kind, leaves in stacked.items()}
        elif buffers is not None:
            stacked = dict(stacked, **buffers)

        # a period of one layer is scanned as the stacked leaves lie: regrouping
        # them costs the SDAR cell 0.8% of its step in slices and updates of the
        # scan's stacked gradients (my chip runs, PR 35)
        single = len(kinds) == 1

        def period(h, p):
            picks, seen = [], {}
            for i, kind in enumerate(kinds):
                if self.kind_leaves:
                    j = seen[kind] = seen.get(kind, -1) + 1  # its place among the period's layers of its kind
                    mine = jax.tree.map(lambda x: x[j], p[kind])
                else:
                    mine = p if single else jax.tree.map(lambda x: x[i], p)
                h, got = layer[kind](mine, h)
                picks.append(got)
            return h, picks[0] if single else jnp.stack(picks)

        if self.kind_leaves:
            by_period = {kind: jax.tree.map(lambda x: x.reshape(-1, kinds.count(kind), *x.shape[1:]), leaves)
                         for kind, leaves in stacked.items()}
        else:
            by_period = stacked if single else jax.tree.map(
                lambda x: x.reshape(-1, len(kinds), *x.shape[1:]), stacked)
        h, picks = jax.lax.scan(period, h, by_period)
        return h, picks.reshape(self.n_scanned, self.n_held)

    def _recomputed_layer(self, kind, mlp, side, attend=None):
        """One layer ``(leaves, h) -> (h, picks)``, recomputed in the backward
        but for its attention kernel's output and row statistics."""
        every = set(self.layer_kinds) | set(self.leading_kinds) | set(self.after_kinds)
        scope = "attention" if len(every) == 1 else f"attention/{kind}"
        keep = jax.checkpoint_policies.save_only_these_names(ATTENTION_OUT, ATTENTION_LSE)
        return jax.checkpoint(partial(self._layer, kind=kind, mlp=mlp, side=side.get(kind), scope=scope,
                                      attend=attend and partial(attend, kind)), policy=keep)

    def layer_after(self, i, params, h, side, attend=None, buffers=None):
        """Block ``i`` of ``after_kinds`` on the stream ``h`` (B, T, hidden):
        the stream after it and the picks its held experts got, (n_held,).
        ``buffers``: its leaves that are no parameters."""
        leaves = dict(params["after"][i], **(buffers or {}))
        return self._recomputed_layer(self.after_kinds[i], self.mlp, side, attend)(leaves, h)

    def attention(self, kind, p, a, side, attend):
        """What a layer's attention adds to the residual stream for its normed
        input ``a`` (B, T, hidden). Here: grouped-query softmax attention with
        per-head q/k norms and RoPE, the kernel the tower's ``attend``."""
        b, t, _ = a.shape
        hd = self.head_dim
        cos, sin = side
        # norm, RoPE and the cast as one pass over the projections as they
        # come, a head a column block; the kernels read them so, and the
        # reshapes at their boundary move nothing
        q = qk_norm_rope(_mm(a, p["wq"]), p["q_norm"], cos, sin, self.n_heads, self.rms_eps,
                         interpret=self.interpret)
        k = qk_norm_rope(_mm(a, p["wk"]), p["k_norm"], cos, sin, self.n_kv_heads, self.rms_eps,
                         interpret=self.interpret)
        v = _mm(a, p["wv"]).astype(jnp.bfloat16)
        o = attend(q.reshape(b, t, self.n_heads, hd), k.reshape(b, t, self.n_kv_heads, hd),
                   v.reshape(b, t, self.n_kv_heads, hd))
        # bfloat16 as the kernel leaves it: what the product takes, and the
        # gradient comes back in what the kernel's backward takes
        return _mm(o.reshape(b, t, -1), p["wo"])

    def _layer(self, p, h, kind, mlp, side, scope, attend):
        b, t, d = h.shape
        with jax.named_scope(scope):
            a = _rms(h, p["norm1"], self.rms_eps)
            h = h + self.attention(kind, p, a, side, attend)
        if mlp == "dense":
            with jax.named_scope("dense_mlp"):
                m = _rms(h, p["norm2"], self.rms_eps)
                # its 9,216-wide intermediates recomputed in its own backward: they do not stand
                # beside what the layer's attention keeps
                return h + jax.checkpoint(_swiglu_mlp)(m, p["dense_gate"], p["dense_up"], p["dense_down"]), None
        with jax.named_scope("moe"):
            m = _rms(h, p["norm2"], self.rms_eps)
            y, picks = self.experts(p, m.reshape(b * t, d))
            h = h + y.reshape(b, t, d)
        return h, picks

    def pick_chunk(self, n_tokens: int) -> int:
        """Picks the expert layer handles at a time: ``pick_room`` times what
        an even router sends the held experts of ``n_tokens`` tokens (an eighth
        over it; a tower of many shares states more), in whole tiles of 512
        rows. The loop over chunks ends with the last live one, so no token is
        dropped whatever the load and nothing larger than a chunk's rows is
        held; near an even load one trip does."""
        picks = n_tokens * self.experts_per_token
        even = -(-picks * self.n_held // self.n_experts)
        return min(picks, -(-int(even * self.pick_room) // 512) * 512)

    def expert_paths(self, n_tokens: int) -> Dict[str, Any]:
        """What a tower's ``*.paths`` event says of the expert layer for
        ``n_tokens`` tokens: the kernels that run the grouped products and the
        (row, K, N) tiles the chunk's shapes gave them, gate and up's first
        and down's after it."""
        chunk, d, f = self.pick_chunk(n_tokens), self.hidden, self.expert_width
        tiles = [grouped_tiles(chunk, k, n) for k, n in ((d, f), (f, d))]
        return {"experts": "pallas_grouped", "experts_tile": "/".join("x".join(map(str, t)) for t in tiles),
                "held": self.n_held, "pick_chunk": chunk}

    def route(self, p, m):
        """The router's law: each token's ``experts_per_token`` experts of all
        ``n_experts`` and their weights, both (N, k); float32 scores."""
        k = self.experts_per_token
        if self.router_law == "softmax":
            probs = jax.nn.softmax(_mm(m, p["router"]), axis=-1)
            top_p, top_e = jax.lax.top_k(probs, k)
            return top_p / jnp.sum(top_p, axis=-1, keepdims=True), top_e  # norm_topk_prob
        if self.router_law != "sigmoid":
            raise ValueError(f"router_law {self.router_law!r}; known: softmax, sigmoid")
        score = jax.nn.sigmoid(_mm(m, p["router"]))
        # the bias moves the selection alone: a buffer, no gradient reaches it
        _, top_e = jax.lax.top_k(jax.lax.stop_gradient(score + p["router_bias"]), k)
        top_s = jnp.take_along_axis(score, top_e, axis=-1)
        return self.routed_scaling * top_s / jnp.sum(top_s, axis=-1, keepdims=True), top_e

    def experts(self, p, m):
        """This chip's experts' part of the layer's result for tokens
        ``m`` (N, hidden), and the picks each held expert got, (n_held,).
        ``p`` holds ``router`` (hidden, n_experts) and the held experts'
        ``gate``, ``up`` (n_held, hidden, width) and ``down``; under
        ``"shared_experts"`` also the shared expert's, whose result for every
        token is added here, once."""
        n, d = m.shape
        k, held = self.experts_per_token, self.n_held
        with jax.named_scope("router"):
            weight, top_e = self.route(p, m)
        with jax.named_scope("dispatch"):
            local = top_e - self.first_held
            local = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
            # picks sorted by held expert, the others last: pick i is token i // k
            sorted_e, order = jax.lax.sort(
                (local, jnp.arange(n * k, dtype=jnp.int32)), num_keys=1, is_stable=True)
            starts = jnp.searchsorted(sorted_e, jnp.arange(held + 1, dtype=jnp.int32)).astype(jnp.int32)
        chunk = self.pick_chunk(n)
        order = jnp.pad(order, (0, -(n * k) % chunk))  # a last chunk may run past the picks
        y = _held_experts(m, weight.reshape(-1), p["gate"], p["up"], p["down"], order, starts, chunk, k,
                          self.interpret)
        if self.mlp == "shared_experts":
            with jax.named_scope("shared"):
                y = y + _swiglu_mlp(m, p["shared_gate"], p["shared_up"], p["shared_down"])
        return y, starts[1:] - starts[:-1]

    def head(self, params, h):
        """Logits of positions ``h`` (..., hidden), float32 over the ids held here."""
        return _mm(_rms(h, params["norm_f"], self.rms_eps), params["head"])


class NextTokenTower(MoETower):
    """A tower trained on next tokens over packed documents: it states
    ``_hidden(variables, dense, emb) -> (residual stream, counters)`` and
    ``head_chunk``, and every position has a label, so head and loss never
    meet whole: ``train_loss`` (which ``build_fused_train_step`` takes where a
    model states one) runs them in chunks of ``head_chunk`` positions, each
    chunk's logits recomputed in the backward, and no ``(T, vocab)`` array is
    ever live. A tower with further objectives over the same head states them
    as further passes (``objectives``). ``models/mellum_moe.py``,
    ``models/kimi_linear_moe.py`` and ``models/joyai_flash_moe.py``."""

    def apply(self, variables, dense, emb, train: bool = True, mutable: Optional[Sequence[str]] = None):
        """Logits of every position, (B, T, vocab) float32. ``dense`` is
        ``[starts (B, T) int32]``, ``emb`` one raw slot, ``(rows (B, T,
        hidden), mask)``. With ``mutable=["batch_stats"]`` also the counters."""
        del train
        h, stats = self._hidden(variables, dense, emb)
        with jax.named_scope("lm_head"):
            logits = self.head(variables["params"], h)
        return (logits, {"batch_stats": stats}) if mutable else logits

    def loss(self, logits, labels):
        """Next-token cross-entropy: ``labels`` = [next token (B, T) int32,
        weight (B, T) float32 (0 at a document's last position, else 1)];
        the weighted mean."""
        targets, weight = labels[0], labels[1]
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
        return jnp.sum(weight * (logz - picked)) / jnp.sum(weight)

    def outputs(self, logits):
        """The most likely next id of each position, (B, T) int32."""
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def objectives(self, variables, dense, emb, labels):
        """The passes through the head that make the training loss, and the
        step's counters: each pass ``(scope, stream (B, T, hidden), norm
        weight, targets (B, T), weights (B, T), coefficient)``; a stream that
        is normed already comes with no norm weight. The loss is the sum over
        the passes of coefficient x the weighted mean cross-entropy; the
        outputs are the first pass's. Here: the next token, once."""
        h, stats = self._hidden(variables, dense, emb)
        return [("lm_head", h, variables["params"]["norm_f"], labels[0], labels[1], 1.0)], stats

    def train_loss(self, variables, dense, emb, labels):
        """``(loss, outputs, counters)`` of a training step: every pass of
        ``objectives`` through the one chunked head (``_head_pass``), the
        head's gradient the passes' summed. A tower that counts ``objective``
        gets the passes' weight sums, then their weighted cross-entropy sums,
        added to it."""
        passes, stats = self.objectives(variables, dense, emb, labels)
        loss, outputs, lives, totals = None, None, [], []
        for scope, h, norm, targets, weight, coefficient in passes:
            weight = weight.astype(jnp.float32)
            with jax.named_scope(scope):
                total, ids = self._head_pass(variables["params"]["head"], h, norm, targets.astype(jnp.int32), weight)
            live = jnp.sum(weight)
            term = total / live if coefficient == 1.0 else coefficient * (total / live)  # no product by one in the one-pass towers' programs
            loss = term if loss is None else loss + term
            outputs = ids if outputs is None else outputs
            lives.append(live)
            totals.append(total)
        if stats is not None and "objective" in stats:
            stats = dict(stats, objective=stats["objective"] + jax.lax.stop_gradient(jnp.stack(lives + totals)))
        return loss, outputs, stats

    def _head_pass(self, head, h, norm, targets, weight):
        """``(sum of weight x cross-entropy, most likely ids (B, T))`` of one
        stream, in chunks of ``head_chunk`` positions under one scan whose body
        is recomputed in the backward: a chunk's logits and their gradient are
        the largest arrays the head ever holds."""
        b, t, _ = h.shape
        chunk = min(self.head_chunk, b * t)
        if (b * t) % chunk:
            raise ValueError(f"{b * t} positions are no whole chunks of {chunk}")
        by_chunk = lambda x: x.reshape((b * t) // chunk, chunk, *x.shape[2:])

        @jax.checkpoint
        def one(total, xs):
            hc, tc, wc = xs
            logits = _mm(hc if norm is None else _rms(hc, norm, self.rms_eps), head)
            picked = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
            total = total + jnp.sum(wc * (jax.nn.logsumexp(logits, axis=-1) - picked))
            return total, jnp.argmax(logits, axis=-1).astype(jnp.int32)

        total, ids = jax.lax.scan(one, jnp.zeros((), jnp.float32), (by_chunk(h), by_chunk(targets), by_chunk(weight)))
        return total, ids.reshape(b, t)
