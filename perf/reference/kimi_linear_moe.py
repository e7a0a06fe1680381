"""Plain reference of the ``kimi_linear_moe`` tower's training step: float32
``jax.numpy`` at ``highest`` matmul precision, **the delta rule as the plain
recurrence, position by position**, the convolution and the attention's dense
mask built from where each position's document starts, a Python loop over the
layers and over the held experts, a dictionary of rows in place of the table.
Imports nothing of ``persia_tpu`` and nothing of another tower's reference.

The model, from the published config (hidden ``d``, ``H`` heads, RMSNorm eps,
no bias, SiLU, untied head). A batch gives each position ``i`` its token and
``s_i``, the index at which its document starts. Layers are numbered from 1:
``linear_attn_config.kda_layers`` are KDA, ``full_attn_layers`` MLA; the first
``first_k_dense_replace`` have a dense MLP, the others the expert layer. For
the residual stream ``h``: ``h += attn(rms(h) * w1)``; ``h += mlp(rms(h) * w2)``.

KDA (heads of ``D`` = 128; ``a`` the normed input): ``q~ = a Wq``, ``k~ = a
Wk``, ``v~ = a Wv``; a depthwise causal convolution of 4 taps inside the
document, ``c(x)_i = silu(sum_t w_t x_{i-t} [i - t >= s_i])``; by head ``q =
l2norm(c(q~)) / sqrt(D)``, ``k = l2norm(c(k~))``, ``v = c(v~)``; ``g = -exp(A_h)
softplus((a Wf_a) Wf_b + dt_bias)``, ``alpha = exp(g)``; ``beta = sigmoid(a
Wb)``; the state ``S`` (D x D a head), 0 before a document's first position:

    S' = diag(alpha_i) S_{i-1};  S_i = S' + beta_i k_i (v_i - S'^T k_i)^T;  o_i = S_i^T q_i

``y = (rms_D(o) * w_o) * sigmoid((a Wg_a) Wg_b)``, ``attn = y Wo``.

MLA (no rotation is applied: ``mla_use_nope``): ``q = a Wq`` (H heads of 128 +
64); ``[c~, r] = a Wkva`` (512 and 64); ``c = rms_512(c~) * w_c``; ``[kn_h, v_h]
= c Wkvb``; ``k_h = [kn_h, r]``; ``P_ij = softmax_j(q_ih . k_jh / sqrt(192))``
over ``s_i <= j <= i``; ``attn = concat_h(P v_h) Wo``.

Dense MLP: ``Wd (silu(Wg m) * (Wu m))``. Expert layer: ``sc = sigmoid(m Wr)``
over all ``router_width``; the 8 largest of ``sc + b`` (``b`` the selection
bias, zeros, no parameter); weights ``routed_scaling_factor * sc_e / sum of
the picked sc``; the picked experts HELD HERE (``num_experts`` from
``first_held_expert``) and the shared expert for every token. ``logits_i =
(rms(h_i) * wf) Whead``; ``loss = sum_i w_i CE(logits_i, x_{i+1}) / sum_i
w_i``. Sparse Adagrad on the token rows a batch touches, Adam on the rest.

Arithmetic, as ``guarantees`` states it: every matrix product (projections,
scores, P v, router, experts, head, and the backward's) takes operands
rounded to bfloat16 and is summed in float32 (``_product`` is the one place
that rounds); of the delta rule q, k and v are so rounded where they enter it
and the recurrence itself is float32. The first control rounds all of those
to float8 (e4m3) instead.

So that the published widths fit one chip: attention runs a block of queries
at a time (``reference_query_block``), the logits a block of positions at a
time (``reference_logit_block``), each layer is recomputed in the backward,
a KDA layer's own part runs ``reference_head_group`` heads at a time, and
the recurrence is a scan over blocks of ``reference_state_block`` positions
whose body is recomputed in its backward, so that the per-position states
(34 GB a layer at 16,384 positions) never stand.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from perf import kimi_linear_weights

_HI = jax.lax.Precision.HIGHEST
# operands of every product rounded to: (exponent bits, mantissa bits)
_ROUNDING = {None: (8, 7), "operands_float8_e4m3": (4, 3)}
CONTROLS = ("operands_float8_e4m3",)
_PAD = 1024  # a step's distinct rows are padded to a multiple of this
L2_EPS = 1e-6


def _round(x, how):
    # reduce_precision, not a cast there and back, which a compiler may drop
    return jax.lax.reduce_precision(x, exponent_bits=how[0], mantissa_bits=how[1])


@partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def _product(spec, a, b, how):
    """einsum of rounded operands, summed in float32; so are both gradients."""
    return jnp.einsum(spec, _round(a, how), _round(b, how), precision=_HI)


def _product_fwd(spec, a, b, how):
    a, b = _round(a, how), _round(b, how)
    return jnp.einsum(spec, a, b, precision=_HI), (a, b)


def _product_bwd(spec, how, res, g):
    _, vjp = jax.vjp(lambda a, b: jnp.einsum(spec, a, b, precision=_HI), *res)
    return vjp(_round(g, how))


_product.defvjp(_product_fwd, _product_bwd)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def document_starts(doc_lengths: np.ndarray, seq_len: int) -> np.ndarray:
    """(B, T) int32: for each position the index at which its document starts,
    from each sequence's document lengths in their order (zeros are skipped)."""
    out = np.zeros((len(doc_lengths), seq_len), np.int32)
    for b, lengths in enumerate(np.asarray(doc_lengths)):
        at = 0
        for n in lengths:
            out[b, at:at + n] = at
            at += int(n)
    return out


def convolution(x, taps, starts):
    """c(x): x (B, T, C), taps (K, C), starts (B, T)."""
    t = x.shape[1]
    at = jnp.arange(t, dtype=jnp.int32)[None, :]
    total = jnp.zeros_like(x)
    for back in range(taps.shape[0]):
        earlier = jnp.roll(x, back, axis=1)  # position i holds x_{i - back}; what wraps is masked
        total = total + jnp.where((at - back >= starts)[..., None], taps[back] * earlier, 0.0)
    return jax.nn.silu(total)


def delta_rule(q, k, v, g, beta, starts, block: int):
    """The recurrence above, one position after another: q, k, v, g (B, T, H,
    D), beta (B, T, H), starts (B, T) -> o (B, T, H, D). A scan over blocks of
    ``block`` positions, each recomputed in the backward."""
    b, t, h, d = q.shape
    first = starts == jnp.arange(t, dtype=jnp.int32)[None, :]

    def position(s, xs):
        qi, ki, vi, gi, bi, new = xs
        s = jnp.where(new[:, None, None, None], 0.0, s)
        s = jnp.exp(gi)[..., None] * s
        read = jnp.einsum("bhkv,bhk->bhv", s, ki, precision=_HI)
        s = s + jnp.einsum("bhk,bhv->bhkv", ki, bi[..., None] * (vi - read), precision=_HI)
        return s, jnp.einsum("bhkv,bhk->bhv", s, qi, precision=_HI)

    @jax.checkpoint
    def run(s, xs):
        return jax.lax.scan(position, s, xs)

    block = min(block, t)
    by_block = lambda x: jnp.moveaxis(x, 1, 0).reshape(t // block, block, *x.shape[:1], *x.shape[2:])
    s0 = jnp.zeros((b, h, d, d), jnp.float32)
    _, o = jax.lax.scan(run, s0, tuple(by_block(x) for x in (q, k, v, g, beta, first)))
    return jnp.moveaxis(o.reshape(t, b, h, d), 0, 1)


def kda_layer(p, a, starts, cfg, how):
    """A group of heads at a time, each group recomputed in the backward (a
    head's part between the projections and the output projection is its own):
    the convolution's, the norms' and the recurrence's arrays of all heads at
    once would not fit beside the parameters at the published widths."""
    b, t, _ = a.shape
    h, hd, hg = cfg["heads"], cfg["head_dim"], min(cfg["head_group"], cfg["heads"])
    proj = lambda x, w: _product("btd,de->bte", x, w, how)
    wide = {"q": proj(a, p["wq"]), "k": proj(a, p["wk"]), "v": proj(a, p["wv"]),
            "decay": proj(proj(a, p["wf_a"]), p["wf_b"]), "gate": proj(proj(a, p["wg_a"]), p["wg_b"])}
    beta = jax.nn.sigmoid(proj(a, p["wb"]))

    @jax.checkpoint
    def group(x, beta, taps, a_log, dt_bias):
        heads = lambda y: y.reshape(b, t, hg, hd)
        q, k, v = (heads(convolution(x[n], taps[n], starts)) for n in "qkv")
        q, k = _l2norm(q) / np.sqrt(hd), _l2norm(k)
        g = -jnp.exp(a_log)[:, None] * heads(jax.nn.softplus(x["decay"] + dt_bias))
        o = delta_rule(_round(q, how), _round(k, how), _round(v, how), g, beta, starts, cfg["state_block"])
        return (_rms(o, p["o_norm"], cfg["eps"]) * jax.nn.sigmoid(heads(x["gate"]))).reshape(b, t, hg * hd)

    out = []
    for first in range(0, h, hg):
        cols = slice(first * hd, (first + hg) * hd)
        out.append(group({n: x[..., cols] for n, x in wide.items()}, beta[..., first:first + hg],
                         {n: p[f"conv_{n}"][:, cols] for n in "qkv"}, p["a_log"][first:first + hg],
                         p["dt_bias"][cols]))
    return proj(jnp.concatenate(out, axis=-1), p["wo"])


def attention(q, k, v, lo, query_block: int, how):
    """q, k (B, T, H, Dk), v (B, T, H, Dv), lo (B, T): query i reads the keys
    lo_i .. i. A block of queries at a time under its rows of the mask."""
    b, t, h, dk = q.shape
    nb = t // query_block
    key = jnp.arange(t, dtype=jnp.int32)

    @jax.checkpoint
    def block(args):
        qb, lob, first = args  # (B, Q, H, Dk), (B, Q), the block's first position
        own = first + jnp.arange(query_block, dtype=jnp.int32)
        mask = (key[None, None, :] >= lob[:, :, None]) & (key[None, None, :] <= own[None, :, None])
        s = _product("bqhd,bkhd->bhqk", qb, k, how) / np.sqrt(dk)
        p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
        return _product("bhqk,bkhd->bqhd", p, v, how)

    out = jax.lax.map(block, (jnp.moveaxis(q.reshape(b, nb, query_block, h, dk), 1, 0),
                              jnp.moveaxis(lo.reshape(b, nb, query_block), 1, 0),
                              jnp.arange(nb, dtype=jnp.int32) * query_block))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, v.shape[-1])


def mla_layer(p, a, starts, cfg, how):
    b, t, _ = a.shape
    h, hd, rank = cfg["heads"], cfg["head_dim"], cfg["rank"]
    proj = lambda x, w: _product("btd,de->bte", x, w, how)
    q = proj(a, p["wq"]).reshape(b, t, h, -1)
    kv_a = proj(a, p["wkv_a"])
    latent = _rms(kv_a[..., :rank], p["kv_norm"], cfg["eps"])
    shared = jnp.broadcast_to(kv_a[:, :, None, rank:], (b, t, h, kv_a.shape[-1] - rank))
    kv = proj(latent, p["wkv_b"]).reshape(b, t, h, -1)
    k = jnp.concatenate([kv[..., :cfg["nope"]], shared], axis=-1)
    o = attention(q, k, kv[..., cfg["nope"]:], starts, min(cfg["query_block"], t), how)
    return proj(o.reshape(b, t, h * hd), p["wo"])


def swiglu(m, gate, up, down, how):
    mid = jax.nn.silu(_product("nd,df->nf", m, gate, how)) * _product("nd,df->nf", m, up, how)
    return _product("nf,fd->nd", mid, down, how)


def expert_layer(p, m, cfg, how):
    """The held experts' part and the shared expert's, for tokens m (N, d),
    and the picks each held expert got."""
    score = jax.nn.sigmoid(_product("nd,de->ne", m, p["router"], how))
    bias = jnp.zeros((score.shape[-1],), jnp.float32)  # the selection bias: zeros, no parameter
    _, top_e = jax.lax.top_k(jax.lax.stop_gradient(score + bias), cfg["k"])
    top_s = jnp.take_along_axis(score, top_e, axis=-1)
    weight = cfg["scaling"] * top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    out, picks = swiglu(m, p["shared_gate"], p["shared_up"], p["shared_down"], how), []
    for e in range(cfg["held"]):
        mine = top_e == cfg["first"] + e
        w_e = jnp.sum(jnp.where(mine, weight, 0.0), axis=-1)
        out = out + w_e[:, None] * swiglu(m, p["gate"][e], p["up"][e], p["down"][e], how)
        picks.append(jnp.sum(mine))
    return out, jnp.stack(picks)


def layer_params(dense, cfg):
    """Each layer's leaves, layer 1 first, from the tree as the tower holds it."""
    seen, out = {}, []
    for l, (kind, _mlp) in enumerate(cfg["kinds"]):
        if l < cfg["lead"]:
            out.append(dense["lead"][l])
        else:
            i = seen[kind] = seen.get(kind, -1) + 1
            out.append(jax.tree.map(lambda x: x[i], dense["layers"][kind]))
    return out


def hidden(dense, x, starts, cfg, how):
    """The residual stream after the last layer (B, T, d) and picks (expert
    layers, held); ``x`` is the (B, T, d) token rows, ``starts`` (B, T) int32."""
    b, t, d = x.shape

    def attend(p, h, kind):
        a = _rms(h, p["norm1"], cfg["eps"])
        return h + (kda_layer if kind == "kda" else mla_layer)(p, a, starts, cfg, how)

    def mlp_of(p, h, mlp):
        m = _rms(h, p["norm2"], cfg["eps"]).reshape(b * t, d)
        if mlp == "dense":
            return h + swiglu(m, p["dense_gate"], p["dense_up"], p["dense_down"], how).reshape(b, t, d), None
        y, picks = expert_layer(p, m, cfg, how)
        return h + y.reshape(b, t, d), picks

    # a layer's two halves are each recomputed in the backward
    h, picks = x, []
    for p, (kind, mlp) in zip(layer_params(dense, cfg), cfg["kinds"]):
        h = jax.checkpoint(partial(attend, kind=kind))(p, h)
        h, got = jax.checkpoint(partial(mlp_of, mlp=mlp))(p, h)
        if got is not None:
            picks.append(got)
    return h, jnp.stack(picks)


def loss_fn(dense, rows_u, inv, starts, targets, weight, cfg, how):
    h, picks = hidden(dense, rows_u[inv], starts, cfg, how)
    b, t, d = h.shape
    hf = _rms(h, dense["norm_f"], cfg["eps"]).reshape(b * t, d)

    def nll(args):  # whole logits of these positions
        hb, tb = args
        logits = _product("nd,dv->nv", hb, dense["head"], how)
        return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]

    block = min(cfg["logit_block"] or b * t, b * t)
    per = jax.lax.map(jax.checkpoint(nll), (hf.reshape(-1, block, d), targets.reshape(-1, block)))
    return jnp.sum(weight.reshape(-1) * per.reshape(-1)) / jnp.sum(weight), picks


@partial(jax.jit, static_argnames=("cfg", "how", "sparse", "adam"), donate_argnums=(0, 1, 2))
def _train_step(dense, m, v, t, rows_u, acc_u, inv, starts, targets, weight, cfg, how, sparse, adam):
    cfg = dict(cfg)
    (loss, picks), (g_dense, g_rows) = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)(
        dense, rows_u, inv, starts, targets, weight, cfg, how)
    lr, eps = sparse
    acc_new = acc_u + g_rows * g_rows
    rows_new = rows_u - lr * g_rows / jnp.sqrt(acc_new + eps)
    alr, b1, b2, aeps = adam
    t = t + 1.0
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    m = jax.tree.map(lambda mm, g: b1 * mm + (1.0 - b1) * g, m, g_dense)
    v = jax.tree.map(lambda vv, g: b2 * vv + (1.0 - b2) * g * g, v, g_dense)
    dense_new = jax.tree.map(
        lambda p, mm, vv: p - alr * (mm / c1) / (jnp.sqrt(vv / c2) + aeps), dense, m, v)
    return loss, picks, dense_new, m, v, t, rows_new, acc_new


def _model_cfg(config: dict) -> tuple:
    return tuple(sorted({
        "heads": int(config["num_attention_heads"]), "head_dim": int(config["v_head_dim"]),
        "nope": int(config["qk_nope_head_dim"]), "rank": int(config["kv_lora_rank"]),
        "eps": float(config["rms_norm_eps"]), "kinds": tuple(kimi_linear_weights.layer_kinds(config)),
        "lead": int(config["first_k_dense_replace"]),
        "k": int(config["num_experts_per_token"]), "held": int(config["num_experts"]),
        "first": int(config["first_held_expert"]), "scaling": float(config["routed_scaling_factor"]),
        "query_block": int(config.get("reference_query_block", 512)),
        "logit_block": int(config.get("reference_logit_block", 0)),
        "state_block": int(config.get("reference_state_block", 128)),
        "head_group": int(config.get("reference_head_group", 8)),
    }.items()))


def initial_dense(config: dict, seed: int) -> dict:
    """The dense leaves from the seed, made on the device in one jitted call."""
    from perf import weights

    build = jax.jit(lambda words: kimi_linear_weights.dense_tree(config, words, jnp))
    return build(jnp.asarray(np.stack(weights.seed_words(seed))))


leaves_by_name = kimi_linear_weights.leaves_by_name


def make(config: dict, seed: int, entry, control: Optional[str] = None) -> "Reference":
    """The reference, or the control of that name, with its weights from the
    seed; the entry names the token rows (``row_birth``)."""
    return Reference(config, seed, entry.row_birth, how=_ROUNDING[control],
                     steps=entry.snapshot_after[-1])


def extra_readings(program: dict, reference: dict) -> Dict[str, float]:
    """``expert_pick_mismatch_share``: over the compared steps, the picks by
    layer and held expert that the program and the reference count
    differently, over the picks the reference counts (a pick that moves from
    one held expert to another counts twice, one that leaves the held ones
    once)."""
    last = max(program["snaps"])

    def picks(run):
        return (np.asarray(run["snaps"][last]["expert_picks"], np.int64)
                - np.asarray(run["snaps"][0]["expert_picks"], np.int64))

    p, r = picks(program), picks(reference)
    return {"expert_pick_mismatch_share": float(np.abs(p - r).sum() / max(int(r.sum()), 1))}


class Reference:
    """The reference trainer. Rows live in a dictionary keyed by the token id
    that gives each key's place in two host arrays; ``row_birth(keys)`` gives
    the initial rows of keys never seen."""

    def __init__(self, config: dict, seed: int, row_birth: Callable[[np.ndarray], np.ndarray], how,
                 steps: Optional[int] = None):
        self.config, self.how, self.row_birth = config, how, row_birth
        self.dim = int(config["hidden_size"])
        so, do = config["sparse_optimizer"], config["dense_optimizer"]
        if so["kind"] != "adagrad" or do["kind"] != "adam":
            raise ValueError("the reference implements Adagrad rows and Adam dense")
        self.acc0 = float(so["initial_accumulator"])
        self._sparse = (float(so["lr"]), float(so["eps"]))
        self._adam = (float(do["lr"]), float(do["b1"]), float(do["b2"]), float(do["eps"]))
        self._cfg = _model_cfg(config)
        self._stepped = False
        self._steps_left = steps  # after the snapshot that follows the last one, the device is freed
        self.dense = initial_dense(config, seed)
        self.m = jax.tree.map(jnp.zeros_like, self.dense)
        self.v = jax.tree.map(jnp.zeros_like, self.dense)
        self.t = jnp.zeros((), jnp.float32)
        n_expert_layers = int(config["num_hidden_layers"]) - int(config["first_k_dense_replace"])
        self.picks = np.zeros((n_expert_layers, int(config["num_experts"])), np.int64)
        self._slot: Dict[int, int] = {}
        self.rows = np.empty((_PAD, self.dim), np.float32)
        self.acc = np.empty((_PAD, self.dim), np.float32)

    def release(self) -> None:
        """Free the dense state on the device (6.5 GB at the cell's size)
        once the compared steps are read: a control, a planted fault's program
        or the next seed's needs the room. Rows, picks and the snapshots taken
        stay readable."""
        for x in jax.tree.leaves((self.dense, self.m, self.v)):
            x.delete()
        self.dense = self.m = self.v = None

    def _positions(self, keys: np.ndarray, create: bool) -> np.ndarray:
        slot = self._slot
        pos = np.fromiter((slot.get(k, -1) for k in keys.tolist()), np.int64, len(keys))
        new = np.flatnonzero(pos < 0)
        if create and len(new):
            n = len(slot)
            while n + len(new) > len(self.rows):
                self.rows = np.concatenate([self.rows, np.empty_like(self.rows)])
                self.acc = np.concatenate([self.acc, np.empty_like(self.acc)])
            pos[new] = np.arange(n, n + len(new))
            self.rows[pos[new]] = self.row_birth(keys[new])
            self.acc[pos[new]] = self.acc0
            slot.update(zip(keys[new].tolist(), pos[new].tolist()))
        return pos

    def lookup(self, keys: np.ndarray):
        """(rows, acc) as held now; keys never trained read their birth rows."""
        keys = np.asarray(keys, np.uint64)
        pos = self._positions(keys, create=False)
        found = pos >= 0
        rows = np.empty((len(keys), self.dim), np.float32)
        acc = np.full((len(keys), self.dim), self.acc0, np.float32)
        rows[found], acc[found] = self.rows[pos[found]], self.acc[pos[found]]
        if (~found).any():
            rows[~found] = self.row_birth(keys[~found])
        return rows, acc

    def snapshot(self, keys: np.ndarray) -> dict:
        rows, acc = self.lookup(keys)
        mu = leaves_by_name(self.m, self.config) if self._stepped else kimi_linear_weights.zeros_by_name(self.config)
        out = {"dense": leaves_by_name(self.dense, self.config), "adam_mu": mu,
               "rows": rows, "acc": acc, "expert_picks": self.picks.copy()}
        if self._steps_left == 0:
            self.release()
        return out

    def step(self, batch: Dict[str, np.ndarray], keys: np.ndarray) -> float:
        """One training step on a batch of the generator (``doc_lengths``
        (B, n), ``labels`` and ``weights`` (B, T)) whose rows are ``keys``
        (B, T) uint64."""
        uniq, inv = np.unique(keys.reshape(-1), return_inverse=True)
        pos = self._positions(uniq, create=True)
        pad = -len(uniq) % _PAD
        rows_p = np.concatenate([self.rows[pos], np.zeros((pad, self.dim), np.float32)])
        acc_p = np.concatenate([self.acc[pos], np.ones((pad, self.dim), np.float32)])
        if self.dense is None:
            raise RuntimeError("this reference freed its dense state after its last compared step")
        starts = document_starts(batch["doc_lengths"], keys.shape[1])
        loss, picks, self.dense, self.m, self.v, self.t, rows_new, acc_new = _train_step(
            self.dense, self.m, self.v, self.t, jnp.asarray(rows_p), jnp.asarray(acc_p),
            jnp.asarray(inv.reshape(keys.shape).astype(np.int32)), jnp.asarray(starts),
            jnp.asarray(batch["labels"], jnp.int32), jnp.asarray(batch["weights"], jnp.float32),
            cfg=self._cfg, how=self.how, sparse=self._sparse, adam=self._adam)
        self.rows[pos] = np.asarray(rows_new)[:len(uniq)]
        self.acc[pos] = np.asarray(acc_new)[:len(uniq)]
        self.picks += np.asarray(picks, np.int64)
        self._stepped = True
        if self._steps_left is not None:
            self._steps_left -= 1
        return float(loss)
