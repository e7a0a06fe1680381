"""Plain reference of the ``mellum_moe`` tower's training step: float32
``jax.numpy`` at ``highest`` matmul precision, a dense mask built from where
each position's document starts, both RoPE tables from their equations, a
Python loop over the held experts, a dictionary of rows in place of the
table. Imports nothing of ``persia_tpu`` and nothing of the other tower's
reference.

The model, from the published config (hidden ``d``, ``Hq`` query and ``Hkv``
K/V heads of ``D``, ``E`` routed experts of width ``f``, ``k`` a token, RMSNorm
eps, no bias, untied head, every layer sparse, ``layer_types`` a period of
(sliding, sliding, sliding, full), ``sliding_window`` ``W``). A batch gives
each position ``i`` of a sequence its token and ``s_i``, the index at which
its document starts; ``pos_i = i - s_i``. For a residual stream ``h``, each layer:

    a = rms(h) * w1;  q = a Wq, k = a Wk, v = a Wv  (heads of D)
    q = rms_D(q) * wq, k = rms_D(k) * wk;  RoPE (rotate-half) at pos_i, the layer's kind's table
    query head g reads K/V head g // (Hq / Hkv)
    P_ij = softmax_j(q_i k_j / sqrt(D)) over lo_i <= j <= i;  h += concat(P v) Wo
        lo_i = s_i on a full layer, max(s_i, i - W + 1) on a sliding one
    m = rms(h) * w2;  p = softmax(m Wr) over all E;  the k largest, weights p_e / sum
    h += sum over picked e HELD HERE of weight_e * Wdown_e (silu(Wgate_e m) * (Wup_e m))

and ``logits_i = (rms(h_i) * wf) Whead`` for every position. RoPE, with
``f_n = theta^(-2n / D)``: sliding layers ``cos(pos f_n)``, ``sin(pos f_n)``;
full layers (YaRN: factor ``s``, original context ``L0``, ``beta_fast``,
``beta_slow``, attention factor ``c``) ``dim(r) = D ln(L0 / (2 pi r)) / (2 ln
theta)``, ``low = max(floor(dim(beta_fast)), 0)``, ``high = min(ceil(dim(
beta_slow)), D - 1)``, ``ramp_n = clip((n - low) / (high - low), 0, 1)``,
``f'_n = (f_n / s) ramp_n + f_n (1 - ramp_n)``, tables ``c cos(pos f'_n)``,
``c sin(pos f'_n)``. ``loss = sum_i w_i CE(logits_i, x_{i+1}) / sum_i w_i``,
``w_i`` 0 at a document's last position. Sparse Adagrad on the token rows a
batch touches (gradients of one id summed first), Adam on everything else.

Departures from the published description, all the configuration's
(``assumed`` and ``reduced`` in its file): the per-head q/k norms (the
lineage's; the config has no key for them); softmax routing before the top-k
(no ``scoring_func`` key); positions that restart at each document and
attention that does not cross documents (the usual packing); no
multi-token-prediction head (the config has no key for one); only the experts
this chip holds (``num_experts`` held of ``router_width``, first
``first_held_expert``): what the absent experts would add is left out, here
as in the program; the first ``num_hidden_layers`` entries of ``layer_types``;
the vocabulary slice; no auxiliary loss; Adagrad and Adam.

Arithmetic, as ``guarantees`` states it: every matrix product (projections,
scores, P v, router, experts, head, and the same products of the backward)
takes operands rounded to bfloat16 and is summed in float32; nothing else is
rounded. ``_product`` is the one place that rounds. The first control rounds
those operands to float8 (e4m3) instead: one precision below.

So that the published widths fit one chip beside the parameters, their
gradient and Adam's moments: attention runs a block of queries at a time
against all keys under its rows of the dense mask (``reference_query_block``),
the logits a block of positions at a time (``reference_logit_block``; whole
where the key is absent), and each layer is recomputed in the backward. The
layers are one ``lax.scan`` over the stacked leaves with each layer's kind
(its window and its RoPE table) as the scan's data: one layer's compile,
where a Python loop over four layers of 16 experts each would be four.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from perf import mellum_weights

_HI = jax.lax.Precision.HIGHEST
# operands of every product rounded to: (exponent bits, mantissa bits)
_ROUNDING = {None: (8, 7), "operands_float8_e4m3": (4, 3)}
CONTROLS = ("operands_float8_e4m3",)
_PAD = 1024  # a step's distinct rows are padded to a multiple of this


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


def _rope(x, cos, sin):
    """x (B, T, H, D), cos and sin (B, T, D)."""
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, :, None, :] + rotated * sin[:, :, None, :]


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


def rope_frequencies(head_dim: int, theta: float, yarn: Optional[dict]):
    """(frequencies (D / 2,) float32, factor on cos and sin) by the equations above."""
    n = np.arange(head_dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * n / head_dim)
    if yarn is None or yarn.get("rope_type") != "yarn":
        return f.astype(np.float32), 1.0
    span = yarn["original_max_position_embeddings"]
    dim = lambda r: head_dim * math.log(span / (2 * math.pi * r)) / (2 * math.log(theta))
    low = max(math.floor(dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(dim(yarn["beta_slow"])), head_dim - 1)
    ramp = np.clip((n - low) / (high - low), 0.0, 1.0)
    return (f / yarn["factor"] * ramp + f * (1.0 - ramp)).astype(np.float32), float(yarn["attention_factor"])


def attention(q, k, v, lo, query_block: int, how):
    """q (B, T, Hq, D), k and v (B, T, Hkv, D), lo (B, T): query i reads the
    keys lo_i .. i. A block of queries at a time under its rows of the mask."""
    b, t, hq, hd = q.shape
    group = hq // k.shape[2]
    nb = t // query_block
    qg = q.reshape(b, nb, query_block, k.shape[2], group, hd)
    key = jnp.arange(t, dtype=jnp.int32)

    @jax.checkpoint
    def block(args):
        qb, lob, first = args  # (B, Q, Hkv, G, D), (B, Q), the block's first position
        own = first + jnp.arange(query_block, dtype=jnp.int32)
        mask = (key[None, None, :] >= lob[:, :, None]) & (key[None, None, :] <= own[None, :, None])
        s = _product("bqhgd,bkhd->bhgqk", qb, k, how) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(mask[:, None, None], s, -jnp.inf), axis=-1)
        return _product("bhgqk,bkhd->bqhgd", p, v, how)

    out = jax.lax.map(block, (jnp.moveaxis(qg, 1, 0), jnp.moveaxis(lo.reshape(b, nb, query_block), 1, 0),
                              jnp.arange(nb, dtype=jnp.int32) * query_block))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, hq, hd)


def expert_layer(p, m, cfg, how):
    """The held experts' part of the layer's result for tokens m (N, d), and
    the picks each held expert got. A loop over the held experts, each over
    every token with the weight 0 where it was not picked."""
    probs = jax.nn.softmax(_product("nd,de->ne", m, p["router"], how), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg["k"])
    weight = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    out, picks = jnp.zeros_like(m), []
    for e in range(cfg["held"]):
        mine = top_e == cfg["first"] + e
        w_e = jnp.sum(jnp.where(mine, weight, 0.0), axis=-1)
        mid = jax.nn.silu(_product("nd,df->nf", m, p["gate"][e], how)) * _product(
            "nd,df->nf", m, p["up"][e], how)
        out = out + w_e[:, None] * _product("nf,fd->nd", mid, p["down"][e], how)
        picks.append(jnp.sum(mine))
    return out, jnp.stack(picks)


def hidden(dense, x, starts, cfg, how):
    """The residual stream after the last layer (B, T, d) and picks (layers,
    held); ``x`` is the (B, T, d) token rows, ``starts`` (B, T) int32."""
    b, t, d = x.shape
    hd, eps = cfg["head_dim"], cfg["eps"]
    at = jnp.arange(t, dtype=jnp.int32)[None, :]
    pos = (at - starts).astype(jnp.float32)
    tables = []
    for yarn in (None, dict(cfg["yarn"])):  # kind 0: sliding (default RoPE), kind 1: full (YaRN)
        f, c = rope_frequencies(hd, cfg["theta"], yarn)
        angle = pos[:, :, None] * jnp.asarray(f)[None, None, :]
        tables.append(jnp.stack([c * jnp.concatenate([jnp.cos(angle)] * 2, axis=-1),
                                 c * jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)]))
    tables = jnp.stack(tables)  # (kind, cos | sin, B, T, D)
    full = jnp.asarray([kind == "full_attention" for kind in cfg["kinds"]])

    @jax.checkpoint
    def layer(h, xs):
        p, is_full = xs
        cos, sin = tables[is_full.astype(jnp.int32)]
        lo = jnp.where(is_full, starts, jnp.maximum(starts, at - (cfg["window"] - 1)))
        a = _rms(h, p["norm1"], eps)
        q = _product("btd,de->bte", a, p["wq"], how).reshape(b, t, -1, hd)
        k = _product("btd,de->bte", a, p["wk"], how).reshape(b, t, -1, hd)
        v = _product("btd,de->bte", a, p["wv"], how).reshape(b, t, -1, hd)
        q = _rope(_rms(q, p["q_norm"], eps), cos, sin)
        k = _rope(_rms(k, p["k_norm"], eps), cos, sin)
        o = attention(q, k, v, lo, min(cfg["query_block"], t), how)
        h = h + _product("bte,ed->btd", o.reshape(b, t, -1), p["wo"], how)
        m = _rms(h, p["norm2"], eps)
        y, picks = expert_layer(p, m.reshape(b * t, d), cfg, how)
        return h + y.reshape(b, t, d), picks

    return jax.lax.scan(layer, x, (dense["layers"], full))


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
    n = int(config["num_hidden_layers"])
    rope = config["rope_parameters"]
    return tuple(sorted({
        "head_dim": int(config["head_dim"]), "eps": float(config["rms_norm_eps"]),
        "theta": float(rope["sliding_attention"]["rope_theta"]),
        "yarn": tuple(sorted(rope["full_attention"].items())),
        "kinds": tuple(config["layer_types"][:n]), "window": int(config["sliding_window"]),
        "k": int(config["num_experts_per_tok"]), "held": int(config["num_experts"]),
        "first": int(config["first_held_expert"]),
        "query_block": int(config.get("reference_query_block", 512)),
        "logit_block": int(config.get("reference_logit_block", 0)),
    }.items()))


def initial_dense(config: dict, seed: int) -> dict:
    """The dense leaves from the seed, made on the device in one jitted call."""
    from perf import weights

    build = jax.jit(lambda words: mellum_weights.dense_tree(config, words, jnp))
    return build(jnp.asarray(np.stack(weights.seed_words(seed))))


leaves_by_name = mellum_weights.leaves_by_name


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
        self.picks = np.zeros((int(config["num_hidden_layers"]), int(config["num_experts"])), np.int64)
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
        mu = leaves_by_name(self.m) if self._stepped else mellum_weights.zeros_by_name(self.config)
        out = {"dense": leaves_by_name(self.dense), "adam_mu": mu,
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
