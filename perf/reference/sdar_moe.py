"""Plain reference of the ``sdar_moe`` tower's training step: float32
``jax.numpy`` at ``highest`` matmul precision, a dense mask built from the
block indices, a Python loop over the held experts, a dictionary of rows in
place of the table. Imports nothing of ``persia_tpu``.

The model, from the published config (hidden ``d``, ``Hq`` query and ``Hkv``
K/V heads of ``hd``, ``E`` routed experts of width ``f``, ``k`` a token, RMSNorm
eps, RoPE base; no bias, untied head), for a residual stream ``h``, each layer:

    a = rms(h) * w1;  q = a Wq, k = a Wk, v = a Wv  (heads of hd)
    q = rms_hd(q) * wq, k = rms_hd(k) * wk;  RoPE (rotate-half) at the position
    query head g reads K/V head g // (Hq / Hkv)
    P = softmax(q k^T / sqrt(hd) + M);  h += concat(P v) Wo
    m = rms(h) * w2;  p = softmax(m Wr) over all E;  the k largest, weights p_e / sum
    h += sum over picked e HELD HERE of weight_e * Wdown_e (silu(Wgate_e m) * (Wup_e m))

and ``logits = (rms(h) * wf) Whead`` for the noised half. The tower sees
``[xt | x0]`` (2L positions, position i of either half at RoPE position i);
with ``beta(i) = i // block_length``, a noised query reads the noised keys of
its own block and the clean keys of earlier blocks, a clean query the clean
keys of its own and earlier blocks. ``loss = sum weight * CE(logits, x0) / (B L)``.
Sparse Adagrad on the token rows a batch touches (gradients of one id summed
first), Adam on everything else.

Departures from the published description, all the configuration's
(``assumed`` and ``reduced`` in its file): the per-head q/k norms (the
lineage's; the config has no key for them); ``block_length`` and the noise
law; only the experts this chip holds (``num_experts`` held of
``router_width``, first ``first_held_expert``): what the absent experts
would add is left out, here as in the program; the vocabulary slice; no
auxiliary loss; Adagrad and Adam.

Arithmetic, as ``guarantees`` states it: every matrix product (projections,
scores, P v, router, experts, head, and the same products of the backward)
takes operands rounded to bfloat16 and is summed in float32; nothing else is
rounded. ``_product`` is the one place that rounds. The first control rounds
those operands to float8 (e4m3) instead: one precision below.

Attention runs a block of queries at a time (``reference_query_block``) and
each layer is recomputed in the backward, so that the published widths fit
one chip beside the parameters, their gradient and Adam's moments; the layers
are one ``lax.scan`` over the stacked leaves (one layer's compile).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from perf import sdar_weights

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
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + rotated * sin[:, None, :]


def dense_mask(seq_len: int, block_length: int) -> np.ndarray:
    """(2L, 2L) bool, True where the query (row) reads the key (column)."""
    beta = np.arange(seq_len) // block_length
    same, earlier = beta[:, None] == beta[None, :], beta[None, :] < beta[:, None]
    return np.block([[same, earlier], [np.zeros_like(same), same | earlier]])


def attention(q, k, v, mask, query_block: int, how):
    """q (B, T, Hq, hd), k and v (B, T, Hkv, hd), mask (T, T) -> (B, T, Hq, hd)."""
    b, t, hq, hd = q.shape
    group = hq // k.shape[2]
    qg = q.reshape(b, t // query_block, query_block, k.shape[2], group, hd)

    @jax.checkpoint
    def block(args):
        qb, mb = args  # (B, Q, Hkv, G, hd), (Q, T)
        s = _product("bqhgd,bkhd->bhgqk", qb, k, how) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(mb, s, -jnp.inf), axis=-1)
        return _product("bhgqk,bkhd->bqhgd", p, v, how)

    out = jax.lax.map(block, (jnp.moveaxis(qg, 1, 0), mask.reshape(t // query_block, query_block, t)))
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


def forward(dense, x, cfg, how):
    """Logits of the noised half (B, L, V) and picks (layers, held); ``x`` is
    the (B, 2L, d) token rows of [xt | x0]."""
    b, t, d = x.shape
    length, hd, eps = t // 2, cfg["head_dim"], cfg["eps"]
    inv = cfg["theta"] ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = (jnp.arange(t, dtype=jnp.float32) % length)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    mask = jnp.asarray(dense_mask(length, cfg["block"]))

    @jax.checkpoint
    def layer(h, p):
        a = _rms(h, p["norm1"], eps)
        q = _product("btd,de->bte", a, p["wq"], how).reshape(b, t, -1, hd)
        k = _product("btd,de->bte", a, p["wk"], how).reshape(b, t, -1, hd)
        v = _product("btd,de->bte", a, p["wv"], how).reshape(b, t, -1, hd)
        q = _rope(_rms(q, p["q_norm"], eps), cos, sin)
        k = _rope(_rms(k, p["k_norm"], eps), cos, sin)
        o = attention(q, k, v, mask, min(cfg["query_block"], t), how)
        h = h + _product("bte,ed->btd", o.reshape(b, t, -1), p["wo"], how)
        m = _rms(h, p["norm2"], eps)
        y, picks = expert_layer(p, m.reshape(b * t, d), cfg, how)
        return h + y.reshape(b, t, d), picks

    h, picks = jax.lax.scan(layer, x, dense["layers"])
    hf = _rms(h[:, :length], dense["norm_f"], eps)
    return _product("bld,dv->blv", hf, dense["head"], how), picks


def loss_fn(dense, rows_u, inv, targets, weight, cfg, how):
    logits, picks = forward(dense, rows_u[inv], cfg, how)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(weight * nll) / (targets.shape[0] * targets.shape[1]), picks


@partial(jax.jit, static_argnames=("cfg", "how", "sparse", "adam"), donate_argnums=(0, 1, 2))
def _train_step(dense, m, v, t, rows_u, acc_u, inv, targets, weight, cfg, how, sparse, adam):
    cfg = dict(cfg)
    (loss, picks), (g_dense, g_rows) = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)(
        dense, rows_u, inv, targets, weight, cfg, how)
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
        "head_dim": int(config["head_dim"]), "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]), "block": int(config["block_length"]),
        "k": int(config["num_experts_per_tok"]), "held": int(config["num_experts"]),
        "first": int(config["first_held_expert"]),
        "query_block": int(config.get("reference_query_block", 512)),
    }.items()))


def initial_dense(config: dict, seed: int) -> dict:
    """The dense leaves from the seed, made on the device in one jitted call."""
    from perf import weights

    build = jax.jit(lambda words: sdar_weights.dense_tree(config, words, jnp))
    return build(jnp.asarray(np.stack(weights.seed_words(seed))))


leaves_by_name = sdar_weights.leaves_by_name


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
        """Free the dense state on the device (7.3 GB at the cell's size)
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
        mu = leaves_by_name(self.m) if self._stepped else sdar_weights.zeros_by_name(self.config)
        out = {"dense": leaves_by_name(self.dense), "adam_mu": mu,
               "rows": rows, "acc": acc, "expert_picks": self.picks.copy()}
        if self._steps_left == 0:
            self.release()
        return out

    def step(self, batch: Dict[str, np.ndarray], keys: np.ndarray) -> float:
        """One training step on a batch of the generator (``labels`` and
        ``weights`` (B, L)) whose rows are ``keys`` (B, 2L) uint64."""
        uniq, inv = np.unique(keys.reshape(-1), return_inverse=True)
        pos = self._positions(uniq, create=True)
        pad = -len(uniq) % _PAD
        rows_p = np.concatenate([self.rows[pos], np.zeros((pad, self.dim), np.float32)])
        acc_p = np.concatenate([self.acc[pos], np.ones((pad, self.dim), np.float32)])
        if self.dense is None:
            raise RuntimeError("this reference freed its dense state after its last compared step")
        loss, picks, self.dense, self.m, self.v, self.t, rows_new, acc_new = _train_step(
            self.dense, self.m, self.v, self.t, jnp.asarray(rows_p), jnp.asarray(acc_p),
            jnp.asarray(inv.reshape(keys.shape).astype(np.int32)),
            jnp.asarray(batch["labels"], jnp.int32), jnp.asarray(batch["weights"], jnp.float32),
            cfg=self._cfg, how=self.how, sparse=self._sparse, adam=self._adam)
        self.rows[pos] = np.asarray(rows_new)[:len(uniq)]
        self.acc[pos] = np.asarray(acc_new)[:len(uniq)]
        self.picks += np.asarray(picks, np.int64)
        self._stepped = True
        if self._steps_left is not None:
            self._steps_left -= 1
        return float(loss)
