"""Plain reference of the ``joyai_flash_moe`` tower's training step: float32
``jax.numpy``, the attention's dense mask built from where each position's
document starts, the rotation on interleaved pairs as published, the
prediction module's second read of the token rows as a plain shift, the layers
of one form under one ``lax.scan`` and the held experts and groups of heads
under ``lax.map`` (one compiled body each), the dense leaves in a flat
dictionary by name, a dictionary of rows in place of the table. Imports
nothing of ``persia_tpu`` and nothing of another tower's reference.

The model, from the published config (hidden ``d``, 32 heads, RMSNorm eps, no
bias, SiLU, untied head). A batch gives each position ``i`` its token ``x_i``
and ``s_i``, the index at which its document starts; ``p_i = i - s_i``. Layers
are numbered from 0: the first ``first_k_dense_replace`` have a dense MLP, the
others the expert layer; the prediction module follows the last. For the
residual stream ``h``: ``h += attn(rms(h) * w1)``; ``h += mlp(rms(h) * w2)``.

Latent attention, every layer (``a`` the normed input): ``cq = rms(a Wqa) *
w_q``; ``[qn_h, qr_h] = cq Wqb`` (heads of 128 + 64); ``[c~, r] = a Wkva`` (512
and 64); ``c = rms(c~) * w_c``; ``[kn_h, v_h] = c Wkvb``. The rotation ``R_p``
of a 64-vector: for ``m`` = 0..31, ``theta_m = rope_theta^(-m / 32)``,

    (y_2m, y_2m+1) = (x_2m cos(p theta_m) - x_2m+1 sin(p theta_m),
                      x_2m sin(p theta_m) + x_2m+1 cos(p theta_m))

``q_ih = [qn_ih, R_{p_i} qr_ih]``, ``k_jh = [kn_jh, R_{p_j} r_j]``; ``P_ij =
softmax_j(q_ih . k_jh / sqrt(192))`` over ``s_i <= j <= i``; ``attn =
concat_h(P v_h) Wo``.

Dense MLP: ``Wd (silu(Wg m) * (Wu m))``. Expert layer: ``sc = sigmoid(m Wr)``
over all ``router_width``; the 8 largest of ``sc + b`` (``b`` the selection
bias, zeros, no parameter); weights ``routed_scaling_factor * sc_e / sum of
the picked sc``; the picked experts HELD HERE (``n_routed_experts`` from
``first_held_expert``) and the shared expert for every token.

Main objective: ``u_i = rms(h_i) * wf``; ``logits_i = u_i Whead``; ``L_main =
sum_i w_i CE(logits_i, x_{i+1}) / sum_i w_i``. Prediction module (depth 1):
``e_i`` the token row of ``x_{i+1}``, zero where ``i`` is its document's last
position; ``z_i = [rms(e_i) * w_e ; rms(u_i) * w_h]``; ``g = z M``; ``g' =`` one
whole expert layer's block over the same ``s``, ``p``; ``logits2_i = (rms(g'_i)
* w_s) Whead``, the same head; ``w2_i = w_i w_{i+1}`` (``w`` past the end 0);
``L_mtp = sum_i w2_i CE(logits2_i, x_{i+2}) / sum_i w2_i``; ``loss = L_main +
mtp_loss_weight * L_mtp``. A token row's gradient is the sum of what its two
uses give it, by ``jax.grad`` of the written loss. Sparse Adagrad on the token
rows a batch touches, Adam on the rest.

Arithmetic, as ``guarantees`` states it: every matrix product (projections,
scores, P v, router, experts, the module's ``M``, both head passes, and the
backward's) takes operands rounded to bfloat16 and is summed in float32
(``_product`` is the one place that rounds, ``_sum32`` the one that
multiplies: what ``highest`` gives over such operands, in one pass); the
rotation and its tables are float32. The first control rounds all of those
operands to float8 (e4m3) instead.

So that the published widths fit one chip: attention runs a block of queries
at a time (``reference_query_block``) and a group of heads at a time
(``reference_head_group``), the logits and the MLPs a block of positions at a
time (``reference_logit_block``, ``reference_mlp_block``), and each layer's two
halves are recomputed in the backward. **A run of the cell has 360 s in all**
in the driver's check, compiles included: a layer, a group of heads and a held
expert are each traced once (unrolled, this program takes minutes to compile),
and the two compared steps with their compile take two minutes at most.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from perf import joyai_flash_weights

_HI = jax.lax.Precision.HIGHEST
# operands of every product rounded to: (exponent bits, mantissa bits)
_ROUNDING = {None: (8, 7), "operands_float8_e4m3": (4, 3)}
CONTROLS = ("operands_float8_e4m3",)
_PAD = 1024  # a step's distinct rows are padded to a multiple of this


def _round(x, how):
    # reduce_precision, not a cast there and back, which a compiler may drop
    return jax.lax.reduce_precision(x, exponent_bits=how[0], mantissa_bits=how[1])


def _operand(x, how):
    """An operand as a product takes it: rounded, then held as bfloat16, which every rounding here fits."""
    return _round(x, how).astype(jnp.bfloat16)


def _sum32(spec, a, b):
    """einsum of bfloat16 operands summed in float32. Written over their exact
    float32 copies at the default precision, which every backend runs: the TPU
    takes a float32 operand's leading bfloat16 part in one pass (here all of it:
    ``highest`` would add five passes over zeros), the CPU multiplies in float32."""
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32), precision=jax.lax.Precision.DEFAULT)


@partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def _product(spec, a, b, how):
    """einsum of rounded operands, summed in float32; so are both gradients
    (``spec`` names every index of an operand in the other operand or in the
    result, so each gradient is one einsum of the result's and the other's)."""
    return _sum32(spec, _operand(a, how), _operand(b, how))


def _product_fwd(spec, a, b, how):
    a, b = _operand(a, how), _operand(b, how)
    return _sum32(spec, a, b), (a, b)


def _product_bwd(spec, how, res, g):
    a, b = res
    ins, out = spec.split("->")
    ia, ib = ins.split(",")
    g = _operand(g, how)
    return _sum32(f"{out},{ib}->{ia}", g, b), _sum32(f"{ia},{out}->{ib}", a, g)


_product.defvjp(_product_fwd, _product_bwd)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


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


def shift(x):
    """``x_{i+1}`` at position ``i`` (axis 1), zero at the last position."""
    return jnp.concatenate([x[:, 1:], jnp.zeros_like(x[:, :1])], axis=1)


def angles(starts, width: int, theta: float):
    """(B, T, width // 2) float32: ``p_i theta^(-m / (width / 2))``."""
    half = width // 2
    freq = np.power(float(theta), -np.arange(half, dtype=np.float64) / half).astype(np.float32)
    pos = (jnp.arange(starts.shape[1], dtype=jnp.int32)[None, :] - starts).astype(jnp.float32)
    return pos[:, :, None] * jnp.asarray(freq)


def _pair_partner(width: int) -> np.ndarray:
    """The (width, width) matrix that sends x to (-x_1, x_0, -x_3, x_2, ...)."""
    out = np.zeros((width, width), np.float32)
    for m in range(width // 2):
        out[2 * m + 1, 2 * m] = -1.0
        out[2 * m, 2 * m + 1] = 1.0
    return out


def rotate(x, angle):
    """``R_p`` on interleaved pairs, float32: x (..., T, [H,] R), ``angle`` (B, T,
    R / 2) broadcast over a head axis if x has one. With c, s of pair m at both
    its columns: y = x c + (-x_1, x_0, -x_3, x_2, ...) s, which is the pair
    formula above written for every column (the partner by an exact product
    with a matrix of 0 and +-1)."""
    cos, sin = (jnp.repeat(f(angle), 2, axis=-1) for f in (jnp.cos, jnp.sin))
    if x.ndim == 4:
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    partner = jnp.einsum("...r,rs->...s", x, jnp.asarray(_pair_partner(x.shape[-1])), precision=_HI)
    return x * cos + partner * sin


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


def latent_attention(p, a, starts, angle, cfg, how):
    """A group of heads at a time between the projections and the output
    projection, each group recomputed in the backward: rotated queries, keys
    with the shared columns repeated a head, scores and their gradients of all
    32 heads at once (a dozen arrays of 16,384 x 32 x 192 float32) would not
    fit beside the parameters at the published widths."""
    b, t, _ = a.shape
    h, nope, rank = cfg["heads"], cfg["nope"], cfg["rank"]
    proj = lambda x, w: _product("btd,de->bte", x, w, how)
    q = proj(_rms(proj(a, p["wq_a"]), p["q_norm"], cfg["eps"]), p["wq_b"]).reshape(b, t, h, -1)
    kv_a = proj(a, p["wkv_a"])
    kv = proj(_rms(kv_a[..., :rank], p["kv_norm"], cfg["eps"]), p["wkv_b"]).reshape(b, t, h, -1)
    shared = rotate(kv_a[..., rank:], angle)

    @jax.checkpoint
    def group(args):
        q, kv = args  # (B, T, heads of the group, .)
        q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], angle)], axis=-1)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(shared[:, :, None, :], q.shape[:3] + shared.shape[-1:])],
                            axis=-1)
        return attention(q, k, kv[..., nope:], starts, min(cfg["query_block"], t), how)

    hg = min(cfg["head_group"], h)
    by_group = lambda x: jnp.moveaxis(x.reshape(b, t, h // hg, hg, x.shape[-1]), 2, 0)
    o = jnp.moveaxis(jax.lax.map(group, (by_group(q), by_group(kv))), 0, 2)
    return proj(o.reshape(b, t, -1), p["wo"])


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

    def held(args):  # one held expert's part and its picks
        e, gate, up, down = args
        mine = top_e == cfg["first"] + e
        w_e = jnp.sum(jnp.where(mine, weight, 0.0), axis=-1)
        return w_e[:, None] * swiglu(m, gate, up, down, how), jnp.sum(mine)

    parts, picks = jax.lax.map(held, (jnp.arange(cfg["held"], dtype=jnp.int32), p["gate"], p["up"], p["down"]))
    return swiglu(m, p["shared_gate"], p["shared_up"], p["shared_down"], how) + jnp.sum(parts, axis=0), picks


def block(p, h, starts, angle, cfg, how):
    """One layer on the stream ``h`` (B, T, d), its MLP by the leaves it
    holds: the stream after it and the held experts' picks (None: dense)."""
    b, t, d = h.shape

    def attend(p, h):
        return h + latent_attention(p, _rms(h, p["norm1"], cfg["eps"]), starts, angle, cfg, how)

    def mlp(p, h):
        # a block of positions at a time, each recomputed in the backward: the 7,168-wide intermediates
        # of the dense MLP, or nine experts' 768-wide ones, of all positions at once are gigabytes
        m = _rms(h, p["norm2"], cfg["eps"]).reshape(b * t, d)
        size = min(cfg["mlp_block"] or b * t, b * t)
        blocks = m.reshape(-1, size, d)
        if "dense_gate" in p:
            y = jax.lax.map(jax.checkpoint(
                lambda mb: swiglu(mb, p["dense_gate"], p["dense_up"], p["dense_down"], how)), blocks)
            return h + y.reshape(b, t, d), None
        y, picks = jax.lax.map(jax.checkpoint(lambda mb: expert_layer(p, mb, cfg, how)), blocks)
        return h + y.reshape(b, t, d), jnp.sum(picks, axis=0)

    # the block is recomputed in the backward, and so is each half inside that: a block keeps its input alone
    return jax.checkpoint(lambda p, h: jax.checkpoint(mlp)(p, jax.checkpoint(attend)(p, h)))(p, h)


def block_leaves(dense, name: str) -> dict:
    """The leaves of block ``name`` (``L<l>``, ``L<a>:<b>`` or ``mtp``) by their
    short names. ``dense`` is a flat dictionary: a leading layer's leaves under
    ``L<l>.<leaf>``, the module's under ``mtp.<leaf>``, and the layers ``a`` to
    ``b - 1`` that follow the leading ones, which are of one form, stacked along
    a first axis under ``L<a>:<b>.<leaf>``."""
    return {n[len(name) + 1:]: x for n, x in dense.items() if n.startswith(name + ".")}


def hidden(dense, x, starts, angle, cfg, how):
    """The residual stream after the last layer (B, T, d) and picks (expert
    layers, held); ``x`` is the (B, T, d) token rows, ``starts`` (B, T) int32,
    ``angle`` the positions' ``angles``. The layers of one form run as one
    ``lax.scan`` over their stacked leaves: written as a Python loop, the
    compiler is free to make every layer's recomputation at once, ahead of
    the backward, and the step does not fit the chip at the published widths
    (and each layer is compiled again)."""
    h, lead, n = x, cfg["lead"], cfg["layers"]
    for l in range(lead):
        h, _ = block(block_leaves(dense, f"L{l}"), h, starts, angle, cfg, how)
    return jax.lax.scan(lambda h, p: block(p, h, starts, angle, cfg, how), h, block_leaves(dense, f"L{lead}:{n}"))


def weighted_nll(hf, head, targets, weight, cfg, how):
    """sum_i w_i CE(hf_i Whead, target_i), the logits a block of positions at a time."""
    b, t, d = hf.shape

    def nll(args):  # whole logits of these positions
        hb, tb = args
        logits = _product("nd,dv->nv", hb, head, how)
        return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]

    blk = min(cfg["logit_block"] or b * t, b * t)
    per = jax.lax.map(jax.checkpoint(nll), (hf.reshape(-1, blk, d), targets.reshape(-1, blk)))
    return jnp.sum(weight.reshape(-1) * per.reshape(-1))


def prediction_module(dense, x, u, starts, angle, cfg, how):
    """g' (B, T, d) and the module's layer's picks: the next token's row beside
    the tower's normed stream, merged, through one whole layer."""
    t = x.shape[1]
    # position i is its document's last where i + 1 starts a document, or is past the end
    last = jnp.concatenate([starts[:, 1:] == jnp.arange(1, t, dtype=jnp.int32)[None, :],
                            jnp.ones_like(starts[:, :1], bool)], axis=1)
    e = jnp.where(last[..., None], 0.0, shift(x))
    mtp = block_leaves(dense, "mtp")
    z = jnp.concatenate([_rms(e, mtp["norm_e"], cfg["eps"]), _rms(u, mtp["norm_h"], cfg["eps"])], axis=-1)
    g = _product("btd,de->bte", z, mtp["merge"], how)
    return block(mtp, g, starts, angle, cfg, how)


def loss_fn(dense, rows_u, inv, starts, targets, weight, cfg, how):
    """The loss, and (picks by expert layer, [sum w, sum w2, sum w CE, sum w2 CE2])."""
    x = rows_u[inv]
    angle = angles(starts, cfg["rope"], cfg["theta"])
    h, picks = hidden(dense, x, starts, angle, cfg, how)
    u = _rms(h, dense["norm_f"], cfg["eps"])
    main = weighted_nll(u, dense["head"], targets, weight, cfg, how)
    loss = main / jnp.sum(weight)
    if not cfg["module"]:
        return loss, (picks, jnp.stack([jnp.sum(weight), main]))
    g, got = prediction_module(dense, x, u, starts, angle, cfg, how)
    w2 = weight * shift(weight)
    second = weighted_nll(_rms(g, dense["mtp.norm_s"], cfg["eps"]), dense["head"], shift(targets), w2, cfg, how)
    loss = loss + cfg["lambda"] * second / jnp.sum(w2)
    return loss, (jnp.concatenate([picks, got[None]]), jnp.stack([jnp.sum(weight), jnp.sum(w2), main, second]))


@partial(jax.jit, static_argnames=("cfg", "how", "sparse"))
def _gradients(dense, rows_u, acc_u, inv, starts, targets, weight, cfg, how, sparse):
    """Loss and gradients by ``jax.grad`` of the written loss, and Adagrad on the batch's rows."""
    cfg = dict(cfg)
    (loss, (picks, sums)), (g_dense, g_rows) = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)(
        dense, rows_u, inv, starts, targets, weight, cfg, how)
    lr, eps = sparse
    acc_new = acc_u + g_rows * g_rows
    rows_new = rows_u - lr * g_rows / jnp.sqrt(acc_new + eps)
    return loss, picks, sums, g_dense, rows_new, acc_new


@partial(jax.jit, static_argnames=("adam",), donate_argnums=(0, 2, 3))
def _adam(dense, grads, m, v, t, adam):
    """Adam on every leaf: ``t`` counts this step."""
    alr, b1, b2, aeps = adam
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    m = jax.tree.map(lambda m, g: b1 * m + (1.0 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1.0 - b2) * g * g, v, grads)
    return jax.tree.map(lambda p, m, v: p - alr * (m / c1) / (jnp.sqrt(v / c2) + aeps), dense, m, v), m, v


def _model_cfg(config: dict) -> tuple:
    return tuple(sorted({
        "heads": int(config["num_attention_heads"]), "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]), "rank": int(config["kv_lora_rank"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]), "layers": int(config["num_hidden_layers"]),
        "lead": int(config["first_k_dense_replace"]),
        "module": int(config["num_nextn_predict_layers"]) > 0, "lambda": float(config.get("mtp_loss_weight", 0.1)),
        "k": int(config["num_experts_per_tok"]), "held": int(config["n_routed_experts"]),
        "first": int(config["first_held_expert"]), "scaling": float(config["routed_scaling_factor"]),
        "query_block": int(config.get("reference_query_block", 512)),
        "logit_block": int(config.get("reference_logit_block", 0)),
        "head_group": int(config.get("reference_head_group", 8)),
        "mlp_block": int(config.get("reference_mlp_block", 0)),
    }.items()))


def initial_dense(config: dict, seed: int) -> dict:
    """The dense leaves from the seed as ``block_leaves`` reads them, made on
    the device in one jitted call."""
    from perf import weights

    lead, n = int(config["first_k_dense_replace"]), int(config["num_hidden_layers"])

    def build(words):
        flat = {name: joyai_flash_weights.leaf(config, words, name, jnp) for name in joyai_flash_weights.leaf_names(config)}
        scanned = [{m: flat.pop(f"L{l}.{m}") for m in joyai_flash_weights.layer_shapes(config, "shared_experts")}
                   for l in range(lead, n)]
        return dict(flat, **{f"L{lead}:{n}.{m}": jnp.stack([x[m] for x in scanned]) for m in scanned[0]})

    return jax.jit(build)(jnp.asarray(np.stack(weights.seed_words(seed))))


def leaves_by_name(dense: dict) -> dict:
    """Host copies of the reference's leaves by the names snapshots use: a
    layer of the stacked ones under ``L<l>.<leaf>`` like any other."""
    out = {}
    for name, x in dense.items():
        block, _, short = name.partition(".")
        if ":" in block:
            first = int(block[1:block.index(":")])
            out.update({f"L{first + i}.{short}": layer for i, layer in enumerate(np.asarray(x))})
        else:
            out[name] = np.asarray(x)
    return out


def make(config: dict, seed: int, entry, control: Optional[str] = None) -> "Reference":
    """The reference, or the control of that name, with its weights from the
    seed; the entry names the token rows (``row_birth``)."""
    return Reference(config, seed, entry.row_birth, how=_ROUNDING[control],
                     steps=entry.snapshot_after[-1])


def extra_readings(program: dict, reference: dict) -> Dict[str, float]:
    """``expert_pick_mismatch_share``: over the compared steps, the picks by
    expert layer (the module's among them) and held expert that the program
    and the reference count differently, over the picks the reference counts
    (a pick that moves from one held expert to another counts twice, one that
    leaves the held ones once). ``mtp_loss_gap``: the prediction module's own
    term over the compared steps, ``sum w2 CE2 / sum w2`` from the ``objective``
    sums both sides keep, the relative gap; 1 where the program's sums did not
    move (a module that is not in the step)."""
    last = max(program["snaps"])

    def moved(run, name, dtype):
        return np.asarray(run["snaps"][last][name], dtype) - np.asarray(run["snaps"][0][name], dtype)

    p, r = moved(program, "expert_picks", np.int64), moved(reference, "expert_picks", np.int64)
    out = {"expert_pick_mismatch_share": float(np.abs(p - r).sum() / max(int(r.sum()), 1))}
    po, ro = moved(program, "objective", np.float64), moved(reference, "objective", np.float64)
    if len(ro) == 4:  # [sum w, sum w2, sum w CE, sum w2 CE2]
        theirs = ro[3] / ro[1]
        out["mtp_loss_gap"] = float(abs(po[3] / po[1] - theirs) / abs(theirs)) if len(po) == 4 and po[1] > 0 else 1.0
    return out


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
        self._steps_left = steps  # after the snapshot that follows the last one, the device is freed
        self.dense = initial_dense(config, seed)
        self.m = jax.tree.map(jnp.zeros_like, self.dense)
        self.v = jax.tree.map(jnp.zeros_like, self.dense)
        self.t = 0
        module = int(config["num_nextn_predict_layers"]) > 0
        n_expert_layers = int(config["num_hidden_layers"]) - int(config["first_k_dense_replace"]) + module
        self.picks = np.zeros((n_expert_layers, int(config["n_routed_experts"])), np.int64)
        self.objective = np.zeros((4 if module else 2,), np.float64)
        self.terms = []  # each step's (L_main, L_mtp)
        self._slot: Dict[int, int] = {}
        self.rows = np.empty((_PAD, self.dim), np.float32)
        self.acc = np.empty((_PAD, self.dim), np.float32)

    def release(self) -> None:
        """Free the dense state (the leaves and Adam's two moments: 6.3 GB on
        the device at the cell's size) once the compared steps are read: a
        control, a planted fault's program or the next seed's needs the room.
        Rows, picks and the snapshots taken stay readable."""
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
        # before the first step Adam's first moment is zeros: no 2.1 GB copy of them
        mu = self.m if self.t else {name: np.zeros(x.shape, np.float32) for name, x in self.dense.items()}
        out = {"dense": leaves_by_name(self.dense), "adam_mu": leaves_by_name(mu),
               "rows": rows, "acc": acc, "expert_picks": self.picks.copy(), "objective": self.objective.copy()}
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
        loss, picks, sums, grads, rows_new, acc_new = _gradients(
            self.dense, jnp.asarray(rows_p), jnp.asarray(acc_p),
            jnp.asarray(inv.reshape(keys.shape).astype(np.int32)), jnp.asarray(starts),
            jnp.asarray(batch["labels"], jnp.int32), jnp.asarray(batch["weights"], jnp.float32),
            cfg=self._cfg, how=self.how, sparse=self._sparse)
        self.t += 1
        self.dense, self.m, self.v = _adam(self.dense, grads, self.m, self.v, float(self.t), adam=self._adam)
        self.rows[pos] = np.asarray(rows_new)[:len(uniq)]
        self.acc[pos] = np.asarray(acc_new)[:len(uniq)]
        self.picks += np.asarray(picks, np.int64)
        sums = np.asarray(sums, np.float64)
        self.objective += sums
        half = len(sums) // 2
        self.terms.append(tuple(sums[half:] / sums[:half]))
        if self._steps_left is not None:
            self._steps_left -= 1
        return float(loss)
