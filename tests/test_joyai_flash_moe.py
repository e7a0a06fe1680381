"""The tower of rotated latent-attention layers with a multi-token-prediction
module (``models/joyai_flash_moe.py``) on the CPU: against the plain reference
(``perf/reference/joyai_flash_moe.py``) at a small size on seeded weights (the
loss, both of its terms, every leaf's gradient, the rows'); the shared latent
attention (``moe_tower.latent_attention``) against a written-out softmax with
and without the query's low rank and the rotation; the rotation against the
interleaved-pair formula at positions that restart inside a tile; the module's
labels, weights and embedding at document ends; a row's gradient as the sum of
its two uses; the expert shares, the shared expert counted once, against the
uncut layer, for a scanned layer and for the module's; ``from_config`` on the
published keys. The Pallas kernels run in the interpreter, by this file's choice."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tests", "perf")):
    if path not in sys.path:
        sys.path.insert(0, path)

from joyai_flash_presets import CATALOG  # noqa: E402
from perf import joyai_flash_weights  # noqa: E402
from perf.reference import joyai_flash_moe as reference  # noqa: E402
from persia_tpu import tracing  # noqa: E402
from persia_tpu.data import IDTypeFeature, Label, PersiaBatch, document_starts  # noqa: E402
from persia_tpu.embedding.optim import Adagrad  # noqa: E402
from persia_tpu.models import JoyAIFlashMoE, KimiLinearMoE  # noqa: E402
from persia_tpu.models.joyai_flash_moe import rope_tables, shifted  # noqa: E402
from persia_tpu.models.moe_tower import _mm, _rms, latent_attention  # noqa: E402
from persia_tpu.ops.flash_attention import interval_attention, interval_tile_counts  # noqa: E402
from persia_tpu.ops.qk_prep import rotate_pairs  # noqa: E402
from persia_tpu.parallel.fused_ctx import FusedTrainCtx  # noqa: E402
from persia_tpu.parallel.fused_step import (  # noqa: E402
    FusedSlotSpec, FusedTrainState, group_stacked_specs,
)

# the leading layer, two expert layers and the module; 4 of 16 experts held, 2 a token
TINY = dict(
    CATALOG, hidden_size=128, num_attention_heads=2, num_key_value_heads=2, kv_lora_rank=64, q_lora_rank=48,
    intermediate_size=96, moe_intermediate_size=64, n_routed_experts=4, router_width=16, first_held_expert=4,
    num_experts_per_tok=2, num_hidden_layers=3, vocab_size=97, router_law="plain", mtp_loss_weight=0.1,
    reference_query_block=16,
    sparse_optimizer={"kind": "adagrad", "lr": 0.01, "initial_accumulator": 0.01, "eps": 1e-10},
    dense_optimizer={"kind": "adam", "lr": 1e-6, "b1": 0.9, "b2": 0.95, "eps": 1e-8})
SEED, BATCH, LENGTH = 2 ** 31 + 17, 2, 64
# three and five documents a sequence, one of 2 tokens and one of 1; every later start inside a tile of 16
DOCS = np.array([[18, 5, 41, 0, 0], [37, 2, 1, 3, 21]], np.int32)
HOW = (8, 7)  # the reference's products on operands rounded to bfloat16


def _model(cfg, **kw):
    return JoyAIFlashMoE.from_config(cfg, **dict({"head_chunk": 32, "tile": 16, "interpret": True}, **kw))


def _tower_params(cfg):
    """The seeded weights as the tower holds them (the reference holds the same leaves by name)."""
    return jax.tree.map(jnp.asarray, joyai_flash_weights.dense_tree(cfg, SEED))


def _batch(seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TINY["vocab_size"], (BATCH, LENGTH))
    labels = np.concatenate([ids[:, 1:], np.zeros((BATCH, 1), ids.dtype)], axis=1).astype(np.int32)
    weights = np.ones((BATCH, LENGTH), np.float32)
    np.put_along_axis(weights, np.cumsum(DOCS, axis=1) - 1, 0.0, axis=1)
    return {"ids": ids, "doc_lengths": DOCS, "labels": labels, "weights": weights}


def _persia_batch(b):
    tokens = IDTypeFeature.from_flat("tokens", b["ids"].astype(np.uint64).reshape(-1),
                                     np.full(BATCH, LENGTH, np.int64))
    return PersiaBatch([tokens], [document_starts(b["doc_lengths"], LENGTH)],
                       labels=[Label(b["labels"]), Label(b["weights"])], requires_grad=True)


def _gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


def _starts(*lengths):
    return np.stack([document_starts([row], LENGTH).data[0] for row in lengths])


@pytest.fixture(scope="module")
def one_step():
    """One ``FusedTrainCtx.train_step`` of the tower and one step of the
    reference, from the same seeded weights on the same batch."""
    cfg, b = TINY, _batch()
    so, do = cfg["sparse_optimizer"], cfg["dense_optimizer"]
    emb_opt = Adagrad(lr=so["lr"], initialization=so["initial_accumulator"], eps=so["eps"])
    model = _model(cfg)
    ctx = FusedTrainCtx(model, optax.adam(do["lr"], b1=do["b1"], b2=do["b2"], eps=do["eps"]),
                        emb_opt, {"tokens": FusedSlotSpec(cfg["vocab_size"], cfg["hidden_size"], pooled=False)})
    dense = _tower_params(cfg)
    table = jnp.asarray(joyai_flash_weights.token_rows(cfg, SEED, np.arange(cfg["vocab_size"])))
    (gname,) = [g.name for g in group_stacked_specs(ctx.specs, ctx.slot_order)]
    ctx.state = FusedTrainState(
        params=jax.tree.map(jnp.copy, dense), batch_stats=model.counters(),
        opt_state=ctx.dense_optimizer.init(dense), tables={gname: table},
        emb_state={gname: {"acc": jnp.full(table.shape, so["initial_accumulator"], jnp.float32)}},
        emb_batch_state=jnp.ones((2,), jnp.float32), step=jnp.zeros((), jnp.int32))
    out = ctx.train_step(_persia_batch(b))
    paths = [e["attrs"] for e in tracing.flight_snapshot() if e["kind"] == "joyai_flash.paths"]
    ref = reference.Reference(cfg, SEED, lambda keys: joyai_flash_weights.token_rows(
        cfg, SEED, np.asarray(keys, np.int64)), how=HOW)
    keys = b["ids"].astype(np.uint64)
    loss_ref = ref.step(b, keys)
    return {"cfg": cfg, "out": out, "state": ctx.state, "table": np.asarray(ctx.state.tables[gname]),
            "ref": ref, "loss_ref": loss_ref, "uniq": np.unique(keys), "batch": b,
            "dense0": joyai_flash_weights.leaves_by_name(dense, cfg), "paths": paths, "model": model}


@pytest.mark.parametrize("what", ["loss", "main_term", "mtp_term", "label_sums", "gradient_by_leaf",
                                  "change_by_leaf", "rows", "picks", "tiles", "buffers", "paths", "leaves"])
def test_tower_against_the_reference(one_step, what):
    s, ref, cfg = one_step, one_step["ref"], one_step["cfg"]
    b1 = cfg["dense_optimizer"]["b1"]
    sums = np.asarray(s["state"].batch_stats["objective"], np.float64)  # [sum w, sum w2, sum w CE, sum w2 CE2]
    if what == "loss":  # bfloat16 operands summed in another order: a few parts in 1e5
        assert abs(s["out"]["loss"] - s["loss_ref"]) <= 3e-4 * abs(s["loss_ref"])
        assert 1.1 * 3.0 < s["loss_ref"] < 1.1 * 6.0  # ln(97) = 4.57 at the start, and a tenth of it again
    elif what in ("main_term", "mtp_term"):  # each term for itself: the second cannot hide behind its 0.1
        i = ("main_term", "mtp_term").index(what)
        mine, theirs = sums[2 + i] / sums[i], ref.terms[0][i]
        assert abs(mine - theirs) <= 3e-4 * theirs and 3.0 < theirs < 6.0
        if what == "mtp_term":
            assert s["out"]["loss"] == pytest.approx(ref.terms[0][0] + 0.1 * theirs, rel=3e-4)
    elif what == "label_sums":  # 8 documents: the main objective leaves out 8 positions, the module 8 + 7 (one has a single token)
        assert sums[0] == 2 * 64 - 8 and sums[1] == 2 * 64 - 15
        np.testing.assert_array_equal(sums[:2], ref.objective[:2])
    elif what == "gradient_by_leaf":  # Adam's first moment after one step is (1 - b1) x the gradient
        mine = joyai_flash_weights.leaves_by_name(s["state"].opt_state[0].mu, cfg)
        theirs = reference.leaves_by_name(ref.m)
        assert set(mine) == set(joyai_flash_weights.leaf_names(cfg))
        for name in theirs:
            assert np.linalg.norm(theirs[name]) > 0, name
            # bfloat16 operands in another order: half a percent; one pick of 128 that falls on the
            # other side moves a router's or a held expert's gradient by a tenth
            ragged = any(n in name for n in ("router", ".gate", ".up", ".down", "mtp.norm2"))
            assert _gap(mine[name] / (1 - b1), theirs[name] / (1 - b1)) < (0.25 if ragged else 0.03), name
    elif what == "change_by_leaf":
        mine = joyai_flash_weights.leaves_by_name(s["state"].params, cfg)
        theirs = reference.leaves_by_name(ref.dense)
        for name, start in s["dense0"].items():
            a, b = np.linalg.norm(mine[name] - start), np.linalg.norm(theirs[name] - start)
            assert b > 0 and abs(a - b) < 0.02 * b, name  # Adam's first step is lr x sign(g)
    elif what == "rows":  # the table's change: the foot's and the module's gradient of a row, summed over its positions
        rows, _ = ref.lookup(s["uniq"])
        start = joyai_flash_weights.token_rows(cfg, SEED, s["uniq"].astype(np.int64))
        assert _gap(s["table"][s["uniq"].astype(np.int64)] - start, rows - start) < 0.03
    elif what == "picks":  # two expert layers and the module's, by held expert; the leading layer routes nothing
        picks = np.asarray(s["state"].batch_stats["expert_picks"])
        assert picks.shape == (3, 4) and np.abs(picks - ref.picks).sum() <= 0.03 * ref.picks.sum()
        assert (picks.sum(axis=1) > 0).all()
    elif what == "tiles":  # four latent blocks' visited and live tile pairs a head, in row 1
        tiles = np.asarray(s["state"].batch_stats["attention_tiles"])
        lo = jnp.asarray(document_starts(DOCS, LENGTH).data)
        np.testing.assert_array_equal(tiles, [[0, 0], 4 * np.asarray(interval_tile_counts(lo, None, 16))])
    elif what == "buffers":  # the selection bias: zeros that the step leaves alone
        bias = np.asarray(s["state"].batch_stats["router_bias"])
        assert bias.shape == (3, 16) and not bias.any()
    elif what == "paths":
        said = s["paths"][-1]
        assert said["latent_attention"] == "pallas_interval_two_products" and said["q_low_rank"] == "48"
        assert said["rope"] == "pallas_pass" and said["rope_pairs"] == "interleaved" and said["tile"] == "16"
        assert said["mtp_depth"] == "1" and said["mtp_embedding"] == "shifted_slot" and said["head_passes"] == "2"
        assert said["experts"] == "pallas_grouped" and said["head_chunk"] == "32"
        # both latent heads a grid step, a call's grid the triangle of 4 tiles a sequence
        assert (said["attention_heads_a_step"], said["attention_grid_steps"]) == ("2", str(2 * 10))
    else:  # the tower's leaves are the weights file's, shape for shape
        shapes = jax.tree.map(lambda x: x.shape, s["state"].params)
        want = jax.tree.map(lambda s: tuple(s), s["model"].param_shapes(),
                            is_leaf=lambda x: isinstance(x, tuple) and (not x or isinstance(x[0], int)))
        assert shapes == want


# ------------------------------------------------- latent attention, shared

def _written_out_latent_attention(p, a, lo, pos, theta, eps=1e-6):
    """The layer in float64 numpy, position by position, pair by pair."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    a = np.asarray(a, np.float64)
    b, t, _ = a.shape
    rank, r = p["kv_norm"].shape[0], p["wkv_a"].shape[1] - p["kv_norm"].shape[0]
    h = p["wo"].shape[0] // 128
    rms = lambda x, w: x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * w
    q = (rms(a @ p["wq_a"], p["q_norm"]) @ p["wq_b"] if "wq_a" in p else a @ p["wq"]).reshape(b, t, h, 128 + r)
    kv_a = a @ p["wkv_a"]
    kv = (rms(kv_a[..., :rank], p["kv_norm"]) @ p["wkv_b"]).reshape(b, t, h, 256)
    shared = kv_a[..., rank:]

    def turn(x, at):  # (y_2m, y_2m+1) = (x_2m c - x_2m+1 s, x_2m s + x_2m+1 c)
        if theta is None:
            return x
        y = np.empty_like(x)
        for m in range(r // 2):
            angle = at * theta ** (-m / (r // 2))
            c, s = np.cos(angle), np.sin(angle)
            y[..., 2 * m] = x[..., 2 * m] * c - x[..., 2 * m + 1] * s
            y[..., 2 * m + 1] = x[..., 2 * m] * s + x[..., 2 * m + 1] * c
        return y

    out = np.zeros((b, t, h, 128))
    for n in range(b):
        for i in range(t):
            keys = np.arange(lo[n, i], i + 1)
            for g in range(h):
                qi = np.concatenate([q[n, i, g, :128], turn(q[n, i, g, 128:], pos[n, i])])
                kj = np.concatenate([kv[n, keys, g, :128],
                                     np.stack([turn(shared[n, j], pos[n, j]) for j in keys])], axis=1)
                score = kj @ qi / np.sqrt(128 + r)
                w = np.exp(score - score.max())
                out[n, i, g] = (w / w.sum()) @ kv[n, keys, g, 128:]
    return out.reshape(b, t, h * 128) @ p["wo"]


@pytest.mark.parametrize("rotated", [False, True], ids=["nope", "rotated"])
@pytest.mark.parametrize("low_rank", [False, True], ids=["full_rank_q", "low_rank_q"])
def test_latent_attention_against_a_written_out_softmax(low_rank, rotated):
    """One function for both latent towers: a full-rank or a low-rank query by
    the leaves it is given, a rotation or none by ``rope``. Float32 inputs that
    bfloat16 holds exactly would hide nothing here: the tolerance is the
    bfloat16 operands' (three products deep)."""
    rng = np.random.default_rng(11)
    d, h, rank, r, t = 64, 2, 32, 64, 32
    lo = np.stack([document_starts([row], t).data[0] for row in ([13, 19], [5, 2, 25])])
    pos = np.arange(t)[None, :] - lo
    w = lambda *shape: jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[0]), jnp.float32)
    p = {"wkv_a": w(d, rank + r), "kv_norm": jnp.asarray(rng.uniform(0.5, 1.5, rank), jnp.float32),
         "wkv_b": w(rank, h * 256), "wo": w(h * 128, d)}
    p.update({"wq_a": w(d, 24), "q_norm": jnp.asarray(rng.uniform(0.5, 1.5, 24), jnp.float32),
              "wq_b": w(24, h * 192)} if low_rank else {"wq": w(d, h * 192)})
    a = jnp.asarray(rng.standard_normal((2, t, d)), jnp.float32)
    theta = 32e6 if rotated else None
    rope = rope_tables(jnp.asarray(lo), r, theta) if rotated else None
    got = latent_attention(p, a, jnp.asarray(lo), n_heads=h, head_dim=128, rope_head_dim=r, kv_lora_rank=rank,
                           eps=1e-6, tile=16, interpret=True, rope=rope)
    want = _written_out_latent_attention(p, a, lo, pos, theta)
    assert got.shape == want.shape and _gap(got, want) < 2e-2
    if rotated:  # and the rotation is no identity: without it the result is another
        plain = latent_attention(p, a, jnp.asarray(lo), n_heads=h, head_dim=128, rope_head_dim=r,
                                 kv_lora_rank=rank, eps=1e-6, tile=16, interpret=True)
        assert _gap(plain, want) > 5e-2


def _one_query_latent_attention(p, a, starts, *, n_heads, head_dim, rope_head_dim, kv_lora_rank, eps, tile,
                                interpret, rope):
    """The layer as PR 42 ran it: the whole (B, T, H, D + R) float32 query from
    one product, its last R columns a head rotated by XLA, concatenated back,
    cast and handed to the kernels whole (they split it)."""
    b, t, _ = a.shape
    h, hd, r = n_heads, head_dim, rope_head_dim
    q = _mm(_rms(_mm(a, p["wq_a"]), p["q_norm"], eps), p["wq_b"]) if "wq_a" in p else _mm(a, p["wq"])
    q = q.reshape(b, t, h, hd + r)
    kv_a = _mm(a, p["wkv_a"])
    latent = _rms(kv_a[..., :kv_lora_rank], p["kv_norm"], eps)
    cos, sin = rope
    q = jnp.concatenate([q[..., :hd], rotate_pairs(q[..., hd:], cos[:, :, None], sin[:, :, None])], axis=-1)
    shared = rotate_pairs(kv_a[..., kv_lora_rank:], cos, sin).astype(jnp.bfloat16)
    kv = _mm(latent, p["wkv_b"]).astype(jnp.bfloat16).reshape(b, t, h, 2 * hd)
    o = interval_attention(q.astype(jnp.bfloat16), kv[..., :hd], kv[..., hd:], starts, tile=tile, interpret=interpret,
                           k_shared=shared)
    return _mm(o.reshape(b, t, h * hd), p["wo"])


@pytest.fixture(scope="module")
def split_and_whole_query():
    """``latent_attention`` (the query as two products, the rotation one pass
    into the kernels' head-major operand) and PR 42's one-query form, on the
    tiny preset's widths, documents that restart inside a tile of 16: the
    output and the gradient of the input and of every leaf, each way."""
    model = _model(TINY)
    rng = np.random.default_rng(7)
    shapes = model.attention_shapes("mla")
    p = {k: jnp.asarray(rng.uniform(0.5, 1.5, s) if len(s) == 1 else rng.standard_normal(s) / np.sqrt(s[0]),
                        jnp.float32) for k, s in shapes.items()}
    a = jnp.asarray(rng.standard_normal((BATCH, LENGTH, TINY["hidden_size"])), jnp.float32)
    lo = jnp.asarray(_starts(*DOCS))
    cot = jnp.asarray(rng.standard_normal((BATCH, LENGTH, TINY["hidden_size"])), jnp.float32)
    how = dict(n_heads=model.n_heads, head_dim=model.head_dim, rope_head_dim=model.rope_head_dim,
               kv_lora_rank=model.kv_lora_rank, eps=model.rms_eps, tile=16, interpret=True,
               rope=rope_tables(lo, model.rope_head_dim, model.rope_theta))
    out = {}
    for name, layer in (("split", latent_attention), ("whole", _one_query_latent_attention)):
        y, pull = jax.vjp(lambda p, a: layer(p, a, lo, **how), p, a)
        out[name] = (y, *pull(cot))
    return out


@pytest.mark.parametrize("what", ["output", "input", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo"])
def test_the_split_query_is_the_one_query_form(split_and_whole_query, what):
    """The same products over the same K, the same float32 rotation before the
    same cast: the output and all but three gradients come out bit for bit;
    the input's, ``wq_a``'s and ``q_norm``'s read 1.8e-5, 3.6e-5 and 1.8e-7,
    the low-rank input's gradient being two products' sum in place of one."""
    (y, dp, da), (y0, dp0, da0) = split_and_whole_query["split"], split_and_whole_query["whole"]
    got, want = {"output": (y, y0), "input": (da, da0)}.get(what, (dp.get(what), dp0.get(what)))
    assert got.shape == want.shape and np.linalg.norm(np.asarray(want)) > 0
    assert _gap(got, want) < 2e-4, what


@pytest.mark.parametrize("tower", ["joyai_flash_rotated", "kimi_linear_nope"])
def test_the_rotation_pass_is_in_the_rotated_towers_step_alone(tower):
    """A training step's gradient program, every block and the head's passes
    in it (traced, not run): the rotated tower's holds the pass's two kernels,
    the NoPE tower's (the delta-rule cell's) neither. What decides is the
    layer's ``rope``, None or tables, and nothing else."""
    model = (_model(TINY) if tower == "joyai_flash_rotated" else
             KimiLinearMoE(vocab=97, n_layers=5, hidden=128, n_heads=2, kv_lora_rank=64, n_held=4, tile=16,
                           kda_chunk=16, head_chunk=32, interpret=True))
    shaped = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype)
    params = jax.tree.map(shaped, model.param_shapes(),
                          is_leaf=lambda x: isinstance(x, tuple) and (not x or isinstance(x[0], int)))

    def gradient(params, rows, at, weight):
        loss = lambda p, r: model.train_loss({"params": p, "batch_stats": model.counters()}, [at], [(r, None)],
                                             [at, weight])[0]
        return jax.grad(loss, argnums=(0, 1))(params, rows)

    at = shaped((1, LENGTH), jnp.int32)
    text = str(jax.make_jaxpr(gradient)(params, shaped((1, LENGTH, model.hidden)), at, shaped((1, LENGTH))))
    kernels = set(re.findall(r"mla_rope_[a-z]+", text))
    assert kernels == ({"mla_rope_fwd", "mla_rope_bwd"} if tower == "joyai_flash_rotated" else set()), kernels
    assert "interval_attention_fwd" in text  # both step through the latent kernels


def test_both_latent_towers_call_the_one_function(monkeypatch):
    """``KimiLinearMoE`` (full-rank query, no rotation) and ``JoyAIFlashMoE``
    (low rank, rotated) hand their leaves to ``moe_tower.latent_attention``."""
    from persia_tpu.models import joyai_flash_moe, kimi_linear_moe

    seen = []

    def spy(p, a, starts, **kw):
        seen.append((sorted(p), kw["rope"] is not None if "rope" in kw else False))
        return jnp.zeros_like(a)

    monkeypatch.setattr(kimi_linear_moe, "latent_attention", spy)
    monkeypatch.setattr(joyai_flash_moe, "latent_attention", spy)
    a, lo = jnp.zeros((1, 16, 128)), jnp.zeros((1, 16), jnp.int32)
    kimi = KimiLinearMoE(vocab=8, n_layers=5, hidden=128, n_heads=2, kv_lora_rank=64)
    kimi.attention("mla", {"wq": 0, "wkv_a": 0}, a, lo, None)
    joy = _model(TINY)
    joy.attention("mla", {"wq_a": 0, "q_norm": 0, "wq_b": 0}, a, joy._side(lo)["mla"], None)
    assert seen == [(["wkv_a", "wq"], False), (["q_norm", "wq_a", "wq_b"], True)]


# ----------------------------------------------------------- the rotation

@pytest.mark.parametrize("case", ["one_document", "restart_inside_a_tile", "two_starts_in_one_tile"])
def test_rotation_against_the_interleaved_pair_formula(case):
    """``rope_tables`` and ``rotate_pairs`` against the published formula in
    float64, pair by pair, at positions that restart where a document starts
    (inside a tile of 16); and the reference's own rotation against the same."""
    lengths = {"one_document": [64], "restart_inside_a_tile": [21, 43], "two_starts_in_one_tile": [18, 3, 6, 37]}[case]
    lo = _starts(lengths)
    pos = (np.arange(LENGTH)[None, :] - lo).astype(np.float64)
    assert pos.min() == 0 and (pos[0, np.cumsum(lengths)[:-1]] == 0).all()  # every document starts at 0
    x = np.random.default_rng(3).standard_normal((1, LENGTH, 2, 64)).astype(np.float32)
    want = np.empty(x.shape, np.float64)
    for m in range(32):
        angle = (pos * 32e6 ** (-m / 32))[:, :, None]
        c, s = np.cos(angle), np.sin(angle)
        want[..., 2 * m] = x[..., 2 * m] * c - x[..., 2 * m + 1] * s
        want[..., 2 * m + 1] = x[..., 2 * m] * s + x[..., 2 * m + 1] * c
    cos, sin = rope_tables(jnp.asarray(lo), 64, 32e6)
    assert cos.shape == (1, LENGTH, 64) and cos.dtype == jnp.float32
    got = rotate_pairs(jnp.asarray(x), cos[:, :, None, :], sin[:, :, None, :])
    # float32 tables: an angle of 42 at the first pair is held to 4e-6
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    theirs = reference.rotate(jnp.asarray(x), reference.angles(jnp.asarray(lo), 64, 32e6))
    np.testing.assert_allclose(np.asarray(theirs), want, atol=2e-5)
    # a rotation keeps every pair's length, and a document's first position is not turned
    np.testing.assert_allclose(np.linalg.norm(np.asarray(got), axis=-1), np.linalg.norm(x, axis=-1), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(got)[0, 0], x[0, 0])


def test_a_score_sees_positions_only_by_their_difference():
    """Rotated q . rotated k depends on p_i - p_j alone: the same document
    moved along the sequence gives the same scores."""
    rng = np.random.default_rng(5)
    q, k = (jnp.asarray(rng.standard_normal((1, 8, 64)), jnp.float32) for _ in range(2))
    scores = []
    for first in (0, 40):
        lo = jnp.full((1, 8), first, jnp.int32)
        cos, sin = rope_tables(jnp.concatenate([jnp.zeros((1, first), jnp.int32), lo], axis=1), 64, 32e6)
        cos, sin = cos[:, first:], sin[:, first:]
        scores.append(np.asarray(jnp.einsum("bqr,bkr->bqk", rotate_pairs(q, cos, sin), rotate_pairs(k, cos, sin),
                                            precision="highest")))
    np.testing.assert_allclose(scores[0], scores[1], atol=1e-4)


# ------------------------------------------------- the prediction module

@pytest.fixture(scope="module")
def module_inputs():
    cfg = dict(TINY, num_hidden_layers=2)  # the leading layer, one expert layer, the module
    model = _model(cfg)
    dense = _tower_params(cfg)
    table = jnp.asarray(joyai_flash_weights.token_rows(cfg, SEED, np.arange(cfg["vocab_size"])))
    b = _batch(4)
    starts = jnp.asarray(document_starts(DOCS, LENGTH).data)
    variables = {"params": dense, "batch_stats": model.counters()}
    labels = [jnp.asarray(b["labels"]), jnp.asarray(b["weights"])]
    # the passes once, for every test that reads them
    passes, _ = model.objectives(variables, [starts], [(table[b["ids"]], None)], labels)
    return {"model": model, "variables": variables, "starts": starts, "rows": table[b["ids"]], "labels": labels,
            "batch": b, "dense": dense, "passes": passes}


ENDS = np.cumsum(DOCS, axis=1) - 1  # each document's last position (a zero-length one repeats the one before)


@pytest.mark.parametrize("what", ["targets", "weights_at_the_last_two", "a_document_of_one", "a_document_of_two",
                                  "coefficients", "streams"])
def test_the_modules_labels_and_weights_at_document_ends(module_inputs, what):
    m = module_inputs
    (scope1, u, norm1, t1, w1, c1), (scope2, g, norm2, t2, w2, c2) = m["passes"]
    labels, weights = m["batch"]["labels"], m["batch"]["weights"]
    if what == "targets":  # x_{i+2}: the generator's label shifted by one, 0 past the end
        np.testing.assert_array_equal(np.asarray(t1), labels)
        np.testing.assert_array_equal(np.asarray(t2)[:, :-1], labels[:, 1:])
        np.testing.assert_array_equal(np.asarray(t2)[:, :-2], m["batch"]["ids"][:, 2:])
        assert not np.asarray(t2)[:, -1].any()
    elif what == "weights_at_the_last_two":  # w2 = w_i w_{i+1}: 0 at a document's last and last-but-one position, else 1
        want = np.ones_like(weights)
        for n in range(BATCH):
            for end, length in zip(ENDS[n], DOCS[n]):
                if length:
                    want[n, max(end - 1, end - length + 1):end + 1] = 0.0
        np.testing.assert_array_equal(np.asarray(w2), want)
        np.testing.assert_array_equal(np.asarray(w1), weights)
    elif what == "a_document_of_one":  # position 39 of sequence 1: its own last, and the one before is the 2-token document's last
        assert DOCS[1, 2] == 1 and np.asarray(w2)[1, 39] == 0 and np.asarray(w1)[1, 39] == 0
    elif what == "a_document_of_two":  # positions 37, 38: neither has a token after next in its document
        assert DOCS[1, 1] == 2 and not np.asarray(w2)[1, 37:39].any() and np.asarray(w1)[1, 37] == 1
    elif what == "coefficients":
        assert (scope1, scope2) == ("lm_head", "mtp/lm_head") and (c1, c2) == (1.0, 0.1)
        assert norm1 is None and norm2 is m["dense"]["mtp"]["norm_s"]  # the tower's stream comes normed, once
    else:  # the main pass's stream is rms(h) * wf of the tower's own hidden stream
        h, _ = m["model"]._hidden(m["variables"], [m["starts"]], [(m["rows"], None)])
        want = reference._rms(h, m["dense"]["norm_f"], 1e-6)
        np.testing.assert_allclose(np.asarray(u), np.asarray(want), rtol=1e-6)
        assert g.shape == u.shape and _gap(g, u) > 0.1


def test_the_module_reads_no_token_of_another_document(module_inputs):
    """``e_i`` is zero at a document's last position and attention stops at the
    document's start: another first token of the NEXT document changes nothing
    the module gives any position before it, to the bit; and it does change
    the module's stream inside that document."""
    m = module_inputs
    model, params = m["model"], m["dense"]
    u = jnp.asarray(np.random.default_rng(1).standard_normal(m["rows"].shape), jnp.float32)
    bias = jnp.zeros((16,), jnp.float32)
    run = lambda rows: np.asarray(model._module(params, u, rows, m["starts"], model._side(m["starts"]), bias)[0])
    first = 18 + 5  # sequence 0's third document starts here
    a, b = run(m["rows"]), run(m["rows"].at[0, first].set(3.0))
    np.testing.assert_array_equal(a[0, :first], b[0, :first])
    np.testing.assert_array_equal(a[1], b[1])
    assert np.abs(a[0, first:] - b[0, first:]).max() == 0  # the module reads x_{i+1}, never x_i: position `first` is no one's next token in its document ...
    c = run(m["rows"].at[0, first + 1].set(3.0))  # ... its second token is its first position's
    assert np.abs(a[0, first:] - c[0, first:]).max() > 1e-3
    np.testing.assert_array_equal(a[0, :first], c[0, :first])


def test_shifted_is_the_next_position():
    x = jnp.arange(12.0).reshape(2, 6)
    np.testing.assert_array_equal(np.asarray(shifted(x)), [[1, 2, 3, 4, 5, 0], [7, 8, 9, 10, 11, 0]])


class _TwoReads(JoyAIFlashMoE):
    """The tower with the module's read of the rows given apart, so that the
    two uses' gradients can be told apart (the class attribute is the test's)."""

    module_rows = None

    def _module(self, params, u, rows, starts, side, router_bias):
        return super()._module(params, u, type(self).module_rows, starts, side, router_bias)


@pytest.fixture(scope="module")
def two_reads(module_inputs):
    m = module_inputs
    model = _TwoReads(**{f: getattr(m["model"], f) for f in m["model"].__dataclass_fields__})

    def loss(foot_rows, module_rows, model=model):
        type(model).module_rows = module_rows
        out = model.train_loss(m["variables"], [m["starts"]], [(foot_rows, None)], m["labels"])[0]
        type(model).module_rows = None
        return out

    apart = jax.jit(jax.grad(loss, argnums=(0, 1)))(m["rows"], m["rows"])
    whole = jax.jit(jax.grad(lambda rows: m["model"].train_loss(m["variables"], [m["starts"]], [(rows, None)],
                                                                m["labels"])[0]))(m["rows"])
    return {"foot": np.asarray(apart[0]), "module": np.asarray(apart[1]), "whole": np.asarray(whole)}


@pytest.mark.parametrize("what", ["the_sum", "both_are_there", "first_tokens_have_one_use"])
def test_a_rows_gradient_is_the_sum_of_its_two_uses(two_reads, what):
    """Token ``x_{i+1}`` is the tower's input at position ``i + 1`` and the
    module's embedding at position ``i``: the gathered slot's gradient at
    ``i + 1`` is the sum of what each use gives it (``sparse_update`` then sums
    a token's positions, as it always did)."""
    g = two_reads
    if what == "the_sum":
        np.testing.assert_allclose(g["whole"], g["foot"] + g["module"], rtol=2e-4, atol=1e-9)
    elif what == "both_are_there":
        inside = 10  # a position inside sequence 0's first document
        assert np.linalg.norm(g["foot"][0, inside]) > 0 and np.linalg.norm(g["module"][0, inside]) > 0
        # the module's term carries 0.1 and one layer; it is no rounding of the foot's
        assert 1e-3 < np.linalg.norm(g["module"]) / np.linalg.norm(g["foot"]) < 1.0
    else:  # a document's first token is no position's next token in its document: the foot's use alone
        for n in range(BATCH):
            for first, length in zip(np.concatenate([[0], np.cumsum(DOCS[n])[:-1]]), DOCS[n]):
                if length:
                    assert not g["module"][n, first].any()
                    # (a document of one token has no label and no reader: no gradient at all)
                    assert g["foot"][n, first].any() == (length > 1)


# -------------------------------------------------------------- the shares

def _expert_leaves(rng, d, f, e):
    router = jnp.asarray(rng.standard_normal((d, e)) * 0.2, jnp.float32)
    gate, up = (jnp.asarray(rng.standard_normal((e, d, f)) * 0.1, jnp.float32) for _ in range(2))
    down = jnp.asarray(rng.standard_normal((e, f, d)) * 0.1, jnp.float32)
    shared = {"shared_gate": gate[0] * 0.7, "shared_up": up[1] * 0.7, "shared_down": down[2] * 0.7}
    return router, gate, up, down, shared


def test_the_shares_add_up_for_an_expert_layer():
    """The parts of one expert layer's result that the 4 shares (0, 4) .. (12,
    4) give, the shared expert counted once, sum to what the uncut reference
    layer gives: 16 experts, 2 a token, scaled by 2.5."""
    cfg = dict(TINY)
    rng = np.random.default_rng(5)
    d, f, n, e = cfg["hidden_size"], cfg["moe_intermediate_size"], 64, cfg["router_width"]
    m = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    router, gate, up, down, shared = _expert_leaves(rng, d, f, e)
    whole, picks_whole = reference.expert_layer(
        dict(shared, router=router, gate=gate, up=up, down=down), m,
        {"k": 2, "held": e, "first": 0, "scaling": 2.5}, HOW)
    bias = jnp.zeros((e,), jnp.float32)
    total, picks = jnp.zeros_like(m), []
    for first in range(0, e, 4):
        model = _model(dict(cfg, first_held_expert=first))
        part, got = model.experts(dict(shared, router=router, router_bias=bias, gate=gate[first:first + 4],
                                       up=up[first:first + 4], down=down[first:first + 4]), m)
        total, picks = total + part, picks + [np.asarray(got)]
    everyones = reference.swiglu(m, shared["shared_gate"], shared["shared_up"], shared["shared_down"], HOW)
    total = total - (e // 4 - 1) * everyones  # what every chip computes alike counts once
    assert np.concatenate(picks).sum() == n * 2
    np.testing.assert_array_equal(np.concatenate(picks), np.asarray(picks_whole))
    assert _gap(total, whole) < 3e-3


def test_the_shares_add_up_for_the_modules_layer():
    """The module's whole layer (rotated latent attention, then the expert
    layer) on a merged stream: what the 4 shares give, their common part (the
    stream, the attention and the shared expert, which every chip computes
    alike) counted once, sums to the uncut reference block's stream."""
    cfg = dict(TINY)
    rng = np.random.default_rng(8)
    d, f, e, t = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["router_width"], 32
    lo = jnp.asarray(np.stack([document_starts([[13, 19]], t).data[0]]))
    g = jnp.asarray(rng.standard_normal((1, t, d)), jnp.float32)
    router, gate, up, down, shared = _expert_leaves(rng, d, f, e)
    attn = {k: v for k, v in jax.tree.map(jnp.asarray, joyai_flash_weights.dense_tree(cfg, SEED)["after"][0]).items()
            if k in ("norm1", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo", "norm2")}
    attn = {k: v * (6.0 if v.ndim == 2 else 1.0) for k, v in attn.items()}  # products of deviation 0.12: the attention is no rounding of the stream
    rcfg = dict(reference._model_cfg(dict(cfg, n_routed_experts=e, first_held_expert=0, reference_query_block=16)))
    angle = reference.angles(lo, 64, 32e6)
    whole, picks_whole = reference.block(dict(attn, **shared, router=router, gate=gate, up=up, down=down),
                                         g, lo, angle, rcfg, HOW)
    total, picks = jnp.zeros_like(g), []
    for first in range(0, e, 4):
        model = _model(dict(cfg, first_held_expert=first))
        leaves = dict(attn, **shared, router=router, gate=gate[first:first + 4], up=up[first:first + 4],
                      down=down[first:first + 4])
        out, got = model.layer_after(0, {"after": (leaves,)}, g, model._side(lo),
                                     buffers={"router_bias": jnp.zeros((e,), jnp.float32)})
        total, picks = total + out, picks + [np.asarray(got)]
    # what every share computes alike: the stream after the attention, and the shared expert on it
    base = g + reference.latent_attention(attn, reference._rms(g, attn["norm1"], 1e-6), lo, angle, rcfg, HOW)
    everyones = base + reference.swiglu(reference._rms(base, attn["norm2"], 1e-6).reshape(t, d), shared["shared_gate"],
                                        shared["shared_up"], shared["shared_down"], HOW).reshape(1, t, d)
    total = total - (e // 4 - 1) * everyones
    assert np.concatenate(picks).sum() == t * 2
    np.testing.assert_array_equal(np.concatenate(picks), np.asarray(picks_whole))
    assert np.linalg.norm(base - g) > 0.1 * np.linalg.norm(g)
    assert _gap(total, whole) < 1e-2 and _gap(total - base, whole - base) < 3e-2


# ------------------------------------------------------------- from_config

def test_from_config_builds_the_published_towers_shapes():
    """The catalog row's keys as they are: 40 layers and the module (41
    blocks), 256 experts, 129,280 ids. Shapes only: no array is built."""
    model = JoyAIFlashMoE.from_config(CATALOG)
    shapes = model.param_shapes()
    assert (model.n_layers, model.n_scanned, model.leading_kinds, model.after_kinds) == (40, 39, ("mla",), ("mla",))
    assert (model.n_experts, model.n_held, model.first_held, model.experts_per_token) == (256, 256, 0, 8)
    assert (model.q_lora_rank, model.kv_lora_rank, model.head_dim, model.rope_head_dim) == (1536, 512, 128, 64)
    assert model.rope_theta == 32e6 and model.routed_scaling == 2.5 and model.rms_eps == 1e-6
    assert (model.mtp_depth, model.mtp_weight, model.dense_width, model.expert_width) == (1, 0.1, 7168, 768)
    assert shapes["head"] == (2048, 129280) and shapes["layers"]["gate"] == (39, 256, 2048, 768)
    assert shapes["layers"]["wq_a"] == (39, 2048, 1536) and shapes["layers"]["wq_b"] == (39, 1536, 32 * 192)
    assert shapes["layers"]["wkv_a"] == (39, 2048, 576) and shapes["layers"]["wkv_b"] == (39, 512, 32 * 256)
    assert shapes["lead"][0]["dense_gate"] == (2048, 7168) and "router" not in shapes["lead"][0]
    assert shapes["after"][0]["router"] == (2048, 256) and shapes["after"][0]["wo"] == (4096, 2048)
    assert shapes["mtp"] == {"norm_e": (2048,), "norm_h": (2048,), "merge": (4096, 2048), "norm_s": (2048,)}
    counters = jax.eval_shape(model.counters)
    assert counters["expert_picks"].shape == (40, 256) and counters["router_bias"].shape == (40, 256)
    assert counters["objective"].shape == (4,) and counters["attention_tiles"].shape == (2, 2)
    count = lambda tree: sum(int(np.prod(s)) for s in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple) and (not x or isinstance(x[0], int))))
    attention = 26_347_520
    assert count(shapes["lead"]) == attention + 44_040_192 + 4_096
    layer = attention + 524_288 + 257 * 4_718_592 + 4_096
    assert count(shapes["layers"]) == 39 * layer and count(shapes["after"]) == layer
    # four times an even router's load in whole tiles: a share's load reads up to 4.1 of even by the seed
    assert model.pick_chunk(16384) == 16384 * 8 and _model(TINY).pick_chunk(16384) == 4 * (16384 * 2 * 4 // 16)


@pytest.mark.parametrize("key,value,says", [
    ("n_group", 8, "one group"), ("topk_group", 4, "one group"), ("scoring_func", "softmax", "sigmoid"),
    ("topk_method", "greedy", "noaux_tc"), ("n_shared_experts", 2, "one shared expert"),
    ("rope_scaling", {"type": "yarn", "factor": 40}, "rope_scaling"), ("rope_interleave", False, "interleaved"),
    ("num_nextn_predict_layers", 2, "depth 1"), ("qk_nope_head_dim", 64, "one width"),
    ("moe_layer_freq", 2, "every layer"), ("tie_word_embeddings", True, "untied"),
])
def test_from_config_raises_on_what_it_does_not_run(key, value, says):
    with pytest.raises(ValueError, match=says):
        JoyAIFlashMoE.from_config(dict(CATALOG, **{key: value}))


@pytest.mark.parametrize("what", ["no_module", "full_rank_query", "no_rotation"])
def test_the_towers_own_arguments(what):
    """What the model's arguments state beyond the published config: a tower
    without the module keeps one objective and no ``after`` block; a full-rank
    query holds ``wq``; ``rope_theta=None`` runs without positions."""
    if what == "no_module":
        model = _model(dict(TINY, num_nextn_predict_layers=0))
        shapes = model.param_shapes()
        assert model.after_kinds == () and "mtp" not in shapes and "after" not in shapes
        assert model.counters()["objective"].shape == (2,) and model.counters()["expert_picks"].shape == (2, 4)
    elif what == "full_rank_query":
        model = _model(dict(TINY, q_lora_rank=None))
        assert model.attention_shapes("mla")["wq"] == (128, 2 * 192) and "wq_a" not in model.attention_shapes("mla")
    else:
        import dataclasses

        model = dataclasses.replace(_model(TINY), rope_theta=None)
        assert model._side(jnp.zeros((1, 16), jnp.int32))["mla"][1] is None
