"""The tower of gated delta-rule and latent-attention layers over packed
documents (``models/kimi_linear_moe.py``) on the CPU: against the plain
reference (``perf/reference/kimi_linear_moe.py``, whose delta rule is the
position-by-position recurrence) at a small size on seeded weights; the expert
shares, the shared expert counted once, against the uncut layer; the
delta-rule kernels (``ops/delta_rule.py``) forward and backward against the
recurrence for document starts on chunk edges, inside a chunk, two in one
chunk and a document shorter than the convolution; the convolution alone;
interval attention at widths 192/128 against dense softmax; sigmoid routing by
hand values. The Pallas kernels run in the interpreter, by this file's choice."""

import importlib
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import kimi_linear_weights  # noqa: E402
from perf.reference import kimi_linear_moe as reference  # noqa: E402
from persia_tpu import tracing  # noqa: E402
from persia_tpu.data import IDTypeFeature, Label, PersiaBatch, document_starts  # noqa: E402
from persia_tpu.embedding.optim import Adagrad  # noqa: E402
from persia_tpu.models import KimiLinearMoE  # noqa: E402
from persia_tpu.models.kimi_linear_moe import short_convolution  # noqa: E402
from persia_tpu.ops.delta_rule import (  # noqa: E402
    _chunk_parts, _prepare_fwd, kda, kda_recurrence, log_decay_floor, running_sums, unit_lower_inverse,
)
from persia_tpu.ops.flash_attention import heads_a_step, interval_attention, interval_tile_counts  # noqa: E402
from persia_tpu.parallel.fused_ctx import FusedTrainCtx  # noqa: E402
from persia_tpu.parallel.fused_step import (  # noqa: E402
    FusedSlotSpec, FusedTrainState, group_stacked_specs,
)

KERNELS = importlib.import_module("persia_tpu.ops.flash_attention")

# the leading layer and one period (kda/dense, kda, kda, mla, kda), 4 of 16 experts held, 2 a token
TINY = {
    "model_type": "kimi_linear", "hidden_size": 128, "num_attention_heads": 2, "num_key_value_heads": 2,
    "head_dim": 72, "v_head_dim": 128, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "kv_lora_rank": 64, "q_lora_rank": None, "mla_use_nope": True,
    "linear_attn_config": {"full_attn_layers": [4, 8], "head_dim": 128, "kda_layers": [1, 2, 3, 5, 6, 7],
                           "num_heads": 2, "short_conv_kernel_size": 4},
    "first_k_dense_replace": 1, "intermediate_size": 96, "moe_intermediate_size": 64,
    "num_experts": 4, "router_width": 16, "first_held_expert": 4, "num_experts_per_token": 2,
    "num_shared_experts": 1, "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
    "num_expert_group": 1, "routed_scaling_factor": 2.446,
    "num_hidden_layers": 5, "vocab_size": 97, "rms_norm_eps": 1e-5,
    "router_law": "plain", "reference_query_block": 16, "reference_state_block": 16,
    "sparse_optimizer": {"kind": "adagrad", "lr": 0.01, "initial_accumulator": 0.01, "eps": 1e-10},
    "dense_optimizer": {"kind": "adam", "lr": 1e-6, "b1": 0.9, "b2": 0.95, "eps": 1e-8},
}
SEED, BATCH, LENGTH = 2 ** 31 + 17, 2, 64
DOCS = np.array([[18, 5, 41], [41, 2, 21]], np.int32)  # three documents a sequence, one of 2 tokens


def _model(cfg, **kw):
    return KimiLinearMoE.from_config(cfg, **dict(
        {"head_chunk": 32, "tile": 16, "kda_chunk": 16, "interpret": True}, **kw))


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
    dense = reference.initial_dense(cfg, SEED)
    table = jnp.asarray(kimi_linear_weights.token_rows(cfg, SEED, np.arange(cfg["vocab_size"])))
    (gname,) = [g.name for g in group_stacked_specs(ctx.specs, ctx.slot_order)]
    ctx.state = FusedTrainState(
        params=jax.tree.map(jnp.copy, dense), batch_stats=model.counters(),
        opt_state=ctx.dense_optimizer.init(dense), tables={gname: table},
        emb_state={gname: {"acc": jnp.full(table.shape, so["initial_accumulator"], jnp.float32)}},
        emb_batch_state=jnp.ones((2,), jnp.float32), step=jnp.zeros((), jnp.int32))
    logits = model.apply({"params": dense, "batch_stats": model.counters()},
                         [jnp.asarray(document_starts(b["doc_lengths"], LENGTH).data)],
                         [(table[b["ids"]], None)])
    rows_u = table[np.unique(b["ids"])]
    inv = np.unique(b["ids"], return_inverse=True)[1].reshape(b["ids"].shape)
    h_ref, _ = reference.hidden(dense, rows_u[inv], jnp.asarray(reference.document_starts(DOCS, LENGTH)),
                                dict(reference._model_cfg(cfg)), (8, 7))
    logits_ref = reference._product("btd,dv->btv", reference._rms(h_ref, dense["norm_f"], 1e-5),
                                    dense["head"], (8, 7))
    out = ctx.train_step(_persia_batch(b))
    paths = [e["attrs"] for e in tracing.flight_snapshot() if e["kind"] == "kimi_linear.paths"]
    ref = reference.Reference(cfg, SEED, lambda keys: kimi_linear_weights.token_rows(
        cfg, SEED, np.asarray(keys, np.int64)), how=(8, 7))
    keys = b["ids"].astype(np.uint64)
    loss_ref = ref.step(b, keys)
    return {"cfg": cfg, "out": out, "state": ctx.state, "table": np.asarray(ctx.state.tables[gname]),
            "ref": ref, "loss_ref": loss_ref, "uniq": np.unique(keys), "logits": (logits, logits_ref),
            "dense0": reference.leaves_by_name(dense, cfg), "paths": paths, "model": model}


def _gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


@pytest.mark.parametrize("what", ["loss", "logits", "gradient_by_leaf", "change_by_leaf", "rows", "picks",
                                  "tiles", "buffers", "paths", "leaves"])
def test_tower_against_the_reference(one_step, what):
    s, ref, cfg = one_step, one_step["ref"], one_step["cfg"]
    b1 = cfg["dense_optimizer"]["b1"]
    if what == "loss":
        assert abs(s["out"]["loss"] - s["loss_ref"]) <= 3e-4 * abs(s["loss_ref"])
        assert 3.0 < s["loss_ref"] < 6.0  # ln(97) = 4.57 at the start
    elif what == "logits":
        mine, theirs = s["logits"]
        assert mine.shape == (BATCH, LENGTH, cfg["vocab_size"]) and _gap(mine, theirs) < 3e-2  # bfloat16 operands through five layers 128 wide
    elif what == "gradient_by_leaf":  # Adam's first moment after one step is (1 - b1) x the gradient
        mine = reference.leaves_by_name(s["state"].opt_state[0].mu, cfg)
        theirs = reference.leaves_by_name(ref.m, cfg)
        assert set(mine) == set(kimi_linear_weights.leaf_names(cfg))
        for name in theirs:
            assert np.linalg.norm(theirs[name]) > 0, name
            # one pick of 128 that falls on the other side moves a router's gradient by a tenth
            assert _gap(mine[name] / (1 - b1), theirs[name] / (1 - b1)) < (0.25 if "router" in name else 0.1), name
    elif what == "change_by_leaf":
        mine = reference.leaves_by_name(s["state"].params, cfg)
        theirs = reference.leaves_by_name(ref.dense, cfg)
        for name, start in s["dense0"].items():
            a, b = np.linalg.norm(mine[name] - start), np.linalg.norm(theirs[name] - start)
            assert b > 0 and abs(a - b) < 0.02 * b, name
    elif what == "rows":
        rows, _ = ref.lookup(s["uniq"])
        start = kimi_linear_weights.token_rows(cfg, SEED, s["uniq"].astype(np.int64))
        assert _gap(s["table"][s["uniq"].astype(np.int64)] - start, rows - start) < 0.03
    elif what == "picks":  # the four expert layers by held expert; the leading layer routes nothing
        picks = np.asarray(s["state"].batch_stats["expert_picks"])
        assert picks.shape == (4, 4) and np.abs(picks - ref.picks).sum() <= 0.03 * ref.picks.sum()
        assert picks.sum() > 0
    elif what == "tiles":  # the one latent layer's visited and live tile pairs a head
        tiles = np.asarray(s["state"].batch_stats["attention_tiles"])
        lo = jnp.asarray(document_starts(DOCS, LENGTH).data)
        np.testing.assert_array_equal(tiles, [[0, 0], np.asarray(interval_tile_counts(lo, None, 16))])
    elif what == "buffers":  # the selection bias: zeros that the step leaves alone
        for kind, count in (("kda", 3), ("mla", 1)):
            bias = np.asarray(s["state"].batch_stats["router_bias"][kind])
            assert bias.shape == (count, 16) and not bias.any()
    elif what == "paths":
        said = s["paths"][-1]
        assert said["kda"] == "pallas_chunk_scan" and said["kda_chunk"] == "16"
        assert said["latent_attention"] == "pallas_interval_two_products" and said["tile"] == "16"
        assert said["experts"] == "pallas_grouped"
        assert said["kda_backward_keeps"] == "chunk_states_float32+chunk_inverse_float32"
        # both latent heads a grid step, a call's grid the triangle of 4 tiles a sequence
        assert (said["attention_heads_a_step"], said["attention_grid_steps"]) == ("2", str(2 * 10))
    else:  # the tower's leaves are the weights file's, shape for shape
        shapes = jax.tree.map(lambda x: x.shape, s["state"].params)
        want = jax.tree.map(lambda s: tuple(s), s["model"].param_shapes(),
                            is_leaf=lambda x: isinstance(x, tuple) and (not x or isinstance(x[0], int)))
        assert shapes == want


def test_the_shares_add_up():
    """The parts of one expert layer's result that the 4 shares (0, 4) .. (12,
    4) give, the shared expert counted once, sum to what the uncut reference
    layer gives: 16 experts, 2 a token."""
    cfg = dict(TINY)
    rng = np.random.default_rng(5)
    d, f, n, e = cfg["hidden_size"], cfg["moe_intermediate_size"], 64, cfg["router_width"]
    m = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((d, e)) * 0.2, jnp.float32)
    gate, up = (jnp.asarray(rng.standard_normal((e, d, f)) * 0.1, jnp.float32) for _ in range(2))
    down = jnp.asarray(rng.standard_normal((e, f, d)) * 0.1, jnp.float32)
    shared = {"shared_gate": gate[0] * 0.7, "shared_up": up[1] * 0.7, "shared_down": down[2] * 0.7}
    whole, picks_whole = reference.expert_layer(
        dict(shared, router=router, gate=gate, up=up, down=down), m,
        {"k": 2, "held": e, "first": 0, "scaling": 2.446}, (8, 7))
    bias = jnp.zeros((e,), jnp.float32)
    total, picks = jnp.zeros_like(m), []
    for first in range(0, e, 4):
        model = _model(dict(cfg, first_held_expert=first))
        part, got = model.experts(dict(shared, router=router, router_bias=bias, gate=gate[first:first + 4],
                                       up=up[first:first + 4], down=down[first:first + 4]), m)
        total, picks = total + part, picks + [np.asarray(got)]
    everyones = reference.swiglu(m, shared["shared_gate"], shared["shared_up"], shared["shared_down"], (8, 7))
    total = total - (e // 4 - 1) * everyones  # what every chip computes alike counts once
    assert np.concatenate(picks).sum() == n * 2
    np.testing.assert_array_equal(np.concatenate(picks), np.asarray(picks_whole))
    assert _gap(total, whole) < 3e-3


def test_sigmoid_routing_by_hand():
    """Scores are sigmoids; the bias moves the selection and not the weights;
    the picked scores are renormalised and scaled by 2.446."""
    model = _model(dict(TINY, router_width=4, num_experts=4, first_held_expert=0))
    logits = np.array([[2.0, 1.0, 0.0, -1.0]], np.float32)
    m = jnp.eye(1, 4, dtype=jnp.float32) * 0 + jnp.asarray([[1.0, 0, 0, 0]])
    router = jnp.zeros((4, 4)).at[0].set(logits[0])
    sc = 1 / (1 + np.exp(-logits[0].astype(np.float64)))
    w, e = model.route({"router": router, "router_bias": jnp.zeros(4)}, m)
    assert sorted(np.asarray(e)[0]) == [0, 1]
    np.testing.assert_allclose(np.sort(np.asarray(w)[0])[::-1], 2.446 * sc[:2] / sc[:2].sum(), rtol=4e-3)
    w, e = model.route({"router": router, "router_bias": jnp.asarray([0.0, 0.0, 0.0, 1.0])}, m)
    assert sorted(np.asarray(e)[0]) == [0, 3]  # 0.269 + 1 passes 0.731
    got = dict(zip(np.asarray(e)[0].tolist(), np.asarray(w)[0].tolist()))
    np.testing.assert_allclose([got[0], got[3]], 2.446 * sc[[0, 3]] / sc[[0, 3]].sum(), rtol=4e-3)
    grad = jax.grad(lambda b: jnp.sum(model.route({"router": router, "router_bias": b}, m)[0]))(jnp.zeros(4))
    assert not np.asarray(grad).any()


# ------------------------------------------------------------- the kernels

def _starts(*lengths):
    return np.stack([document_starts([row], LENGTH).data[0] for row in lengths])


# where documents start against chunks of 16: one document; starts on chunk edges; inside a chunk;
# two in one chunk; a document shorter than the convolution's 4 taps
KDA_CASES = {
    "one_document": _starts([64]),
    "chunk_edges": _starts([16, 32, 16]),
    "inside_a_chunk": _starts([21, 43]),
    "two_in_one_chunk": _starts([18, 3, 6, 37]),
    "shorter_than_the_convolution": _starts([30, 2, 1, 31]),
}


def _kda_inputs(seed=3, heads=2):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    shape = (1, LENGTH, heads, 128)
    q = jnp.asarray(unit(rng.standard_normal(shape)) / np.sqrt(128), jnp.float32)
    k = jnp.asarray(unit(rng.standard_normal(shape)), jnp.float32)
    v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    g = jnp.asarray(-np.exp(rng.uniform(np.log(1e-3), np.log(1.6), shape)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.1, 0.9, shape[:3]), jnp.float32)
    return q, k, v, g, beta


@pytest.fixture(scope="module", params=sorted(KDA_CASES))
def kda_case(request):
    lo = jnp.asarray(KDA_CASES[request.param])
    args = _kda_inputs()
    ct = jnp.asarray(np.random.default_rng(9).standard_normal(args[2].shape), jnp.float32)
    mine = lambda *a: kda(*a, lo, chunk=16, interpret=True)
    theirs = lambda *a: kda_recurrence(*a, lo)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * ct), argnums=(0, 1, 2, 3, 4))(*args)
    return {"forward": (mine(*args), theirs(*args)),
            **{n: pair for n, pair in zip(("dq", "dk", "dv", "dg", "dbeta"), zip(grads(mine), grads(theirs)))}}


@pytest.mark.parametrize("what", ["forward", "dq", "dk", "dv", "dg", "dbeta"])
def test_delta_rule_against_the_recurrence(kda_case, what):
    mine, theirs = kda_case[what]
    assert np.isfinite(np.asarray(mine)).all()
    assert _gap(mine, theirs) < 1.5e-2  # bfloat16 operands against float32 at highest


def test_delta_rule_forgets_at_a_document_start():
    """A position reads no state of another document: what comes before a
    start moves nothing after it, to the bit."""
    q, k, v, g, beta = _kda_inputs()
    lo = jnp.asarray(KDA_CASES["two_in_one_chunk"])
    other = v.at[:, :18].set(7.0)
    a, b = (np.asarray(kda(q, k, x, g, beta, lo, chunk=16, interpret=True)) for x in (v, other))
    np.testing.assert_array_equal(a[:, 18:], b[:, 18:])
    assert np.abs(a[:, :18] - b[:, :18]).max() > 0.1


@pytest.mark.parametrize("decay", [-1.6, log_decay_floor(64)])
def test_delta_rule_survives_the_strongest_decay(decay):
    """64 positions at the initial law's strongest decay (e^-1.6 a step) and at
    the floor the tower holds a learned one to (e^-2.5): the chunk of 64's
    factors about its middle stay finite and nothing is cut off."""
    q, k, v, g, beta = _kda_inputs()
    g = jnp.full_like(g, decay)
    lo = jnp.zeros((1, LENGTH), jnp.int32)
    mine, theirs = kda(q, k, v, g, beta, lo, chunk=64, interpret=True), kda_recurrence(q, k, v, g, beta, lo)
    assert np.isfinite(np.asarray(mine)).all() and _gap(mine, theirs) < 1.5e-2
    # the pairs no position reads overflow there (e^51 x e^51): no gradient may meet them
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) ** 2), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    mine, theirs = grads(lambda *a: kda(*a, lo, chunk=64, interpret=True)), grads(lambda *a: kda_recurrence(*a, lo))
    for name, a, b in zip("qkvgb", mine, theirs):  # g's own gradient is round-off there: every state is gone in three steps
        assert np.isfinite(np.asarray(a)).all() and (name == "g" or _gap(a, b) < 3e-2), name


@pytest.mark.parametrize("what", ["value", "gradient"])
@pytest.mark.parametrize("c", [2, 4, 16, 64])
def test_unit_lower_inverse(c, what):
    """Against ``jnp.linalg.inv``; at 2 the product-free first level is the
    only level. Entries of deviation 1 up to 16 and 0.25 at 64, where the
    inverse of a random triangle of deviation 1 has entries of 1e8 (a
    chunk's own are ``beta k_i . k_j`` with a decay, under 1)."""
    rng = np.random.default_rng(1)
    a = jnp.asarray(np.tril(rng.standard_normal((3, c, c)), -1) * min(1.0, 16 / c), jnp.float32)
    inverse = jax.vmap(unit_lower_inverse)  # the kernels' own, a chunk's (C, C) at a time
    if what == "value":
        t = inverse(a)
        np.testing.assert_allclose(np.asarray(t @ (jnp.eye(c) + a)), np.broadcast_to(np.eye(c), a.shape), atol=2e-4)
        if c == 2:
            np.testing.assert_array_equal(np.asarray(t), np.asarray(jnp.eye(2) - a))
    else:
        ct = jnp.asarray(rng.standard_normal(a.shape), jnp.float32)
        mine = jax.grad(lambda a: jnp.sum(inverse(a) * ct))(a)
        theirs = jax.grad(lambda a: jnp.sum(jnp.linalg.inv(jnp.eye(c) + jnp.tril(a, -1)) * ct))(a)
        assert _gap(mine, theirs) < 1e-3


@pytest.mark.parametrize("reverse", [False, True])
@pytest.mark.parametrize("c", [16, 64])
def test_running_sums_in_three_passes(c, reverse):
    """The triangle against three bfloat16 parts of ``g`` is a float32 sum:
    against a float64 ``cumsum`` over the decays' whole range."""
    g = -np.random.default_rng(c).uniform(1e-3, 2.5, (c, 128)).astype(np.float32)
    want = np.cumsum(g[::-1].astype(np.float64), axis=0)[::-1] if reverse else np.cumsum(g.astype(np.float64), axis=0)
    np.testing.assert_allclose(np.asarray(running_sums(jnp.asarray(g), reverse=reverse)), want, rtol=1e-6)


def test_the_forward_kernel_writes_the_inverse_the_backward_reads():
    """``T`` of ``kda_prepare_fwd`` is ``unit_lower_inverse(pairs * beta)``
    made outside it, chunk by chunk, with two documents starting inside a
    chunk; and it is float32."""
    q, k, v, g, beta = _kda_inputs()
    lo, chunk = jnp.asarray(KDA_CASES["two_in_one_chunk"]), 16
    operands, t_inv = _prepare_fwd(q, k, v, g, beta, lo, chunk, True)
    assert t_inv.shape == (1, 2, LENGTH // chunk, chunk, chunk) and t_inv.dtype == jnp.float32
    assert len(operands) == 6 and operands[3].shape == t_inv.shape  # P beside it
    for head in range(2):
        for n in range(LENGTH // chunk):
            rows = slice(n * chunk, (n + 1) * chunk)
            b, l = beta[0, rows, head, None], lo[0, rows]
            parts = _chunk_parts(q[0, rows, head], k[0, rows, head], g[0, rows, head], b, l[:, None], l[None, :],
                                 n * chunk, t_inv=0.0)  # everything but the inverse
            want = unit_lower_inverse(parts["pairs"] * b)
            np.testing.assert_allclose(np.asarray(t_inv[0, head, n]), np.asarray(want), rtol=1e-6, atol=1e-7)
    assert np.abs(np.asarray(t_inv) - np.eye(chunk)).max() > 1e-2  # and not the identity


@pytest.mark.parametrize("case", ["inside", "at_the_start", "shorter_than_the_taps"])
def test_short_convolution_stops_at_a_document_start(case):
    rng = np.random.default_rng(2)
    x = rng.standard_normal((1, 12, 3)).astype(np.float32)
    taps = rng.standard_normal((4, 3)).astype(np.float32)
    lengths = {"inside": [12], "at_the_start": [5, 7], "shorter_than_the_taps": [5, 2, 5]}[case]
    starts = np.repeat(np.cumsum([0] + lengths[:-1]), lengths)[None].astype(np.int32)
    want = np.zeros_like(x)
    for i in range(12):
        for t in range(4):
            if i - t >= starts[0, i]:
                want[0, i] += taps[t] * x[0, i - t]
    want = want / (1 + np.exp(-want))
    got = short_convolution(jnp.asarray(x), jnp.asarray(taps), jnp.asarray(starts))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(reference.convolution(jnp.asarray(x), jnp.asarray(taps),
                                                                jnp.asarray(starts))), want, rtol=1e-5, atol=1e-6)


def _dense_latent_attention(q, k, k_shared, v, lo):
    """Scores 192 wide, values 128: query i reads keys lo_i .. i, in plain jax.numpy."""
    b, t, h, _ = q.shape
    kk = jnp.concatenate([k, jnp.broadcast_to(k_shared[:, :, None, :], (b, t, h, k_shared.shape[-1]))], axis=-1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk, precision="highest") / np.sqrt(q.shape[-1])
    at = jnp.arange(t)
    mask = (at[None, None, :] >= lo[:, :, None]) & (at[None, None, :] <= at[None, :, None])
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


# the mask's case, and how q's further columns come: sliced from a 192-wide q, or head-major
# beside it (``q_shared``, as the rotated-latent tower hands them over); 4 heads a grid step
LATENT_CASES = ["causal", "documents", "documents-q_shared"]


def _latent_case(name):
    """((q, k, k_shared, v), the kernels' attention, dense attention, the
    gradients of a fixed projection of either)."""
    case, _, form = name.partition("-")
    lo = jnp.asarray({"causal": np.zeros((2, LENGTH), np.int32),
                      "documents": _starts([18, 5, 41], [9, 3, 2, 50])}[case])
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((2, LENGTH, 4, 192)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, LENGTH, 4, 128)), jnp.float32) for _ in range(2))
    shared = jnp.asarray(rng.standard_normal((2, LENGTH, 64)), jnp.float32)
    ct = jnp.asarray(rng.standard_normal(v.shape), jnp.float32)
    if form == "q_shared":
        mine = lambda q, k, s, v: interval_attention(q[..., :128], k, v, lo, tile=16, interpret=True, k_shared=s,
                                                     q_shared=jnp.swapaxes(q[..., 128:], 1, 2))
    else:
        mine = lambda q, k, s, v: interval_attention(q, k, v, lo, tile=16, interpret=True, k_shared=s)
    theirs = lambda q, k, s, v: _dense_latent_attention(q, k, s, v, lo)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * ct), argnums=(0, 1, 2, 3))(q, k, shared, v)
    return (q, k, shared, v), mine, theirs, grads


@pytest.fixture(scope="module", params=LATENT_CASES)
def latent_case(request):
    args, mine, theirs, grads = _latent_case(request.param)
    return {"forward": (mine(*args), theirs(*args)),
            **{n: pair for n, pair in zip(("dq", "dk", "dk_shared", "dv"), zip(grads(mine), grads(theirs)))}}


@pytest.mark.parametrize("what", ["forward", "dq", "dk", "dk_shared", "dv"])
def test_interval_attention_at_two_widths_against_dense(latent_case, what):
    mine, theirs = latent_case[what]
    assert mine.shape == theirs.shape and _gap(mine, theirs) < 2e-5


@pytest.fixture(scope="module", params=LATENT_CASES[2:])
def latent_step_case(request):
    """The kernels' output and gradients with 4 heads a grid step and with
    one, the heads unrolled (``_each_head``: in the interpreter only so are
    they bit for bit)."""
    assert heads_a_step(4, 4, 128, 16, jnp.float32, shared=64) == 4
    args, mine, _, grads = _latent_case(request.param)
    run = lambda: dict(zip(("forward", "dq", "dk", "dk_shared", "dv"), (mine(*args), *grads(mine))))
    with mock.patch.object(KERNELS, "_each_head", lambda n, fn: [fn(j) for j in range(n)]):
        several = run()
        with mock.patch.object(KERNELS, "_HEADS_A_STEP_MAX", 1):
            return several, run()


@pytest.mark.parametrize("what", ["forward", "dq", "dk", "dk_shared", "dv"])
def test_two_widths_step_several_heads_as_one_bit_for_bit(latent_step_case, what):
    """4 query heads a grid step give what a grid of one head a step gives:
    the second product's operands a head and the shared columns' sums a K/V
    head included."""
    several, one = latent_step_case
    np.testing.assert_array_equal(np.asarray(several[what]), np.asarray(one[what]))


def test_from_config_reads_the_published_pattern():
    model = _model(TINY)
    assert model.leading_kinds == ("kda",) and model.layer_kinds == ("kda", "kda", "mla", "kda")
    assert (model.n_experts, model.n_held, model.first_held, model.experts_per_token) == (16, 4, 4, 2)
    assert model.pick_chunk(16384) == 2 * (16384 * 2 * 4 // 16)  # twice the even load, whole tiles
    with pytest.raises(ValueError, match="whole periods"):
        KimiLinearMoE.from_config(dict(TINY, num_hidden_layers=4))
