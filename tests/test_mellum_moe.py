"""The causal mixture-of-experts tower over packed documents
(``models/mellum_moe.py``), the tower it shares with the block-diffusion one
(``models/moe_tower.py``) and the interval attention kernels
(``ops/flash_attention.py``) on the CPU: against the plain reference
(``perf/reference/mellum_moe.py``) at a small size on seeded weights, the
expert shares against the uncut layer, the kernels against dense
``jax.numpy``, the RoPE tables against hand values, the chunked head and loss
against whole logits. The Pallas kernels run in the interpreter, by this
file's choice."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import mellum_weights  # noqa: E402
from perf.reference import mellum_moe as reference  # noqa: E402
from persia_tpu import tracing  # noqa: E402
from persia_tpu.data import (  # noqa: E402
    IDTypeFeature, Label, NonIDTypeFeature, PersiaBatch, document_starts,
)
from persia_tpu.embedding.optim import Adagrad  # noqa: E402
from persia_tpu.models import MellumMoE, SDARMoE  # noqa: E402
from persia_tpu.models.mellum_moe import YARN, rope_frequencies  # noqa: E402
from persia_tpu.ops.flash_attention import (  # noqa: E402
    _DEAD, _LE, _LO, _WHOLE, _interval_kinds, _interval_lo, _interval_tables, interval_attention,
    interval_tile_counts, interval_visits,
)
from persia_tpu.parallel.fused_ctx import FusedTrainCtx, batch_to_fused  # noqa: E402
from persia_tpu.parallel.fused_step import (  # noqa: E402
    FusedSlotSpec, FusedTrainState, group_stacked_specs,
)

# one period (three sliding layers, one full), 4 of 8 experts held, 2 a token, window 8, L 64
TINY = {
    "hidden_size": 128, "head_dim": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
    "moe_intermediate_size": 64, "num_experts": 4, "router_width": 8, "first_held_expert": 2,
    "num_experts_per_tok": 2, "num_hidden_layers": 4, "vocab_size": 97, "rms_norm_eps": 1e-6,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"], "sliding_window": 8,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 4,
                           "original_max_position_embeddings": 32, "beta_fast": 4, "beta_slow": 1,
                           "attention_factor": 1.1386},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "reference_query_block": 16,
    "sparse_optimizer": {"kind": "adagrad", "lr": 0.01, "initial_accumulator": 0.01, "eps": 1e-10},
    "dense_optimizer": {"kind": "adam", "lr": 1e-6, "b1": 0.9, "b2": 0.95, "eps": 1e-8},
}
SEED, BATCH, LENGTH = 2 ** 31 + 11, 2, 64
DOCS = np.array([[18, 5, 41], [41, 18, 5]], np.int32)  # three documents a sequence


def _model(cfg, **kw):
    return MellumMoE.from_config(cfg, **dict({"head_chunk": 32, "tile": 16, "interpret": True}, **kw))


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
    table = jnp.asarray(mellum_weights.token_rows(cfg, SEED, np.arange(cfg["vocab_size"])))
    (gname,) = [g.name for g in group_stacked_specs(ctx.specs, ctx.slot_order)]
    ctx.state = FusedTrainState(
        params=jax.tree.map(jnp.copy, dense), batch_stats=model.counters(),
        opt_state=ctx.dense_optimizer.init(dense), tables={gname: table},
        emb_state={gname: {"acc": jnp.full(table.shape, so["initial_accumulator"], jnp.float32)}},
        emb_batch_state=jnp.ones((2,), jnp.float32), step=jnp.zeros((), jnp.int32))
    out = ctx.train_step(_persia_batch(b))
    paths = [e["attrs"] for e in tracing.flight_snapshot() if e["kind"] == "mellum_moe.paths"]
    ref = reference.Reference(cfg, SEED, lambda keys: mellum_weights.token_rows(
        cfg, SEED, np.asarray(keys, np.int64)), how=(8, 7))
    keys = b["ids"].astype(np.uint64)
    loss_ref = ref.step(b, keys)
    return {"cfg": cfg, "out": out, "state": ctx.state, "table": np.asarray(ctx.state.tables[gname]),
            "acc": np.asarray(ctx.state.emb_state[gname]["acc"]), "ref": ref, "loss_ref": loss_ref,
            "uniq": np.unique(keys), "dense0": reference.leaves_by_name(dense), "paths": paths}


def _gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


@pytest.mark.parametrize("what", ["loss", "outputs", "gradient_by_leaf", "change_by_leaf", "rows",
                                  "accumulators", "picks", "tiles", "paths"])
def test_tower_against_the_reference(one_step, what):
    s, ref, cfg = one_step, one_step["ref"], one_step["cfg"]
    b1 = cfg["dense_optimizer"]["b1"]
    if what == "loss":
        assert abs(s["out"]["loss"] - s["loss_ref"]) <= 2e-4 * abs(s["loss_ref"])
        assert 3.0 < s["loss_ref"] < 6.0  # ln(97) = 4.57 at the start
    elif what == "outputs":  # the model's own: an id a position, no sigmoid of the logits
        assert s["out"]["preds"].shape == (BATCH, LENGTH) and s["out"]["preds"].dtype == np.int32
    elif what == "gradient_by_leaf":  # Adam's first moment after one step is (1 - b1) x the gradient
        mine = reference.leaves_by_name(s["state"].opt_state[0].mu)
        theirs = reference.leaves_by_name(ref.m)
        assert set(mine) == set(mellum_weights.leaf_names(cfg)) and len(mine) == 4 * 12 + 2
        for name in theirs:
            assert np.linalg.norm(theirs[name]) > 0, name
            assert _gap(mine[name] / (1 - b1), theirs[name] / (1 - b1)) < 0.1, name  # a flipped pick moves an expert's leaf by percents at 128 tokens
    elif what == "change_by_leaf":
        mine = reference.leaves_by_name(s["state"].params)
        theirs = reference.leaves_by_name(ref.dense)
        for name, start in s["dense0"].items():
            assert np.linalg.norm(theirs[name] - start) > 0, name
            # Adam's first step is lr x sign(g), element by element: the norms are compared
            a, b = np.linalg.norm(mine[name] - start), np.linalg.norm(theirs[name] - start)
            assert abs(a - b) < 0.02 * b, name
    elif what == "rows":
        rows, _ = ref.lookup(s["uniq"])
        start = mellum_weights.token_rows(cfg, SEED, s["uniq"].astype(np.int64))
        assert _gap(s["table"][s["uniq"].astype(np.int64)] - start, rows - start) < 0.03
        untouched = np.setdiff1d(np.arange(cfg["vocab_size"]), s["uniq"])
        np.testing.assert_array_equal(s["table"][untouched],
                                      mellum_weights.token_rows(cfg, SEED, untouched))
    elif what == "accumulators":
        _, acc = ref.lookup(s["uniq"])
        assert _gap(s["acc"][s["uniq"].astype(np.int64)] - 0.01, acc - 0.01) < 0.05
    elif what == "picks":
        picks = np.asarray(s["state"].batch_stats["expert_picks"])
        assert picks.shape == (4, 4) and np.abs(picks - ref.picks).sum() <= 0.03 * ref.picks.sum()
        assert picks.sum() > 0
    elif what == "tiles":  # visited and live tile pairs a head, three sliding layers and one full
        tiles = np.asarray(s["state"].batch_stats["attention_tiles"])
        lo = jnp.asarray(document_starts(DOCS, LENGTH).data)
        sliding, full = (np.asarray(interval_tile_counts(lo, w, 16)) for w in (8, None))
        np.testing.assert_array_equal(tiles, [3 * sliding, full])
        assert (tiles[:, 0] == tiles[:, 1]).all() and tiles[0, 0] // 3 < tiles[1, 0]
    else:  # what the entry prints to stderr
        said = s["paths"][-1]
        assert said["attention"] == "pallas_interval" and said["experts"] == "pallas_grouped"
        # the grouped products' (row, K, N) tiles, gate and up's then down's, for this length's chunk
        chunk = int(said["pick_chunk"])
        assert said["experts_tile"] == "{0}x128x64/{0}x64x128".format(min(512, -(-chunk // 16) * 16))
        assert (said["window"], said["tile"], said["head_chunk"], said["seq_len"]) == ("8", "16", "32", "64")
        assert (said["grid_sliding"], said["grid_full"]) == (str(1 + 2 * 3), str(10))
        # q/k norm, RoPE and the cast as one pass, a sequence's positions one block
        assert (said["qk_prep"], said["qk_prep_tile"]) == ("pallas_rows", str(LENGTH))


def test_the_shares_add_up():
    """The parts of one layer's result that the 4 shares (0, 16) .. (48, 16)
    give sum to what the uncut reference layer gives: 64 experts, 8 a token."""
    cfg = dict(TINY, router_width=64, num_experts_per_tok=8, num_experts=16)
    rng = np.random.default_rng(5)
    d, f, n = cfg["hidden_size"], cfg["moe_intermediate_size"], 64
    m = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((d, 64)) * 0.2, jnp.float32)
    gate, up = (jnp.asarray(rng.standard_normal((64, d, f)) * 0.1, jnp.float32) for _ in range(2))
    down = jnp.asarray(rng.standard_normal((64, f, d)) * 0.1, jnp.float32)
    whole, picks_whole = reference.expert_layer(
        {"router": router, "gate": gate, "up": up, "down": down}, m,
        {"k": 8, "held": 64, "first": 0}, (8, 7))
    total, picks = jnp.zeros_like(m), []
    for first in range(0, 64, 16):
        model = _model(dict(cfg, first_held_expert=first))
        part, got = model.experts({"router": router, "gate": gate[first:first + 16],
                                   "up": up[first:first + 16], "down": down[first:first + 16]}, m)
        total, picks = total + part, picks + [np.asarray(got)]
    assert np.concatenate(picks).sum() == n * 8  # every pick is some share's
    np.testing.assert_array_equal(np.concatenate(picks), np.asarray(picks_whole))
    assert _gap(total, whole) < 2e-3


# ------------------------------------------------------------- the kernels

def _dense_attention(q, k, v, lo, window):
    """Query i reads keys max(lo_i, i - window + 1) .. i, in plain jax.numpy."""
    b, t, hq, d = q.shape
    group = hq // k.shape[2]
    kk, vv = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk, precision="highest") / np.sqrt(d)
    at = jnp.arange(t)
    lo = lo if window is None else jnp.maximum(lo, at[None, :] - window + 1)
    mask = (at[None, None, :] >= lo[:, :, None]) & (at[None, None, :] <= at[None, :, None])
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vv, precision="highest")


def _starts(*lengths):
    return np.stack([document_starts([row], LENGTH).data[0] for row in lengths])


# lo and the window: a causal sequence, a window, packed documents (one that starts and ends
# inside the second tile of 16, and later tiles that must skip the first), both together
ATTENTION_CASES = {
    "causal": (np.zeros((2, LENGTH), np.int32), None),
    "window": (np.zeros((2, LENGTH), np.int32), 20),
    "documents": (_starts([18, 5, 41], [9, 3, 2, 50]), None),
    "documents_and_window": (_starts([18, 5, 41], [40, 3, 21]), 12),
}


@pytest.fixture(scope="module", params=sorted(ATTENTION_CASES))
def attention_case(request):
    lo, window = ATTENTION_CASES[request.param]
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((2, LENGTH, 32, 128)), jnp.float32)  # 32 query heads
    k, v = (jnp.asarray(rng.standard_normal((2, LENGTH, 4, 128)), jnp.float32) for _ in range(2))  # over 4
    ct = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    lo = jnp.asarray(lo)
    mine = lambda q, k, v: interval_attention(q, k, v, lo, window=window, tile=16, interpret=True)
    theirs = lambda q, k, v: _dense_attention(q, k, v, lo, window)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * ct), argnums=(0, 1, 2))(q, k, v)
    return {"forward": (mine(q, k, v), theirs(q, k, v)),
            **{n: pair for n, pair in zip(("dq", "dk", "dv"), zip(grads(mine), grads(theirs)))}}


@pytest.mark.parametrize("what", ["forward", "dq", "dk", "dv"])
def test_interval_attention_against_dense(attention_case, what):
    mine, theirs = attention_case[what]
    assert float(jnp.max(jnp.abs(mine - theirs))) < 2e-5 * max(1.0, float(jnp.max(jnp.abs(theirs))))


@pytest.mark.parametrize("case", sorted(ATTENTION_CASES))
def test_a_tile_pairs_kind_comes_from_the_batch(case):
    """Dead, whole, cut by the diagonal alone, cut by ``lo``: from the q tile's
    least and greatest ``lo``, and exactly the pairs that hold a live pair."""
    lo, window = ATTENTION_CASES[case]
    lo = _interval_lo(jnp.asarray(lo), window)
    kinds = np.asarray(_interval_kinds(lo, 16))
    at = np.arange(LENGTH)
    live = (at[None, None, :] >= np.asarray(lo)[:, :, None]) & (at[None, None, :] <= at[None, :, None])
    by_tile = live.reshape(2, 4, 16, 4, 16)
    holds, full = by_tile.any(axis=(2, 4)), by_tile.all(axis=(2, 4))
    np.testing.assert_array_equal(kinds != _DEAD, holds)  # no dead pair visited, no live one skipped
    np.testing.assert_array_equal(kinds == _WHOLE, full)
    visited, counted = interval_tile_counts(jnp.asarray(ATTENTION_CASES[case][0]), window, 16)
    assert int(visited) == int(counted) == holds.sum()
    if case == "causal":
        assert (kinds[:, np.arange(4), np.arange(4)] == _LE).all() and (kinds != _LO).all()
    if case == "documents":  # [18, 5, 41]: the document 18..22 lies inside tile 1; tile 3 skips tile 0
        assert kinds[0, 1, 1] == _LO and kinds[0, 3, 0] == _DEAD and kinds[0, 3, 1] == _LO
        assert kinds[0, 3, 2] == _WHOLE and kinds[0, 3, 3] == _LE
    # the visit lists: the live pairs first, in row-major order, then dead steps on the last pair's blocks
    visits = interval_visits(4, 16, window)
    rows, cols, kind, first, last = (np.asarray(x).reshape(2, visits) for x in _interval_tables(
        jnp.asarray(kinds), visits))
    for b in range(2):
        n = int(holds[b].sum())
        want = np.argwhere(holds[b])
        np.testing.assert_array_equal(np.stack([rows[b, :n], cols[b, :n]], axis=1), want)
        np.testing.assert_array_equal(kind[b, :n], kinds[b][holds[b]])
        assert (kind[b, n:] == _DEAD).all() and (rows[b, n:] == want[-1, 0]).all()
        assert (cols[b, n:] == want[-1, 1]).all() and not first[b, n:].any() and not last[b, n:].any()
        assert first[b, :n].sum() == last[b, :n].sum() == 4  # once a q tile


def test_the_grid_covers_the_worst_case():
    assert interval_visits(32, 512) == 528  # the triangle of 32 tiles
    assert interval_visits(32, 512, 1024) == 1 + 2 + 30 * 3  # a window of 1,024 reaches three tiles of 512
    assert interval_visits(4, 16, 20) == 1 + 2 + 3 + 3 and interval_visits(4, 16, 8) == 1 + 3 * 2


def test_interval_attention_refuses_shapes_it_cannot_tile():
    q = jnp.zeros((1, 48, 2, 128), jnp.bfloat16)
    lo = jnp.zeros((1, 48), jnp.int32)
    with pytest.raises(ValueError, match="multiple of the tile"):
        interval_attention(q, q, q, lo, tile=32, interpret=True)
    with pytest.raises(ValueError, match="do not fit"):
        interval_attention(q, q, q, lo[:, :32], tile=16, interpret=True)


# ------------------------------------------------------------------- RoPE

def test_rope_tables_against_hand_values():
    """The published numbers: D 128, theta 5e5, YaRN factor 16 over 8,192,
    beta_fast 32, beta_slow 1."""
    plain, one = rope_frequencies(128, 5e5)
    yarn, factor = rope_frequencies(128, 5e5, YARN)
    assert one == 1.0 and factor == 1.2772588722239782
    dim = lambda turns: 128 * math.log(8192 / (2 * math.pi * turns)) / (2 * math.log(5e5))
    assert (math.floor(dim(32)), math.ceil(dim(1))) == (18, 35)  # low, high: 18.08 and 34.98
    assert plain[0] == 1.0 and plain[1] == np.float32(5e5 ** (-2 / 128))
    np.testing.assert_array_equal(yarn[:19], plain[:19])  # the fast dimensions are kept
    np.testing.assert_allclose(yarn[35:], plain[35:] / 16, rtol=1e-6)  # the slow ones divided by the factor
    n = 26  # inside the ramp: (26 - 18) / 17 of the way
    ramp = 8 / 17
    want = 5e5 ** (-2 * n / 128) * (ramp / 16 + 1 - ramp)
    assert yarn[n] == pytest.approx(want, rel=1e-6) and plain[n] == pytest.approx(5e5 ** (-52 / 128), rel=1e-6)
    # the reference's own equations give the same table
    theirs, c = reference.rope_frequencies(128, 5e5, dict(YARN, rope_type="yarn"))
    np.testing.assert_array_equal(theirs, yarn)
    assert c == factor


# ---------------------------------------------------------- head and loss

@pytest.mark.parametrize("what", ["loss", "outputs", "gradient", "counters"])
def test_chunked_head_and_loss_against_whole_logits(what):
    model = _model(TINY)
    b = _batch(3)
    rng = np.random.default_rng(3)
    rows = jnp.asarray(rng.standard_normal((BATCH, LENGTH, 128)) * 0.02, jnp.float32)
    starts = [jnp.asarray(document_starts(DOCS, LENGTH).data)]
    labels = [jnp.asarray(b["labels"]), jnp.asarray(b["weights"])]
    variables = model.init(jax.random.PRNGKey(0), starts, [(rows, None)])

    def chunked(params):
        loss, ids, stats = model.train_loss(dict(variables, params=params), starts, [(rows, None)], labels)
        return loss, (ids, stats)

    def whole(params):
        logits, stats = model.apply(dict(variables, params=params), starts, [(rows, None)],
                                    mutable=["batch_stats"])
        return model.loss(logits, labels), (model.outputs(logits), stats["batch_stats"])

    (l1, (ids1, st1)), g1 = jax.value_and_grad(chunked, has_aux=True)(variables["params"])
    (l2, (ids2, st2)), g2 = jax.value_and_grad(whole, has_aux=True)(variables["params"])
    if what == "loss":
        assert float(l1) == pytest.approx(float(l2), rel=1e-6)
    elif what == "outputs":
        np.testing.assert_array_equal(np.asarray(ids1), np.asarray(ids2))
    elif what == "gradient":
        for a, c in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            assert _gap(a, c) < 1e-5
    else:
        for name in ("expert_picks", "attention_tiles"):
            np.testing.assert_array_equal(np.asarray(st1[name]), np.asarray(st2[name]))


# ------------------------------------------------------------ side inputs

def test_an_integer_feature_stays_int32_through_batch_to_fused():
    b = _batch()
    fb = batch_to_fused(_persia_batch(b), {"tokens": FusedSlotSpec(97, 128, pooled=False)})
    (starts,) = fb["dense"]
    assert starts.dtype == np.int32 and starts.shape == (BATCH, LENGTH)
    np.testing.assert_array_equal(starts, reference.document_starts(DOCS, LENGTH))  # the two ways agree
    assert starts[0, 17] == 0 and starts[0, 18] == starts[0, 22] == 18 and starts[0, 23] == 23
    # a float feature is float32 as before, whatever it came as
    click = PersiaBatch([IDTypeFeature.from_flat("tokens", np.arange(2, dtype=np.uint64), np.ones(2, np.int64))],
                        [NonIDTypeFeature(np.ones((2, 3), np.float64))], labels=[Label(np.ones((2, 1), np.float32))])
    assert batch_to_fused(click)["dense"][0].dtype == np.float32
    with pytest.raises(ValueError, match="do not fit"):
        document_starts([[40, 30]], LENGTH)


# ------------------------------------------------- the shared tower's move

def test_sdar_on_the_shared_tower_reads_its_values_before_the_move():
    """``SDARMoE`` at a small size from fixed keys: loss, logits and picks as
    ``models/sdar_moe.py`` gave them while it held the tower itself (PR 34's
    tree, this CPU backend); the move changed no operation."""
    m = SDARMoE(vocab=97, n_layers=2, block_len=4, hidden=128, n_heads=4, n_kv_heads=2, head_dim=128,
                n_experts=16, experts_per_token=4, expert_width=64, first_held=4, n_held=8, interpret=True)
    rows = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 128), jnp.float32)
    var = m.init(jax.random.PRNGKey(0), None, [(rows, None)])
    labels = [jnp.asarray(np.random.default_rng(0).integers(0, 96, (2, 32)), jnp.int32),
              jnp.ones((2, 32), jnp.float32)]
    logits, stats = m.apply(var, None, [(rows, None)], mutable=["batch_stats"])
    assert float(m.loss(logits, labels)) == pytest.approx(SDAR_BEFORE["loss"], rel=1e-6)
    assert float(jnp.abs(logits).sum()) == pytest.approx(SDAR_BEFORE["abs_logits"], rel=1e-6)
    np.testing.assert_array_equal(np.asarray(stats["batch_stats"]["expert_picks"]), SDAR_BEFORE["picks"])
    assert set(stats["batch_stats"]) == {"expert_picks"}  # the counters its entry builds its state with


SDAR_BEFORE = {"loss": 4.593346118927002, "abs_logits": 1125.6807861328125,
               "picks": [[33, 36, 34, 35, 27, 29, 36, 26], [25, 37, 19, 33, 30, 41, 36, 31]]}
