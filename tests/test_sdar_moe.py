"""The block-diffusion mixture-of-experts tower (``models/sdar_moe.py``), its
attention kernels (``ops/flash_attention.py``) and grouped products
(``ops/grouped_matmul.py``) on the CPU: against the plain reference
(``perf/reference/sdar_moe.py``) at a small size on seeded weights, the expert
shares against the uncut layer, the kernels against dense ``jax.numpy``.
The Pallas kernels run in the interpreter, by this file's choice."""

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

from perf import sdar_weights  # noqa: E402
from perf.reference import sdar_moe as reference  # noqa: E402
from persia_tpu import tracing  # noqa: E402
from persia_tpu.data import IDTypeFeature, Label, PersiaBatch  # noqa: E402
from persia_tpu.embedding.optim import Adagrad  # noqa: E402
from persia_tpu.models import SDARMoE  # noqa: E402
from persia_tpu.ops.flash_attention import (  # noqa: E402
    _CUT, _DEAD, _EQ, _LE, _LT, _WHOLE, _cut_slabs, _live_tiles, _sub_tile, _visit_tables,
    block_diffusion_attention, block_diffusion_mask, block_diffusion_plan,
)
from persia_tpu.ops.grouped_matmul import grouped_matmul, grouped_outer  # noqa: E402
from persia_tpu.parallel.fused_ctx import FusedTrainCtx, batch_to_fused  # noqa: E402
from persia_tpu.parallel.fused_step import (  # noqa: E402
    FusedSlotSpec, FusedTrainState, group_stacked_specs,
)

# 2 layers, 8 of 16 experts held, 4 a token, b 4, L 32
TINY = {
    "hidden_size": 128, "head_dim": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
    "moe_intermediate_size": 64, "num_experts": 8, "router_width": 16, "first_held_expert": 4,
    "num_experts_per_tok": 4, "num_hidden_layers": 2, "vocab_size": 97, "block_length": 4,
    "rms_norm_eps": 1e-6, "rope_theta": 1e6, "reference_query_block": 16,
    "sparse_optimizer": {"kind": "adagrad", "lr": 0.01, "initial_accumulator": 0.01, "eps": 1e-10},
    "dense_optimizer": {"kind": "adam", "lr": 1e-6, "b1": 0.9, "b2": 0.95, "eps": 1e-8},
}
SEED, BATCH, LENGTH = 2 ** 31 + 7, 2, 32


def _model(cfg, **kw):
    return SDARMoE(
        vocab=cfg["vocab_size"], n_layers=cfg["num_hidden_layers"], block_len=cfg["block_length"],
        hidden=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_experts=cfg["router_width"], experts_per_token=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"], first_held=cfg["first_held_expert"],
        n_held=cfg["num_experts"], interpret=True, **kw)


def _batch(seed=0):
    rng = np.random.default_rng(seed)
    mask_id = TINY["vocab_size"] - 1
    x0 = rng.integers(0, mask_id, (BATCH, LENGTH))
    t = np.repeat(0.1 + 0.9 * rng.random((BATCH, LENGTH // 4)), 4, axis=1)
    masked = rng.random((BATCH, LENGTH)) < t
    return {"ids": np.concatenate([np.where(masked, mask_id, x0), x0], axis=1),
            "labels": x0.astype(np.int32), "weights": np.where(masked, 1.0 / t, 0.0).astype(np.float32)}


def _persia_batch(b):
    tokens = IDTypeFeature.from_flat("tokens", b["ids"].astype(np.uint64).reshape(-1),
                                     np.full(BATCH, 2 * LENGTH, np.int64))
    return PersiaBatch([tokens], labels=[Label(b["labels"]), Label(b["weights"])], requires_grad=True)


@pytest.fixture(scope="module")
def one_step():
    """One ``FusedTrainCtx.train_step`` of the tower and one step of the
    reference, from the same seeded weights on the same batch."""
    cfg, b = TINY, _batch()
    so, do = cfg["sparse_optimizer"], cfg["dense_optimizer"]
    emb_opt = Adagrad(lr=so["lr"], initialization=so["initial_accumulator"], eps=so["eps"])
    ctx = FusedTrainCtx(_model(cfg), optax.adam(do["lr"], b1=do["b1"], b2=do["b2"], eps=do["eps"]),
                        emb_opt, {"tokens": FusedSlotSpec(cfg["vocab_size"], cfg["hidden_size"], pooled=False)})
    dense = reference.initial_dense(cfg, SEED)
    table = jnp.asarray(sdar_weights.token_rows(cfg, SEED, np.arange(cfg["vocab_size"])))
    (gname,) = [g.name for g in group_stacked_specs(ctx.specs, ctx.slot_order)]
    ctx.state = FusedTrainState(
        params=jax.tree.map(jnp.copy, dense),
        batch_stats={"expert_picks": jnp.zeros((2, 8), jnp.int32)},
        opt_state=ctx.dense_optimizer.init(dense), tables={gname: table},
        emb_state={gname: {"acc": jnp.full(table.shape, so["initial_accumulator"], jnp.float32)}},
        emb_batch_state=jnp.ones((2,), jnp.float32), step=jnp.zeros((), jnp.int32))
    out = ctx.train_step(_persia_batch(b))
    paths = [e["attrs"] for e in tracing.flight_snapshot() if e["kind"] == "sdar_moe.paths"]
    ref = reference.Reference(cfg, SEED, lambda keys: sdar_weights.token_rows(
        cfg, SEED, np.asarray(keys, np.int64)), how=(8, 7))
    keys = b["ids"].astype(np.uint64)
    loss_ref = ref.step(b, keys)
    uniq = np.unique(keys)
    return {"cfg": cfg, "out": out, "state": ctx.state, "table": np.asarray(ctx.state.tables[gname]),
            "acc": np.asarray(ctx.state.emb_state[gname]["acc"]), "ref": ref, "loss_ref": loss_ref,
            "uniq": uniq, "dense0": reference.leaves_by_name(dense), "paths": paths}


def _gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


@pytest.mark.parametrize("what", ["loss", "outputs", "gradient_by_leaf", "change_by_leaf", "rows",
                                  "accumulators", "picks"])
def test_tower_against_the_reference(one_step, what):
    s, ref, cfg = one_step, one_step["ref"], one_step["cfg"]
    b1 = cfg["dense_optimizer"]["b1"]
    if what == "loss":
        assert abs(s["out"]["loss"] - s["loss_ref"]) <= 2e-4 * abs(s["loss_ref"])
        assert 3.0 < s["loss_ref"] < 6.0  # ln(97) = 4.57 at the start
    elif what == "outputs":  # the model's own: an id a noised position, no sigmoid of the logits
        assert s["out"]["preds"].shape == (BATCH, LENGTH) and s["out"]["preds"].dtype == np.int32
    elif what == "gradient_by_leaf":  # Adam's first moment after one step is (1 - b1) x the gradient
        mine = reference.leaves_by_name(s["state"].opt_state[0].mu)
        theirs = reference.leaves_by_name(ref.m)
        assert set(mine) == set(sdar_weights.leaf_names(cfg))
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
        start = sdar_weights.token_rows(cfg, SEED, s["uniq"].astype(np.int64))
        assert _gap(s["table"][s["uniq"].astype(np.int64)] - start, rows - start) < 0.03
        untouched = np.setdiff1d(np.arange(cfg["vocab_size"]), s["uniq"])
        np.testing.assert_array_equal(s["table"][untouched],
                                      sdar_weights.token_rows(cfg, SEED, untouched))
    elif what == "accumulators":
        _, acc = ref.lookup(s["uniq"])
        assert _gap(s["acc"][s["uniq"].astype(np.int64)] - 0.01, acc - 0.01) < 0.05
    else:
        picks = np.asarray(s["state"].batch_stats["expert_picks"])
        assert picks.shape == (2, 8) and np.abs(picks - ref.picks).sum() <= 0.02 * ref.picks.sum()
        assert picks.sum() > 0


def test_the_paths_event_carries_the_attention_plan(one_step):
    """What the entry prints to stderr: the path names, and what the kernels
    execute a head at this length (one tile of 32 a half: three cut visits)."""
    said = one_step["paths"][-1]
    assert said["attention"] == "pallas_block_mask" and said["experts"] == "pallas_grouped"
    # the grouped products' (row, K, N) tiles, gate and up's then down's: 512 picks a chunk here
    assert said["pick_chunk"] == "512" and said["experts_tile"] == "512x128x64/512x64x128"
    # q/k norm, RoPE and the cast as one pass, the 2L positions of a sequence one block
    assert (said["qk_prep"], said["qk_prep_tile"]) == ("pallas_rows", str(2 * LENGTH))
    plan = block_diffusion_plan(LENGTH, TINY["block_length"])
    assert {k: said[k] for k in plan} == {k: str(v) for k, v in plan.items()}
    assert (plan["visits"], plan["visits_whole"], plan["sub_tile"]) == (3, 0, 8)
    assert (plan["sub_tiles_executed"], plan["sub_tiles_visited"]) == (4 + 10 + 10, 48)


def test_the_shares_add_up():
    """The parts of one layer's result that the 8 shares (0, 16) .. (112, 16)
    give sum to what the uncut reference layer gives: 128 experts, 8 a token."""
    cfg = dict(TINY, router_width=128, num_experts_per_tok=8, num_experts=16)
    rng = np.random.default_rng(5)
    d, f, n = cfg["hidden_size"], cfg["moe_intermediate_size"], 64
    m = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((d, 128)) * 0.2, jnp.float32)
    gate, up = (jnp.asarray(rng.standard_normal((128, d, f)) * 0.1, jnp.float32) for _ in range(2))
    down = jnp.asarray(rng.standard_normal((128, f, d)) * 0.1, jnp.float32)
    whole, picks_whole = reference.expert_layer(
        {"router": router, "gate": gate, "up": up, "down": down}, m,
        {"k": 8, "held": 128, "first": 0}, (8, 7))
    total, picks = jnp.zeros_like(m), []
    for first in range(0, 128, 16):
        model = _model(dict(cfg, first_held_expert=first))
        part, got = model.experts({"router": router, "gate": gate[first:first + 16],
                                   "up": up[first:first + 16], "down": down[first:first + 16]}, m)
        total, picks = total + part, picks + [np.asarray(got)]
    assert np.concatenate(picks).sum() == n * 8  # every pick is some share's
    np.testing.assert_array_equal(np.concatenate(picks), np.asarray(picks_whole))
    assert _gap(total, whole) < 2e-3


def test_no_pick_is_dropped_when_every_pick_is_held():
    """All experts held and one expert taking most picks: the chunk loop runs
    to the last live chunk."""
    cfg = dict(TINY, router_width=4, num_experts=4, first_held_expert=0, num_experts_per_tok=2)
    rng = np.random.default_rng(6)
    d, f, n = cfg["hidden_size"], cfg["moe_intermediate_size"], 64
    m = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    p = {"router": jnp.asarray(rng.standard_normal((d, 4)) * 0.01, jnp.float32).at[:, 0].add(0.05 * m.mean(0)),
         "gate": jnp.asarray(rng.standard_normal((4, d, f)) * 0.1, jnp.float32),
         "up": jnp.asarray(rng.standard_normal((4, d, f)) * 0.1, jnp.float32),
         "down": jnp.asarray(rng.standard_normal((4, f, d)) * 0.1, jnp.float32)}
    got, picks = _model(cfg).experts(p, m)
    want, _ = reference.expert_layer(p, m, {"k": 2, "held": 4, "first": 0}, (8, 7))
    assert int(picks.sum()) == n * 2
    assert _gap(got, want) < 2e-3


# ------------------------------------------------------------- the kernels

def test_mask_for_l8_b4_is_the_hand_written_matrix():
    n, c = 0, 1  # [noised | clean] keys across, queries down
    want = np.array([
        # noised keys       clean keys
        [c, c, c, c, n, n, n, n,  n, n, n, n, n, n, n, n],
        [c, c, c, c, n, n, n, n,  n, n, n, n, n, n, n, n],
        [c, c, c, c, n, n, n, n,  n, n, n, n, n, n, n, n],
        [c, c, c, c, n, n, n, n,  n, n, n, n, n, n, n, n],
        [n, n, n, n, c, c, c, c,  c, c, c, c, n, n, n, n],
        [n, n, n, n, c, c, c, c,  c, c, c, c, n, n, n, n],
        [n, n, n, n, c, c, c, c,  c, c, c, c, n, n, n, n],
        [n, n, n, n, c, c, c, c,  c, c, c, c, n, n, n, n],
        # clean queries read no noised key
        [n, n, n, n, n, n, n, n,  c, c, c, c, n, n, n, n],
        [n, n, n, n, n, n, n, n,  c, c, c, c, n, n, n, n],
        [n, n, n, n, n, n, n, n,  c, c, c, c, n, n, n, n],
        [n, n, n, n, n, n, n, n,  c, c, c, c, n, n, n, n],
        [n, n, n, n, n, n, n, n,  c, c, c, c, c, c, c, c],
        [n, n, n, n, n, n, n, n,  c, c, c, c, c, c, c, c],
        [n, n, n, n, n, n, n, n,  c, c, c, c, c, c, c, c],
        [n, n, n, n, n, n, n, n,  c, c, c, c, c, c, c, c],
    ], bool)
    np.testing.assert_array_equal(block_diffusion_mask(8, 4), want)
    np.testing.assert_array_equal(reference.dense_mask(8, 4), want)  # the reference builds its own


def test_dead_tile_pairs_are_never_visited():
    kinds = _live_tiles(4096, 4, 512)
    live, full = kinds != _DEAD, kinds == _WHOLE
    assert live.shape == (16, 16) and live.sum() == 80 and full.sum() == 56
    assert not live[8:, :8].any()  # clean queries read no noised key
    rows, cols, kind, first, last = (np.asarray(x) for x in _visit_tables(kinds))
    assert len(rows) == 80  # the grid's steps: the live pairs and nothing else
    assert rows.tolist() == sorted(rows.tolist()) and first.sum() == last.sum() == 16
    at = rows == 3  # a noised q tile: its own tile, then clean tiles 0 .. 3, the last one partly
    assert cols[at].tolist() == [3, 8, 9, 10, 11]
    assert kind[at].tolist() == [_EQ, _WHOLE, _WHOLE, _WHOLE, _LT]
    assert first[at].tolist() == [1, 0, 0, 0, 0] and last[at].tolist() == [0, 0, 0, 0, 1]
    assert cols[rows == 8].tolist() == [8]  # the first clean q tile reads its own tile alone
    assert kind[rows == 11].tolist() == [_WHOLE, _WHOLE, _WHOLE, _LE]
    k_rows, k_cols, k_kind, *_ = (np.asarray(x) for x in _visit_tables(kinds.T))
    assert k_cols[k_rows == 3].tolist() == [3]  # a noised k tile is read by its own q tile alone
    assert k_cols[k_rows == 15].tolist() == [7, 15]  # the pair's kind, whichever the rows are
    assert k_kind[k_rows == 15].tolist() == [_LT, _LE] and k_kind[k_rows == 3].tolist() == [_EQ]
    dense = block_diffusion_mask(64, 4)
    np.testing.assert_array_equal(_live_tiles(64, 4, 16) != _DEAD,
                                  dense.reshape(8, 16, 8, 16).any(axis=(1, 3)))


# (seq_len, block_len, tile, sub-tile): the benchmark cell's; a cut tile of 4 x 4
# sub-tiles; the same under blocks that are no power of two; a block wider than
# a quarter of the tile, which is then walked as one sub-tile
PLANS = [(4096, 4, 512, 128), (128, 4, 64, 16), (96, 6, 48, 12), (48, 12, 24, 24)]


@pytest.mark.parametrize("by_keys", [False, True], ids=["by_query_columns", "by_key_rows"])
@pytest.mark.parametrize("seq_len,block_len,tile,width", PLANS)
def test_the_plan_executes_every_live_pair_and_counts_what_it_executes(
        seq_len, block_len, tile, width, by_keys):
    """The slabs the kernels' walk executes, rebuilt from the tiles' kinds by
    the walk's own rule (``_cut_slabs``, the kind's test on block indices in
    the tile), are the dense mask: every live pair is inside an executed
    sub-tile, no sub-tile is executed twice, and what the test leaves of the
    executed pairs is the mask and nothing else."""
    plan = block_diffusion_plan(seq_len, block_len, tile)
    kinds, sub = _live_tiles(seq_len, block_len, tile), _sub_tile(tile, block_len)
    assert plan["tile"] == tile and plan["sub_tile"] == sub == width
    n, nsub = 2 * seq_len // tile, tile // sub
    block = np.arange(tile) // block_len
    executed = np.zeros((n, tile, n, tile), np.int32)  # q tile, query, k tile, key: times executed
    allowed = np.zeros(executed.shape, bool)
    for qi, ki in zip(*np.nonzero(kinds)):
        if kinds[qi, ki] == _WHOLE:
            executed[qi, :, ki], allowed[qi, :, ki] = 1, True
            continue
        for q0, nq, k0, nk in _cut_slabs(kinds[qi, ki], nsub, by_keys):
            queries, keys = slice(q0 * sub, (q0 + nq) * sub), slice(k0 * sub, (k0 + nk) * sub)
            executed[qi, queries, ki, keys] += 1
            allowed[qi, queries, ki, keys] = _CUT[kinds[qi, ki]](block[None, keys], block[queries, None])
    dense = block_diffusion_mask(seq_len, block_len)
    np.testing.assert_array_equal(allowed.reshape(dense.shape), dense)
    assert executed.max() == 1 and not (dense & (executed.reshape(dense.shape) == 0)).any()
    assert plan["pairs_executed"] == executed.sum() and plan["pairs_live"] == dense.sum()
    assert plan["sub_tiles_visited"] == plan["visits"] * nsub * nsub
    if seq_len == 4096:
        counts = {k: v for k, v in plan.items() if k.startswith("visits") or k.startswith("sub_tiles")}
        assert counts == {"visits": 80, "visits_whole": 56, "visits_noised_diagonal": 8,
                          "visits_noised_on_clean": 8, "visits_clean_diagonal": 8,
                          "sub_tiles_executed": 1088, "sub_tiles_visited": 1280}


@pytest.mark.parametrize("seq_len,block_len,tile", [(32, 3, 16), (64, 12, 16), (36, 4, 24)])
def test_a_tile_cut_off_its_diagonal_is_refused(seq_len, block_len, tile):
    """A block that straddles two tiles cuts them in no way the kernels walk."""
    with pytest.raises(ValueError, match="off its diagonal"):
        _live_tiles(seq_len, block_len, tile)


# (L, block_len, tile, query heads, K/V heads): the first case of PR 33; cut tiles of
# 4 x 4 sub-tiles of 16 beside whole and dead tiles; the same under blocks of 6
ATTENTION_CASES = [(32, 4, 16, 32, 4), (128, 4, 64, 4, 2), (96, 6, 48, 4, 2)]


@pytest.fixture(scope="module", params=ATTENTION_CASES, ids=lambda c: "L{}-b{}-tile{}".format(*c[:3]))
def attention_case(request):
    """Query heads over K/V heads under M: the kernels' output and gradients
    in the interpreter, and dense ``jax.numpy``'s."""
    length, b, tile, hq, hkv = request.param
    d = 128
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 2 * length, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2 * length, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2 * length, hkv, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    mask = jnp.asarray(block_diffusion_mask(length, b))

    def dense(q, k, v):
        kk, vv = jnp.repeat(k, hq // hkv, axis=2), jnp.repeat(v, hq // hkv, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk, precision="highest") / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vv, precision="highest")

    def kernel(q, k, v):
        return block_diffusion_attention(q, k, v, length, b, tile=tile, interpret=True)

    out = {}
    for name, f in (("kernel", kernel), ("dense", dense)):
        o, vjp = jax.vjp(f, q, k, v)
        out[name] = dict(zip(("forward", "dq", "dk", "dv"), (o,) + vjp(w)))
    return out


@pytest.mark.parametrize("what", ["forward", "dq", "dk", "dv"])
def test_attention_kernels_against_dense(attention_case, what):
    got, want = attention_case["kernel"][what], attention_case["dense"][what]
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_attention_refuses_shapes_it_cannot_tile():
    q = jnp.zeros((1, 64, 4, 64))
    with pytest.raises(ValueError, match="head size"):
        block_diffusion_attention(q, q, q, 32, 4, interpret=True)
    q = jnp.zeros((1, 60, 4, 128))
    with pytest.raises(ValueError, match="do not fit"):
        block_diffusion_attention(q, q, q, 32, 4, interpret=True)


@pytest.mark.parametrize("what", ["product", "transposed_weights", "outer"])
def test_grouped_products_against_a_loop(what):
    rng = np.random.default_rng(1)
    m, k, n, sizes = 64, 16, 24, [10, 0, 30, 8]  # 16 rows of no group at the end
    bf = lambda x: jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
    x, w, g = bf(rng.standard_normal((m, k))), bf(rng.standard_normal((4, k, n))), bf(rng.standard_normal((m, n)))
    gs = jnp.asarray(sizes, jnp.int32)
    f32 = lambda a: np.asarray(a.astype(jnp.float32), np.float64)
    starts = np.concatenate([[0], np.cumsum(sizes)])
    if what == "product":
        want = np.zeros((m, n))
        for i in range(4):
            want[starts[i]:starts[i + 1]] = f32(x)[starts[i]:starts[i + 1]] @ f32(w)[i]
        got = grouped_matmul(x, w, gs, interpret=True)
    elif what == "transposed_weights":
        want = np.zeros((m, k))
        for i in range(4):
            want[starts[i]:starts[i + 1]] = f32(g)[starts[i]:starts[i + 1]] @ f32(w)[i].T
        got = grouped_matmul(g, jnp.swapaxes(w, 1, 2), gs, interpret=True)
    else:
        want = np.stack([f32(x)[starts[i]:starts[i + 1]].T @ f32(g)[starts[i]:starts[i + 1]] for i in range(4)])
        got = grouped_outer(x, g, gs, interpret=True)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=1e-5)


def test_integer_labels_stay_integers_through_batch_to_fused():
    b = _batch()
    fused = batch_to_fused(_persia_batch(b), {"tokens": FusedSlotSpec(97, 128, pooled=False)})
    assert fused["labels"][0].dtype == np.int32 and fused["labels"][1].dtype == np.float32
    np.testing.assert_array_equal(fused["labels"][0], b["labels"])
    assert fused["ids"]["tokens"].shape == (BATCH, 2 * LENGTH) and fused["dense"] == []
    floats = PersiaBatch([IDTypeFeature.from_flat("tokens", np.zeros(2, np.uint64), np.ones(2, np.int64))],
                         labels=[Label(np.ones((2, 1), np.float64))], requires_grad=True)
    assert batch_to_fused(floats)["labels"][0].dtype == np.float32
