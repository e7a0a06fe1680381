"""FusedTrainCtx: the TrainCtx-shaped API over the all-in-HBM tier."""

import numpy as np
import optax
import pytest

from persia_tpu.data import (
    IDTypeFeature,
    IDTypeFeatureWithSingleID,
    Label,
    NonIDTypeFeature,
    PersiaBatch,
)
from persia_tpu.embedding.optim import Adagrad
from persia_tpu.models import DNN
from persia_tpu.parallel.fused_ctx import FusedTrainCtx, batch_to_fused
from persia_tpu.parallel.fused_step import FusedSlotSpec

SPECS = {
    "a": FusedSlotSpec(vocab=64, dim=8),
    "b": FusedSlotSpec(vocab=32, dim=8),
}


def _ctx():
    return FusedTrainCtx(
        model=DNN(dense_mlp_size=8, sparse_mlp_size=16, hidden_sizes=(16,)),
        dense_optimizer=optax.adam(1e-2),
        embedding_optimizer=Adagrad(lr=0.1),
        specs=SPECS,
    )


def _batch(seed, n=16, learnable=True):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 64, n).astype(np.uint64)
    b = rng.integers(0, 32, n).astype(np.uint64)
    dense = rng.normal(size=(n, 4)).astype(np.float32)
    if learnable:  # label correlated with slot-a id parity + dense[0]
        logit = (a % 2).astype(np.float32) * 2 - 1 + dense[:, 0]
        y = (logit > 0).astype(np.float32).reshape(-1, 1)
    else:
        y = rng.integers(0, 2, (n, 1)).astype(np.float32)
    return PersiaBatch(
        [IDTypeFeatureWithSingleID("a", a), IDTypeFeatureWithSingleID("b", b)],
        non_id_type_features=[NonIDTypeFeature(dense)],
        labels=[Label(y)],
        requires_grad=True,
    )


def test_trains_and_loss_drops():
    with _ctx() as ctx:
        losses = [ctx.train_step(_batch(i))["loss"] for i in range(30)]
        assert np.all(np.isfinite(losses))
        assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.05, losses


def test_eval_batch_shape():
    with _ctx() as ctx:
        ctx.train_step(_batch(0))
        preds = ctx.eval_batch(_batch(1, learnable=False))
        assert preds.shape[0] == 16 and np.all(np.isfinite(preds))


def test_checkpoint_roundtrip(tmp_path):
    with _ctx() as ctx:
        for i in range(5):
            ctx.train_step(_batch(i))
        ref = ctx.eval_batch(_batch(100, learnable=False))
        ctx.dump_checkpoint(str(tmp_path))
        for i in range(5, 10):  # diverge
            ctx.train_step(_batch(i))
        assert not np.allclose(ref, ctx.eval_batch(_batch(100, learnable=False)))
        ctx.load_checkpoint(str(tmp_path))
        np.testing.assert_array_equal(
            ref, ctx.eval_batch(_batch(100, learnable=False))
        )


def test_checkpoint_layout_mismatch_rejected(tmp_path):
    with _ctx() as ctx:
        ctx.train_step(_batch(0))
        ctx.dump_checkpoint(str(tmp_path))
    other = FusedTrainCtx(
        model=DNN(dense_mlp_size=8, sparse_mlp_size=16, hidden_sizes=(32, 16)),
        dense_optimizer=optax.adam(1e-2),
        embedding_optimizer=Adagrad(lr=0.1),
        specs=SPECS,
    )
    other.train_step(_batch(0))
    with pytest.raises(ValueError, match="layout mismatch"):
        other.load_checkpoint(str(tmp_path))


def test_batch_to_fused_lil_padding():
    lil = IDTypeFeature("a", [
        np.array([1, 2, 3], np.uint64),
        np.array([], np.uint64),
        np.array([7], np.uint64),
    ])
    fb = batch_to_fused(PersiaBatch(
        [lil],
        non_id_type_features=[NonIDTypeFeature(np.zeros((3, 2), np.float32))],
        labels=[Label(np.zeros((3, 1), np.float32))],
        requires_grad=True,
    ))
    np.testing.assert_array_equal(
        fb["ids"]["a"],
        np.array([[1, 2, 3], [-1, -1, -1], [7, -1, -1]], np.int32),
    )


def test_batch_to_fused_count_coincidence_not_single_id():
    """Total ids == batch size must NOT be mistaken for one-id-per-sample
    (regression: [[1,2],[],[7]] has 3 ids over 3 samples)."""
    lil = IDTypeFeature("a", [
        np.array([1, 2], np.uint64),
        np.array([], np.uint64),
        np.array([7], np.uint64),
    ])
    fb = batch_to_fused(PersiaBatch(
        [lil],
        non_id_type_features=[NonIDTypeFeature(np.zeros((3, 2), np.float32))],
        labels=[Label(np.zeros((3, 1), np.float32))],
        requires_grad=True,
    ))
    np.testing.assert_array_equal(
        fb["ids"]["a"], np.array([[1, 2], [-1, -1], [7, -1]], np.int32)
    )


def test_out_of_vocab_ids_rejected_or_folded():
    """Open hash-sign ids against dense [0, vocab) tables must fail loudly
    by default (int32 wrap / XLA clamped gather would silently corrupt),
    and fold deterministically with fold_ids=True."""
    import pytest as _pytest

    big = np.array([2**63 + 5, 1], dtype=np.uint64)
    batch = PersiaBatch(
        [IDTypeFeatureWithSingleID("a", big), IDTypeFeatureWithSingleID("b", np.array([0, 1], np.uint64))],
        non_id_type_features=[NonIDTypeFeature(np.zeros((2, 4), np.float32))],
        labels=[Label(np.zeros((2, 1), np.float32))],
        requires_grad=True,
    )
    with _pytest.raises(ValueError, match="outside"):
        batch_to_fused(batch, SPECS)
    fb = batch_to_fused(batch, SPECS, fold_ids=True)
    assert fb["ids"]["a"][0] == (2**63 + 5) % 64
    ctx = FusedTrainCtx(
        model=DNN(dense_mlp_size=8, sparse_mlp_size=16, hidden_sizes=(16,)),
        dense_optimizer=optax.adam(1e-2),
        embedding_optimizer=Adagrad(lr=0.1),
        specs=SPECS, fold_ids=True,
    )
    m = ctx.train_step(batch)
    assert np.isfinite(m["loss"])


def test_refused_batch_leaves_the_state():
    """A batch ``train_step`` refuses at conversion (an out-of-vocab id,
    folding off) raises that error and touches no leaf of the state; the
    next good batch trains to what a context that never saw it reaches."""
    import jax

    def leaves(ctx):
        return [np.asarray(x) for x in jax.tree_util.tree_leaves(ctx.state)]

    good = _batch(1)
    a = good.id_type_features[0].flat_counts()[0].copy()
    a[3] = SPECS["a"].vocab  # one past the table
    bad = PersiaBatch(
        [IDTypeFeatureWithSingleID("a", a), good.id_type_features[1]],
        non_id_type_features=good.non_id_type_features,
        labels=good.labels,
        requires_grad=True,
    )
    seen, clean = _ctx(), _ctx()
    for ctx in (seen, clean):
        ctx.train_step(_batch(0), fetch_metrics=False)
    before = leaves(seen)
    with pytest.raises(ValueError, match="outside"):
        seen.train_step(bad)
    for i, (x, y) in enumerate(zip(before, leaves(seen))):
        np.testing.assert_array_equal(x, y, err_msg=f"leaf {i}")
    for ctx in (seen, clean):
        ctx.train_step(_batch(2), fetch_metrics=False)
    for i, (x, y) in enumerate(zip(leaves(clean), leaves(seen))):
        np.testing.assert_array_equal(x, y, err_msg=f"leaf {i}")
