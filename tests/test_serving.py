"""HTTP serving: train → checkpoint → serve → client AUC round-trip."""

import numpy as np
import optax
import pytest

from persia_tpu.config import EmbeddingConfig, SlotConfig
from persia_tpu.ctx import InferCtx, TrainCtx
from persia_tpu.embedding.optim import Adagrad
from persia_tpu.embedding.store import EmbeddingStore
from persia_tpu.embedding.worker import EmbeddingWorker
from persia_tpu.models import DNN
from persia_tpu.serving import InferenceClient, InferenceServer
from persia_tpu.testing import SyntheticClickDataset, roc_auc

VOCABS = (32, 16, 8)


def _ctx():
    cfg = EmbeddingConfig(
        slots_config={f"cat_{i}": SlotConfig(dim=8) for i in range(len(VOCABS))},
        feature_index_prefix_bit=8,
    )
    store = EmbeddingStore(capacity=1 << 14, num_internal_shards=2,
                           optimizer=Adagrad(lr=0.1).config, seed=7)
    worker = EmbeddingWorker(cfg, [store])
    return TrainCtx(
        model=DNN(dense_mlp_size=8, sparse_mlp_size=32, hidden_sizes=(32,)),
        dense_optimizer=optax.adam(3e-3),
        embedding_optimizer=Adagrad(lr=0.1),
        worker=worker,
        embedding_config=cfg,
    ), cfg


@pytest.fixture(scope="module")
def served():
    train = SyntheticClickDataset(num_samples=1024, vocab_sizes=VOCABS, seed=1)
    ctx, cfg = _ctx()
    with ctx:
        for _ in range(3):
            for batch in train.batches(batch_size=128):
                ctx.train_step(batch)
    infer = InferCtx(model=ctx.model, state=ctx.state, worker=ctx.worker,
                     embedding_config=cfg)
    srv = InferenceServer(infer, port=0).start()
    cli = InferenceClient(f"127.0.0.1:{srv.port}")
    yield ctx, srv, cli
    srv.stop()


def test_health_and_metrics(served):
    _, _, cli = served
    h = cli.health()
    assert h["status"] == "ok" and h["model"] == "DNN"
    assert "persia" in cli.metrics_text() or cli.metrics_text() is not None


def test_predict_matches_local_eval(served):
    ctx, _, cli = served
    test = SyntheticClickDataset(num_samples=128, vocab_sizes=VOCABS, seed=2)
    batch = next(iter(test.batches(batch_size=128, requires_grad=False)))
    remote = cli.predict(batch)
    local = ctx.eval_batch(batch)
    np.testing.assert_allclose(remote.reshape(-1), np.asarray(local).reshape(-1),
                               atol=1e-5)


def test_served_auc_beats_chance(served):
    _, _, cli = served
    test = SyntheticClickDataset(num_samples=512, vocab_sizes=VOCABS, seed=3)
    preds, labels = [], []
    for batch in test.batches(batch_size=128, requires_grad=False):
        preds.append(cli.predict(batch))
        labels.append(batch.labels[0].data)
    auc = roc_auc(np.concatenate(labels), np.concatenate(preds))
    # this gate checks "the served model carries real learned signal", not a
    # quality pin of exact AUCs: the 3-epoch synthetic
    # run plateaus at ~0.79-0.80 (deterministic), so 0.75 is comfortably above
    # chance while robust to the plateau's exact landing point
    assert auc > 0.75


def test_bad_payload_is_400_not_crash(served):
    _, srv, cli = served
    import urllib.error

    with pytest.raises(urllib.error.HTTPError) as ei:
        cli.predict_bytes(b"garbage")
    assert ei.value.code == 400
    assert cli.health()["status"] == "ok"  # server survived


def test_checkpoint_round_trip_through_ctx(tmp_path):
    """dump_checkpoint → fresh ctx → load_checkpoint → identical predictions."""
    train = SyntheticClickDataset(num_samples=512, vocab_sizes=VOCABS, seed=4)
    ctx, cfg = _ctx()
    with ctx:
        for batch in train.batches(batch_size=128):
            ctx.train_step(batch)
    ckpt = str(tmp_path / "ckpt")
    ctx.dump_checkpoint(ckpt)

    ctx2, cfg2 = _ctx()
    with ctx2:
        test = SyntheticClickDataset(num_samples=64, vocab_sizes=VOCABS, seed=5)
        batch = next(iter(test.batches(batch_size=64, requires_grad=False)))
        # initialize dense shapes, then restore both halves
        emb = ctx2.worker.forward_directly(batch, train=False)
        device_batch, _ = ctx2.prepare_features(batch, emb)
        import jax

        ctx2.init_state(jax.random.PRNGKey(0), device_batch)
        ctx2.load_checkpoint(ckpt)
        np.testing.assert_allclose(
            np.asarray(ctx2.eval_batch(batch)).reshape(-1),
            np.asarray(ctx.eval_batch(batch)).reshape(-1),
            atol=1e-6,
        )
