"""Trainer-process entry for the trainer-SIGKILL/auto-resume chaos tests
(tests/test_chaos.py) — NOT a pytest module.

One hybrid trainer: TrainCtx over an in-process EmbeddingWorker whose PS
replicas are the parent's subprocess parameter servers (StoreClients).
The loop is the crash-consistent job-state protocol end to end:

- ``ctx.resume(JS_DIR)`` on boot — rewinds the PS to the newest fence on
  a warm start, arms the apply-journal on a cold one;
- journaled ``train_step``s over a deterministic synthetic stream;
- ``ctx.snapshot_job`` every JS_SNAPSHOT_EVERY steps;
- a per-step progress beacon (chaos.write_progress) the parent's
  TrainerKiller watches to land a REAL mid-step SIGKILL.

On clean completion the final dense/optimizer state ships to JS_OUT as
flax's deterministic msgpack bytes (fsync'd atomic publish), so the
parent compares runs by byte equality — the strongest parity check.

Env: JS_PS_ADDRS (comma), JS_DIR, JS_PROGRESS, JS_OUT, JS_STEPS,
JS_SNAPSHOT_EVERY, JS_SEED, JS_BATCH.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def main() -> None:
    import flax.serialization
    import optax

    from persia_tpu.chaos import write_progress
    from persia_tpu.config import EmbeddingConfig, SlotConfig
    from persia_tpu.ctx import TrainCtx
    from persia_tpu.embedding.optim import Adagrad
    from persia_tpu.embedding.worker import EmbeddingWorker
    from persia_tpu.jobstate import JobStateManager, fsync_write_bytes
    from persia_tpu.models import DNN
    from persia_tpu.service.clients import StoreClient
    from persia_tpu.testing import SyntheticClickDataset

    ps_addrs = os.environ["JS_PS_ADDRS"].split(",")
    js_dir = os.environ["JS_DIR"]
    progress = os.environ["JS_PROGRESS"]
    out_path = os.environ["JS_OUT"]
    steps = int(os.environ["JS_STEPS"])
    every = int(os.environ["JS_SNAPSHOT_EVERY"])
    seed = int(os.environ.get("JS_SEED", "9"))
    bs = int(os.environ.get("JS_BATCH", "32"))

    cfg = EmbeddingConfig(
        slots_config={"cat_0": SlotConfig(dim=8), "cat_1": SlotConfig(dim=8)},
        feature_index_prefix_bit=8,
    )
    batches = list(
        SyntheticClickDataset(
            num_samples=steps * bs, vocab_sizes=(64, 32), seed=seed
        ).batches(bs)
    )[:steps]

    clients = [StoreClient(a) for a in ps_addrs]
    for c in clients:
        c.wait_ready()
    ctx = TrainCtx(
        model=DNN(dense_mlp_size=8, sparse_mlp_size=16, hidden_sizes=(32,)),
        dense_optimizer=optax.adam(3e-3),
        embedding_optimizer=Adagrad(lr=0.1),
        worker=EmbeddingWorker(cfg, clients),
        embedding_config=cfg,
    ).__enter__()

    mgr = JobStateManager(js_dir)
    manifest = ctx.resume(mgr)  # rewind-to-fence (bit-identical replay)
    start = manifest.step if manifest is not None else 0
    print(
        f"[jobstate-trainer pid {os.getpid()}] start step {start} "
        f"(resume info: {ctx.last_resume_info})", flush=True,
    )

    for i in range(start, steps):
        ctx.train_step(batches[i])
        # beacon AFTER the step's gradients applied: the killer lands
        # between "gradient sent" and the next manifest commit — the
        # exact double-apply window the journal/rewind must close
        write_progress(progress, i + 1)
        if (i + 1) % every == 0 and (i + 1) < steps:
            ctx.snapshot_job(mgr)

    fsync_write_bytes(out_path, flax.serialization.to_bytes(ctx.state))
    print(f"[jobstate-trainer pid {os.getpid()}] done at step {steps}", flush=True)


if __name__ == "__main__":
    main()
