"""Dense-plane sync quality/memory bench on virtual CPU devices.

Prices the ISSUE-13 dense plane end to end on whatever host runs it:

- quality: 20-step CriteoSynthetic runs (same hidden-ground-truth stream,
  seeds 5/7, as bench.py's quality-at-throughput gate) per dense sync mode,
  scored by held-out AUC — the block-scaled int8 ring must sit within 0.02
  AUC of the f32 allreduce or the byte saving is fiction.
- memory: measured per-replica optimizer-state bytes, replicated vs
  ZeRO-style sharded (``per_replica_opt_state_bytes`` over real
  addressable shards — not a model).
- dp-invariance: the SAME seeded global-batch stream trained under
  f32-sharded at n=8 (in-process) and n=32/64 (subprocess re-exec with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N``) must land the
  same dense params to a derived bound (adam |update| <= lr/step, so
  reduction-order noise across n is capped at steps*lr in the degenerate
  worst case; measured drift is recorded next to the bound).
- wire: the ``dense_sync_wire_bytes`` rows (single source of truth shared
  with bench.py records, WIRE_BENCH.json and the telemetry counter).

Usage: ``python benchmarks/dense_sync_bench.py`` (prints JSON to stdout).
Everything here is a count or a quality check from a CPU host; it says
nothing about step time, and no collective here crosses a real wire.
The id slots feed the dense tower through a FIXED seeded hash-projection
table per slot (numpy host-side, not learnable) — identical for every
mode, so mode-vs-mode AUC deltas isolate the sync arithmetic; absolute
AUCs are lower than the full learnable-embedding tiers and are not
comparable to bench.py's quality numbers.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

# run as a script (python benchmarks/dense_sync_bench.py) sys.path[0] is
# benchmarks/ — the repo root must be importable for persia_tpu
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

N_DEV = int(os.environ.get("DENSE_SYNC_BENCH_DEVICES", "8"))
os.environ.setdefault(
    "XLA_FLAGS", f"--xla_force_host_platform_device_count={N_DEV}"
)

import numpy as np  # noqa: E402

BATCH = 64          # divisible by every n in (8, 32, 64)
STEPS = 20
EVAL_BATCHES = 16   # wide held-out tail: AUC estimation noise must sit far
                    # below the 0.02 mode-spread gate at this step budget
NOISE = 0.5         # CriteoSynthetic label noise; default 1.0 leaves a
                    # 20-step model near chance where AUC is all variance
DIM = 16
HASH_ROWS = 512
LR = 1e-2


def _hash_tables(n_slots):
    rng = np.random.default_rng(123)
    return [
        rng.normal(size=(HASH_ROWS, DIM)).astype(np.float32) * 0.1
        for _ in range(n_slots)
    ]


def _to_pooled(pb, tables):
    """PersiaBatch → the grad_sync host-batch form: each single-id slot's
    id indexes its fixed hash table (id % rows) → one (B, DIM) pooled
    feature per slot."""
    emb = []
    for f, tbl in zip(pb.id_type_features, tables):
        flat, _ = f.flat_counts()
        emb.append({"pooled": tbl[np.asarray(flat, np.uint64) % HASH_ROWS]})
    return {
        "dense": [np.asarray(d.data, np.float32) for d in pb.non_id_type_features],
        "labels": [np.asarray(l.data, np.float32) for l in pb.labels],
        "emb": emb,
    }


def _stream(steps, eval_batches):
    from persia_tpu.testing.datasets import CriteoSynthetic

    n_slots = 26
    ds = CriteoSynthetic(
        num_samples=(steps + eval_batches) * BATCH,
        vocab_sizes=[100_000] * n_slots,
        noise=NOISE, seed=5, task_seed=7,
    )
    tables = _hash_tables(n_slots)
    all_b = [_to_pooled(pb, tables) for pb in ds.batches(BATCH)]
    return all_b[:steps], all_b[steps:]


def _model():
    import jax.numpy as jnp

    from persia_tpu.models import DLRM

    return DLRM(
        embedding_dim=DIM, bottom_mlp=(64, DIM), top_mlp=(64,),
        compute_dtype=jnp.float32,
    )


def _build(mode, mesh, model, opt, sample):
    """(state, step) for a dense sync mode, placed for the mesh."""
    import jax

    from persia_tpu.parallel.grad_sync import (
        BlockInt8Ring,
        build_sync_train_step,
        init_sync_opt_state,
        place_sync_state,
        sync_mode_algorithm,
    )
    from persia_tpu.parallel.train_step import init_train_state, replicate_state

    algorithm, sharded = sync_mode_algorithm(mode)
    state = init_train_state(model, jax.random.PRNGKey(0), sample, opt)
    wrapped = sharded or isinstance(algorithm, BlockInt8Ring)
    if wrapped:
        state = state.replace(
            opt_state=init_sync_opt_state(state.params, opt, mesh, algorithm,
                                          sharded_update=sharded)
        )
        state = place_sync_state(state, mesh, algorithm, sharded_update=sharded)
    else:
        state = replicate_state(state, mesh)
    step = build_sync_train_step(model, opt, mesh, algorithm,
                                 sharded_update=sharded)
    return state, step


def _flat_params(state):
    import jax

    return np.concatenate(
        [np.asarray(p, np.float64).reshape(-1)
         for p in jax.tree.leaves(state.params)]
    )


def _train(mode, train_b, mesh, model, opt):
    from persia_tpu.parallel.train_step import (
        shard_device_batch,
        unpack_step_header,
    )

    from persia_tpu.parallel.grad_sync import init_residual

    state, step = _build(mode, mesh, model, opt, train_b[0])
    residual = init_residual(state.params) if mode == "bytegrad" else None
    losses = []
    for hb in train_b:
        if residual is not None:
            state, (header, _), residual = step(
                state, shard_device_batch(hb, mesh), residual
            )
        else:
            state, (header, _) = step(state, shard_device_batch(hb, mesh))
        loss, _ = unpack_step_header(np.asarray(header), hb)
        losses.append(float(loss))
    return state, losses


def _eval_auc(state, eval_b, model):
    import jax

    from persia_tpu.parallel.train_step import (
        _embedding_model_inputs,
        _split_emb,
    )
    from persia_tpu.testing.synthetic import roc_auc

    @jax.jit
    def fwd(params, dense, emb_diff):
        model_emb = _embedding_model_inputs(emb_diff, emb_static)
        return model.apply({"params": params}, dense, model_emb, train=False)

    preds, labels = [], []
    for hb in eval_b:
        emb_diff, emb_static = _split_emb(hb["emb"])
        logits = fwd(state.params, hb["dense"], emb_diff)
        preds.append(1.0 / (1.0 + np.exp(-np.asarray(logits).reshape(-1))))
        labels.append(np.concatenate([l.reshape(-1) for l in hb["labels"]]))
    return float(roc_auc(np.concatenate(labels), np.concatenate(preds)))


def bench_quality():
    """Held-out AUC per dense sync mode on the shared learnable stream.
    Gate: every quantized/sharded mode within 0.02 AUC of f32."""
    import optax

    from persia_tpu.parallel.mesh import data_parallel_mesh

    mesh = data_parallel_mesh()
    model = _model()
    train_b, eval_b = _stream(STEPS, EVAL_BATCHES)
    out = {}
    for mode in ("f32", "bytegrad", "block-int8-ring",
                 "f32-sharded", "block-int8-ring-sharded"):
        state, losses = _train(mode, train_b, mesh, model, optax.adam(LR))
        out[mode] = {
            "auc": round(_eval_auc(state, eval_b, model), 6),
            "loss_first5": round(float(np.mean(losses[:5])), 4),
            "loss_last5": round(float(np.mean(losses[-5:])), 4),
        }
        assert np.isfinite(losses).all(), (mode, losses)
        assert out[mode]["loss_last5"] < out[mode]["loss_first5"], (mode, losses)
    spread = max(
        abs(out[m]["auc"] - out["f32"]["auc"]) for m in out if m != "f32"
    )
    out["auc_spread_vs_f32"] = round(spread, 6)
    assert spread < 0.02, f"quality gate: AUC spread {spread} >= 0.02: {out}"
    return out


def bench_opt_memory():
    """Measured per-replica optimizer-state bytes, replicated vs sharded
    (real addressable-shard nbytes, adam moments on the bench model)."""
    import optax

    from persia_tpu.parallel.grad_sync import per_replica_opt_state_bytes
    from persia_tpu.parallel.mesh import data_parallel_mesh

    mesh = data_parallel_mesh()
    n = mesh.shape["data"]
    model = _model()
    train_b, _ = _stream(1, 0)
    opt = optax.adam(LR)
    rep, _ = _build("f32", mesh, model, opt, train_b[0])
    shd, _ = _build("f32-sharded", mesh, model, opt, train_b[0])
    rep_b = per_replica_opt_state_bytes(rep.opt_state)
    shd_b = per_replica_opt_state_bytes(shd.opt_state["opt"])
    out = {
        "n": n,
        "replicated_bytes_per_replica": rep_b,
        "sharded_bytes_per_replica": shd_b,
        "ratio": round(shd_b / rep_b, 4),
    }
    # chunk padding + optax's replicated scalar count keep the ratio a bit
    # above the ideal 1/n; 1.35/n is the honest measured envelope
    assert shd_b < rep_b * 1.35 / n, out
    return out


def _dp_child_params(n, path):
    """Re-exec this module under a forced n-device CPU topology; the child
    trains f32-sharded on the fixed stream and writes its flat params."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["DENSE_SYNC_BENCH_DEVICES"] = str(n)
    env["JAX_PLATFORMS"] = "cpu"
    subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--dp-child", path],
        check=True, env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    return np.load(path)


def _dp_run_here():
    import optax

    from persia_tpu.parallel.mesh import data_parallel_mesh

    train_b, _ = _stream(STEPS, 0)
    state, losses = _train(
        "f32-sharded", train_b, data_parallel_mesh(), _model(), optax.adam(LR)
    )
    return _flat_params(state), losses


def bench_dp_invariance():
    """f32-sharded final dense params at n=8 vs n=32 vs n=64 on the SAME
    seeded global-batch stream. Derived bound (__graft_entry__.py idiom):
    adam caps |update| at lr per step, so reduction-order divergence across
    n is <= STEPS*LR = 0.2 in the degenerate worst case; the gate is 1.5x
    the measured 8-virtual-device CPU drift envelope from the n=1-vs-n=8
    oracle (5.22e-3), far inside that bound."""
    p8, losses = _dp_run_here()
    out = {
        "steps": STEPS,
        "derived_worst_case_bound": STEPS * LR,
        "gate_atol": 1.5 * 5.22e-3,
        "loss_first5": round(float(np.mean(losses[:5])), 4),
        "loss_last5": round(float(np.mean(losses[-5:])), 4),
    }
    for n in (32, 64):
        with tempfile.NamedTemporaryFile(suffix=".npy", delete=False) as f:
            path = f.name
        try:
            pn = _dp_child_params(n, path)
        finally:
            os.unlink(path)
        drift = float(np.abs(p8 - pn).max())
        out[f"max_param_drift_n8_vs_n{n}"] = round(drift, 8)
        assert drift <= out["gate_atol"], (n, drift, out)
    return out


def bench_wire():
    import jax
    import optax

    from persia_tpu.parallel.grad_sync import (
        DENSE_SYNC_MODES,
        dense_param_count,
        dense_sync_wire_bytes,
    )
    from persia_tpu.parallel.train_step import init_train_state

    train_b, _ = _stream(1, 0)
    state = init_train_state(
        _model(), jax.random.PRNGKey(0), train_b[0], optax.sgd(0.1)
    )
    p = dense_param_count(state.params)
    n = N_DEV
    rows = {
        m: dense_sync_wire_bytes(m, p, n) for m in DENSE_SYNC_MODES
    }
    f32 = rows["f32"]
    assert f32 / rows["block-int8-ring"] >= 3.5, rows
    return {
        "dense_params": p, "n": n,
        "bytes_per_step_per_replica": rows,
        "block_int8_ring_vs_f32": round(f32 / rows["block-int8-ring"], 2),
    }


def main():
    if len(sys.argv) > 2 and sys.argv[1] == "--dp-child":
        p, _ = _dp_run_here()
        np.save(sys.argv[2], p)
        return

    import jax

    out = {
        "note": (
            "Virtual CPU devices: quality gate (held-out AUC per sync mode "
            "on the shared CriteoSynthetic stream, spread vs f32 < 0.02), "
            "per-replica optimizer-state bytes from real addressable-shard "
            "sizes, dp-invariance of the sharded update at n=8/32/64, and "
            "the dense_sync_wire_bytes model. No step time: all 'replicas' "
            "share one memory bus, so no bytes cross a real wire; the "
            "collectives' time on four chips is not measured."
        ),
        "platform": jax.default_backend(),
        "quality": bench_quality(),
        "opt_state_memory": bench_opt_memory(),
        "dp_invariance": bench_dp_invariance(),
        "wire": bench_wire(),
        "env": {
            "devices": N_DEV,
            "batch": BATCH,
            "steps": STEPS,
            "eval_batches": EVAL_BATCHES,
            "lr": LR,
            "jax": jax.__version__,
        },
    }
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
