"""Auto-tiering vs the three single-tier configs on a mixed-skew synthetic.

Emits ONE JSON line: four subprocess-isolated modes over the SAME id
streams —

- ``fused-all``   every table fully device-resident (real fused path,
                  parallel/fused_step) — the in-memory ideal, IF it fits;
- ``cached-all``  every slot behind the HBM write-back cache;
- ``ps-all``      every slot streamed through the host C++ PS
                  (the reference's async regime, repo-default int8 wire);
- ``auto``        persia_tpu.embedding.tiering: starts naive (all cached),
                  the profiler+planner demote the heavy-tail slots to the
                  PS at a live snapshot fence mid-job, pins/hot stay.

The workload is the skew recommenders actually have (PAPER.md): a couple
of tiny-vocab "pin" slots with heavy traffic, hot slots whose stable
working set a cache can exploit, and near-uniform heavy-tail slots whose
signs barely repeat. Shapes tie to the repo's published records: dim 16
and the 65536-row device budget from BENCH_100T.json, batch 4096 from
bench.py.

What each mode reports:

- counts that hold on any host: hit rates, eviction rows/step, PS rows/
  step, migrations, and ``d2h_bytes_per_step`` (this run's MEASURED
  per-step wire rows at the configured wire widths). fused-all must also
  FIT: at this workload's vocabulary (107M rows x 160 B/row, the
  BENCH_100T bytes-per-row arithmetic) it needs ~17.1 GB of HBM against
  the 16 GB chip (``fits_device_hbm``).
- ``samples_per_sec_host_cpu``: measured on THIS host. On a chipless
  host the "device" is the host core and there is no host<->device wire,
  so the device-side cache machinery buys nothing there; it is a host
  number and never a device metric. What the modes do on the chip is
  not measured by this script.
"""

import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# ---------------------------------------------------------------- workload
BATCH = int(os.environ.get("TIERING_BATCH", "4096"))
DIM = 16
N_DENSE = 5
PIN_SLOTS, HOT_SLOTS, COLD_SLOTS = 2, 6, 6
PIN_VOCAB = 2048
HOT_VOCAB = 1 << 20
COLD_VOCAB = 1 << 24
# stable per-slot hot working set: high within-batch DISTINCT count (the
# PS pays per distinct row) but ~100% across-batch reuse (a cache pool
# serves it) — the regime where the cached tier earns its HBM
HOT_WS = int(os.environ.get("TIERING_HOT_WS", str(1 << 13)))
CACHE_ROWS = 1 << 16          # = BENCH_100T.json capacity_per_replica
FILL_STEPS = int(os.environ.get("TIERING_FILL_STEPS", "250"))
PROFILE_STEPS = 24            # auto: fenced profiling prefix of the fill
FENCE_EVERY = 8
MEASURE_STEPS = int(os.environ.get("TIERING_MEASURE_STEPS", "30"))
DISPATCH_K = 4
PS_WIRE = os.environ.get("TIERING_PS_WIRE", "int8")  # repo default (bench.py)

# ---------------------------------------------------- published references
# chip HBM + bytes/row: the BENCH_100T.json capacity arithmetic (f32 row +
# optimizer state + entry metadata at dim 16)
HBM_BYTES = 16.0e9            # TPU v5e
BYTES_PER_ROW = 160

SLOT_NAMES = (
    [f"pin_{i}" for i in range(PIN_SLOTS)]
    + [f"hot_{i}" for i in range(HOT_SLOTS)]
    + [f"cold_{i}" for i in range(COLD_SLOTS)]
)
VOCAB_OF = {}
for _i in range(PIN_SLOTS):
    VOCAB_OF[f"pin_{_i}"] = PIN_VOCAB
for _i in range(HOT_SLOTS):
    VOCAB_OF[f"hot_{_i}"] = HOT_VOCAB
for _i in range(COLD_SLOTS):
    VOCAB_OF[f"cold_{_i}"] = COLD_VOCAB
TOTAL_ROWS = sum(VOCAB_OF.values())
COLD_NAMES = [n for n in SLOT_NAMES if n.startswith("cold_")]


def _ids_for(rng, offsets, name):
    v = VOCAB_OF[name]
    if name.startswith("pin_"):
        return rng.integers(0, v, BATCH).astype(np.uint64)
    if name.startswith("cold_"):
        return rng.integers(0, v, BATCH).astype(np.uint64)
    return (
        rng.integers(0, HOT_WS, BATCH).astype(np.uint64)
        + np.uint64(offsets[name])
    ) % v


def _stream(seed=7):
    """The shared id/dense/label stream: every mode consumes the same
    batches (same seed -> same draws), so the comparison is apples-equal.
    The hot working-set OFFSETS are a property of the workload, not the
    phase — always derived from a fixed seed, so the fill and measure
    streams (different draw seeds) sample the same working sets."""
    base = np.random.default_rng(7)
    offsets = {n: int(base.integers(0, VOCAB_OF[n])) for n in SLOT_NAMES}
    return np.random.default_rng(seed), offsets


def _persia_batches(count, seed=7):
    from persia_tpu.data import (
        IDTypeFeatureWithSingleID,
        Label,
        NonIDTypeFeature,
        PersiaBatch,
    )

    rng, offsets = _stream(seed)
    for _ in range(count):
        yield PersiaBatch(
            [
                IDTypeFeatureWithSingleID(n, _ids_for(rng, offsets, n))
                for n in SLOT_NAMES
            ],
            non_id_type_features=[NonIDTypeFeature(
                rng.normal(size=(BATCH, N_DENSE)).astype(np.float32)
            )],
            labels=[Label(
                rng.integers(0, 2, (BATCH, 1)).astype(np.float32)
            )],
            requires_grad=True,
        )


def measured_distinct_per_step(sample_batches=16):
    """Exact mean distinct-sign count per slot per batch (the unit the PS
    tier pays in: checkout + gradient return are per DISTINCT row)."""
    rng, offsets = _stream()
    acc = {n: 0 for n in SLOT_NAMES}
    for _ in range(sample_batches):
        for n in SLOT_NAMES:
            acc[n] += np.unique(_ids_for(rng, offsets, n)).size
    return {n: acc[n] / sample_batches for n in SLOT_NAMES}


# ----------------------------------------------------------- wire arithmetic

def _grad_wire_bytes(rows_per_step):
    """d2h gradient-return bytes/step for PS-placed rows at the configured
    wire dtype (int8 error-feedback wire by default, bench.py's published
    ps-stream config: 1 B/element + per-slot absmax scales)."""
    width = {"int8": 1, "bfloat16": 2, "float32": 4}[PS_WIRE]
    return rows_per_step * DIM * width


def _evict_wire_bytes(rows_per_step):
    # bf16 eviction wire: embedding row + Adagrad accumulator aux
    return rows_per_step * (DIM * 2 + DIM * 2)


# ------------------------------------------------------------------- modes

def _small_dlrm():
    """Deliberately small dense model: this record prices the SPARSE-tier
    machinery (what tiering changes), not MLP FLOPs — bench.py's full
    DLRM shape keeps the headline records."""
    from persia_tpu.models import DLRM

    return DLRM(embedding_dim=DIM, bottom_mlp=(64, 32, DIM), top_mlp=(64, 32))


def _cached_ctx(ps_slots):
    import optax

    from persia_tpu.config import EmbeddingConfig, SlotConfig
    from persia_tpu.embedding.hbm_cache import CachedTrainCtx
    from persia_tpu.embedding.native_store import create_store
    from persia_tpu.embedding.optim import Adagrad
    from persia_tpu.embedding.worker import EmbeddingWorker

    cfg = EmbeddingConfig(
        slots_config={n: SlotConfig(dim=DIM) for n in SLOT_NAMES},
        feature_index_prefix_bit=8,
    )
    store = create_store(
        "auto", capacity=1 << 24, num_internal_shards=16,
        optimizer=Adagrad(lr=0.05).config, seed=1,
    )
    worker = EmbeddingWorker(cfg, [store], num_threads=4, device_pooling=True)
    return CachedTrainCtx(
        model=_small_dlrm(), dense_optimizer=optax.adam(1e-3),
        embedding_optimizer=Adagrad(lr=0.05), worker=worker,
        embedding_config=cfg, cache_rows=CACHE_ROWS, ps_slots=ps_slots,
        ps_wire_dtype=PS_WIRE, init_seed=3,
    ).__enter__()


def _metric_sum(name):
    from persia_tpu.metrics import get_metrics

    snap = get_metrics().snapshot(prefix="persia_tpu_")
    return sum((snap.get(name) or {}).values())


def _measure_stream(ctx, start_step):
    """The timed saturated window (store filled, cache warm, placement
    final): throughput plus the per-step eviction wire actually paid.
    Hit rate and evictions are deltas over the window, not cumulative —
    the fill phase's deliberate thrash is not the saturated number."""
    hit0 = _metric_sum("persia_tpu_cache_hit_count")
    miss0 = _metric_sum("persia_tpu_cache_miss_count")
    ev0 = _metric_sum("persia_tpu_cache_evict_count")
    t0 = time.perf_counter()
    ctx.train_stream(
        _persia_batches(MEASURE_STEPS, seed=29), fetch_final=False,
        dispatch_k=DISPATCH_K, start_step=start_step,
    )
    elapsed = time.perf_counter() - t0
    m = ctx.last_metrics()
    assert m is not None and np.isfinite(m["loss"])
    evict_rows = (_metric_sum("persia_tpu_cache_evict_count") - ev0) / MEASURE_STEPS
    hit = _metric_sum("persia_tpu_cache_hit_count") - hit0
    miss = _metric_sum("persia_tpu_cache_miss_count") - miss0
    st = ctx.stream_stats() or {}
    return {
        "samples_per_sec_host_cpu": round(MEASURE_STEPS * BATCH / elapsed, 1),
        "feeder_util": (
            round(st.get("feeder_busy_s", 0.0) / st["wall_s"], 3)
            if st.get("wall_s") else None
        ),
        "tiers": st.get("tiers"),
        "migrations": st.get("migrations", 0),
        "cache_hit_rate": (
            round(hit / (hit + miss), 4) if hit + miss else None
        ),
        "evict_rows_per_step": round(evict_rows, 1),
    }


def _ps_rows_per_step(ps_slots, distinct):
    return sum(distinct[n] for n in ps_slots)


def bench_fused_all():
    import jax
    import jax.numpy as jnp
    import optax

    from persia_tpu.embedding.optim import Adagrad
    from persia_tpu.parallel.fused_step import (
        FusedSlotSpec,
        build_fused_train_step,
        init_fused_state,
    )

    specs = {n: FusedSlotSpec(vocab=VOCAB_OF[n], dim=DIM) for n in SLOT_NAMES}
    order = sorted(specs)
    model = _small_dlrm()
    step = build_fused_train_step(
        model, optax.adam(1e-3), Adagrad(lr=0.05).config, specs, order,
        jit=True, stack=True,
    )
    rng, offsets = _stream()

    def make_batch():
        return {
            "dense": [rng.normal(size=(BATCH, N_DENSE)).astype(np.float32)],
            "labels": [rng.integers(0, 2, (BATCH, 1)).astype(np.float32)],
            "ids": {
                n: jnp.asarray(_ids_for(rng, offsets, n).astype(np.int32))
                for n in order
            },
        }

    t0 = time.perf_counter()
    state = init_fused_state(
        model, jax.random.PRNGKey(0), specs, make_batch(),
        optax.adam(1e-3), Adagrad(lr=0.05).config, stack=True,
    )
    # JAX004: init_fused_state returns as soon as the last table init is
    # DISPATCHED — without the sync init_s measured enqueue, not the
    # actual table/optimizer-state materialization the number claims
    jax.block_until_ready(state)
    init_s = time.perf_counter() - t0
    batches = [make_batch() for _ in range(6)]
    for i in range(5):
        state, (loss, _) = step(state, batches[i % 6])
    loss.block_until_ready()
    t0 = time.perf_counter()
    for i in range(MEASURE_STEPS):
        state, (loss, _) = step(state, batches[i % 6])
    loss.block_until_ready()
    elapsed = time.perf_counter() - t0
    table_bytes = TOTAL_ROWS * BYTES_PER_ROW
    return {
        "samples_per_sec_host_cpu": round(MEASURE_STEPS * BATCH / elapsed, 1),
        "init_s": round(init_s, 1),
        "table_rows": TOTAL_ROWS,
        "table_gb_at_bytes_per_row": round(table_bytes / 1e9, 2),
        "fits_device_hbm": bool(table_bytes <= HBM_BYTES),
        "d2h_bytes_per_step": 0,
    }


def bench_cached_all(distinct):
    ctx = _cached_ctx(ps_slots=[])
    ctx.train_stream(
        _persia_batches(FILL_STEPS), fetch_final=False, dispatch_k=DISPATCH_K,
    )
    rec = _measure_stream(ctx, start_step=FILL_STEPS)
    # wire bill on a chip: the cold flood's admit (h2d) + evict (d2h) churn
    rec["d2h_bytes_per_step"] = round(
        _evict_wire_bytes(rec["evict_rows_per_step"])
    )
    return rec


def bench_ps_all(distinct):
    ctx = _cached_ctx(ps_slots=list(SLOT_NAMES))
    ctx.train_stream(
        _persia_batches(FILL_STEPS), fetch_final=False, dispatch_k=DISPATCH_K,
    )
    rec = _measure_stream(ctx, start_step=FILL_STEPS)
    rows = _ps_rows_per_step(SLOT_NAMES, distinct)
    rec["ps_rows_per_step"] = round(rows)
    rec["d2h_bytes_per_step"] = round(_grad_wire_bytes(rows))
    return rec


def bench_auto(distinct):
    from persia_tpu.embedding.tiering import enable_auto_tier

    ctx = _cached_ctx(ps_slots=[])  # naive start: everything cached
    # reuse = decayed_total/unique: the hot slots score ~2 (each working-set
    # row re-hit ~2x per decay window at this batch), the heavy tail ~0.5 —
    # admit at 1.5 so both sides clear the hysteresis margin decisively
    ctrl = enable_auto_tier(
        ctx, cached_min_reuse=1.5, min_dwell=1, vocabs=dict(VOCAB_OF),
        fused_row_budget=PIN_SLOTS * PIN_VOCAB,
    )
    before = dict(ctrl.placements)
    td = tempfile.mkdtemp(prefix="tiering_bench_js_")
    # fenced profiling prefix: the sketch sees the stream, the planner
    # demotes the heavy-tail slots at a live fence (feeder parked, ledger
    # drained, manifest committed), pins/hot stay device-side
    ctx.train_stream(
        _persia_batches(PROFILE_STEPS), fetch_final=False,
        dispatch_k=DISPATCH_K, snapshot_every=FENCE_EVERY, job_state=td,
    )
    placements = dict(ctrl.placements)
    migrated = sorted(s for s in placements if placements[s] != before[s])
    # rest of the fill in the final placement (same store fill as the
    # single-tier modes), then the timed saturated window
    ctx.train_stream(
        _persia_batches(FILL_STEPS - PROFILE_STEPS, seed=11),
        fetch_final=False, dispatch_k=DISPATCH_K, start_step=PROFILE_STEPS,
    )
    rec = _measure_stream(ctx, start_step=FILL_STEPS)
    ps_now = sorted(s for s, t in placements.items() if t == "ps")
    rows = _ps_rows_per_step(ps_now, distinct)
    rec.update({
        "placements_before": before,
        "placements_after": placements,
        "migrated_slots": migrated,
        "tiering_migrations_metric": int(
            _metric_sum("persia_tpu_tiering_migrations")
        ),
        "flap_suppressed_metric": int(
            _metric_sum("persia_tpu_tiering_flap_suppressed")
        ),
        "ps_rows_per_step": round(rows),
        "d2h_bytes_per_step": round(
            _grad_wire_bytes(rows)
            + _evict_wire_bytes(rec["evict_rows_per_step"])
        ),
    })
    return rec


_MODES = {
    "fused-all": lambda d: bench_fused_all(),
    "cached-all": bench_cached_all,
    "ps-all": bench_ps_all,
    "auto": bench_auto,
}


def _run_mode_isolated(mode):
    """One fresh subprocess per mode (bench.py convention): no shared JAX
    allocations, metrics, or store state across configs."""
    import subprocess

    budget_s = float(os.environ.get("TIERING_MODE_BUDGET_S", "900"))
    env = dict(os.environ, TIERING_MODE=mode)
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env, capture_output=True, text=True, timeout=budget_s,
        )
    except subprocess.TimeoutExpired:
        return {"error": "budget exceeded"}
    for line in reversed((out.stdout or "").strip().splitlines()):
        try:
            d = json.loads(line)
        except ValueError:
            continue
        if isinstance(d, dict) and "mode_result" in d:
            return d["mode_result"]
    return {
        "error": f"rc={out.returncode}",
        "stderr_tail": "\n".join(
            (out.stderr or "").strip().splitlines()[-6:]
        ),
    }


def main():
    mode = os.environ.get("TIERING_MODE")
    distinct = measured_distinct_per_step()
    if mode:
        rec = _MODES[mode](distinct)
        print(json.dumps({"mode_result": rec}), flush=True)
        return

    import jax

    results = {m: _run_mode_isolated(m) for m in _MODES}
    out = {
        "bench": "tiering_mixed_skew",
        "platform": jax.default_backend(),
        "workload": {
            "batch_size": BATCH,
            "embedding_dim": DIM,
            "slots": {
                "pin": {"n": PIN_SLOTS, "vocab": PIN_VOCAB},
                "hot": {"n": HOT_SLOTS, "vocab": HOT_VOCAB,
                        "working_set": HOT_WS},
                "cold": {"n": COLD_SLOTS, "vocab": COLD_VOCAB},
            },
            "distinct_rows_per_batch": {
                k: round(v, 1) for k, v in distinct.items()
            },
            "fill_steps": FILL_STEPS,
            "measure_steps": MEASURE_STEPS,
        },
        "device_budget": {
            "hbm_gb": HBM_BYTES / 1e9,
            "bytes_per_row": BYTES_PER_ROW,
            "total_vocab_rows": TOTAL_ROWS,
            "total_vocab_gb": round(TOTAL_ROWS * BYTES_PER_ROW / 1e9, 2),
            "cache_rows": CACHE_ROWS,
        },
        "modes": results,
        "note": (
            "samples_per_sec_host_cpu is measured on a chipless build "
            "host (jax cpu backend): the 'device' IS the host core and "
            "there is no host<->device wire, so device-side cache "
            "machinery buys nothing there (CPU-host numbers are NOT chip "
            "numbers). The host run still measures the real workload "
            "structure this bench exists for: cached-all collapses under "
            "heavy-tail eviction thrash, auto live-migrates the heavy-tail "
            "slots to the PS at a fence and recovers the cached tier's hit "
            "rate, and the per-step PS/evict row counts are measured, not "
            "assumed. What each mode runs at on the chip is not measured."
        ),
        "env": {
            "TIERING_BATCH": BATCH,
            "TIERING_HOT_WS": HOT_WS,
            "TIERING_FILL_STEPS": FILL_STEPS,
            "TIERING_MEASURE_STEPS": MEASURE_STEPS,
            "TIERING_PS_WIRE": PS_WIRE,
        },
    }
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
