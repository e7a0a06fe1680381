"""Benchmark: DLRM (Criteo shape) training throughput on a TPU.

Fails at start when JAX finds no TPU: a CPU run yields correctness and
counts, never a device metric. Every record names the device it ran on.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Config mirrors the Criteo-DLRM shape (BASELINE.json): 13 dense features,
26 categorical slots (dim 16, vocab 1M each), batch 4096.

Default mode = the TPU-native fused path: all 26 tables resident in HBM,
the whole hybrid step (gather → DLRM fwd/bwd → optax dense update →
duplicate-safe sparse Adagrad) is ONE jitted XLA program
(persia_tpu/parallel/fused_step.py). Host↔device traffic per step is just
the raw batch: one int32 id buffer + one f32 dense/label buffer in; loss
stays on device and is fetched once at the end. This is the idiomatic TPU
answer to the reference's async CPU-PS pipeline for tables that fit in HBM;
the C++ host-PS tier (BENCH_MODE=hybrid) remains the capacity tier for
beyond-HBM vocab (reference's 100T regime, README.md:29).

``vs_baseline`` divides measured samples/sec by REF_SAMPLES_PER_SEC, the
derived per-A100 DLRM training throughput (BASELINE.md shows the
arithmetic; the reference repo publishes no absolute numbers). ``mfu`` is
model-FLOPs utilization: dense-model train FLOPs/sample (computed below
from the bench shape) x samples/sec / the chip's bf16 peak, taken from
PEAK_BF16_FLOPS by ``device_kind`` — DLRM is embedding/wire-bound, so
single-digit MFU is the honest, expected number (the FLOPs are in the
MLPs; the work is in the gathers and the wires).
"""

import json
import os
import time

import numpy as np

# Derived per-A100 anchor (see BASELINE.md "Per-A100 baseline"): public
# HugeCTR/MLPerf-class DLRM training lands ~3.5M samples/s on a DGX-A100
# (8xA100) => ~440k per A100; rounded UP to 500k as a generous anchor.
REF_SAMPLES_PER_SEC = 500_000.0

BATCH_SIZE = 4096
N_DENSE = 13
N_SLOTS = 26
EMB_DIM = 16
VOCAB = 1_000_000
BOTTOM_MLP = (256, 64, EMB_DIM)
TOP_MLP = (512, 256)
WARMUP_STEPS = 5
MEASURE_STEPS = 200

# Peak dense bf16 FLOP/s per chip, keyed by ``jax.devices()[0].device_kind``.
# A kind that is not listed is an error, never a default.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16).
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def _device() -> dict:
    """The accelerator this process measures on, as JAX reports it. A run
    that finds no TPU stops here: nothing below may print a device metric
    from a CPU."""
    import jax

    from persia_tpu.compile_cache import enable_compile_cache

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU and JAX found platform {d.platform!r} "
            f"({d.device_kind}); a CPU run yields no device metric"
        )
    enable_compile_cache()
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def _peak_bf16_flops(device: dict) -> float:
    try:
        return PEAK_BF16_FLOPS[device["kind"]]
    except KeyError:
        raise SystemExit(
            f"no peak listed for device_kind {device['kind']!r}: add it to "
            "PEAK_BF16_FLOPS with its source before reporting a utilization"
        ) from None


class _Progress:
    """Per-mode progress reporter: a ``{"bench_progress": ...}`` JSON line
    every ``every`` steps, so a long mode shows it is alive. Progress lines
    are never a result: a mode that dies or blows its budget fails the
    suite (see ``_run_mode_isolated``)."""

    def __init__(self, every: int = 25):
        self.every = every
        self.t0 = None
        self.n = 0

    def start(self):
        self.t0 = time.perf_counter()

    def tick(self):
        self.n += 1
        if self.t0 is not None and self.n % self.every == 0:
            el = time.perf_counter() - self.t0
            print(json.dumps({"bench_progress": {
                "steps": self.n,
                "samples_per_sec": round(self.n * BATCH_SIZE / el, 1),
            }}), flush=True)

    def wrap(self, batches):
        """Count batches as the stream's feeder consumes them — runs ahead
        of device execution by <= the prefetch depth, so the rates on
        these lines slightly overestimate."""
        for b in batches:
            yield b
            self.tick()


def _model_train_flops_per_sample() -> float:
    """Dense-model training FLOPs per sample at the bench shape (matmul
    FLOPs, MAC=2; backward ~= 2x forward; embedding gather/update FLOPs
    excluded by the usual model-FLOPs convention).

    bottom MLP 13->256->64->16, interaction einsum over 27 vectors of
    dim 16 (full (27,27) product as executed on the MXU), top MLP
    (16+351)->512->256->1."""
    bottom = 13 * 256 + 256 * 64 + 64 * 16
    n_vec = N_SLOTS + 1
    interact = n_vec * n_vec * EMB_DIM
    top_in = EMB_DIM + n_vec * (n_vec - 1) // 2
    top = top_in * 512 + 512 * 256 + 256 * 1
    fwd = 2 * (bottom + interact + top)
    return 3.0 * fwd  # fwd + ~2x fwd backward


def bench_fused():
    import jax
    import jax.numpy as jnp
    import optax

    from persia_tpu.embedding.optim import Adagrad
    from persia_tpu.models import DLRM
    from persia_tpu.parallel.fused_step import (
        FusedSlotSpec,
        build_fused_train_step,
        init_fused_state,
        pack_ids,
        unpack_ids,
    )

    stack = os.environ.get("BENCH_STACK", "1") == "1"
    specs = {f"cat_{i}": FusedSlotSpec(vocab=VOCAB, dim=EMB_DIM) for i in range(N_SLOTS)}
    slot_order = sorted(specs)
    model = DLRM(embedding_dim=EMB_DIM, bottom_mlp=BOTTOM_MLP, top_mlp=TOP_MLP)
    sparse_cfg = Adagrad(lr=0.05).config
    dense_opt = optax.adam(1e-3)

    rng = np.random.default_rng(0)

    def make_host_batch():
        ids, _ = pack_ids(
            {
                n: rng.integers(0, VOCAB, BATCH_SIZE, dtype=np.int32)
                for n in slot_order
            },
            slot_order,
        )
        densel = np.concatenate(
            [
                rng.normal(size=(BATCH_SIZE, N_DENSE)).astype(np.float32),
                rng.integers(0, 2, (BATCH_SIZE, 1)).astype(np.float32),
            ],
            axis=1,
        )
        return ids, densel

    id_shapes = [(BATCH_SIZE,)] * N_SLOTS

    raw_step = build_fused_train_step(
        model, dense_opt, sparse_cfg, specs, slot_order, jit=False, stack=stack
    )

    def packed_step(state, flat_ids, densel):
        ids = unpack_ids(flat_ids, slot_order, id_shapes)
        batch = {
            "dense": [jax.lax.slice(densel, (0, 0), (BATCH_SIZE, N_DENSE))],
            "labels": [jax.lax.slice(densel, (0, N_DENSE), (BATCH_SIZE, N_DENSE + 1))],
            "ids": ids,
        }
        return raw_step(state, batch)

    step = jax.jit(packed_step, donate_argnums=(0,))
    # BENCH_FUSED_K>1: amortize dispatch overhead across K steps with one
    # jitted multi-step program (the fused-path analogue of the cached
    # stream's dispatch_k; parallel/fused_step.build_fused_multi_step is
    # the library form)
    K = max(1, int(os.environ.get("BENCH_FUSED_K", "1")))

    def multi_body(state, ids_t, dl_t):
        loss = None
        for ids, dl in zip(ids_t, dl_t):
            state, (loss, _) = packed_step(state, ids, dl)
        return state, loss

    multi = jax.jit(multi_body, donate_argnums=(0,)) if K > 1 else None

    # init on a sample batch
    ids0, dl0 = make_host_batch()
    sample = {
        "dense": [dl0[:, :N_DENSE]],
        "labels": [dl0[:, N_DENSE:]],
        "ids": {
            n: jnp.asarray(ids0.reshape(N_SLOTS, BATCH_SIZE)[i])
            for i, n in enumerate(slot_order)
        },
    }
    state = init_fused_state(
        model, jax.random.PRNGKey(0), specs, sample, dense_opt, sparse_cfg,
        stack=stack,
    )

    host_batches = [make_host_batch() for _ in range(8)]

    def group(i):
        picks = [host_batches[(i + j) % len(host_batches)] for j in range(K)]
        return (
            tuple(jnp.asarray(g[0]) for g in picks),
            tuple(jnp.asarray(g[1]) for g in picks),
        )

    if K > 1:
        for i in range(0, max(WARMUP_STEPS, K), K):
            ids_t, dl_t = group(i)
            state, loss = multi(state, ids_t, dl_t)
        loss.block_until_ready()
        steps_run = ((MEASURE_STEPS + K - 1) // K) * K
        t0 = time.perf_counter()
        for i in range(0, steps_run, K):
            ids_t, dl_t = group(i)
            state, loss = multi(state, ids_t, dl_t)
        loss.block_until_ready()
        elapsed = time.perf_counter() - t0
        return _fused_record(steps_run * BATCH_SIZE / elapsed, k=K)

    for i in range(WARMUP_STEPS):
        ids, dl = host_batches[i % len(host_batches)]
        state, (loss, _) = step(state, jnp.asarray(ids), jnp.asarray(dl))
    loss.block_until_ready()

    t0 = time.perf_counter()
    for i in range(MEASURE_STEPS):
        ids, dl = host_batches[i % len(host_batches)]
        state, (loss, _) = step(state, jnp.asarray(ids), jnp.asarray(dl))
    loss.block_until_ready()
    elapsed = time.perf_counter() - t0
    return _fused_record(MEASURE_STEPS * BATCH_SIZE / elapsed, k=1)


def _fused_record(samples_per_sec: float, k: int) -> dict:
    """The fused-tier mode record: like _stream_record, it carries the
    dense-plane sync fields — "local"/0 by construction (one device, one
    program), but stated explicitly so fused/stream/hybrid rows compare on
    the same vocabulary instead of by omission."""
    return {
        "samples_per_sec": round(samples_per_sec, 1),
        "dispatch_mode": f"fused-k{k}" if k > 1 else "fused",
        "sync_mode": "local",
        "dense_wire_bytes_per_step": 0,
    }


def bench_link():
    """Measure the host↔device link of the first device (one ~4 MiB
    transfer each way + the small-fetch round-trip). The number
    contextualizes every wire-bound mode: ps-stream and hybrid are
    physically capped at link_d2h / grad_bytes_per_sample samples/sec, so
    the record of WHAT the link did during the run is part of the result."""
    import jax

    dev = jax.devices()[0]
    add = jax.jit(lambda x, i: x + i)
    a = np.random.default_rng(0).standard_normal(1 << 20, dtype=np.float32)  # 4 MiB
    bufs = [a + np.float32(i) for i in range(4)]
    t0 = time.perf_counter()
    ys = [jax.device_put(b, dev) for b in bufs]
    jax.block_until_ready(ys)
    h2d = 4 * len(bufs) / (time.perf_counter() - t0)
    zs = [add(ys[0], float(i)) for i in range(4)]
    jax.block_until_ready(zs)
    t0 = time.perf_counter()
    for z in zs:
        np.asarray(z)
    d2h = 4 * len(zs) / (time.perf_counter() - t0)
    small = add(ys[0][:256], 1.0)
    small.block_until_ready()
    t0 = time.perf_counter()
    for i in range(5):
        np.asarray(add(ys[0][:256], float(i)))
    rt_ms = (time.perf_counter() - t0) / 5 * 1e3
    return {
        "h2d_MBps": round(h2d, 1),
        "d2h_MBps": round(d2h, 1),
        "small_d2h_roundtrip_ms": round(rt_ms, 3),
    }


def _zipf_ids(rng, n, vocab, offset, a=1.2):
    """Rank-skewed ids (production-like): zipf ranks clipped into [0, vocab).
    ``offset`` is a FIXED per-slot shift so each slot has its own stable hot
    set (stable across batches — that is what a cache can exploit) while
    slots stay decorrelated from each other."""
    raw = rng.zipf(a, n).astype(np.uint64)
    return (raw + np.uint64(offset)) % vocab


def _cached_tier_ctx(ps_all: bool = False):
    """THE bench configuration of the cached/ps tiers, shared by
    bench_cached, bench_ps_stream and the quality gate — the quality
    assertion prices exactly the configuration the throughput headline
    runs, env knobs included (one builder, no copy to drift).

    bf16 eviction + checkout wires (the reference ships f16 wires,
    lib.rs:157-180) halve the host↔device bytes; the in-HBM training math
    and the checkpoint flush stay f32. Touch-gated admission (the
    reference's admit_probability semantics: non-admitted signs read
    zeros, their gradients drop) keeps one-hit-wonder zipf-tail signs out,
    collapsing steady-state evictions to the recurring working set."""
    import optax

    from persia_tpu.config import EmbeddingConfig, SlotConfig
    from persia_tpu.embedding.hbm_cache import CachedTrainCtx
    from persia_tpu.embedding.native_store import create_store
    from persia_tpu.embedding.optim import Adagrad
    from persia_tpu.embedding.worker import EmbeddingWorker
    from persia_tpu.models import DLRM

    cfg = EmbeddingConfig(
        slots_config={f"cat_{i}": SlotConfig(dim=EMB_DIM) for i in range(N_SLOTS)},
        feature_index_prefix_bit=8,
    )
    store = create_store(
        "native", capacity=1 << 25, num_internal_shards=64,
        optimizer=Adagrad(lr=0.05).config, seed=1,
    )
    # device_pooling: PS-tier slots ship per-DISTINCT rows/gradients across
    # the link (~3x fewer d2h bytes at this zipf skew)
    worker = EmbeddingWorker(cfg, [store], num_threads=16, device_pooling=True)
    model = DLRM(embedding_dim=EMB_DIM, bottom_mlp=BOTTOM_MLP, top_mlp=TOP_MLP)
    kw = dict(
        model=model, dense_optimizer=optax.adam(1e-3),
        embedding_optimizer=Adagrad(lr=0.05), worker=worker,
        embedding_config=cfg,
    )
    if ps_all:
        kw.update(
            cache_rows=8,  # unused: every slot rides the PS path
            ps_slots=[f"cat_{i}" for i in range(N_SLOTS)],
            # int8 error-feedback gradient-return wire by default (~4× vs
            # f32, 2× vs the previous bf16 on the d2h ceiling that caps
            # this regime); quality-gated by the int8-vs-f32 parity test
            # (tests/test_hbm_cache.py) and priced by BENCH_MODE=quality.
            # BENCH_PS_WIRE=bfloat16/float32 restores the wider wires.
            ps_wire_dtype=os.environ.get("BENCH_PS_WIRE", "int8"),
        )
    else:
        kw.update(
            # 2M rows in HBM vs 26M-sign PS vocabulary; shrink via env to
            # reach the post-fill eviction steady state in fewer steps
            cache_rows=int(os.environ.get("BENCH_CACHE_ROWS", str(1 << 21))),
            wb_wire_dtype="bfloat16",
            aux_wire_dtype=os.environ.get("BENCH_AUX_WIRE", "bfloat16"),
            admit_touches=int(os.environ.get("BENCH_ADMIT_TOUCHES", "2")),
        )
    return CachedTrainCtx(**kw).__enter__()


def _dispatch_k() -> int:
    """Multi-step fused dispatch depth for the stream modes (the K-step
    hazard-free packing in hbm_cache/stream.py); BENCH_DISPATCH_K=1
    restores the serial one-step-per-dispatch cadence for A/B runs."""
    return int(os.environ.get("BENCH_DISPATCH_K", "8"))


def _stream_record(ctx, samples_per_sec: float) -> dict:
    """The cached-tier mode record: throughput plus the dispatch-mode and
    feeder-utilization fields that make hot-loop regressions visible from
    the committed JSON alone (a saturated number that quietly fell back to
    single-step dispatch, or a feeder pinned at 100%, is a finding)."""
    st = ctx.stream_stats() or {}
    total = st.get("packed_steps", 0) + st.get("single_steps", 0)
    if st.get("dispatch_k", 1) > 1:
        dispatch_mode = f"kstep-{st.get('dispatch_k')}"
    else:
        dispatch_mode = "single"
    rec = {
        "samples_per_sec": round(samples_per_sec, 1),
        "dispatch_mode": dispatch_mode,
        "packed_step_frac": (
            round(st.get("packed_steps", 0) / total, 3) if total else 0.0
        ),
        "packs": st.get("packs", 0),
        "feeder_util": (
            round(st.get("feeder_busy_s", 0.0) / st["wall_s"], 3)
            if st.get("wall_s") else None
        ),
        # resilience accounting: a cached run that trained on degraded
        # (synthetic) lookups must say so in its own record
        "degraded_steps": st.get("degraded_steps", 0),
        "degraded_lookup_frac_max": st.get("degraded_lookup_frac_max", 0.0),
        # tier accounting (auto-tiering observability): where every slot
        # lives at stream end, per-group occupancy, and the cache hit rate
        # — a placement regression shows up here before it shows up in
        # samples_per_sec
        "tiers": st.get("tiers"),
        "migrations": st.get("migrations", 0),
        "cache_hit_rate": _cache_hit_rate(),
        # dense-plane sync accounting (grad_sync mode vocabulary): which
        # collective the dense half rode and its modeled bytes/step — the
        # baseline the block-int8-ring WIRE_BENCH rows are priced against
        "sync_mode": st.get("sync_mode", ctx.sync_mode),
        "dense_wire_bytes_per_step": st.get(
            "dense_wire_bytes_per_step", ctx.dense_wire_bytes_per_step()
        ),
    }
    return rec


def _cache_hit_rate():
    """Process-cumulative HBM hit rate from the tier's metrics (each bench
    mode runs subprocess-isolated, so cumulative == this run)."""
    from persia_tpu.metrics import get_metrics

    snap = get_metrics().snapshot(prefix="persia_tpu_cache_")
    hit = sum((snap.get("persia_tpu_cache_hit_count") or {}).values())
    miss = sum((snap.get("persia_tpu_cache_miss_count") or {}).values())
    return round(hit / (hit + miss), 4) if hit + miss else None


def _zipf_batch_maker(seed: int = 0, batch_size: int = BATCH_SIZE,
                      n_slots: int = N_SLOTS, vocab: int = VOCAB):
    """Batch factory shared by the cached/hybrid/ps-stream modes, the
    stage profiler and chip_smoke.py: single-id zipf streams with a stable
    per-slot hot set, plus dense features and labels. Defaults are the
    bench shape; chip_smoke's CPU test passes a tiny one."""
    from persia_tpu.data import (
        IDTypeFeatureWithSingleID,
        Label,
        NonIDTypeFeature,
        PersiaBatch,
    )

    rng = np.random.default_rng(seed)
    slot_offsets = rng.integers(0, vocab, n_slots, dtype=np.uint64)

    def make_batch():
        ids = [
            IDTypeFeatureWithSingleID(
                f"cat_{i}", _zipf_ids(rng, batch_size, vocab, slot_offsets[i])
            )
            for i in range(n_slots)
        ]
        return PersiaBatch(
            ids,
            non_id_type_features=[
                NonIDTypeFeature(rng.normal(size=(batch_size, N_DENSE)).astype(np.float32))
            ],
            labels=[Label(rng.integers(0, 2, (batch_size, 1)).astype(np.float32))],
            requires_grad=True,
        )

    return make_batch


def bench_cached():
    """The capacity tier with the HBM write-back cache: vocabulary lives on
    the host C++ PS (beyond-HBM regime, reference README.md:29), the working
    set lives in HBM, the sparse optimizer runs on device, and the previous
    step's eviction write-back overlaps the current step
    (persia_tpu/embedding/hbm_cache.py)."""
    steps = int(os.environ.get("BENCH_CACHED_STEPS", "100"))
    ctx = _cached_tier_ctx()

    make_batch = _zipf_batch_maker()

    # distinct batches (not a short cycle): hit rate comes from the zipf
    # skew + warm cache, not from replaying identical batches
    warmup = max(WARMUP_STEPS, 8)
    batches = [make_batch() for _ in range(warmup + steps)]

    # the timed window stays free of device→host fetches
    # (fetch_final=False): the loss header is synced without a transfer
    # and materialized only after the window, so the timing holds no
    # metric fetch the training loop does not need
    ctx.train_stream(batches[:warmup], fetch_final=False,
                     dispatch_k=_dispatch_k())

    prog = _Progress()
    prog.start()
    t0 = time.perf_counter()
    ctx.train_stream(prog.wrap(batches[warmup:]), fetch_final=False,
                     dispatch_k=_dispatch_k())
    elapsed = time.perf_counter() - t0
    m = ctx.last_metrics()  # d2h outside the timed window
    assert m is not None and np.isfinite(m["loss"])
    return _stream_record(ctx, steps * BATCH_SIZE / elapsed)


def bench_cached_saturated():
    """Steady-state eviction regime on the record: a deliberately small
    cache (default 2^18 rows vs the 26M-sign stream) trained long enough
    (>=600 steps) that fills finish and every step carries real eviction
    write-back traffic — the number the README previously only simulated.
    Same builder/env knobs as the headline cached mode."""
    steps = int(os.environ.get("BENCH_CACHED_SAT_STEPS", "600"))
    os.environ.setdefault("BENCH_CACHE_ROWS", str(1 << 18))
    ctx = _cached_tier_ctx()
    make_batch = _zipf_batch_maker()
    warmup = 8
    batches = [make_batch() for _ in range(warmup + steps)]
    ctx.train_stream(batches[:warmup], fetch_final=False,
                     dispatch_k=_dispatch_k())
    prog = _Progress()
    prog.start()
    t0 = time.perf_counter()
    ctx.train_stream(prog.wrap(batches[warmup:]), fetch_final=False,
                     dispatch_k=_dispatch_k())
    elapsed = time.perf_counter() - t0
    m = ctx.last_metrics()
    assert m is not None and np.isfinite(m["loss"])
    return _stream_record(ctx, steps * BATCH_SIZE / elapsed)


def bench_ps_stream():
    """The PERSIA-parity fully-async regime: ALL slots PS-resident (no HBM
    cache rows at all), driven through ``CachedTrainCtx.train_stream`` —
    forwards run in the stream's feeder thread, gradients return as bf16
    through the write-back thread's batched CONCURRENT d2h fetches, so the
    pipeline trains under bounded staleness ≤ prefetch + psgrad_batch (the
    reference's lookup-worker regime, forward.rs:640-779).

    Ceiling note: this regime's throughput is bound above by the
    device→host gradient wire — samples/sec ≤ d2h_bandwidth /
    grad_bytes_per_sample (26·16 B/sample on the int8 wire); ``bench_link``
    measures the bandwidth. Gradients of cached slots never leave the chip,
    which is the architectural argument for the cached tier.
    """
    steps = int(os.environ.get("BENCH_PS_STREAM_STEPS", "30"))
    ctx = _cached_tier_ctx(ps_all=True)

    make_batch = _zipf_batch_maker()

    warmup = 4
    batches = [make_batch() for _ in range(warmup + steps)]
    ctx.train_stream(batches[:warmup], prefetch=4, psgrad_batch=16,
                     fetch_final=False)
    prog = _Progress(every=5)
    prog.start()
    t0 = time.perf_counter()
    ctx.train_stream(prog.wrap(batches[warmup:]), prefetch=4, psgrad_batch=16,
                     fetch_final=False)
    elapsed = time.perf_counter() - t0
    m = ctx.last_metrics()
    assert m is not None and np.isfinite(m["loss"])
    return steps * BATCH_SIZE / elapsed


def bench_hybrid():
    """The host C++ PS tier driven by the legacy per-step sync path with
    the DataLoader's pipelined lookups (bounded staleness = loader
    staleness); the fully-streamed async number is BENCH_MODE=ps-stream."""
    import optax

    from persia_tpu.config import EmbeddingConfig, SlotConfig
    from persia_tpu.ctx import TrainCtx
    from persia_tpu.data_loader import DataLoader
    from persia_tpu.embedding.native_store import create_store
    from persia_tpu.embedding.optim import Adagrad
    from persia_tpu.embedding.worker import EmbeddingWorker
    from persia_tpu.models import DLRM

    steps = int(os.environ.get("BENCH_HYBRID_STEPS", "100"))
    cfg = EmbeddingConfig(
        slots_config={f"cat_{i}": SlotConfig(dim=EMB_DIM) for i in range(N_SLOTS)},
        feature_index_prefix_bit=8,
    )
    store = create_store(
        "native", capacity=1 << 25, num_internal_shards=64,
        optimizer=Adagrad(lr=0.05).config, seed=1,
    )
    # device_pooling: only per-DISTINCT rows cross the host↔device link in
    # either direction (~3x fewer wire bytes at this zipf skew than (B,dim)
    # pooled tensors)
    worker = EmbeddingWorker(cfg, [store], num_threads=16, device_pooling=True)
    model = DLRM(embedding_dim=EMB_DIM, bottom_mlp=BOTTOM_MLP, top_mlp=TOP_MLP)
    ctx = TrainCtx(
        model=model, dense_optimizer=optax.adam(1e-3),
        embedding_optimizer=Adagrad(lr=0.05), worker=worker,
        embedding_config=cfg, wire_dtype="bfloat16",
    ).__enter__()

    # single-id contiguous wire (the production shape; also what cached and
    # ps-stream use): distinct batches at 100+ steps would not fit in host
    # RAM as per-sample array lists
    make_batch = _zipf_batch_maker()

    # distinct batches end to end (no short replay cycle: the PS LRU must
    # see the real zipf stream, not a warmed 8-batch loop)
    batches = [make_batch() for _ in range(WARMUP_STEPS + steps)]

    for i in range(WARMUP_STEPS):
        ctx.train_step(batches[i])

    loader = DataLoader(
        iter(batches[WARMUP_STEPS:]), ctx, num_workers=4, staleness=4
    )
    prog = _Progress()
    prog.start()
    t0 = time.perf_counter()
    for tb in loader:
        # defer the header fetch out of the loop (the gradient d2h is
        # inherent to the PS path; the metric d2h is not)
        ctx.train_step_prepared(tb, loader, fetch_metrics=False)
        prog.tick()
    loader.flush()
    elapsed = time.perf_counter() - t0
    m = ctx.last_prepared_metrics()
    assert m is not None and np.isfinite(m["loss"])
    return steps * BATCH_SIZE / elapsed


# -------------------------------------------------- quality-at-throughput


def _quality_data(steps: int):
    """Shared learnable stream (CriteoSynthetic: hidden ground-truth model,
    deterministic per batch_id) split into one training epoch + a held-out
    eval tail. Identical for every tier — same seed, same step budget."""
    from persia_tpu.testing.datasets import CriteoSynthetic

    eval_batches = 4
    ds = CriteoSynthetic(
        num_samples=(steps + eval_batches) * BATCH_SIZE,
        vocab_sizes=[VOCAB] * N_SLOTS,
        seed=5, task_seed=7,
    )
    all_b = list(ds.batches(BATCH_SIZE))
    return all_b[:steps], all_b[steps:]


def _auc_of(preds, labels) -> float:
    from persia_tpu.testing.synthetic import roc_auc

    return float(roc_auc(np.concatenate(labels), np.concatenate(preds)))


def _quality_cached(steps, ps_all=False):
    train_b, eval_b = _quality_data(steps)
    # the SAME builder the throughput benches use (env knobs included):
    # the quality number prices exactly the configuration of the headline
    ctx = _cached_tier_ctx(ps_all=ps_all)
    stream_kw = dict(fetch_final=False)
    if ps_all:
        stream_kw.update(prefetch=4, psgrad_batch=16)
    # first two batches train UNTIMED (jit compilation happens there); the
    # quality epoch still covers every batch exactly once
    ctx.train_stream(train_b[:2], **stream_kw)
    t0 = time.perf_counter()
    ctx.train_stream(train_b[2:], **stream_kw)
    elapsed = time.perf_counter() - t0
    preds, labels = [], []
    for b in eval_b:
        preds.append(ctx.eval_batch(b).reshape(-1))
        labels.append(np.asarray(b.labels[0].data).reshape(-1))
    return {
        "samples_per_sec": round((steps - 2) * BATCH_SIZE / elapsed, 1),
        "auc": round(_auc_of(preds, labels), 10),
    }


def _quality_fused(steps):
    import jax
    import jax.numpy as jnp
    import optax

    from persia_tpu.embedding.optim import Adagrad
    from persia_tpu.models import DLRM
    from persia_tpu.parallel.fused_step import (
        FusedSlotSpec,
        build_fused_eval_step,
        build_fused_train_step,
        init_fused_state,
    )

    train_b, eval_b = _quality_data(steps)
    specs = {f"cat_{i}": FusedSlotSpec(vocab=VOCAB, dim=EMB_DIM) for i in range(N_SLOTS)}
    slot_order = sorted(specs)
    model = DLRM(embedding_dim=EMB_DIM, bottom_mlp=BOTTOM_MLP, top_mlp=TOP_MLP)
    dense_opt = optax.adam(1e-3)
    sparse_cfg = Adagrad(lr=0.05).config
    step = build_fused_train_step(
        model, dense_opt, sparse_cfg, specs, slot_order, stack=True
    )
    eval_step = build_fused_eval_step(model, specs, slot_order, stack=True)

    def to_fused(b):
        ids = {}
        for f in b.id_type_features:
            flat, counts = f.flat_counts()
            assert len(flat) == len(counts), "quality stream is single-id"
            ids[f.name] = flat.astype(np.int32)
        return {
            "dense": [np.asarray(b.non_id_type_features[0].data, np.float32)],
            "labels": [np.asarray(b.labels[0].data, np.float32)],
            "ids": ids,
        }

    fb = [to_fused(b) for b in train_b]
    state = init_fused_state(
        model, jax.random.PRNGKey(0), specs, fb[0], dense_opt, sparse_cfg,
        stack=True,
    )
    state, (loss, _) = step(state, fb[0])  # compile outside the window
    state = init_fused_state(
        model, jax.random.PRNGKey(0), specs, fb[0], dense_opt, sparse_cfg,
        stack=True,
    )
    t0 = time.perf_counter()
    for b in fb:
        state, (loss, _) = step(state, b)
    jax.block_until_ready(loss)
    elapsed = time.perf_counter() - t0
    preds, labels = [], []
    for b in eval_b:
        f = to_fused(b)
        preds.append(np.asarray(eval_step(state, f)).reshape(-1))
        labels.append(f["labels"][0].reshape(-1))
    return {
        "samples_per_sec": round(steps * BATCH_SIZE / elapsed, 1),
        "auc": round(_auc_of(preds, labels), 10),
    }


# Exact-AUC oracle (the reference CI pins 16-digit AUCs per backend,
# examples/src/adult-income/train.py:146-150): expected held-out AUC per
# tier at the DEFAULT 200-step budget, fixed seeds, keyed by the
# ``device_kind`` they were recorded on. Each tier is internally
# deterministic (the e2e suite asserts bit-identical AUC for the hybrid
# path; the cached stream orders its write-backs, and K-step packing is
# bit-transparent — pinned by test_stream_kstep_packing_bitwise_parity); a
# drift here means a semantic change to that tier's math, not noise.
# Applies only at steps=200 on a listed device; set BENCH_QUALITY_STRICT=0
# to record instead of assert (when changing the math intentionally, rerun
# twice and update these from two agreeing runs).
EXPECTED_AUC = {
    # device_kind -> tier -> (expected AUC, tolerance). Recorded from two
    # BENCH_MODE=quality runs on the TPU v5e under jax 0.9.0 / libtpu 0.0.34
    # (PR 21). cached and fused are EXACT (1e-6): both runs agreed to the
    # last printed digit (the stream is bit-deterministic —
    # test_stream_deterministic_under_flush_timing, chip_smoke.py — and the
    # fused tier is one deterministic XLA program). ps-stream trains its
    # slots under bounded staleness with ASYNC gradient returns over the
    # int8 error-feedback wire — the reference's async mode — so its value
    # is timing-dependent BY DESIGN: the two runs landed 0.6305409820 and
    # 0.6307135757 (1.7e-4 apart); pinned at their midpoint with the
    # tolerance the gate has always carried for this tier.
    "TPU v5 lite": {
        "cached": (0.6308596032, 1e-6),
        "ps-stream": (0.6306272789, 5e-3),
        "fused": (0.6302019103, 1e-6),
    },
}


def _check_expected_auc(out: dict, steps: int) -> None:
    kind = out["device"]["kind"]
    strict = os.environ.get("BENCH_QUALITY_STRICT", "1") != "0"
    expected = EXPECTED_AUC.get(kind)
    if steps != 200 or expected is None:
        return
    out["expected_auc"] = expected
    if not strict:
        return
    for tier, (want, tol) in expected.items():
        got = out[tier]["auc"]
        assert abs(got - want) < tol, (
            f"{tier} AUC {got!r} != pinned {want!r} (tol {tol}) on "
            f"{kind} — a semantic change to this tier's math (update "
            f"EXPECTED_AUC only if intentional)"
        )


def bench_quality():
    """The north-star artifact (BASELINE.md): samples/sec AT matched model
    quality. All three tiers train on the IDENTICAL learnable stream
    (CriteoSynthetic, hidden ground truth) for the same step budget and are
    scored by held-out AUC; each runs in its own subprocess (one process
    per chip: this parent never imports JAX). The
    spread assertion makes a throughput 'win' that trades away accuracy
    (e.g. over-aggressive admission gating or wire quantization) fail the
    bench instead of passing silently; the EXPECTED_AUC oracle pins each
    tier's exact value the way the reference CI does. Writes
    BENCH_QUALITY.json."""
    import subprocess
    import sys

    steps = int(os.environ.get("BENCH_QUALITY_STEPS", "200"))
    if steps < 3:
        raise SystemExit(
            "BENCH_QUALITY_STEPS must be >= 3 (the first 2 batches are the "
            "untimed compile warmup)"
        )
    budget_s = float(os.environ.get("BENCH_MODE_BUDGET_S", "1800"))
    out = {}
    for tier in ("cached", "ps-stream", "fused"):
        env = dict(os.environ, BENCH_QUALITY_TIER=tier,
                   BENCH_QUALITY_STEPS=str(steps))
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                env=env, capture_output=True, text=True, timeout=budget_s,
            )
        except subprocess.TimeoutExpired:
            raise RuntimeError(
                f"quality tier {tier!r} exceeded its {budget_s:.0f}s budget "
                "— rerun with a larger BENCH_MODE_BUDGET_S or fewer "
                "BENCH_QUALITY_STEPS"
            )
        lines = r.stdout.strip().splitlines()
        if r.returncode != 0 or not lines:
            raise RuntimeError(
                f"quality tier {tier!r} failed (rc={r.returncode}):\n"
                + "\n".join(r.stderr.strip().splitlines()[-15:])
            )
        out[tier] = json.loads(lines[-1])
    # the children name the device (each checked it); this parent never
    # touches JAX, so the chip is free for every child
    device = [v.pop("device") for v in out.values()][-1]
    aucs = [v["auc"] for v in out.values()]
    out["device"] = device
    out["auc_spread"] = round(max(aucs) - min(aucs), 6)
    out["steps"] = steps
    _check_expected_auc(out, steps)
    # the tiers must agree on quality: bf16 wires, touch gating and bounded
    # staleness are allowed to cost at most this much AUC vs the exact
    # all-in-HBM run on the same budget
    assert out["auc_spread"] < 0.02, f"tier AUC spread too wide: {out}"
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_QUALITY.json"), "w") as f:
        json.dump(out, f, indent=1)
    return out


def _quality_tier_main(tier: str, steps: int):
    device = _device()
    if tier == "cached":
        res = _quality_cached(steps)
    elif tier == "ps-stream":
        res = _quality_cached(steps, ps_all=True)
    elif tier == "fused":
        res = _quality_fused(steps)
    else:
        raise SystemExit(f"unknown quality tier {tier!r}")
    print(json.dumps({**res, "device": device}), flush=True)


def _bench_kill_resume():
    """Trainer kill-resume scenario for the chaos artifact: a journaled
    TrainCtx run is abandoned mid-window (the state a SIGKILLed trainer
    leaves: PS alive, trainer memory gone), then resumed from the newest
    manifest. Records recovery metrics for BOTH resume modes —
    ``rewind`` (PS shards rewound to the fence; the replay re-applies and
    must end bit-identical to an uninterrupted run, asserted here) and
    ``journal`` (PS kept; the replayed window's applies dedupe against
    the apply-journal — journal_hits counts them)."""
    import shutil
    import tempfile

    import optax

    from persia_tpu.config import EmbeddingConfig, SlotConfig
    from persia_tpu.ctx import TrainCtx
    from persia_tpu.embedding.optim import Adagrad
    from persia_tpu.embedding.store import EmbeddingStore
    from persia_tpu.embedding.worker import EmbeddingWorker
    from persia_tpu.jobstate import JobStateManager
    from persia_tpu.models import DNN
    from persia_tpu.testing import SyntheticClickDataset

    STEPS, K, KILL_AT = 12, 4, 9
    cfg = EmbeddingConfig(
        slots_config={"cat_0": SlotConfig(dim=8), "cat_1": SlotConfig(dim=8)},
        feature_index_prefix_bit=8,
    )
    batches = list(
        SyntheticClickDataset(num_samples=STEPS * 64, vocab_sizes=(64, 32), seed=9)
        .batches(64)
    )[:STEPS]

    def make_ctx(stores):
        return TrainCtx(
            model=DNN(dense_mlp_size=8, sparse_mlp_size=16, hidden_sizes=(32,)),
            dense_optimizer=optax.adam(3e-3),
            embedding_optimizer=Adagrad(lr=0.1),
            worker=EmbeddingWorker(cfg, stores), embedding_config=cfg,
        ).__enter__()

    out = {"steps": STEPS, "snapshot_every": K, "killed_at_step": KILL_AT}
    for mode, restore_ps in (("rewind", True), ("journal", False)):
        tmp = tempfile.mkdtemp(prefix=f"bench_resume_{mode}_")
        try:
            stores = [
                EmbeddingStore(capacity=1 << 16, num_internal_shards=4, seed=7)
                for _ in range(2)
            ]
            mgr = JobStateManager(tmp)
            ctx1 = make_ctx(stores)
            ctx1.resume(mgr)
            for i in range(KILL_AT):
                ctx1.train_step(batches[i])
                if (i + 1) % K == 0:
                    ctx1.snapshot_job(mgr)
            del ctx1  # the trainer "dies"; the PS tier survives

            t0 = time.perf_counter()
            ctx2 = make_ctx(stores)
            m = ctx2.resume(mgr, restore_ps=restore_ps)
            resume_s = time.perf_counter() - t0
            for i in range(m.step, STEPS):
                ctx2.train_step(batches[i])
            router = ctx2.worker.lookup_router
            out[mode] = {
                "time_to_resume_s": round(resume_s, 4),
                "steps_replayed": STEPS - m.step,
                "journal_hits": router.journal_skips,
                "resume_info": ctx2.last_resume_info,
            }
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    return out


def bench_chaos():
    """Chaos soak: the cached stream against REAL subprocess PS shards
    fronted by fault-injecting proxies (persia_tpu/chaos.py), with a
    scripted mid-run SIGKILL of one shard that a RUNNING self-heal loop
    (``kill_ps_autoheal`` + autopilot Healer promoting a warm standby —
    no scripted restore) must recover from autonomously,
    plus a trainer kill-resume scenario recording recovery metrics
    (time-to-resume, steps replayed, journal hits). The record carries
    the chaos config, the injected-fault counts, breaker trips/states,
    and the degraded-lookup accounting — a soak run is only evidence if
    the artifact shows what was injected and what it cost.

    Spec via ``BENCH_CHAOS`` (see chaos.parse_chaos_spec), e.g.
    ``python bench.py --chaos=reset=0.02,slow=0.01,seed=7``. Data-plane
    content faults (NaN dense features, label flips, sign corruption,
    gradient spikes — persia_tpu/health's detection surface) ride along
    via ``BENCH_CHAOS_DATA`` (chaos.parse_data_chaos_spec) and their
    counts land in the artifact. Runs on the
    CPU-host topology; the number is a liveness/robustness datapoint, not
    a throughput headline."""
    import optax

    from persia_tpu.chaos import (
        ChaosAction, ChaosPlane, DataPlaneChaos, parse_chaos_spec,
        parse_data_chaos_spec,
    )
    from persia_tpu.config import EmbeddingConfig, SlotConfig
    from persia_tpu.data import (
        IDTypeFeatureWithSingleID, Label, NonIDTypeFeature, PersiaBatch,
    )
    from persia_tpu.embedding import hbm_cache as hbm
    from persia_tpu.embedding.optim import Adagrad
    from persia_tpu.embedding.worker import EmbeddingWorker
    from persia_tpu.helper import ServiceCtx
    from persia_tpu.metrics import get_metrics
    from persia_tpu.models import DLRM
    from persia_tpu.service.resilience import ResiliencePolicy, RetryPolicy

    cfg_chaos = parse_chaos_spec(os.environ.get("BENCH_CHAOS", ""))
    # data-plane content faults (BENCH_CHAOS_DATA, chaos.parse_data_chaos_spec
    # format) — poisons the health layer detects, vs. the transport faults
    # above which the crc/breaker layer detects
    data_chaos = DataPlaneChaos(
        parse_data_chaos_spec(os.environ.get("BENCH_CHAOS_DATA", ""))
    )
    data_faults_on = any((
        data_chaos.cfg.nan_prob, data_chaos.cfg.label_flip_prob,
        data_chaos.cfg.sign_corrupt_prob, data_chaos.cfg.spike_prob,
    ))
    steps = int(os.environ.get("BENCH_CHAOS_STEPS", "60"))
    n_slots, batch = 6, 1024
    # corrupt frames must be DETECTED, not silently trained on
    os.environ.setdefault("PERSIA_RPC_CRC", "1")
    emb_cfg = EmbeddingConfig(
        slots_config={
            f"cat_{i}": SlotConfig(dim=EMB_DIM) for i in range(n_slots)
        },
        feature_index_prefix_bit=8,
    )
    policy = ResiliencePolicy(
        retry=RetryPolicy(max_attempts=4, base_s=0.02, max_s=0.5, seed=1),
        breaker_failure_threshold=3, breaker_reset_s=0.5,
        degrade_after_s=10.0, max_degraded_frac=1.0,
    )
    with ServiceCtx(num_parameter_servers=2, num_embedding_workers=0,
                    seed=7) as svc:
        svc.spawn_standby_ps()  # warm standby the healer promotes mid-soak
        plane = ChaosPlane(svc, cfg_chaos, schedule=[
            # fence snapshot + SIGKILL with NO scripted restore: the
            # running Healer (lease+probe detector -> two-phase journal ->
            # promote the warm standby) is the only recovery path — the
            # soak certifies the autonomous loop, not an operator script
            ChaosAction(step=max(steps // 3, 1), op="kill_ps_autoheal",
                        idx=0),
            # arm a seeded kill for the POST-STREAM reshard: the handoff op
            # it lands on comes from the chaos seed (reshard_fault_hook)
            ChaosAction(step=max(2 * steps // 3, 2), op="kill_during_reshard",
                        idx=1, handoff_op="import", op_index=-1),
        ])
        healer = None
        try:
            ps = plane.ps_clients(policy=policy)
            for c in ps:
                c.wait_ready()
            worker = EmbeddingWorker(emb_cfg, ps, policy=policy)
            import tempfile as _tf

            from persia_tpu.autopilot import enable_self_heal
            from persia_tpu.service.failure_detector import DetectorConfig

            # NOTE: the promoted slot is served by a DIRECT StoreClient
            # (the standby's own address) — the dead shard's chaos proxy
            # stays behind, so transport faults stop applying to that slot
            # after the heal; fault_counts() still records what landed
            healer = enable_self_heal(
                svc, _tf.mkdtemp(prefix="bench_selfheal_"),
                router=worker.lookup_router,
                detector_config=DetectorConfig(
                    miss_threshold=3, probe_timeout_s=0.5),
                probe_timeout_s=0.5,
            )
            healer.start(interval_s=0.1)
            ctx = hbm.CachedTrainCtx(
                model=DLRM(embedding_dim=EMB_DIM, bottom_mlp=(64, EMB_DIM),
                           top_mlp=(64,)),
                dense_optimizer=optax.adam(1e-3),
                embedding_optimizer=Adagrad(lr=0.05),
                worker=worker, embedding_config=emb_cfg,
                cache_rows=1 << 14, init_seed=7,
                # content faults poison the model without the on-device
                # finite gate: arm the probe whenever data chaos is on
                health_probe=data_faults_on,
            ).__enter__()
            sentinel = None
            if data_faults_on:
                from persia_tpu.health import SentinelConfig, StreamSentinel

                # count-rungs only (finite skip / clip): the soak measures
                # injected-vs-detected, the rollback ladder is exercised by
                # tests/test_health.py with a jobstate fence to return to
                sentinel = StreamSentinel.from_ctx(
                    ctx, SentinelConfig(z_threshold=1e9, warmup_steps=1 << 30)
                )
            rng = np.random.default_rng(3)
            # BENCH_CHAOS_LOAD (chaos.parse_load_spec) swaps the uniform
            # draw for a seeded load SHAPE — zipf ramp / spike / hot-set
            # rotation — the same schedule autopilot_bench.py soaks under
            load_sched = None
            load_spec = os.environ.get("BENCH_CHAOS_LOAD", "")
            if load_spec:
                from persia_tpu.chaos import LoadSchedule, parse_load_spec

                load_sched = LoadSchedule(parse_load_spec(load_spec))

            def batches():
                for step in range(steps):
                    ids = [
                        IDTypeFeatureWithSingleID(
                            f"cat_{j}",
                            load_sched.signs(step, batch, slot=j)
                            if load_sched is not None
                            else rng.integers(0, 200_000, batch,
                                              dtype=np.uint64),
                        )
                        for j in range(n_slots)
                    ]
                    yield PersiaBatch(
                        ids,
                        non_id_type_features=[NonIDTypeFeature(
                            rng.normal(size=(batch, N_DENSE)).astype(np.float32))],
                        labels=[Label(
                            rng.integers(0, 2, (batch, 1)).astype(np.float32))],
                        requires_grad=True,
                    )

            prog = _Progress(every=10)
            prog.start()
            t0 = time.perf_counter()
            ctx.train_stream(
                prog.wrap(plane.wrap_batches(data_chaos.wrap(batches()))),
                fetch_final=False,
                sentinel=sentinel,
            )
            elapsed = time.perf_counter() - t0
            m = ctx.last_metrics()
            assert m is not None
            # a poisoned final batch legitimately reports a non-finite
            # LOSS (its update was zeroed on device); the health claim is
            # that the non-finite never lands in trained state
            if not data_faults_on:
                assert np.isfinite(m["loss"])
            st = ctx.stream_stats() or {}
            # the healer must not fight the reshard below (2->4->2 swaps
            # every shard's process); stop it once the stream is drained
            healer.stop()
            healer.detector.close()
            heal_rec = {
                "heals": len(healer.mttr_s),
                "mttr_s": [round(x, 4) for x in healer.mttr_s],
                "pending_after": healer.pending() is not None,
                "detector_false_positive_guard":
                    healer.detector.false_positive_guard,
            }
            healer = None
            # elastic reshard under fire: the stream above is drained (the
            # fence), so grow the PS tier 2->4 with the armed seeded kill
            # landing mid-handoff, resume to completion, shrink back. The
            # artifact records the interruption and both runs' op ledgers;
            # reshard_kills rides in faults_injected.
            import tempfile as _tempfile

            js = _tempfile.mkdtemp(prefix="bench_reshard_js_")
            hook = plane.reshard_fault_hook()
            try:
                grow = svc.reshard_ps(4, js, step=steps, fault_hook=hook)
                interrupted = False
            except Exception:  # noqa: BLE001 — the armed kill fired
                interrupted = True
                grow = svc.resume_reshard(js, fault_hook=hook)
            shrink = svc.reshard_ps(2, js, step=steps + 1)
            reshard_rec = {
                "interrupted": interrupted,
                "grow": {k: v for k, v in (grow or {}).items()
                         if k != "skew_splits"},
                "shrink": {k: v for k, v in shrink.items()
                           if k != "skew_splits"},
            }
            return {
                "samples_per_sec": round(steps * batch / elapsed, 1),
                "steps": steps,
                "chaos": cfg_chaos.to_dict(),
                "load": (load_sched.cfg.to_dict()
                         if load_sched is not None else None),
                # trainer kill-resume recovery metrics (jobstate.py):
                # time-to-resume, steps replayed, journal hits per mode
                "kill_resume": _bench_kill_resume(),
                "self_heal": heal_rec,
                "reshard": reshard_rec,
                "faults_injected": plane.fault_counts(),
                "data_chaos": data_chaos.cfg.to_dict(),
                "data_faults_injected": dict(data_chaos.counts),
                "data_faults_detected": (
                    dict(sentinel.stats) if sentinel is not None else {}
                ),
                "degraded_steps": st.get("degraded_steps", 0),
                "degraded_lookup_frac_max": st.get(
                    "degraded_lookup_frac_max", 0.0
                ),
                "breaker_trips": policy.breaker_trips(),
                "breaker_states": policy.breaker_states(),
                "resilience_metrics": get_metrics().snapshot(
                    "persia_tpu_degraded"
                ),
            }
        finally:
            if healer is not None:
                healer.stop()
                healer.detector.close()
            plane.stop()


_BENCHES = {
    "fused": bench_fused,
    "hybrid": bench_hybrid,
    "cached": bench_cached,
    "cached-saturated": bench_cached_saturated,
    "ps-stream": bench_ps_stream,
    "link": bench_link,
    "chaos": bench_chaos,  # opt-in (--chaos / BENCH_MODE=chaos); not in "all"
}


def _run_mode_isolated(mode: str):
    """Run one mode in a fresh subprocess under a wall-clock budget and
    return ``(record, device)`` from the child's result line. One process
    per chip: this parent never imports JAX, so each child finds the chip
    free and checks the device itself; the persistent compile cache
    (persia_tpu/compile_cache.py) keeps the respawn cost to process
    start-up once the programs have been compiled.

    A mode that dies, prints nothing or blows its budget fails the suite:
    there is no partial record and no fallback headline."""
    import subprocess
    import sys

    budget_s = float(os.environ.get("BENCH_MODE_BUDGET_S", "1500"))
    env = dict(os.environ, BENCH_MODE=mode)
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env, capture_output=True, text=True, timeout=budget_s,
        )
    except subprocess.TimeoutExpired:
        raise SystemExit(
            f"bench mode {mode!r} exceeded its {budget_s:.0f}s budget"
        ) from None
    lines = [l for l in (out.stdout or "").strip().splitlines() if l.strip()]
    if out.returncode != 0 or not lines:
        raise SystemExit(
            f"bench mode {mode!r} failed (rc={out.returncode}):\n"
            + "\n".join((out.stderr or "").strip().splitlines()[-15:])
        )
    rec = json.loads(lines[-1])
    return rec["modes"][mode], rec["device"]


def _mode_value(v):
    """Samples/sec of a mode record: a bare number or a dict record
    carrying ``samples_per_sec`` (the stream modes, which also report
    dispatch_mode/feeder_util); None for records without a throughput
    (link)."""
    if isinstance(v, (int, float)):
        return float(v)
    if isinstance(v, dict) and "samples_per_sec" in v:
        return float(v["samples_per_sec"])
    return None


def _result_line(results: dict, device: dict) -> str:
    # headline = the capacity tier's SATURATED steady-state (eviction
    # write-back on every step), not the flattering fill phase — a reader
    # of the one-line JSON gets the number the 100T regime actually runs
    # at; the fill figure stays in cached_regimes. "fused" (all-in-HBM)
    # rides along as the in-memory ceiling.
    throughput = {
        k: _mode_value(v) for k, v in results.items()
        if k != "link" and _mode_value(v) is not None
    }
    if "cached-saturated" in throughput:
        headline_mode = "cached-saturated"
    elif "cached" in throughput:
        headline_mode = "cached"
    else:
        headline_mode = next(iter(throughput), "none")
    headline = throughput.get(headline_mode, 0.0)
    flops = _model_train_flops_per_sample()
    out = {
        "metric": "dlrm_criteo_shape_samples_per_sec_per_chip",
        "value": headline,
        # which mode the headline number came from: a run of only a chaos
        # soak must not be readable as a cached-tier measurement
        "headline_mode": headline_mode,
        "value_regime": (
            "saturated" if "cached-saturated" in throughput
            else ("fill" if "cached" in throughput else "first-measured")
        ),
        "unit": "samples/sec",
        "device": device,
        "vs_baseline": round(headline / REF_SAMPLES_PER_SEC, 4),
        "model_flops_per_sample": round(flops),
        "mfu": round(headline * flops / _peak_bf16_flops(device), 5),
        "modes": results,
    }
    chaos_rec = results.get("chaos")
    if isinstance(chaos_rec, dict) and "chaos" in chaos_rec:
        # chaos soak active: the injected-fault config is part of the
        # record's identity — a reader must never mistake a chaos run's
        # numbers for clean-run numbers
        out["chaos"] = chaos_rec["chaos"]
    if "link" in results:
        # the measured host↔device link bounds the wire-bound modes and
        # must be legible from the artifact's top level
        link = results["link"]
        out["h2d_MBps"] = link.get("h2d_MBps")
        out["d2h_MBps"] = link.get("d2h_MBps")
        out["small_d2h_roundtrip_ms"] = link.get("small_d2h_roundtrip_ms")
        out["link"] = link
    # the cached tier is honest only as a pair: the 100-step fill-phase
    # number AND the steady-state eviction regime; the stream records also
    # carry dispatch_mode + feeder_util so a hot-loop regression is visible
    # from this JSON alone
    if "cached" in results and "cached-saturated" in results:
        out["cached_regimes"] = {
            "fill": _mode_value(results["cached"]),
            "saturated": _mode_value(results["cached-saturated"]),
        }
    return json.dumps(out)


def main():
    tier = os.environ.get("BENCH_QUALITY_TIER")
    if tier:  # quality-tier subprocess
        _quality_tier_main(tier, int(os.environ.get("BENCH_QUALITY_STEPS", "200")))
        return
    mode = os.environ.get("BENCH_MODE", "all")
    if mode == "quality":
        out = bench_quality()
        print(json.dumps({"metric": "quality_auc_at_throughput", **out}), flush=True)
        return
    if mode not in ("all", *_BENCHES):
        raise SystemExit(
            f"BENCH_MODE must be one of all/quality/{'/'.join(_BENCHES)}, got {mode!r}"
        )
    results = {}
    if mode == "all":
        # headline mode FIRST, and a cumulative result line after EVERY
        # mode: a harness that parses the last stdout line still gets a
        # complete record if the run is cut off mid-suite. The link
        # measurement runs LAST (closest conditions to the wire-bound
        # modes it contextualizes). This parent stays off JAX: the chip
        # belongs to one process at a time, and each child checks it.
        order = sorted(
            (n for n in _BENCHES if n != "chaos"),  # chaos is opt-in only
            key=lambda n: (n == "link", n != "cached"),
        )
        for m in order:
            r, device = _run_mode_isolated(m)
            results[m] = round(r, 1) if isinstance(r, float) else r
            print(_result_line(results, device), flush=True)
        return
    device = _device()
    r = _BENCHES[mode]()
    results[mode] = round(r, 1) if isinstance(r, float) else r
    print(_result_line(results, device), flush=True)


if __name__ == "__main__":
    import sys

    # --chaos[=spec] CLI: run the chaos soak mode with the given fault
    # spec (chaos.parse_chaos_spec format); env vars still override
    for _a in sys.argv[1:]:
        if _a == "--chaos":
            os.environ.setdefault("BENCH_CHAOS", "reset=0.02,slow=0.01,seed=7")
            os.environ.setdefault("BENCH_MODE", "chaos")
        elif _a.startswith("--chaos="):
            os.environ["BENCH_CHAOS"] = _a.split("=", 1)[1]
            os.environ.setdefault("BENCH_MODE", "chaos")
        else:
            raise SystemExit(f"unknown argument {_a!r} (supported: --chaos[=spec])")
    main()
