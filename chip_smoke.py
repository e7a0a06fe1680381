"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the repo's main path once on one TPU, in ONE process (the only one
that touches JAX): ``CachedTrainCtx.train_stream`` feeding the jitted
cached-tier step from the native feeder over a native PS, at the full DLRM
width below (``Shape``'s defaults), then a few steps through
the other two trainer contexts (pinned tables, per-step PS path), then the
Pallas flash-attention kernel compiled against its dense reference, then —
when four TPU devices are present — the cached phase on a 4-device data
mesh against the one-device run.

It refuses to start without a TPU, force-rebuilds the native cores so no
ignored ``native/*.so`` from an earlier session is loaded, and catches no
phase's failure: any exception or failed assertion exits non-zero and the
result line is not printed. The last line of stdout on success is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Everything printed before it is a fact about this run (compile seconds,
peak HBM, link numbers, dispatch latency), not a benchmark metric: nothing
here is warmed up, repeated or sized to be a rate.

The phases are plain functions that take sizes, so tests/test_chip_smoke.py
drives them tiny on CPU; only ``main()`` holds the device check.
"""

from __future__ import annotations

import gc
import hashlib
import json
import sys
import time
from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# n=1 vs n=N runs of one seeded stream differ by reduction order only.
# The loss bound is the one __graft_entry__.dryrun_multichip states. Its
# dense-parameter gate (7.8e-3) was calibrated on its toy tower; at the
# smoke's width Adam (|update| <= lr per step, so 30 steps cap the drift at
# 3e-2) carries reduction-order noise further: measured 1.20e-2 on four
# v5e chips and 6.9e-3 on four virtual CPU devices (PR 21). Gate at 1.5x
# the chips' measurement, as that file does: a doubling fails.
DP_LOSS_ATOL = 1.2e-3
DP_PARAM_ATOL = 1.5 * 1.20e-2


# dense features of a Criteo-shaped batch
N_DENSE = 13


@dataclass(frozen=True)
class Shape:
    """Model and traffic sizes of one smoke run. The defaults are a
    Criteo-shaped DLRM (26 slots of a million rows, dim 16); the CPU test
    passes a tiny one."""

    batch: int = 4096
    n_slots: int = 26
    emb_dim: int = 16
    vocab: int = 1_000_000
    bottom_mlp: Tuple[int, ...] = (256, 64, 16)
    top_mlp: Tuple[int, ...] = (512, 256)
    # small enough that the stream reaches the eviction steady state within
    # the smoke's step count
    cache_rows: int = 1 << 18
    store_capacity: int = 1 << 25
    dispatch_k: int = 8


def say(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ device


def device_facts() -> Dict:
    """What JAX reports for this process: the device (the result line's
    ``device`` object) and the installed versions."""
    import importlib.metadata as md

    import jax
    import jaxlib

    devs = jax.devices()
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {
        "device": {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        },
        "versions": {
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu,
        },
    }


def memory_facts() -> Optional[Dict]:
    """Device-0 allocator counters (None where the backend reports none,
    e.g. CPU). ``peak_bytes_in_use`` is the process maximum so far."""
    import jax

    st = jax.devices()[0].memory_stats()
    if not st:
        return None
    return {
        k: int(st[k])
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
        if k in st
    }


def _tree_bytes(tree) -> int:
    import jax

    return int(sum(x.nbytes for x in jax.tree.leaves(tree)))


def _leaf_platforms(tree) -> List[str]:
    import jax

    return sorted({
        d.platform for x in jax.tree.leaves(tree) for d in x.devices()
    })


def _flat_params(params) -> np.ndarray:
    """The dense parameters as one host vector (one d2h per leaf — after
    the phase's own work, never inside it)."""
    import jax

    return np.concatenate(
        [np.asarray(leaf).reshape(-1) for leaf in jax.tree.leaves(params)]
    )


# ------------------------------------------------------------------ native


def rebuild_native() -> List[str]:
    """Force-rebuild the five native cores from ``native/*.cpp`` before
    anything loads one: ``native/*.so`` are ignored files, and a copy of a
    working directory carries whatever an earlier session left there."""
    from persia_tpu.embedding import native_store, native_worker
    from persia_tpu.embedding.hbm_cache import directory
    from persia_tpu.service import codec, native_rpc

    cores = (native_store, native_worker, directory, codec, native_rpc)
    for mod in cores:
        assert mod._LIB is None, f"{mod.__name__} loaded its core before the rebuild"
    return [mod.build_native(force=True) for mod in cores]


# ------------------------------------------------------------------- link


def _dispatch_latency_us(n: int = 200) -> Dict:
    """Per-dispatch cost of a trivial jitted call: ``enqueue`` chains n
    calls and syncs once (what a d2h-free training loop pays per
    dispatch); ``roundtrip`` syncs after each call."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1.0)
    x = jax.device_put(jnp.zeros((8, 128), jnp.float32))
    x = f(x)
    x.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n):
        x = f(x)
    x.block_until_ready()
    enqueue = (time.perf_counter() - t0) / n
    t0 = time.perf_counter()
    for _ in range(n):
        x = f(x)
        x.block_until_ready()
    roundtrip = (time.perf_counter() - t0) / n
    return {
        "enqueue_us": round(enqueue * 1e6, 1),
        "roundtrip_us": round(roundtrip * 1e6, 1),
    }


def _link() -> Dict:
    """The host<->device link of the first device: one ~4 MiB transfer each
    way, then the round trip of a small fetch."""
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


def phase_link() -> Dict:
    """The host↔device link and whether a device→host fetch changes what a
    later dispatch costs. Must run before anything else fetches from the
    device: ``before`` is only meaningful while the process has done no
    d2h at all."""
    before = _dispatch_latency_us()
    link = _link()  # performs the process's first d2h
    after = _dispatch_latency_us()
    return {
        "link": link,
        "dispatch_before_first_d2h": before,
        "dispatch_after_d2h": after,
    }


# ---------------------------------------------------------- trainer phases


def _embedding_config(shape: Shape):
    from persia_tpu.config import EmbeddingConfig, SlotConfig

    return EmbeddingConfig(
        slots_config={
            f"cat_{i}": SlotConfig(dim=shape.emb_dim) for i in range(shape.n_slots)
        },
        feature_index_prefix_bit=8,
    )


def _native_worker(shape: Shape, cfg):
    """One in-process native PS behind an EmbeddingWorker —
    ``"native"``, never ``"auto"``: a missing C++ core must
    fail here, not drop to the numpy store."""
    from persia_tpu.embedding.native_store import create_store
    from persia_tpu.embedding.optim import Adagrad
    from persia_tpu.embedding.worker import EmbeddingWorker

    store = create_store(
        "native", capacity=shape.store_capacity, num_internal_shards=64,
        optimizer=Adagrad(lr=0.05).config, seed=1,
    )
    worker = EmbeddingWorker(cfg, [store], num_threads=16, device_pooling=True)
    return store, worker


def _dlrm(shape: Shape):
    from persia_tpu.models import DLRM

    return DLRM(
        embedding_dim=shape.emb_dim, bottom_mlp=shape.bottom_mlp,
        top_mlp=shape.top_mlp,
    )


def _metric_total(name: str, series: str = "") -> float:
    """Process-cumulative value of a metric summed over its labels;
    ``series`` picks a histogram's ``_count`` / ``_sum``."""
    from persia_tpu.metrics import get_metrics

    snap = get_metrics().snapshot(prefix=name)
    return sum((snap.get(name + series) or {}).values())


def _batches(shape: Shape, seed: int, steps: int, learnable: bool = False):
    """``steps`` batches of the benchmark's zipf stream
    (``perf/generators/zipf.py``: ranks of a power law of exponent 1.2 over
    each slot's ``vocab`` rows, a fixed rotation a slot, 13 normal dense
    features), one id a slot and sample. Its labels are coin flips;
    ``learnable`` replaces them with a function of the dense features (as
    __graft_entry__._make_batch does), so that gradients carry a signal and
    two runs that differ only in reduction order stay close under Adam
    instead of random-walking apart."""
    from perf.generators.zipf import ZipfBatches
    from persia_tpu.data import (
        IDTypeFeatureWithSingleID,
        Label,
        NonIDTypeFeature,
        PersiaBatch,
    )

    gen = ZipfBatches(
        {"table_rows": [shape.vocab] * shape.n_slots, "num_dense": N_DENSE},
        {"batch": shape.batch, "zipf_a": 1.2}, seed,
    )
    for _ in range(steps):
        b = gen.next_batch()
        labels = b["labels"]
        if learnable:
            labels = (b["dense"].sum(axis=1, keepdims=True) > 0).astype(np.float32)
        yield PersiaBatch(
            [
                IDTypeFeatureWithSingleID(f"cat_{i}", ids.astype(np.uint64))
                for i, ids in enumerate(b["ids"])
            ],
            non_id_type_features=[NonIDTypeFeature(b["dense"])],
            labels=[Label(labels)],
            requires_grad=True,
        )


def phase_cached(
    shape: Shape, steps: int, seed: int = 0, n_ps_slots: int = 0, mesh=None,
    learnable: bool = False,
) -> Dict:
    """The main path: ``CachedTrainCtx.train_stream`` over a native PS with
    narrow wires (bf16 write-back and checkout, touch-gated admission,
    K-step packing). ``n_ps_slots`` moves that many slots to the PS path
    (int8 gradient-return wire), so the d2h gradient wire and the PS update
    run too — such steps are never packed (stream._packable), which is why
    the all-cached leg and the mixed leg are separate runs.

    Returns the run's facts; asserts what must hold for any run (finite
    loss of the batch's shape, state on the default backend's devices,
    every forward reference released, no degraded step)."""
    import jax
    import optax

    from persia_tpu.embedding.hbm_cache import CachedTrainCtx
    from persia_tpu.embedding.optim import Adagrad

    cfg = _embedding_config(shape)
    store, worker = _native_worker(shape, cfg)
    ps_slots = [f"cat_{i}" for i in range(shape.n_slots - n_ps_slots, shape.n_slots)]
    evict0 = _metric_total("persia_tpu_cache_evict_count")
    psgrad0 = _metric_total("persia_tpu_update_gradient_time_cost_sec", "_count")
    staged: List = []
    ctx = CachedTrainCtx(
        model=_dlrm(shape), dense_optimizer=optax.adam(1e-3),
        embedding_optimizer=Adagrad(lr=0.05), worker=worker,
        embedding_config=cfg, cache_rows=shape.cache_rows, mesh=mesh,
        wb_wire_dtype="bfloat16", aux_wire_dtype="bfloat16", admit_touches=2,
        ps_slots=ps_slots, ps_wire_dtype="int8" if ps_slots else "float32",
    )
    stage = ctx._stage

    def recording_stage(*args):
        out = stage(*args)
        if not staged:
            staged.append(out[0])  # first step's staged device inputs
        return out

    ctx._stage = recording_stage
    t0 = time.perf_counter()
    with ctx:
        m = ctx.train_stream(
            _batches(shape, seed, steps, learnable=learnable),
            dispatch_k=shape.dispatch_k,
        )
        wall = time.perf_counter() - t0
        st = ctx.stream_stats()
        state = ctx.state
        out = {
            "steps": steps,
            "loss": float(m["loss"]),
            "preds_shape": tuple(m["preds"].shape),
            "wall_s": round(wall, 2),
            "packs": st["packs"],
            "packed_steps": st["packed_steps"],
            "single_steps": st["single_steps"],
            "degraded_steps": st["degraded_steps"],
            "rows_evicted": int(
                _metric_total("persia_tpu_cache_evict_count") - evict0
            ),
            # cold rows are seeded on the host and first reach the PS when
            # they are evicted, so after an all-cached stream every PS row
            # is a landed write-back
            "ps_rows": int(store.size()),
            "ps_grad_updates": int(
                _metric_total("persia_tpu_update_gradient_time_cost_sec", "_count")
                - psgrad0
            ),
            "staleness": int(worker.staleness),
            "state_platforms": _leaf_platforms(state),
            "state_logical_bytes": _tree_bytes(state) + _tree_bytes(ctx._ev_rings),
            "memory": memory_facts(),
            "params": _flat_params(state.params),
            "batch_shards": sorted(
                (str(s.device), tuple(s.data.shape))
                for s in staged[0]["dense"][0].addressable_shards
            ),
            "pool_shards": {
                g: sorted(
                    (str(s.device), tuple(s.data.shape))
                    for s in t.addressable_shards
                )
                for g, t in state.tables.items()
            },
        }
    out["params_digest"] = hashlib.sha256(out["params"].tobytes()).hexdigest()[:16]
    assert np.isfinite(out["loss"]), out["loss"]
    assert out["preds_shape"] == (shape.batch, 1), out["preds_shape"]
    assert out["state_platforms"] == [jax.default_backend()], out["state_platforms"]
    assert out["degraded_steps"] == 0, out["degraded_steps"]
    assert out["staleness"] == 0, out["staleness"]
    assert out["packed_steps"] + out["single_steps"] == steps, out
    del ctx, state
    gc.collect()
    return out


def phase_hybrid(shape: Shape, steps: int, seed: int = 0) -> Dict:
    """The per-step PS path: ``TrainCtx`` (bf16
    embedding wire) with a few synchronous steps, then the pipelined
    ``DataLoader`` with gradients returning through its backward engine."""
    import jax
    import optax

    from persia_tpu.ctx import TrainCtx
    from persia_tpu.data_loader import DataLoader
    from persia_tpu.embedding.optim import Adagrad

    cfg = _embedding_config(shape)
    store, worker = _native_worker(shape, cfg)
    sync_steps = 2
    batches = _batches(shape, seed, steps)
    with TrainCtx(
        model=_dlrm(shape), dense_optimizer=optax.adam(1e-3),
        embedding_optimizer=Adagrad(lr=0.05), worker=worker,
        embedding_config=cfg, wire_dtype="bfloat16",
    ) as ctx:
        for _ in range(sync_steps):
            first = ctx.train_step(next(batches))
        loader = DataLoader(batches, ctx, num_workers=4, staleness=4)
        for tb in loader:
            ctx.train_step_prepared(tb, loader, fetch_metrics=False)
        loader.flush()
        m = ctx.last_prepared_metrics()
        out = {
            "steps": steps,
            "first_loss": float(first["loss"]),
            "loss": float(m["loss"]),
            "preds_shape": tuple(m["preds"].shape),
            "ps_rows": int(store.size()),
            "staleness": int(worker.staleness),
            "state_platforms": _leaf_platforms(ctx.state),
            "state_logical_bytes": _tree_bytes(ctx.state),
            "memory": memory_facts(),
        }
    assert np.isfinite(out["loss"]) and np.isfinite(out["first_loss"]), out
    assert out["preds_shape"] == (shape.batch, 1), out["preds_shape"]
    assert out["state_platforms"] == [jax.default_backend()], out["state_platforms"]
    assert out["ps_rows"] > 0 and out["staleness"] == 0, out
    del ctx
    gc.collect()
    return out


def _device_row_bytes(dim: int) -> Optional[int]:
    """Bytes one ``(rows, dim)`` f32 table row occupies on device 0 under
    the chip's tiling, measured from the allocator (None where the backend
    reports no counters)."""
    import jax
    import jax.numpy as jnp

    before = memory_facts()
    if before is None:
        return None
    rows = 1 << 16
    probe = jax.block_until_ready(jnp.ones((rows, dim), jnp.float32))
    after = memory_facts()
    del probe
    return max(1, (after["bytes_in_use"] - before["bytes_in_use"]) // rows)


def pinned_vocab_that_fits(shape: Shape) -> Tuple[int, Dict]:
    """The per-slot vocabulary the pinned placement can hold on this
    device: ``shape.vocab`` when the stacked table and its Adagrad state
    (two ``(n_slots * vocab, dim)`` f32 arrays) fit in four fifths of the
    free HBM at the row size the chip really allocates, else the largest
    multiple of 4096 rows that does. Returns ``(vocab, finding)``."""
    row_bytes = _device_row_bytes(shape.emb_dim)
    logical = 4 * shape.emb_dim
    finding = {
        "logical_row_bytes": logical,
        "device_row_bytes": row_bytes,
        "asked_vocab_per_slot": shape.vocab,
    }
    if row_bytes is None:
        return shape.vocab, {**finding, "fits": "not measured"}
    mem = memory_facts()
    free = mem["bytes_limit"] - mem["bytes_in_use"]
    need = 2 * shape.n_slots * shape.vocab * row_bytes
    finding.update(free_bytes=free, needed_bytes=need)
    if need <= 0.8 * free:
        return shape.vocab, {**finding, "fits": True}
    vocab = int(0.8 * free / (2 * shape.n_slots * row_bytes)) // 4096 * 4096
    assert vocab > 0, finding
    return vocab, {**finding, "fits": False, "vocab_per_slot_run": vocab}


def phase_pinned(shape: Shape, steps: int, seed: int = 0) -> Dict:
    """The pinned placement — every table whole in
    HBM, one stacked table per dim, one jitted program per step — through
    its trainer context. Runs at the largest vocabulary that fits the
    device and says so."""
    import jax
    import optax

    from persia_tpu.embedding.optim import Adagrad
    from persia_tpu.parallel.fused_ctx import FusedTrainCtx
    from persia_tpu.parallel.fused_step import FusedSlotSpec

    vocab, finding = pinned_vocab_that_fits(shape)
    shape = replace(shape, vocab=vocab)  # ids are drawn below the cut too
    specs = {
        f"cat_{i}": FusedSlotSpec(vocab=vocab, dim=shape.emb_dim)
        for i in range(shape.n_slots)
    }
    with FusedTrainCtx(
        model=_dlrm(shape), dense_optimizer=optax.adam(1e-3),
        embedding_optimizer=Adagrad(lr=0.05), specs=specs, stack=True,
    ) as ctx:
        for b in _batches(shape, seed, steps):
            ctx.train_step(b, fetch_metrics=False)
        m = ctx.last_metrics()
        out = {
            "steps": steps,
            "loss": float(m["loss"]),
            "preds_shape": tuple(m["preds"].shape),
            "table_rows": shape.n_slots * vocab,
            "fit": finding,
            "state_platforms": _leaf_platforms(ctx.state),
            "state_logical_bytes": _tree_bytes(ctx.state),
            "memory": memory_facts(),
        }
    assert np.isfinite(out["loss"]), out["loss"]
    assert out["preds_shape"] == (shape.batch, 1), out["preds_shape"]
    assert out["state_platforms"] == [jax.default_backend()], out["state_platforms"]
    del ctx
    gc.collect()
    return out


# ----------------------------------------------------------------- kernel

# (L, D): L not a multiple of the 256/512 blocks and an exact multiple; D
# at half a lane tile and a full one
KERNEL_SHAPES = ((1000, 64), (1000, 128), (4096, 64), (4096, 128))
# max |flash - reference| allowed, by input dtype. The oracle runs its
# matmuls at "highest" precision. The kernel passes no precision, and on
# the v5e its f32 path lands a bf16-operand-sized distance from the oracle
# (2^-9 relative on outputs that reach ~4 on causal rows with few keys):
# measured 7.6e-3 for f32 and 7.0e-3 for bf16 inputs (PR 21 chip run), so
# both dtypes get a bf16-operand bound; the CPU interpreter is exact to
# 4e-7 in f32.
KERNEL_ATOL = {"float32": 2e-2, "bfloat16": 3e-2}


def phase_kernel(
    shapes: Sequence[Tuple[int, int]] = KERNEL_SHAPES, interpret: bool = False,
    block_q: int = 256, block_k: int = 512,
) -> Dict:
    """``ops.flash_attention`` (the repo's one Pallas kernel) against
    ``parallel.sequence.reference_attention`` for every (L, D) × {f32,
    bf16} × {full, causal}. ``interpret`` is False on the chip — the kernel
    is compiled by Mosaic — and True only in the CPU test."""
    import jax
    import jax.numpy as jnp

    from persia_tpu.ops import flash_attention
    from persia_tpu.parallel.sequence import reference_attention

    rng = np.random.default_rng(0)
    worst: Dict[str, float] = {}
    cases = 0
    for l, d in shapes:
        q32, k32, v32 = (
            jnp.asarray(rng.standard_normal((1, l, 2, d)), jnp.float32)
            for _ in range(3)
        )
        for dtype in (jnp.float32, jnp.bfloat16):
            q, k, v = (x.astype(dtype) for x in (q32, k32, v32))
            for causal in (False, True):
                out = flash_attention(
                    q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                    interpret=interpret,
                )
                # the oracle sees the same (rounded) inputs, in f32, with
                # full-precision matmuls
                with jax.default_matmul_precision("highest"):
                    ref = reference_attention(
                        q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), causal=causal,
                    )
                assert out.shape == q.shape and out.dtype == q.dtype, (
                    out.shape, out.dtype
                )
                err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
                name = jnp.dtype(dtype).name
                say(f"  kernel L={l} D={d} {name} causal={causal}: "
                    f"max|err|={err:.2e}")
                assert np.isfinite(err) and err <= KERNEL_ATOL[name], (
                    l, d, name, causal, err
                )
                worst[name] = max(worst.get(name, 0.0), err)
                cases += 1
    return {"cases": cases, "interpret": interpret, "max_abs_err": worst}


def phase_sequence_kernels(length: int = 2048, heads: int = 4, chunk: int = 64, tile: int = 512,
                           interpret: bool = False) -> Dict:
    """The chunked delta rule (``ops.delta_rule.kda``, its four kernels) against the
    plain recurrence, interval attention at widths 192/128 (``k_shared``)
    against dense softmax, and a whole latent-attention layer with a low-rank
    query and rotated columns (``models.moe_tower.latent_attention``) against
    the same written out in float32, over packed documents whose starts fall
    inside chunks and tiles; forward and every gradient, as relative gaps."""
    import jax
    import jax.numpy as jnp

    from persia_tpu.ops.delta_rule import kda, kda_recurrence
    from persia_tpu.ops.flash_attention import interval_attention

    rng = np.random.default_rng(0)
    docs = [length // 2 + 3, length // 4 + 5, length // 8 - 7]
    docs.append(length - sum(docs))
    lo = jnp.asarray(np.repeat(np.cumsum([0] + docs[:-1]), docs)[None].astype(np.int32))
    normal = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    gap = lambda a, b: float(jnp.linalg.norm((a - b).astype(jnp.float32)) / jnp.linalg.norm(b))

    def gaps(mine, theirs, args):
        ct = jnp.asarray(normal(*theirs(*args).shape))
        out = {"forward": gap(mine(*args), theirs(*args))}
        grads = [jax.grad(lambda *a: jnp.sum(f(*a) * ct), argnums=tuple(range(len(args))))(*args)
                 for f in (mine, theirs)]
        out.update({f"d{i}": gap(a, b) for i, (a, b) in enumerate(zip(*grads))})
        return out

    shape = (1, length, heads, 128)
    delta = gaps(lambda *a: kda(*a, lo, chunk=chunk, interpret=interpret), lambda *a: kda_recurrence(*a, lo), [
        jnp.asarray(unit(normal(*shape)) / np.sqrt(128)), jnp.asarray(unit(normal(*shape))),
        jnp.asarray(normal(*shape)), jnp.asarray(-np.exp(rng.uniform(np.log(1e-3), np.log(1.6), shape)), jnp.float32),
        jnp.asarray(rng.uniform(0.1, 0.9, shape[:3]), jnp.float32)])
    say(f"  delta rule L={length} H={heads} chunk={chunk}: " + " ".join(f"{k}={v:.1e}" for k, v in delta.items()))
    assert all(np.isfinite(v) and v < 2e-2 for v in delta.values()), delta  # bfloat16 operands against float32

    def dense(q, k, shared, v):
        kk = jnp.concatenate([k, jnp.broadcast_to(shared[:, :, None, :], (*k.shape[:3], shared.shape[-1]))], -1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk, precision="highest") / np.sqrt(q.shape[-1])
        at = jnp.arange(length)
        mask = (at[None, None, :] >= lo[:, :, None]) & (at[None, None, :] <= at[None, :, None])
        p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")

    latent = gaps(lambda q, k, s, v: interval_attention(q, k, v, lo, tile=tile, interpret=interpret, k_shared=s),
                  dense, [jnp.asarray(normal(1, length, heads, 192)), jnp.asarray(normal(*shape)),
                          jnp.asarray(normal(1, length, 64)), jnp.asarray(normal(*shape))])
    say(f"  interval attention 192/128 L={length} H={heads}: " + " ".join(f"{k}={v:.1e}" for k, v in latent.items()))
    assert all(np.isfinite(v) and v < 2e-2 for v in latent.values()), latent

    # the layer both latent towers call, as the joyai_llm_flash family states it: q through wq_a, a norm
    # and wq_b, the last 64 columns of every head's query and the shared key columns rotated by the
    # position inside the document (interleaved pairs, theta 32e6)
    from persia_tpu.models.joyai_flash_moe import rope_tables
    from persia_tpu.models.moe_tower import latent_attention

    d, q_rank, rank = 256, 192, 128
    names = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo")
    shapes = ((d, q_rank), (q_rank,), (q_rank, heads * 192), (d, rank + 64), (rank,), (rank, heads * 256), (heads * 128, d))
    leaves = [jnp.asarray(1.0 + 0.1 * normal(*s) if len(s) == 1 else normal(*s) / np.sqrt(s[0])) for s in shapes]
    pos = (jnp.arange(length, dtype=jnp.int32)[None, :] - lo).astype(jnp.float32)
    angle = pos[:, :, None] * jnp.asarray(np.repeat(32e6 ** (-np.arange(32) / 32.0), 2).astype(np.float32))

    def layer(a, *leaves):
        return latent_attention(dict(zip(names, leaves)), a, lo, n_heads=heads, head_dim=128, rope_head_dim=64,
                                kv_lora_rank=rank, eps=1e-6, tile=tile, interpret=interpret,
                                rope=rope_tables(lo, 64, 32e6))

    def written_out(a, wq_a, q_norm, wq_b, wkv_a, kv_norm, wkv_b, wo):
        hi = lambda x, w: jnp.dot(x, w, precision="highest")
        rms = lambda x, w: x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * w

        def turn(x, angle):  # (y_2m, y_2m+1) = (x_2m c - x_2m+1 s, x_2m s + x_2m+1 c)
            even, odd, c, s = x[..., 0::2], x[..., 1::2], jnp.cos(angle[..., 0::2]), jnp.sin(angle[..., 0::2])
            return jnp.stack([even * c - odd * s, even * s + odd * c], axis=-1).reshape(x.shape)

        q = hi(rms(hi(a, wq_a), q_norm), wq_b).reshape(1, length, heads, 192)
        kv_a = hi(a, wkv_a)
        kv = hi(rms(kv_a[..., :rank], kv_norm), wkv_b).reshape(1, length, heads, 256)
        q = jnp.concatenate([q[..., :128], turn(q[..., 128:], angle[:, :, None, :])], axis=-1)
        o = dense(q, kv[..., :128], turn(kv_a[..., rank:], angle), kv[..., 128:])
        return hi(o.reshape(1, length, heads * 128), wo)

    rotated = gaps(layer, written_out, [jnp.asarray(normal(1, length, d))] + leaves)
    say(f"  rotated latent attention (low-rank q) L={length} H={heads}: "
        + " ".join(f"{k}={v:.1e}" for k, v in rotated.items()))
    assert all(np.isfinite(v) and v < 3e-2 for v in rotated.values()), rotated  # bfloat16 operands, four products deep
    return {"delta_rule": delta, "latent_attention": latent, "rotated_latent_attention": rotated,
            "interpret": interpret}


# -------------------------------------------------------------- multichip


def phase_multichip(shape: Shape, steps: int = 30, n_devices: int = 4,
                    seed: int = 0) -> Dict:
    """The cached phase on an ``n_devices`` data mesh at the same global
    batch, against the same seeded stream on one device: batch leaves must
    shard over all the devices, the cache pools must be replicated on all
    of them, and loss and dense parameters must agree inside the
    dp-invariance bounds ``__graft_entry__.dryrun_multichip`` states."""
    from persia_tpu.parallel import data_parallel_mesh

    one = phase_cached(shape, steps, seed=seed, learnable=True)
    many = phase_cached(
        shape, steps, seed=seed, mesh=data_parallel_mesh(n_devices),
        learnable=True,
    )
    batch_devices = {d for d, _ in many["batch_shards"]}
    assert len(batch_devices) == n_devices, many["batch_shards"]
    assert all(
        shp[0] == shape.batch // n_devices for _, shp in many["batch_shards"]
    ), many["batch_shards"]
    for g, shards in many["pool_shards"].items():
        assert {d for d, _ in shards} == batch_devices, (g, shards)
        assert shards[0][1] == one["pool_shards"][g][0][1], (g, shards)
    loss_diff = abs(one["loss"] - many["loss"])
    param_diff = float(np.abs(
        one["params"].astype(np.float64) - many["params"]
    ).max())
    say(f"  multichip n=1 vs n={n_devices}: loss {one['loss']:.6f} vs "
        f"{many['loss']:.6f} (diff {loss_diff:.2e}), max |param diff| "
        f"{param_diff:.2e}")
    assert loss_diff <= DP_LOSS_ATOL, (one["loss"], many["loss"])
    assert param_diff <= DP_PARAM_ATOL, param_diff
    return {
        "n_devices": n_devices,
        "steps": steps,
        "loss_1": one["loss"],
        "loss_n": many["loss"],
        "loss_diff": loss_diff,
        "max_param_diff": param_diff,
        "batch_shards": many["batch_shards"],
        "pool_shards": many["pool_shards"],
        "packed_steps_n": many["packed_steps"],
        "memory": many["memory"],
    }


# ------------------------------------------------------------------- main


def main() -> None:
    t_start = time.perf_counter()
    facts = device_facts()
    device = facts["device"]
    if device["platform"] != "tpu":
        sys.exit(
            "chip_smoke: refusing to run — jax.devices()[0].platform is "
            f"{device['platform']!r} ({device['kind']}), not 'tpu'. This "
            "script proves the program on the chip; run it through chiprun."
        )
    from persia_tpu.compile_cache import CompileMeter, enable_compile_cache

    say(f"platform: {device['platform']}")
    say(f"device_kind: {device['kind']}")
    say(f"device_count: {device['count']}")
    say(f"versions: {json.dumps(facts['versions'])}")
    say(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    say(f"native cores rebuilt: {rebuild_native()} "
        f"({time.perf_counter() - t0:.1f}s)")
    meter = CompileMeter()
    shape = Shape()

    def run(name: str, phase, *args, **kw) -> Dict:
        """One phase: its facts plus what it compiled, on one JSON line
        (the parameter vector stays out)."""
        mark = meter.mark()
        out = phase(*args, **kw)
        out["compile"] = meter.since(mark)
        say(f"{name}: " + json.dumps(
            {k: v for k, v in out.items() if k != "params"}
        ))
        return out

    run("link", phase_link)

    # all 26 slots cached, twice with one seed: the K-step packed path, the
    # eviction steady state, and bit-determinism (a staging-buffer or
    # donation race shows up as a loss that differs between the runs). The
    # second run compiles its step programs anew (new closures), so its
    # compile seconds are the persistent cache at work.
    cold = run("cached run 1 (cold)", phase_cached, shape, steps=96)
    assert cold["rows_evicted"] > 0, "the cached run never reached eviction"
    assert cold["ps_rows"] > 0, "no eviction write-back reached the PS"
    assert cold["packs"] > 0 and cold["packed_steps"] > 0, "no K-step dispatch"
    warm = run("cached run 2 (warm)", phase_cached, shape, steps=96)
    assert warm["loss"] == cold["loss"], (cold["loss"], warm["loss"])
    assert warm["params_digest"] == cold["params_digest"], "dense params differ"
    say(f"cached determinism: loss {cold['loss']!r} bit-identical across "
        f"two seeded runs, params digest {cold['params_digest']}")

    # three slots on the PS path: d2h int8 gradient wire + PS update
    mixed = run("cached mixed-tier (3 ps slots)", phase_cached, shape,
                steps=24, n_ps_slots=3)
    assert mixed["ps_grad_updates"] > 0, "no PS gradient update ran"

    run("hybrid (TrainCtx + DataLoader)", phase_hybrid, shape, steps=12)
    run("pinned (FusedTrainCtx)", phase_pinned, shape, steps=12)
    say("kernel (flash_attention vs reference_attention, compiled):")
    run("kernel", phase_kernel)
    say("sequence kernels (delta rule vs recurrence, interval attention 192/128 vs dense, "
        "rotated latent attention vs written out, compiled):")
    run("sequence kernels", phase_sequence_kernels)

    if device["count"] >= 4:
        run("multichip", phase_multichip, shape)
    else:
        say(f"multichip: not run ({device['count']} device)")

    say(f"total: {time.perf_counter() - t_start:.1f}s, compile "
        f"{json.dumps(meter.since((0.0, 0, 0, 0)))}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
