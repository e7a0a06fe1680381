"""Device-side sparse optimizer updates for HBM-resident embedding tables.

The reference applies sparse optimizers on the CPU parameter server with AVX2
kernels after the embedding worker has *accumulated gradients per sign*
(`embedding_worker_service/mod.rs:703-872` sums duplicate-id gradients, then
`embedding_parameter_service/mod.rs:359-427` runs `Optimizable::update` per
row). This module is the TPU counterpart for tables that live in HBM: the
same per-unique-row math (`persia_tpu/embedding/optim.py` — SGD / Adagrad
(±vectorwise-shared) / Adam), expressed as static-shape XLA:

1. sort ids, segment-sum duplicate gradients (the worker's per-sign
   accumulation); a second sort of the sorted ids packs the distinct ones to
   the front, so no id is gathered or scattered one element at a time,
2. gather the touched rows + optimizer state,
3. apply the optimizer math on the (N, dim) block,
4. write the new rows back at strictly ascending, distinct indices: row by
   row by DMA where the array's layout allows (``_row_write_path``), else
   scatter-added as deltas.

Steps 2-4 run a chunk of rows a trip in a loop over the live rows only: the
invalid tail of the static N positions is dropped, not executed.

Everything is functional and jit/shard friendly; no dynamic shapes. The row
loop's trip count is dynamic, so the update is not reverse-differentiable
(nothing differentiates an optimizer step).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from persia_tpu.embedding.optim import (
    OPTIMIZER_ADAGRAD,
    OPTIMIZER_ADAM,
    OPTIMIZER_SGD,
    OptimizerConfig,
)
from persia_tpu.tracing import record_event


def init_sparse_state(cfg: OptimizerConfig, vocab: int, dim: int) -> Dict[str, jnp.ndarray]:
    """Per-table optimizer state arrays (the HBM layout of the reference's
    trailing `[emb | state]` block, `persia-embedding-holder/src/emb_entry.rs:16-76`)."""
    if cfg.kind == OPTIMIZER_SGD:
        return {}
    if cfg.kind == OPTIMIZER_ADAGRAD:
        width = 1 if cfg.vectorwise_shared else dim
        return {"acc": jnp.full((vocab, width), cfg.initialization, dtype=jnp.float32)}
    if cfg.kind == OPTIMIZER_ADAM:
        return {
            "m": jnp.zeros((vocab, dim), dtype=jnp.float32),
            "v": jnp.zeros((vocab, dim), dtype=jnp.float32),
        }
    raise ValueError(f"unknown optimizer kind {cfg.kind}")


_PAD_SENTINEL = np.iinfo(np.int32).max


def dedup_gradients(
    ids: jnp.ndarray, grads: jnp.ndarray, mask: jnp.ndarray = None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-sign gradient accumulation with static shapes.

    ids (N,) int, grads (N, D) → (uid (N,), gsum (N, D), valid (N,) bool).
    ``mask`` (N,) bool marks live entries; without it every entry is live.
    Contract: with U distinct live ids, rows k < U hold the k-th distinct
    live id (strictly ascending) and the sum of its gradients, and are the
    only rows flagged valid. Masked-out entries (batch padding, ids that
    name no row) are folded into one out-of-vocab sentinel *before* the
    sort, so they sort last, land in row U flagged invalid and can never
    touch a real row — not even through weight decay, which applies to
    every *touched* row. Rows from U on hold the sentinel as their uid, and
    past the sentinel's own row a zero sum.

    The ids ride two sorts, and nothing N-wide is gathered or scattered but
    the gradient rows: on the v5e an int32 gather or scatter takes its
    elements one after another at 4.6-7.1 ns each, 0.49-0.76 ms a pass of the
    cells' 106,496, where the two sorts take 0.10 and 0.045 ms (PERF.md, PR
    31). The first sort must stay STABLE: equal ids keep their input order,
    which fixes the order in which the segment sum adds their float32
    gradients. The second sorts keys alone and need not be (stable it costs
    0.06 ms more).
    """
    n = ids.shape[0]
    if mask is not None:
        ids = jnp.where(mask, ids, _PAD_SENTINEL)
        grads = grads * mask[..., None].astype(grads.dtype)
    sids, order = jax.lax.sort(
        (ids, jnp.arange(n, dtype=jnp.int32)), num_keys=1, is_stable=True)
    sg = grads[order]
    is_new = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), sids[1:] != sids[:-1]]
    )
    seg = jnp.cumsum(is_new) - 1  # (N,) segment index per sorted element, non-decreasing
    gsum = jax.ops.segment_sum(sg, seg, num_segments=n, indices_are_sorted=True)
    uid = jax.lax.sort(jnp.where(is_new, sids, _PAD_SENTINEL), is_stable=False)
    valid = uid != _PAD_SENTINEL
    return uid, gsum, valid


def scatter_indices(uid: jnp.ndarray, valid: jnp.ndarray, vocab: int) -> jnp.ndarray:
    """Row indices for the write-back of ``dedup_gradients``' rows into a
    (vocab, ·) array: the valid prefix keeps its ids (strictly ascending, in
    ``[0, vocab)`` when every live id is), the sentinel's row and the whole
    tail get ``vocab + position``. The result is strictly ascending and
    distinct over all N positions, and a ``mode="drop"`` scatter discards
    everything past the valid prefix."""
    n = uid.shape[0]
    if vocab + n > _PAD_SENTINEL:
        raise ValueError(f"vocab {vocab} + n {n} overflows the int32 row index")
    return jnp.where(valid, uid, vocab + jnp.arange(n, dtype=uid.dtype))


def _apply_rows(
    cfg: OptimizerConfig,
    w: jnp.ndarray,
    st: Dict[str, jnp.ndarray],
    g: jnp.ndarray,
    batch_state: jnp.ndarray,
):
    """Optimizer math on a dense (N, D) block of touched rows — mirrors
    ``OptimizerConfig.update_dense`` bit-for-bit in f32."""
    w = w.astype(jnp.float32)
    g = g.astype(jnp.float32)
    # weight decay applies to SGD/Adagrad only — the reference's Adam branch
    # has no decay term (persia_tpu/embedding/optim.py update_dense,
    # mirroring persia-common/src/optim.rs adam_avx2)
    if cfg.weight_decay and cfg.kind in (OPTIMIZER_SGD, OPTIMIZER_ADAGRAD):
        g = g + cfg.weight_decay * w
    if cfg.kind == OPTIMIZER_SGD:
        return w - cfg.lr * g, {}
    if cfg.kind == OPTIMIZER_ADAGRAD:
        if cfg.vectorwise_shared:
            g2 = jnp.mean(g * g, axis=-1, keepdims=True)  # (N, 1)
            acc = st["acc"] * cfg.g_square_momentum + g2
            new_w = w - cfg.lr * g / jnp.sqrt(acc + cfg.eps)
        else:
            acc = st["acc"] * cfg.g_square_momentum + g * g
            new_w = w - cfg.lr * g / jnp.sqrt(acc + cfg.eps)
        return new_w, {"acc": acc}
    if cfg.kind == OPTIMIZER_ADAM:
        m = st["m"] * cfg.beta1 + (1.0 - cfg.beta1) * g
        v = st["v"] * cfg.beta2 + (1.0 - cfg.beta2) * g * g
        beta1_pow, beta2_pow = batch_state[0], batch_state[1]
        m_hat = m / (1.0 - beta1_pow)
        v_hat = v / (1.0 - beta2_pow)
        new_w = w - cfg.lr * m_hat / (jnp.sqrt(v_hat) + cfg.eps)
        return new_w, {"m": m, "v": v}
    raise ValueError(f"unknown optimizer kind {cfg.kind}")


def sparse_update(
    cfg: OptimizerConfig,
    table: jnp.ndarray,
    state: Dict[str, jnp.ndarray],
    ids: jnp.ndarray,
    grads: jnp.ndarray,
    batch_state: jnp.ndarray = None,
    mask: jnp.ndarray = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Apply one sparse optimizer step for the rows named by ``ids``.

    table (V, D) f32, state from ``init_sparse_state``, ids (N,) int,
    grads (N, D). Duplicate ids have their gradients summed first (reference
    worker semantics). ``batch_state`` = (beta1^t, beta2^t) f32[2] for Adam
    (the reference's per-feature-group accumulated beta powers,
    `persia-common/src/optim.rs:99-221`).

    Contract: an entry is live when ``mask`` (N,) bool allows it (default:
    all) and ``0 <= id < V``. Masked-out (padding), negative and
    out-of-range entries touch no row at all. Each distinct live row is
    gathered, updated and written back exactly once, ``_CHUNK_ROWS`` rows at
    a time in a loop that ends with the last live row: the dead tail of the
    static N positions is never executed, and what a last, partly live
    chunk holds of it carries indices ``>= V`` that the write-back drops. Rows
    only touched with zero effective delta are bit-identical unchanged.
    """
    if batch_state is None:
        batch_state = jnp.ones((2,), dtype=jnp.float32)
    ids = ids.astype(jnp.int32)
    vocab = table.shape[0]
    live = (ids >= 0) & (ids < vocab)
    if mask is not None:
        live = live & mask
    # named scopes: a device trace names each of this update's operations by
    # the part it belongs to (PERF.md, "device ms by scope")
    with jax.named_scope("sparse_update"):
        with jax.named_scope("dedup"):
            uid, gsum, valid = dedup_gradients(ids, grads, live)
            sidx = scatter_indices(uid, valid, vocab)
            n_live = jnp.sum(valid, dtype=jnp.int32)
        with jax.named_scope("row_update"):
            table, state = _update_live_rows(cfg, table, state, sidx, gsum, n_live, batch_state)
    return table, state


# Rows a trip of the row-update loop handles. Either write-back pays by the
# position: the v5e's scatter 75-80 ns an update whether it lands or is
# dropped, the row-write kernel one DMA descriptor a live row and a scalar
# test a dead one. So the loop's trip count (the live rows, not the static N)
# is what the step pays for; 512 to 2048 rows a trip read within 0.3 ms of
# each other with the scatter (PERF.md, PR 27) and with the kernel (PR 29).
_CHUNK_ROWS = 1024


def _update_live_rows(cfg, table, state, sidx, gsum, n_live, batch_state):
    """Gather, optimizer math and write-back for the first ``n_live`` of
    ``scatter_indices``' N positions, a chunk a trip. A chunk that would run
    past N starts at N - chunk instead and sends the positions an earlier
    trip already wrote out of range, so no row is written twice."""
    n = sidx.shape[0]
    vocab = table.shape[0]
    chunk = min(_CHUNK_ROWS, n)
    paths = {}
    for name, full in {"table": table, **state}.items():
        paths[name] = _row_write_path(full)
        # once a traced sparse_update and array: which write-back it was built with
        record_event("sparse_update.row_write", array=name, path=paths[name], rows=full.shape[0],
                     dim=full.shape[1], dtype=full.dtype.name, chunk=chunk)

    def write(name, full, idx, old, new):
        """``full[idx] = old + (new - old)`` in ``full``'s dtype: the value a
        scatter-add of the delta leaves there, whichever path writes it."""
        delta = (new - old.astype(jnp.float32)).astype(full.dtype)
        if paths[name] == "dma":
            with jax.named_scope(f"write_{name}"):
                return _write_rows_dma(full, idx, old + delta)
        with jax.named_scope(f"scatter_{name}"):
            return _scatter_add_rows(full, idx, delta)

    def body(trip, carry):
        table, state = carry
        lo = trip * chunk
        start = jnp.minimum(lo, n - chunk)
        pos = start + jnp.arange(chunk, dtype=sidx.dtype)
        idx = jnp.where(pos >= lo, jax.lax.dynamic_slice(sidx, (start,), (chunk,)), vocab + pos)
        g = jax.lax.dynamic_slice(gsum, (start, 0), (chunk, gsum.shape[1]))
        with jax.named_scope("gather_rows"):
            # dropped positions gather row V-1; what they compute is dropped too
            rows = jnp.minimum(idx, vocab - 1)
            w = table[rows]
            st_rows = {k: v[rows] for k, v in state.items()}
        new_w, new_st = _apply_rows(cfg, w, st_rows, g, batch_state)
        table = write("table", table, idx, w, new_w)
        state = {k: write(k, full, idx, st_rows[k], new_st[k]) for k, full in state.items()}
        return table, state

    return jax.lax.fori_loop(0, (n_live + chunk - 1) // chunk, body, (table, state))


def _scatter_add_rows(full: jnp.ndarray, idx: jnp.ndarray, delta: jnp.ndarray) -> jnp.ndarray:
    """``full[idx] += delta`` for a slice of ``scatter_indices``' output.
    The indices are distinct by construction and say so. They are ascending
    too and do NOT say so: on the v5e ``indices_are_sorted=True`` costs a
    third more outside a loop and a copy of the whole operand a trip inside
    one (PERF.md, PR 27)."""
    return full.at[idx].add(delta.astype(full.dtype), mode="drop", unique_indices=True)


def _backend() -> Tuple[str, int]:
    """(platform, devices) the step is being traced for."""
    return jax.default_backend(), jax.device_count()


def _row_write_path(full: jnp.ndarray) -> str:
    """``"dma"`` where ``_write_rows_dma`` can write ``full``'s rows, else
    ``"scatter"``. The kernel copies one row a DMA, and Mosaic slices one row
    out of a 32-bit array only where the array is one tile column wide: a
    float32 row of exactly 128 lanes, one contiguous 512 B piece of the TPU's
    (8, 128) tiling. A wider row (256 lanes as much as an 8 KB row of 2,048)
    is 512 B pieces 4 KB apart, and the compiler refuses the slice ("Slice
    shape along dimension 0 must be aligned to tiling (8)", in VMEM and in
    HBM alike; PERF.md, PR 33); bfloat16 packs two rows to a sublane, and a
    (V, 1) or dim-16 row is a sliver of a tile. It needs one device too:
    GSPMD cannot partition a custom call, so a process that sees several
    devices (tables row-sharded by ``shard_fused_state``, pools on the cached
    tier's ``data`` mesh) keeps the compiler's scatter, as every CPU run does."""
    platform, devices = _backend()
    one_tile_column = full.dtype == jnp.float32 and full.shape[1] == 128
    return "dma" if platform == "tpu" and devices == 1 and one_tile_column else "scatter"


# Positions a trip of the kernel's loop handles: unrolled by hand, since
# Mosaic unrolls a ``fori_loop`` whole or not at all (40 ns a row at 1, 27 at
# 8, 25 at 16 with a wait a row; PERF.md, PR 29).
_DMA_UNROLL = 8


def _write_rows_dma(
    full: jnp.ndarray, idx: jnp.ndarray, rows: jnp.ndarray, *, interpret: bool = False
) -> jnp.ndarray:
    """``full[idx] = rows`` in place, a row a DMA, for a slice of
    ``scatter_indices``' output; positions whose index is ``>= V`` are
    skipped (what ``mode="drop"`` does for the scatter).

    full (V, D) stays in HBM and is aliased to the output; idx (C,) int32 is
    scalar-prefetched; rows (C, D), of ``full``'s dtype, is one VMEM block.
    Every live position starts its own asynchronous copy of one (1, D) row on
    one DMA semaphore, and all of a chunk's copies are started before any is
    waited for: nothing orders them. That is legal only because the live
    indices are DISTINCT: ``scatter_indices``' contract. Two positions naming
    one row would race. The semaphore counts what has arrived, so the copies
    are waited for by their number and not one by one: a wait for 2**b rows'
    worth for every set bit b of the live count (a wait a row costs 7 ns a
    row more; PERF.md, PR 29).

    Compiled by Mosaic unless the caller passes ``interpret=True`` (the CPU
    tests do); ``_row_write_path`` says where the compiled kernel applies."""
    vocab, dim = full.shape
    chunk = idx.shape[0]
    unroll = _DMA_UNROLL if chunk % _DMA_UNROLL == 0 else 1

    def kernel(idx_ref, rows_ref, full_ref, out_ref, sem):
        del full_ref  # the same buffer as out_ref

        def start_live(trip, n_started):
            for k in range(unroll):
                i = trip * unroll + k
                row = idx_ref[i]
                live = row < vocab

                @pl.when(live)
                def _(i=i, row=row):
                    pltpu.make_async_copy(
                        rows_ref.at[pl.ds(i, 1)], out_ref.at[pl.ds(row, 1)], sem).start()

                n_started = n_started + live.astype(jnp.int32)
            return n_started

        n_started = jax.lax.fori_loop(0, chunk // unroll, start_live, jnp.int32(0))
        for bit in range(chunk.bit_length()):
            # never started: its size is what the wait takes off the semaphore
            block = rows_ref.at[pl.ds(0, 1 << bit)]
            rows_worth = pltpu.make_async_copy(block, block, sem)
            pl.when((n_started >> bit) & 1 == 1)(rows_worth.wait)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(full.shape, full.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[
                pl.BlockSpec((chunk, dim), lambda _, idx_ref: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        input_output_aliases={2: 0},  # operand 0 is idx, 1 rows, 2 full
        interpret=interpret,
        name="sparse_row_write",
    )(idx, rows, full)


def masked_flat_ids_grads(
    ids: jnp.ndarray, grads: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Flatten bag/single-id slots for ``sparse_update``: ids (B,) or (B, L)
    with -1 padding + per-position grads → (flat_ids, flat_grads (N, D),
    flat_mask). Padding keeps its -1 id but is masked out, so it touches no
    table row (not even through weight decay)."""
    mask = (ids >= 0).reshape(-1)
    flat_ids = ids.reshape(-1)
    flat_g = grads.reshape(-1, grads.shape[-1])
    return flat_ids, flat_g, mask
