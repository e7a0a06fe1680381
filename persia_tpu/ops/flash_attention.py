"""Flash attention as a Pallas TPU kernel.

Tiled online-softmax attention: the [L, L] score matrix is never
materialized in HBM. Grid = (B*H, q_blocks, k_blocks); the innermost grid
dimension is sequential on TPU, so VMEM scratch carries the (m, l, acc)
online-softmax state across k blocks and the output block is written once on
the last k step. fp32 accumulation regardless of input dtype; MXU matmuls via
``preferred_element_type``.

The kernel is compiled by Mosaic unless the caller asks for
``interpret=True`` (the CPU tests do); nothing picks the interpreter on the
caller's behalf. The backward pass recomputes attention densely under XLA
(``@jax.custom_vjp``) — exact gradients, O(L^2) memory on the backward only.

Used by the model zoo for long user-behavior sequences (DIN-style attention)
and usable as the local block of ring attention for L/n still too large for
dense scores.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
               *, scale: float, causal: bool, block_q: int, block_k: int,
               seq_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Causal: blocks entirely above the diagonal contribute nothing — skip
    # their compute (their DMA is already pipelined; compute is the cost).
    block_live = True
    if causal:
        block_live = ki * block_k <= qi * block_q + block_q - 1

    @pl.when(block_live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [block_q, d]
        k = k_ref[0].astype(jnp.float32)  # [block_k, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [block_q, block_k]

        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_len  # padded keys never attend
        if causal:
            mask = mask & (k_pos <= q_pos)
        s = jnp.where(mask, s, _NEG_BIG)

        m_prev = m_ref[:]                       # [block_q, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)          # [block_q, 1]
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[:] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _fa_forward(q, k, v, scale, causal, block_q, block_k, interpret):
    b, l, h, d = q.shape
    # Snap the block cap to a power of two so clamping can't produce a block
    # that fails to divide the padded length; pad to lcm(bq, bk) so BOTH
    # grids cover every row/column.
    cap = 8
    while cap < _round_up(l, 8):
        cap *= 2
    bq = min(block_q, cap)
    bk = min(block_k, cap)
    lp = _round_up(l, math.lcm(bq, bk))

    def prep(x):
        x = jnp.moveaxis(x, 2, 1).reshape(b * h, l, d)
        return jnp.pad(x, ((0, 0), (0, lp - l), (0, 0)))

    qf, kf, vf = prep(q), prep(k), prep(v)
    grid = (b * h, lp // bq, lp // bk)
    out = pl.pallas_call(
        functools.partial(
            _fa_kernel, scale=scale, causal=causal,
            block_q=bq, block_k=bk, seq_len=l,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, lp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # m
            pltpu.VMEM((bq, 1), jnp.float32),   # l
            pltpu.VMEM((bq, d), jnp.float32),   # acc
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return jnp.moveaxis(out[:, :l, :].reshape(b, h, l, d), 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret):
    return _fa_forward(q, k, v, scale, causal, block_q, block_k, interpret)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    return _fa_forward(q, k, v, scale, causal, block_q, block_k, interpret), (q, k, v)


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, g):
    from persia_tpu.parallel.sequence import reference_attention

    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: reference_attention(q, k, v, causal=causal, scale=scale), q, k, v
    )
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Tiled attention: q, k, v [B, L, H, D] → [B, L, H, D].

    ``interpret=True`` runs the kernel in the Pallas interpreter (CPU
    tests); the default compiles it, which needs a TPU.
    """
    if q.ndim != 4:
        raise ValueError(f"expected [B, L, H, D], got shape {q.shape}")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _flash(q, k, v, scale, causal, block_q, block_k, interpret)
