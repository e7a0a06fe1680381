"""What stands between the q and k projections and the attention kernels, as
one pass: the per-head RMS norm, rotate-half RoPE and the cast to bfloat16,
over the ``(B, T, H * D)`` array the projection leaves.

A head is a ``D``-lane column block of that array, as the attention kernels'
own ``BlockSpec``s read it, so q and k never take the ``(B, T, H, D)`` layout
in HBM and nothing between the two layouts is copied. The forward reads a
float32 element once and writes it once as bfloat16; the backward reads the
float32 input and the bfloat16 gradient the attention backward hands over and
writes the float32 gradient. All arithmetic is float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

QK_PREP_TILE = 1024  # positions a block: 512 KB of float32 a head of 128
_PARTIAL_ROWS = 8  # the weight's gradient leaves a block as one (8, D) tile


def qk_prep_tile(t: int) -> int:
    """The positions a block holds for a sequence of ``t``: the most that
    divide ``t`` in whole tiles of 8 rows, up to ``QK_PREP_TILE``."""
    if t % 8:
        raise ValueError(f"the length {t} must be a multiple of the tile of 8")
    return max(r for r in range(8, min(QK_PREP_TILE, t) + 1, 8) if t % r == 0)


def _normed(x, eps):
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * r, r


def _fwd_kernel(x_ref, w_ref, cos_ref, sin_ref, o_ref, *, eps):
    y = _normed(x_ref[0], eps)[0] * w_ref[...]
    half = y.shape[-1] // 2
    o_ref[0] = (y * cos_ref[0] + pltpu.roll(y, half, 1) * sin_ref[0]).astype(o_ref.dtype)


def _bwd_kernel(x_ref, w_ref, cos_ref, sin_ref, g_ref, dx_ref, dw_ref, *, eps):
    n, r = _normed(x_ref[0], eps)
    g = g_ref[0].astype(jnp.float32)
    half = g.shape[-1] // 2
    dy = g * cos_ref[0] + pltpu.roll(g * sin_ref[0], half, 1)
    dn = dy * w_ref[...]
    dx_ref[0] = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
    dw_ref[0, 0] = jnp.sum((dy * n).reshape(-1, _PARTIAL_ROWS, n.shape[-1]), axis=0)


def _specs(x, cos, n_heads, rows):
    """The grid (sequence, block of positions, head: the tables' block stays
    where it is while the heads pass) and the blocks of an x-shaped array, of
    the weight, of a table (one for all sequences or one each) and of the
    weight's gradient as the blocks leave it, ``(B, T / rows, 8, H * D)``."""
    b, t, width = x.shape
    d = width // n_heads
    per_sequence = cos.shape[0] > 1
    return ((b, t // rows, n_heads),
            pl.BlockSpec((1, rows, d), lambda bi, p, h: (bi, p, h)),
            pl.BlockSpec((1, d), lambda bi, p, h: (0, 0)),
            pl.BlockSpec((1, rows, d), lambda bi, p, h: (bi if per_sequence else 0, p, 0)),
            pl.BlockSpec((1, 1, _PARTIAL_ROWS, d), lambda bi, p, h: (bi, p, 0, h)))


_EVERY_BLOCK_ITS_OWN = pltpu.CompilerParams(dimension_semantics=("parallel",) * 3)


def _forward(x, w, cos, sin, n_heads, eps, rows, interpret):
    grid, x_spec, w_spec, table_spec, _ = _specs(x, cos, n_heads, rows)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[x_spec, w_spec, table_spec, table_spec],
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.bfloat16),
        compiler_params=_EVERY_BLOCK_ITS_OWN,
        interpret=interpret,
        name="qk_norm_rope_fwd",
    )(x, w, cos, sin)


def _backward(x, w, cos, sin, g, n_heads, eps, rows, interpret):
    grid, x_spec, w_spec, table_spec, partial_spec = _specs(x, cos, n_heads, rows)
    b, t, width = x.shape
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        grid=grid,
        in_specs=[x_spec, w_spec, table_spec, table_spec, x_spec],
        out_specs=[x_spec, partial_spec],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, t // rows, _PARTIAL_ROWS, width), jnp.float32)],
        compiler_params=_EVERY_BLOCK_ITS_OWN,
        interpret=interpret,
        name="qk_norm_rope_bwd",
    )(x, w, cos, sin, g)
    return dx, jnp.sum(dw.reshape(-1, n_heads, width // n_heads), axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _prep(x, w, cos, sin, n_heads, eps, rows, interpret):
    return _forward(x, w, cos, sin, n_heads, eps, rows, interpret)


def _prep_fwd(x, w, cos, sin, n_heads, eps, rows, interpret):
    return _forward(x, w, cos, sin, n_heads, eps, rows, interpret), (x, w, cos, sin)


def _prep_bwd(n_heads, eps, rows, interpret, res, g):
    x, w, cos, sin = res
    dx, dw = _backward(x, w, cos, sin, g, n_heads, eps, rows, interpret)
    return dx, dw.reshape(w.shape), None, None  # no table gets a gradient


_prep.defvjp(_prep_fwd, _prep_bwd)


def qk_norm_rope(
    x: jax.Array,
    weight: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    n_heads: int,
    eps: float,
    interpret: bool = False,
) -> jax.Array:
    """``rope(rms_norm(x by head) * weight)`` as bfloat16: x [B, T, H * D]
    float32 as a projection leaves it, ``weight`` [D], ``cos`` and ``sin``
    [T, D] (one table for all sequences) or [B, T, D] (one a sequence), which
    the rank says -> [B, T, H * D] bfloat16, head ``h`` in the lanes
    ``h * D .. (h + 1) * D`` as before.

    A head and position: ``y = x * rsqrt(mean(x * x) + eps) * weight``, then
    rotate-half RoPE, ``y * cos + concat(-y[D/2:], y[:D/2]) * sin``, the
    rotation as one lane roll by ``D / 2`` under a sine table signed once.
    Forward and backward are one Pallas kernel each over blocks of
    ``qk_prep_tile(T)`` positions of one head; the backward takes the
    bfloat16 gradient, recomputes the norm from ``x`` and sums the weight's
    gradient in float32 (a block's rows to one tile in the kernel, the tiles
    outside).
    """
    if x.ndim != 3 or x.shape[-1] % n_heads:
        raise ValueError(f"expected [B, T, {n_heads} heads * D], got shape {x.shape}")
    b, t, width = x.shape
    d = width // n_heads
    if d % 128 or t % 8:
        raise ValueError(f"the length {t} must be a multiple of the tile of 8, and the head "
                         f"size {d} of 128")
    if cos.shape != sin.shape or cos.shape not in ((t, d), (b, t, d)) or weight.shape != (d,):
        raise ValueError(f"tables {cos.shape}, {sin.shape} and weight {weight.shape} do not fit "
                         f"x {x.shape} of {n_heads} heads")
    # concat(-y[D/2:], y[:D/2]) = roll(y, D/2) * (-1 on the first half of the lanes, +1 on the rest)
    sign = jnp.where(jnp.arange(d) < d // 2, -1.0, 1.0).astype(jnp.float32)
    cos, sin = (table.astype(jnp.float32).reshape(-1, t, d) for table in (cos, sin * sign))
    return _prep(x.astype(jnp.float32), weight.astype(jnp.float32).reshape(1, d), cos, sin,
                 int(n_heads), float(eps), qk_prep_tile(t), interpret)
