"""The grouped products of the experts a chip holds, as Pallas kernels.

``rows`` (M, K) are sorted by group: the first ``sizes[0]`` rows belong to
group 0, the next ``sizes[1]`` to group 1, and so on; rows past ``sum(sizes)``
belong to none. Three products, the expert layer's forward and its
hand-written backward: ``grouped_matmul`` (rows by their group's weights),
the same with the weights contracted over their last axis (so the backward
needs no transposed copy of them), and ``grouped_outer`` (the weights'
gradient). The arithmetic is fixed: operands as given (bfloat16) in one MXU
pass whatever the process's default matmul precision (``Precision.DEFAULT``
is pinned in the kernels: under ``highest`` Mosaic refuses bfloat16
operands), sums and results float32. **Rows of no group give exactly 0** and
an empty group's gradient block is 0: every block of every result is written.

**The walk.** A grid step is one visit of a row tile by one group
(``_schedule``, made from ``sizes`` on the device): a tile inside one group is
visited once, a tile that group boundaries cut once a group that has rows in
it, with the other groups' rows masked on the store; a tile past the last
live row once, to be written as zeros (it names the group of the step
before, so no weights move for it). The grid is static, ``M / tile + G - 1``
steps, which bounds the visits of any split; the steps no split needs do
nothing. So the cost is one visit a live row tile and one more a boundary
inside a tile, whatever the split.

**The tiles** are a function of ``(M, K, N)`` alone (``grouped_tiles``): row
tiles of 512 (all of ``M`` when it is less; ``M`` is padded to whole tiles,
which the expert layer's chunks are), the whole ``K`` in a block, and the
widest ``N`` block, a multiple of 128 lanes that divides ``N`` or ``N``
itself, that keeps the double-buffered blocks inside ``VMEM_BLOCKS``. With
the whole ``K`` in a block a group's weights keep their block index from one
row tile to the next and are fetched once a group, not once a tile: at
(512, 2304) x (2304, 896) a visit moves 2.4 MB of rows in and 1.8 MB of
float32 out for 2.1 GFLOP, where a 128-row tile that re-reads its weights is
HBM-bound. The blocks take more than the compiler's scoped default of 16 MiB
(16 to 22 MiB at the benchmark's widths, 30 with the (K, N) product the
weights' gradient holds beside its blocks), so each call asks for
``VMEM_LIMIT`` of the chip's 128 MiB.

On the v5e (PERF.md section 6, PR 39: the kernels alone, 30 calls each) a
product of (36864, 2304) rows with two groups of 16,384 takes 0.86 ms, 80% of
the MXU's peak over the live rows' FLOPs, where ``lax.ragged_dot`` took 2.78;
contracted over the weights' last axis 0.85; the weights' gradient 1.00
against ``ragged_dot_general``'s 3.80, a sixth of it the writing of the
fourteen empty groups' blocks. Rows in tiles of 1,024 read 7% faster on that
split and cost twice as much a boundary inside a tile; 256 read slower.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 512
VMEM_BLOCKS = 40 * 2 ** 20  # what the double-buffered blocks of a call may take
VMEM_LIMIT = 64 * 2 ** 20  # of 128 MiB: the blocks and the product's temporaries

def grouped_tiles(m: int, k: int, n: int) -> tuple[int, int, int]:
    """The (row, K, N) tile of ``grouped_matmul`` for rows (m, k) and a result
    (m, n), and of ``grouped_outer`` for rows (m, k) and a result (G, k, n):
    see the module's docstring."""
    tm = ROW_TILE if m >= ROW_TILE else -(-m // 16) * 16
    widths = [n] + [w for w in range(n - n % 128, 0, -128) if n % w == 0]

    def blocks(tn):  # bytes, double-buffered: bfloat16 rows and (K, tn) weights, a float32 result
        return 2 * (2 * tm * k + 2 * k * tn + 4 * max(tm, k) * tn)

    fits = [w for w in widths if blocks(w) <= VMEM_BLOCKS]
    return tm, k, (fits or widths[-1:])[0]


def _schedule(sizes, tiles: int, tm: int, every_group: bool):
    """What each of the ``tiles + G - 1`` grid steps visits, four int32 arrays:
    the row tile, the group, and the group's rows in the tile as ``lo .. hi``
    counted from the tile's first row (``hi <= lo``: none).

    In row order: a group's visits are the tiles it has rows in; with
    ``every_group`` an empty group gets one visit that holds no row (its
    result block has to be written), without it the tiles past the last live
    row get one each (theirs has to). A visit with no rows names the group,
    and an empty group's the tile, of the step before, so those blocks are
    not fetched again; the steps left over repeat the last visit with no
    rows."""
    g = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    begins = ends - sizes
    live_tiles = (ends[-1] + tm - 1) // tm
    # the rows of no group as a last pseudo-group over the tiles that hold nothing else
    first = jnp.append(jnp.where(sizes > 0, begins, jnp.maximum(begins - 1, 0)) // tm, live_tiles)
    count = jnp.append(jnp.where(sizes > 0, (ends - 1) // tm - first[:g] + 1, int(every_group)),
                       0 if every_group else tiles - live_tiles)
    through = jnp.cumsum(count)
    step = jnp.arange(tiles + g - 1, dtype=jnp.int32)
    visit = jnp.minimum(step, through[g] - 1)  # a step left over is at the last visit
    owner = jnp.searchsorted(through, visit, side="right").astype(jnp.int32)
    tile = first[owner] + visit - (through - count)[owner]
    # the last group that has a visit: what a tile past the live rows names
    last = jnp.minimum(jnp.searchsorted(through[:g], through[g - 1] - 1, side="right"), g - 1)
    group = jnp.where(owner < g, owner, last).astype(jnp.int32)
    holds = (owner < g) & (step == visit)
    at = tile * tm
    lo = jnp.where(holds, jnp.clip(begins[group] - at, 0, tm), 0)
    hi = jnp.where(holds, jnp.clip(ends[group] - at, 0, tm), 0)
    return tile, group, lo, hi


def _rows_kept(shape, lo, hi):
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= lo) & (row < hi)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)


def _matmul_kernel(tile_ref, group_ref, lo_ref, hi_ref, rows_ref, w_ref, out_ref, *, tm, over):
    del group_ref
    i = pl.program_id(1)
    lo, hi = lo_ref[i], hi_ref[i]
    fresh = (i == 0) | (tile_ref[i] != tile_ref[jnp.maximum(i - 1, 0)])

    def product():
        return _dot(rows_ref[...], w_ref[...], ((1,), (over,)))

    @pl.when(hi - lo == tm)
    def _whole():
        out_ref[...] = product()

    @pl.when((hi > lo) & (hi - lo < tm))
    def _cut():
        # the other groups' rows keep what their visits wrote, or 0 on the tile's first
        kept = jnp.where(fresh, 0.0, out_ref[...])
        out_ref[...] = jnp.where(_rows_kept(out_ref.shape, lo, hi), product(), kept)

    @pl.when((hi <= lo) & fresh)
    def _none():
        out_ref[...] = jnp.zeros_like(out_ref)


def _outer_kernel(tile_ref, group_ref, lo_ref, hi_ref, rows_ref, grads_ref, out_ref, *, tm):
    del tile_ref
    i = pl.program_id(1)
    lo, hi = lo_ref[i], hi_ref[i]

    @pl.when((i == 0) | (group_ref[i] != group_ref[jnp.maximum(i - 1, 0)]))
    def _fresh():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(hi - lo == tm)
    def _whole():
        out_ref[...] += _dot(rows_ref[...], grads_ref[...], ((0,), (0,)))

    @pl.when((hi > lo) & (hi - lo < tm))
    def _cut():
        grads = grads_ref[...]
        grads = jnp.where(_rows_kept(grads.shape, lo, hi), grads, jnp.zeros_like(grads))
        out_ref[...] += _dot(rows_ref[...], grads, ((0,), (0,)))


def _padded(x, tm):
    return jnp.pad(x, ((0, -x.shape[0] % tm), (0, 0)))


# the blocks of a grid step (j: the N block, i: the walk's step) by the walk's arrays
def _tile_all_k(j, i, tile, group, lo, hi):
    return tile[i], 0


def _tile_n(j, i, tile, group, lo, hi):
    return tile[i], j


def _group_k_n(j, i, tile, group, lo, hi):
    return group[i], 0, j


def _group_n_k(j, i, tile, group, lo, hi):
    return group[i], j, 0


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=VMEM_LIMIT)


def grouped_matmul(rows, weights, sizes, transposed: bool = False, interpret: bool = False):
    """rows (M, K) x weights (G, K, N) by ``sizes`` (G,) int32 -> (M, N)
    float32: row i of group g meets ``weights[g]``; rows of no group give 0.
    ``transposed``: the weights are (G, N, K) and contracted over their last
    axis, the product with each group's transposed weights."""
    tm, _, tn = grouped_tiles(*rows.shape, weights.shape[1 if transposed else 2])
    return _matmul(rows, weights, sizes, tm, tn, transposed, interpret)


def grouped_outer(rows, grads, sizes, interpret: bool = False):
    """rows (M, K), grads (M, N) -> (G, K, N) float32: ``rows_g^T grads_g``
    over each group's rows, the weights' gradient of ``grouped_matmul``; an
    empty group's block is 0."""
    tm, _, tn = grouped_tiles(*rows.shape, grads.shape[1])
    return _outer(rows, grads, sizes, tm, tn, interpret)


# jitted, so that the step's many calls of one shape are traced and lowered once
# (a tower's period holds some fifteen a layer; PERF.md section 6, PR 39)

@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _matmul(rows, weights, sizes, tm, tn, transposed, interpret):
    m, k = rows.shape
    n = weights.shape[1 if transposed else 2]
    rows = _padded(rows, tm)
    walk = _schedule(sizes, rows.shape[0] // tm, tm, every_group=False)
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, tm=tm, over=1 if transposed else 0),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(walk),
            grid=(n // tn, walk[0].shape[0]),
            in_specs=[pl.BlockSpec((tm, k), _tile_all_k),
                      pl.BlockSpec((None, tn, k), _group_n_k) if transposed
                      else pl.BlockSpec((None, k, tn), _group_k_n)],
            out_specs=pl.BlockSpec((tm, tn), _tile_n)),
        out_shape=jax.ShapeDtypeStruct((rows.shape[0], n), jnp.float32),
        compiler_params=_PARAMS,
        interpret=interpret,
        name="grouped_matmul_t" if transposed else "grouped_matmul",
    )(*walk, rows, weights)
    return out[:m]


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _outer(rows, grads, sizes, tm, tn, interpret):
    k, n = rows.shape[1], grads.shape[1]
    rows, grads = _padded(rows, tm), _padded(grads, tm)
    walk = _schedule(sizes, rows.shape[0] // tm, tm, every_group=True)
    return pl.pallas_call(
        functools.partial(_outer_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(walk),
            grid=(n // tn, walk[0].shape[0]),
            in_specs=[pl.BlockSpec((tm, k), _tile_all_k), pl.BlockSpec((tm, tn), _tile_n)],
            out_specs=pl.BlockSpec((None, k, tn), _group_k_n)),
        out_shape=jax.ShapeDtypeStruct((sizes.shape[0], k, n), jnp.float32),
        compiler_params=_PARAMS,
        interpret=interpret,
        name="grouped_outer",
    )(*walk, rows, grads)
