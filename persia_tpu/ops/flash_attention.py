"""Flash attention as Pallas TPU kernels.

Tiled online-softmax attention: the [L, L] score matrix is never
materialized in HBM. fp32 accumulation regardless of input dtype; MXU matmuls
via ``preferred_element_type``. One mechanism under two masks, and one older
forward kernel:

**Interval attention** (``interval_attention``): query ``i`` reads the keys
``[lo_i, i]``, ``lo`` an int32 array that comes with the batch. A causal
sequence (``lo`` = 0), a window (``window``: no key before ``i - window + 1``)
and packed documents (``lo_i`` = the start of ``i``'s document) are this one
thing. K/V heads are shared by groups of query heads. Which (q tile, k tile)
pairs are dead, whole or cut is reduced on the device a call from each q
tile's least and greatest ``lo`` and compacted into visit lists that the
kernels take as prefetched scalars: a dead pair is neither fetched nor
computed. Forward, dq and dk/dv are all kernels and only the log-sum-exp a
row is kept between them, so nothing is L^2 anywhere. ``models/mellum_moe.py``
runs on it, at one packed sequence of 16,384 positions, 32 query heads over 4
K/V heads, in the benchmark's cell ``mellum2-ep4-pack16k``. With
``k_shared`` a score is wider than a value: R further key columns that every
K/V head shares and R further columns a query head, their product added to
the scores a tile in all three kernels (latent attention without positions:
``models/kimi_linear_moe.py``, 32 heads at 192/128, in the cell
``kimi-linear-ep32-pack16k``); without it the kernels are what they were.

**The block-diffusion mask** (``block_diffusion_attention``) beside it: the
same three kernel bodies, the mask given by ``(seq_len, block_len)``, so its
visit tables are numpy made when the step is traced; a tile the mask cuts is
walked by sub-tiles over what it holds. ``models/sdar_moe.py`` runs on it, at
2 x 8,192 positions in the cell ``sdar-ep8-bd4-seq4k``. The interval kernels
walk the tile on their diagonal the same way.

``flash_attention`` (one K/V head a query head, any length and head size):
``causal=True`` pads to whole tiles and lanes and runs the interval kernels,
forward and backward. Full attention keeps the first forward kernel (grid =
(B*H, q_blocks, k_blocks), VMEM scratch carries the online-softmax state
across k blocks) and a backward that recomputes attention densely under XLA:
O(L^2) memory on the backward only, for sequences whose dense scores fit. No
model of the zoo calls it; ``parallel/sequence.py``'s ring attention takes
dense blocks of its own.

The kernels are compiled by Mosaic unless the caller asks for
``interpret=True`` (the CPU tests do); nothing picks the interpreter on the
caller's behalf.
"""

from __future__ import annotations

import functools
import math
import operator
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
               *, scale: float, block_q: int, block_k: int, seq_len: int):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # [block_q, d]
    k = k_ref[0].astype(jnp.float32)  # [block_k, d]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [block_q, block_k]

    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = k_pos < seq_len  # padded keys never attend
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


def _fa_forward(q, k, v, scale, block_q, block_k, interpret):
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
        functools.partial(_fa_kernel, scale=scale, block_q=bq, block_k=bk, seq_len=l),
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, block_q, block_k, interpret):
    return _fa_forward(q, k, v, scale, block_q, block_k, interpret)


def _flash_fwd(q, k, v, scale, block_q, block_k, interpret):
    return _fa_forward(q, k, v, scale, block_q, block_k, interpret), (q, k, v)


def _flash_bwd(scale, block_q, block_k, interpret, res, g):
    from persia_tpu.parallel.sequence import reference_attention

    q, k, v = res
    _, vjp = jax.vjp(lambda q, k, v: reference_attention(q, k, v, scale=scale), q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _causal(q, k, v, scale, tile, interpret):
    """A causal sequence through ``interval_attention`` (``lo`` = 0): the
    length padded to whole tiles (padded keys lie after every real query) and
    the head size to whole lanes (zeros add nothing to a score)."""
    b, l, h, d = q.shape
    tile = min(_round_up(tile, 8), _round_up(l, 8))
    pad = ((0, 0), (0, _round_up(l, tile) - l), (0, 0), (0, _round_up(d, 128) - d))
    out = interval_attention(jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad),
                             jnp.zeros((b, l + pad[1][1]), jnp.int32), scale=scale, tile=tile,
                             interpret=interpret)
    return out[:, :l, :, :d]


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
    """Tiled attention: q, k, v [B, L, H, D] → [B, L, H, D]. ``causal`` runs
    the interval kernels (square tiles of the smaller block, forward and
    backward); full attention the forward kernel above.

    ``interpret=True`` runs the kernels in the Pallas interpreter (CPU
    tests); the default compiles them, which needs a TPU.
    """
    if q.ndim != 4:
        raise ValueError(f"expected [B, L, H, D], got shape {q.shape}")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if causal:
        return _causal(q, k, v, scale, min(block_q, block_k), interpret)
    return _flash(q, k, v, scale, block_q, block_k, interpret)


# ---------------------------------------------------------------------------
# Block-diffusion attention: K/V heads shared by groups of query heads, the
# mask given by (seq_len, block_len), dead (q tile, k tile) pairs never
# visited, and a backward that keeps only the row statistics.
# ---------------------------------------------------------------------------

# Every product of these kernels takes its operands as they come (bfloat16 on
# the chip) in one pass and sums in float32, whatever the process's default
# matmul precision says: Mosaic refuses a bfloat16 operand at "highest".
_ONE_PASS = jax.lax.Precision.DEFAULT
# Square tiles of 512 positions: at L 4096 on the v5e, 256 and 1024 both run
# slower (my chip runs, PR 33). A shorter sequence is one tile of its length.
# A tile the mask cuts is walked by sub-tiles of a quarter of it (``_sub_tile``)
# and costs 0.4 to 1.0 of a whole one (0.9-1.3 us on the noised diagonal,
# 1.4-2.1 in a triangle, against 1.8-2.1), where it cost 1.4-1.6 of one
# (3.1-3.5 us) while its mask was computed position by position on the tile's
# grid, 1.5 us a visit in each kernel (my chip runs, PR 34).
BLOCK_DIFFUSION_TILE = 512
ATTENTION_OUT, ATTENTION_LSE = "block_diffusion_attention_out", "block_diffusion_attention_lse"
# What a (q tile, k tile) pair holds. A tile the mask cuts is a diagonal tile
# of one of three kinds, named by the test a key's block passes against the
# query's: the noised diagonal, noised queries on clean keys, the clean diagonal.
_DEAD, _WHOLE, _EQ, _LT, _LE = range(5)
_LO = 5  # interval attention: a tile the batch's intervals cut anywhere but along its own diagonal
_CUT = {_EQ: operator.eq, _LT: operator.lt, _LE: operator.le}  # key block ? query block
_KIND_NAMES = {_WHOLE: "whole", _EQ: "noised_diagonal", _LT: "noised_on_clean", _LE: "clean_diagonal"}


def block_diffusion_allowed(q_pos, k_pos, seq_len: int, block_len: int):
    """Whether query position ``q_pos`` may read key position ``k_pos`` of the
    ``2 * seq_len`` positions ``[noised | clean]`` (numpy int arrays,
    non-negative; they broadcast). With ``beta(i) = i // block_len`` the block
    of position ``i`` of either half: a noised query reads the noised keys of
    its own block and the clean keys of earlier blocks; a clean query reads
    the clean keys of its own and earlier blocks and no noised key. The
    definition: the kernels take a cut tile's mask from its kind."""
    q_clean, k_clean = q_pos >= seq_len, k_pos >= seq_len
    qb = (q_pos - np.where(q_clean, seq_len, 0)) // block_len
    kb = (k_pos - np.where(k_clean, seq_len, 0)) // block_len
    return (k_clean & (kb <= qb) & (q_clean | (kb < qb))) | (~q_clean & ~k_clean & (kb == qb))


def block_diffusion_mask(seq_len: int, block_len: int) -> np.ndarray:
    """The dense (2L, 2L) mask, True where a query (row) reads a key (column)."""
    pos = np.arange(2 * seq_len)
    return block_diffusion_allowed(pos[:, None], pos[None, :], seq_len, block_len)


@functools.lru_cache(maxsize=8)
def _live_tiles(seq_len: int, block_len: int, tile: int) -> np.ndarray:
    """The kind of every (q tile, k tile) pair of the mask, (n, n) int32:
    ``_DEAD`` (no allowed pair), ``_WHOLE`` (nothing else), or the cut kind
    whose test the tile's own mask equals, block index against block index
    from the tile's corner. Read from the mask itself: a shape that cuts a
    tile any other way (a block that straddles tiles) raises."""
    n = 2 * seq_len // tile
    kinds = np.zeros((n, n), np.int32)
    pos = np.arange(2 * seq_len)
    block = np.arange(tile) // block_len
    for qi in range(n):
        rows = block_diffusion_allowed(
            pos[qi * tile:(qi + 1) * tile, None], pos[None, :], seq_len, block_len)
        tiles = rows.reshape(tile, n, tile)
        kinds[qi] = np.where(tiles.all(axis=(0, 2)), _WHOLE, _DEAD)
        for ki in np.nonzero(tiles.any(axis=(0, 2)) & (kinds[qi] == _DEAD))[0]:
            fits = [kind for kind, test in _CUT.items()
                    if np.array_equal(tiles[:, ki], test(block[None, :], block[:, None]))]
            if not fits:
                raise ValueError(
                    f"the block-diffusion mask of seq_len {seq_len} and block_len {block_len} cuts "
                    f"tile pair ({qi}, {ki}) of {tile} positions off its diagonal")
            kinds[qi, ki] = fits[0]
    kinds.setflags(write=False)
    return kinds


def _sub_tile(tile: int, block_len: int) -> int:
    """The width of the query columns and key rows a cut tile is walked by: a
    quarter of the tile where blocks do not straddle it (128 at tiles of 512,
    the MXU's width on the v5e), else the tile as one. Measured at the
    benchmark's cell (ms a layer, forward + dq + dk/dv; my chip runs, PR 34):
    28.06 at 128, 28.09 at 256, 28.24 with a cut tile as one sub-tile, 39.66
    before; the forward alone is quickest unwalked (8.40 against 8.54), dq at
    256 (9.73 against 10.24), dk/dv at 128 (9.30 against 10.02 unwalked)."""
    return tile // 4 if tile % (4 * block_len) == 0 else tile


def _cut_slabs(kind: int, nsub: int, by_keys: bool):
    """The slabs a cut tile of ``kind`` with ``nsub`` x ``nsub`` sub-tiles is
    walked in, each (first query column, columns, first key row, rows) in
    sub-tiles: the noised diagonal's are its diagonal sub-tiles; a triangle's
    are, by query column, the key rows up to the column's own, or, by key
    row, the query columns from the row's own on. The kind's test cuts the
    slab's sub-tile on the diagonal and passes the rest of it."""
    if kind == _EQ:
        return [(i, 1, i, 1) for i in range(nsub)]
    if by_keys:
        return [(i, nsub - i, i, 1) for i in range(nsub)]
    return [(i, 1, 0, i + 1) for i in range(nsub)]


def _visit_tables(kinds: np.ndarray):
    """The live (row, column) pairs of ``kinds`` in row-major order, as flat
    int32 arrays for scalar prefetch: each pair's row, its column, its kind,
    whether the pair is the first of its row, whether the last. A kernel's
    grid runs over the pairs and nothing else: a dead pair costs no grid step
    (0.35 us each; at L 4096 and tiles of 512, 80 pairs of 256). The kind is
    the (q tile, k tile) pair's whichever of the two the rows are."""
    rows, cols = np.nonzero(kinds)
    first = np.concatenate([[True], rows[1:] != rows[:-1]])
    last = np.concatenate([rows[1:] != rows[:-1], [True]])
    as_i32 = lambda x: jnp.asarray(np.asarray(x, np.int32))
    return tuple(as_i32(x) for x in (rows, cols, kinds[rows, cols], first, last))


def block_diffusion_plan(seq_len: int, block_len: int, tile: int = BLOCK_DIFFUSION_TILE) -> dict:
    """What the kernels execute for one head of one sequence: the visited
    tile pairs by kind, the sub-tile's width, the sub-tiles executed of all
    that the visited tiles hold, and the pairs executed beside the live ones."""
    tile = min(tile, seq_len)
    kinds = _live_tiles(seq_len, block_len, tile)
    sub = _sub_tile(tile, block_len)
    nsub = tile // sub
    held = {kind: sum(nq * nk for _, nq, _, nk in _cut_slabs(kind, nsub, False)) for kind in _CUT}
    held[_WHOLE] = nsub * nsub
    visits = {kind: int((kinds == kind).sum()) for kind in held}
    executed = sum(visits[kind] * held[kind] for kind in held)
    plan = {f"visits_{_KIND_NAMES[kind]}": visits[kind] for kind in held}
    return dict(plan, visits=sum(visits.values()), tile=tile, sub_tile=sub,
                sub_tiles_executed=executed, sub_tiles_visited=sum(visits.values()) * nsub * nsub,
                pairs_executed=executed * sub * sub, pairs_live=seq_len * (seq_len + block_len))


def _raw_scores(q, k, more=None):
    s = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32, precision=_ONE_PASS)
    return s if more is None else s + more


def _bd_scores(q, k, scale, test, block_len, q_at, k_at, more=None):
    """Scaled scores of keys ``k`` against queries ``q`` in float32, keys down
    and queries across: in this orientation a query's statistics are a row,
    reduced over sublanes and stored lane-dense. With a ``test`` the keys and
    queries are a slab of a cut tile that starts at the tile's positions
    ``k_at`` and ``q_at``, and a key is masked unless its block passes the
    test against the query's: the block indices are a column and a row of
    positions in the tile, the grid pays one compare and one select. ``more``:
    a second product's part of the same scores, unscaled, added before the scale."""
    s = _raw_scores(q, k, more) * scale
    if test is None:
        return s

    def block(shape, axis, at):
        pos = jax.lax.broadcasted_iota(jnp.int32, shape, axis) + at
        if block_len & (block_len - 1):
            return jax.lax.div(pos, jnp.int32(block_len))
        return jax.lax.shift_right_logical(pos, jnp.int32(block_len.bit_length() - 1))

    return jnp.where(test(block((k.shape[0], 1), 0, k_at), block((1, q.shape[0]), 1, q_at)),
                     s, _NEG_BIG)


def _walk(kind, visit, tile, sub, by_keys, cuts=_CUT, by_lo=False):
    """Runs ``visit(queries, keys, test)`` over what a tile pair of ``kind`` (a
    prefetched scalar) holds: a whole tile in one visit; a cut tile of
    ``cuts`` in the slabs of ``_cut_slabs``, by query columns in the kernels
    that keep sums a query and by key rows in the one that keeps sums a key,
    so that each sum runs over the operands it ran over before, in their
    order, less the exact zeros of what no query of the slab can read: those
    are never computed. ``by_lo``: a tile the batch's intervals cut off the
    diagonal (``_LO``) is one visit, its mask the visit's own to make."""
    pl.when(kind == _WHOLE)(lambda: visit(pl.ds(0, tile), pl.ds(0, tile), None))
    for cut, test in cuts.items():
        @pl.when(kind == cut)
        def _cut(cut=cut, test=test):
            for q0, nq, k0, nk in _cut_slabs(cut, tile // sub, by_keys):
                visit(pl.ds(q0 * sub, nq * sub), pl.ds(k0 * sub, nk * sub), test)
    if by_lo:
        pl.when(kind == _LO)(lambda: visit(pl.ds(0, tile), pl.ds(0, tile), _LO))


_NO_MORE = (lambda: None, lambda dst, queries, keys: None, lambda: None)


# The three kernels' bodies, shared by the two masks. ``at`` is the grid
# step's place in the prefetched tables, ``scores(q, k, test, queries, keys)``
# the mask's scaled and masked scores of a slab (keys down, queries across),
# ``walk(kind, visit, by_keys=...)`` its walk over a tile pair of that kind.

def _fwd_body(at, kind_ref, first_ref, last_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
              m_ref, l_ref, acc_ref, scores, walk):
    @pl.when(first_ref[at] == 1)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def visit(queries, keys, test):
        st = scores(q_ref[0, queries], k_ref[0, keys], test, queries, keys)
        m_prev = m_ref[:1, queries]
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        # once a query has met a key it may read, m_new is a real score and a
        # masked score's exp is 0. Under the block-diffusion mask its first keys
        # hold one (its own block, or block 0 of the clean half); under
        # intervals they may not (a document that starts inside the tile):
        # what it sums until then is finite and leaves with corr = 0 at the
        # first real score, which its own key on the diagonal is at the latest
        pt = jnp.exp(st - m_new)
        corr = jnp.exp(m_prev - m_new)
        l = l_ref[:1, queries] * corr + jnp.sum(pt, axis=0, keepdims=True)
        l_ref[:, queries] = jnp.broadcast_to(l, (_STAT_ROWS, queries.size))
        # the output is accumulated transposed, (D, queries): v^T p^T
        v = v_ref[0, keys]
        acc_ref[:, queries] = acc_ref[:, queries] * corr + jax.lax.dot_general(
            v, pt.astype(v.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_ONE_PASS)
        m_ref[:, queries] = jnp.broadcast_to(m_new, (_STAT_ROWS, queries.size))

    walk(kind_ref[at], visit, by_keys=False)

    @pl.when(last_ref[at] == 1)
    def _finalize():
        o_ref[0] = jnp.transpose(acc_ref[:] / l_ref[:1]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:1] + jnp.log(l_ref[:1])


def _dq_body(at, kind_ref, first_ref, last_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
             dq_ref, acc_ref, scores, walk, scale, more=_NO_MORE):
    # ``more`` (here and in ``_dkv_body``): what a second product of the scores
    # adds to the kernel, as (init, visit(dst, queries, keys), finalize)
    @pl.when(first_ref[at] == 1)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        more[0]()

    def visit(queries, keys, test):
        k, do = k_ref[0, keys], do_ref[0, queries]
        st = scores(q_ref[0, queries], k, test, queries, keys)
        pt = jnp.exp(st - lse_ref[0, 0, :, queries])
        dpt = jax.lax.dot_general(v_ref[0, keys], do, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32, precision=_ONE_PASS)
        dst = (pt * (dpt - delta_ref[0, 0, :, queries])).astype(k.dtype)
        acc_ref[queries] += jax.lax.dot_general(dst, k, (((0,), (0,)), ((), ())),
                                                preferred_element_type=jnp.float32,
                                                precision=_ONE_PASS)
        more[1](dst, queries, keys)

    walk(kind_ref[at], visit, by_keys=False)

    @pl.when(last_ref[at] == 1)
    def _finalize():
        dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)
        more[2]()


def _dkv_body(at, kind_ref, first_ref, last_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
              dk_ref, dv_ref, dk_acc, dv_acc, scores, walk, scale, group, more=_NO_MORE):
    # here a "row" of the tables is a k tile and its "columns" the q tiles that
    # read it; a pair's kind is the same pair's, and a cut tile is walked by
    # key rows, each over the query columns that read it
    g = pl.program_id(3)

    @pl.when((first_ref[at] == 1) & (g == 0))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        more[0]()

    def visit(queries, keys, test):
        q, do = q_ref[0, queries], do_ref[0, queries]
        st = scores(q, k_ref[0, keys], test, queries, keys)
        pt = jnp.exp(st - lse_ref[0, 0, :, queries])
        dv_acc[keys] += jax.lax.dot_general(pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32, precision=_ONE_PASS)
        dpt = jax.lax.dot_general(v_ref[0, keys], do, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32, precision=_ONE_PASS)
        dst = (pt * (dpt - delta_ref[0, 0, :, queries])).astype(q.dtype)
        dk_acc[keys] += jax.lax.dot_general(dst, q, (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32, precision=_ONE_PASS)
        more[1](dst, queries, keys)

    walk(kind_ref[at], visit, by_keys=True)

    @pl.when((last_ref[at] == 1) & (g == group - 1))
    def _finalize():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)
        more[2]()


def _bd_mask(scale, tile, sub, block_len):
    """The block-diffusion mask's ``scores`` and ``walk`` for the bodies above."""
    def scores(q, k, test, queries, keys):
        return _bd_scores(q, k, scale, test, block_len, queries.start, keys.start)

    return scores, functools.partial(_walk, tile=tile, sub=sub)


def _bd_fwd_kernel(row_ref, col_ref, kind_ref, first_ref, last_ref, *refs, scale, tile, sub,
                   block_len):
    _fwd_body(pl.program_id(2), kind_ref, first_ref, last_ref, *refs,
              *_bd_mask(scale, tile, sub, block_len))


def _bd_dq_kernel(row_ref, col_ref, kind_ref, first_ref, last_ref, *refs, scale, tile, sub,
                  block_len):
    _dq_body(pl.program_id(2), kind_ref, first_ref, last_ref, *refs,
             *_bd_mask(scale, tile, sub, block_len), scale)


def _bd_dkv_kernel(row_ref, col_ref, kind_ref, first_ref, last_ref, *refs, scale, tile, sub, group,
                   block_len):
    _dkv_body(pl.program_id(2), kind_ref, first_ref, last_ref, *refs,
              *_bd_mask(scale, tile, sub, block_len), scale, group)


_STAT_ROWS = 8  # the forward's running max and sum a query: one row, kept in a whole (8, tile) tile


def _bd_plan(q, k, seq_len, block_len, tile):
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    if t != 2 * seq_len or k.shape != (b, t, hkv, d) or hq % hkv:
        raise ValueError(f"q {q.shape} and k {k.shape} do not fit seq_len {seq_len}")
    tile = min(tile, seq_len)
    if seq_len % tile or tile % 8 or d % 128:
        raise ValueError(f"seq_len {seq_len} must be a multiple of the tile {tile}, the tile of "
                         f"8, and the head size {d} of 128")
    return tile, _sub_tile(tile, block_len), _live_tiles(seq_len, block_len, tile)


def _forward_call(kernel, name, tables, per_batch, q, k, v, lo, tile, interpret, more=None):
    """The forward kernel over the visits of ``tables``: ``per_batch`` 0 where
    one list of visits serves every sequence (the block-diffusion mask), else
    the visits a sequence, each with a list of its own; ``lo`` (B, 1, T) the
    intervals' starts where the kernel takes them, else None. ``more``: the
    scores' second product, ``(q_more (B, Hq, T, R), k_shared (B, T, R))``,
    which the kernel takes after ``lo``."""
    b, t, hq, d = q.shape
    group = hq // k.shape[2]
    at = (lambda bi, p: p) if not per_batch else (lambda bi, p: bi * per_batch + p)
    # heads stay where the projections left them: a head is a 128-lane column
    # block of the (B, T, H * D) array, so nothing is transposed in HBM
    q2, k2, v2 = (x.reshape(b, t, -1) for x in (q, k, v))
    q_at = lambda bi, h, p, row, col, *_: (bi, row[at(bi, p)], h)
    kv_at = lambda bi, h, p, row, col, *_: (bi, col[at(bi, p)], h // group)
    lo_in = [] if lo is None else [
        pl.BlockSpec((1, 1, tile), lambda bi, h, p, row, *_: (bi, 0, row[at(bi, p)]))]
    if more is not None:
        r = more[1].shape[-1]
        lo_in += [pl.BlockSpec((1, 1, tile, r), lambda bi, h, p, row, *_: (bi, h, row[at(bi, p)], 0)),
                  pl.BlockSpec((1, tile, r), lambda bi, h, p, row, col, *_: (bi, col[at(bi, p)], 0))]
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b, hq, per_batch or tables[0].shape[0]),
            in_specs=lo_in + [pl.BlockSpec((1, tile, d), q_at), pl.BlockSpec((1, tile, d), kv_at),
                              pl.BlockSpec((1, tile, d), kv_at)],
            out_specs=[pl.BlockSpec((1, tile, d), q_at),
                       pl.BlockSpec((1, 1, 1, tile),
                                    lambda bi, h, p, row, *_: (bi, h, 0, row[at(bi, p)]))],
            scratch_shapes=[pltpu.VMEM((_STAT_ROWS, tile), jnp.float32),
                            pltpu.VMEM((_STAT_ROWS, tile), jnp.float32),
                            pltpu.VMEM((d, tile), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, t, hq * d), q.dtype),
                   jax.ShapeDtypeStruct((b, hq, 1, t), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=f"{name}_fwd",
    )(*tables, *([] if lo is None else [lo]), *(more or ()), q2, k2, v2)
    return out.reshape(b, t, hq, d), lse


def _backward_call(dq_kernel, dkv_kernel, name, tables, tables_t, per_batch, q, k, v, out, lse, do,
                   lo, tile, interpret, more=None):
    """dq over ``tables`` (a q tile's k tiles) and dk, dv over ``tables_t`` (a k
    tile's q tiles, the group's query heads innermost so that a k tile's sums
    stay in VMEM); the arguments as ``_forward_call``'s. With ``more`` also
    the gradients of the second product's operands: ``q_more``'s, and
    ``k_shared``'s by K/V head (B, Hkv, T, R) float32, for the caller to sum."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    at = (lambda bi, p: p) if not per_batch else (lambda bi, p: bi * per_batch + p)
    visits = per_batch or tables[0].shape[0]
    # sum_j P_ij dP_ij = o_i . do_i: a row statistic too, lane-dense like lse
    delta = jnp.einsum("bthd,bthd->bht", out.astype(jnp.float32),
                       do.astype(jnp.float32))[:, :, None, :]
    do = do.astype(q.dtype)
    q2, k2, v2, do2 = (x.reshape(b, t, -1) for x in (q, k, v, do))
    lo_arg = ([] if lo is None else [lo]) + list(more or ())
    r = more[1].shape[-1] if more else 0

    q_at = lambda bi, h, p, row, col, *_: (bi, row[at(bi, p)], h)
    kv_at = lambda bi, h, p, row, col, *_: (bi, col[at(bi, p)], h // group)
    stat_at = lambda bi, h, p, row, *_: (bi, h, 0, row[at(bi, p)])
    lo_in = [] if lo is None else [
        pl.BlockSpec((1, 1, tile), lambda bi, h, p, row, *_: (bi, 0, row[at(bi, p)]))]
    more_at = lambda bi, h, p, row, *_: (bi, h, row[at(bi, p)], 0)
    if more:
        lo_in += [pl.BlockSpec((1, 1, tile, r), more_at),
                  pl.BlockSpec((1, tile, r), lambda bi, h, p, row, col, *_: (bi, col[at(bi, p)], 0))]
    dq, *dq_more = pl.pallas_call(
        dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b, hq, visits),
            in_specs=lo_in + [pl.BlockSpec((1, tile, d), q_at), pl.BlockSpec((1, tile, d), kv_at),
                              pl.BlockSpec((1, tile, d), kv_at), pl.BlockSpec((1, tile, d), q_at),
                              pl.BlockSpec((1, 1, 1, tile), stat_at),
                              pl.BlockSpec((1, 1, 1, tile), stat_at)],
            out_specs=[pl.BlockSpec((1, tile, d), q_at)] + (
                [pl.BlockSpec((1, 1, tile, r), more_at)] if more else []),
            scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)] + (
                [pltpu.VMEM((tile, r), jnp.float32)] if more else []),
        ),
        out_shape=[jax.ShapeDtypeStruct((b, t, hq * d), q.dtype)] + (
            [jax.ShapeDtypeStruct(more[0].shape, more[0].dtype)] if more else []),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=f"{name}_dq",
    )(*tables, *lo_arg, q2, k2, v2, do2, lse, delta)

    kv_at = lambda bi, hk, p, g, row, col, *_: (bi, row[at(bi, p)], hk)
    q_at = lambda bi, hk, p, g, row, col, *_: (bi, col[at(bi, p)], hk * group + g)
    stat_at = lambda bi, hk, p, g, row, col, *_: (bi, hk * group + g, 0, col[at(bi, p)])
    lo_in = [] if lo is None else [
        pl.BlockSpec((1, 1, tile), lambda bi, hk, p, g, row, col, *_: (bi, 0, col[at(bi, p)]))]
    shared_at = lambda bi, hk, p, g, row, *_: (bi, hk, row[at(bi, p)], 0)
    if more:
        lo_in += [pl.BlockSpec((1, 1, tile, r),
                               lambda bi, hk, p, g, row, col, *_: (bi, hk * group + g, col[at(bi, p)], 0)),
                  pl.BlockSpec((1, tile, r), lambda bi, hk, p, g, row, *_: (bi, row[at(bi, p)], 0))]
    dk, dv, *dk_more = pl.pallas_call(
        dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b, hkv, visits, group),
            in_specs=lo_in + [pl.BlockSpec((1, tile, d), q_at), pl.BlockSpec((1, tile, d), kv_at),
                              pl.BlockSpec((1, tile, d), kv_at), pl.BlockSpec((1, tile, d), q_at),
                              pl.BlockSpec((1, 1, 1, tile), stat_at),
                              pl.BlockSpec((1, 1, 1, tile), stat_at)],
            out_specs=[pl.BlockSpec((1, tile, d), kv_at), pl.BlockSpec((1, tile, d), kv_at)] + (
                [pl.BlockSpec((1, 1, tile, r), shared_at)] if more else []),
            scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32),
                            pltpu.VMEM((tile, d), jnp.float32)] + (
                [pltpu.VMEM((tile, r), jnp.float32)] if more else []),
        ),
        out_shape=[jax.ShapeDtypeStruct((b, t, hkv * d), k.dtype),
                   jax.ShapeDtypeStruct((b, t, hkv * d), v.dtype)] + (
            [jax.ShapeDtypeStruct((b, hkv, t, r), jnp.float32)] if more else []),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=f"{name}_dkv",
    )(*tables_t, *lo_arg, q2, k2, v2, do2, lse, delta)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)) + tuple(dq_more + dk_more)


def _bd_forward(q, k, v, seq_len, block_len, scale, tile, interpret):
    tile, sub, kinds = _bd_plan(q, k, seq_len, block_len, tile)
    kernel = functools.partial(_bd_fwd_kernel, scale=scale, tile=tile, sub=sub, block_len=block_len)
    return _forward_call(kernel, "block_diffusion_attention", _visit_tables(kinds), 0,
                         q, k, v, None, tile, interpret)


def _bd_backward(q, k, v, out, lse, do, seq_len, block_len, scale, tile, interpret):
    tile, sub, kinds = _bd_plan(q, k, seq_len, block_len, tile)
    how = dict(scale=scale, tile=tile, sub=sub, block_len=block_len)
    return _backward_call(
        functools.partial(_bd_dq_kernel, **how),
        functools.partial(_bd_dkv_kernel, group=q.shape[2] // k.shape[2], **how),
        "block_diffusion_attention", _visit_tables(kinds), _visit_tables(kinds.T), 0,
        q, k, v, out, lse, do, None, tile, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _bd_attention(q, k, v, seq_len, block_len, scale, tile, interpret):
    return _bd_forward(q, k, v, seq_len, block_len, scale, tile, interpret)[0]


def _bd_attention_fwd(q, k, v, seq_len, block_len, scale, tile, interpret):
    out, lse = _bd_forward(q, k, v, seq_len, block_len, scale, tile, interpret)
    # named, so that a caller that recomputes its layer in the backward can keep
    # these two (jax.checkpoint_policies.save_only_these_names) and not run the
    # forward kernel a second time
    out, lse = checkpoint_name(out, ATTENTION_OUT), checkpoint_name(lse, ATTENTION_LSE)
    return out, (q, k, v, out, lse)


def _bd_attention_bwd(seq_len, block_len, scale, tile, interpret, res, do):
    return _bd_backward(*res, do, seq_len, block_len, scale, tile, interpret)


_bd_attention.defvjp(_bd_attention_fwd, _bd_attention_bwd)


def block_diffusion_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    seq_len: int,
    block_len: int,
    tile: int = BLOCK_DIFFUSION_TILE,
    interpret: bool = False,
) -> jax.Array:
    """Attention under the block-diffusion mask of ``(seq_len, block_len)``
    (``block_diffusion_allowed``) over ``[noised | clean]``: q [B, 2L, Hq, D],
    k and v [B, 2L, Hkv, D] -> [B, 2L, Hq, D]; query head ``g`` reads K/V head
    ``g // (Hq // Hkv)``.

    Forward and backward are Pallas kernels over square tiles of ``tile``
    positions. Each q tile visits only the k tiles that hold a pair it may
    read (a prefetched table of the pairs and their kinds; 80 of 256 tile
    pairs at L 4096 and tile 512, 56 whole and 24 cut). A tile the mask cuts
    is a diagonal tile of one of three kinds and costs what it holds: it is
    walked by sub-tiles (``_sub_tile``: 128 positions there) over the slabs its
    queries can read (``_cut_slabs``: 4 or 10 of its 16 sub-tiles, 1,088 of
    the 1,280 a head; ``block_diffusion_plan`` counts them), masked by one
    compare of the keys' block indices (a column) with the queries' (a row).
    The backward (one kernel for dq, one for dk and dv summed over the group's
    query heads) recomputes the probabilities from the forward's log-sum-exp
    a row: nothing L^2 is ever held. Products take the inputs' dtype as
    operands and accumulate in float32; the softmax is float32, over scores
    scaled by ``D ** -0.5``. ``tile`` is for the CPU tests, which cut a short
    sequence into several.
    """
    if q.ndim != 4:
        raise ValueError(f"expected [B, 2L, H, D], got shape {q.shape}")
    return _bd_attention(q, k, v, int(seq_len), int(block_len), q.shape[-1] ** -0.5, int(tile),
                         interpret)


# ---------------------------------------------------------------------------
# Interval attention: query i reads the keys [lo_i, i], and ``lo`` comes with
# the batch. Causal (lo = 0), windowed (lo_i = i - w + 1) and packed documents
# (lo_i = the start of i's document) are this one mechanism; which tile pairs
# are dead, whole or cut is worked out on the device a call.
# ---------------------------------------------------------------------------

_DIAGONAL = {_LE: operator.le}  # a tile cut by its own diagonal alone: key position <= query position


def _interval_lo(lo, window):
    """``lo`` (B, T) clipped to what a query may read at all: not before 0,
    not after itself, not before ``i - window + 1`` under a window."""
    at = jnp.arange(lo.shape[1], dtype=jnp.int32)[None, :]
    floor = 0 if window is None else jnp.maximum(at - (window - 1), 0)
    return jnp.clip(lo.astype(jnp.int32), floor, at)


def _interval_kinds(lo, tile):
    """The kind of every (q tile, k tile) pair, (B, n, n) int32, from the q
    tile's least and greatest ``lo`` against the k tile's ends: dead above
    the diagonal and where the k tile ends before any query's interval
    starts; whole below the diagonal where it starts after every query's
    ``lo``; the tile on the diagonal that no ``lo`` enters is cut by the
    diagonal alone (``_LE``), every other by ``lo`` (``_LO``). With square
    tiles a pair that is not dead holds a pair some query reads (the query
    with the least ``lo`` and the later of that ``lo`` and the k tile's first
    key), so nothing dead is ever visited."""
    b, t = lo.shape
    n = t // tile
    by_tile = lo.reshape(b, n, tile)
    lo_min, lo_max = by_tile.min(axis=-1)[:, :, None], by_tile.max(axis=-1)[:, :, None]
    qi = jnp.arange(n, dtype=jnp.int32)[None, :, None]
    ki = jnp.arange(n, dtype=jnp.int32)[None, None, :]
    k0 = ki * tile
    dead = (ki > qi) | (k0 + tile - 1 < lo_min)
    whole = (ki < qi) & (k0 >= lo_max)
    cut = jnp.where((ki == qi) & (lo_max <= k0), _LE, _LO)
    return jnp.where(dead, _DEAD, jnp.where(whole, _WHOLE, cut)).astype(jnp.int32)


def interval_visits(n_tiles: int, tile: int, window: Optional[int] = None) -> int:
    """The most tile pairs a sequence of ``n_tiles`` tiles can have live,
    whatever ``lo`` is: the triangle, or under a window the k tiles a q
    tile's window reaches. The kernels' grids are this long; a sequence's
    live pairs come first and the steps after them neither fetch nor compute."""
    reach = n_tiles if window is None else -(-(window - 1) // tile) + 1
    return sum(min(qi + 1, reach) for qi in range(n_tiles))


def _interval_tables(kinds, visits):
    """``_visit_tables`` made on the device, a sequence: the live pairs of
    ``kinds`` (B, n, n) in row-major order, padded to ``visits`` with dead
    steps at the last live pair's place (the same blocks: nothing is fetched
    for them), as flat (B * visits,) int32 arrays for scalar prefetch."""
    b, n, _ = kinds.shape
    flat = kinds.reshape(b, n * n)
    count = jnp.sum(flat != _DEAD, axis=1, dtype=jnp.int32)[:, None]
    at = jax.vmap(lambda f: jnp.nonzero(f, size=visits, fill_value=0)[0])(flat).astype(jnp.int32)
    p = jnp.arange(visits, dtype=jnp.int32)[None, :]
    live = p < count
    at = jnp.where(live, at, jnp.take_along_axis(at, count - 1, axis=1))
    rows, cols = at // n, at % n
    kind = jnp.where(live, jnp.take_along_axis(flat, at, axis=1), _DEAD)
    edge = jnp.full((b, 1), -1, jnp.int32)
    first = live & (rows != jnp.concatenate([edge, rows[:, :-1]], axis=1))
    last = live & ((rows != jnp.concatenate([rows[:, 1:], edge], axis=1)) | (p == count - 1))
    return tuple(x.reshape(-1).astype(jnp.int32) for x in (rows, cols, kind, first, last))


def interval_tile_counts(lo, window: Optional[int] = None, tile: int = BLOCK_DIFFUSION_TILE):
    """(tile pairs the kernels visit, tile pairs that hold a pair some query
    reads) of one head over the batch, int32 scalars on the device: the first
    from the kinds the visit lists are made of, the second from each query's
    own first and last k tile. A counter for the caller to keep."""
    tile = min(tile, lo.shape[1])
    lo = _interval_lo(lo, window)
    visited = jnp.sum(_interval_kinds(lo, tile) != _DEAD, dtype=jnp.int32)
    first_tile = (lo // tile).reshape(lo.shape[0], -1, tile).min(axis=-1)  # (B, n)
    own = jnp.arange(first_tile.shape[1], dtype=jnp.int32)[None, :]
    return visited, jnp.sum(own - first_tile + 1, dtype=jnp.int32)


def _iv_mask(scale, tile, sub, lo_ref, q_tile, k_tile, more=None):
    """The interval mask's ``scores`` and ``walk``: a tile cut by its diagonal
    alone goes the block-diffusion kernels' way (blocks of one position); any
    other cut tile takes ``lo_j <= key <= query`` from the keys' positions (a
    column), the queries' (a row) and the queries' ``lo`` (a row). ``more``
    ``(q_more_ref, k_shared_ref)``: columns every K/V head shares, whose
    product with the query head's further columns is added to the scores."""
    def second(queries, keys):
        if more is None:
            return None
        return _raw_scores(more[0][0, 0, queries], more[1][0, keys])

    def scores(q, k, test, queries, keys):
        if test is None or callable(test):
            return _bd_scores(q, k, scale, test, 1, queries.start, keys.start, second(queries, keys))
        s = _raw_scores(q, k, second(queries, keys)) * scale
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (k.shape[0], 1), 0) + (k_tile * tile + keys.start)
        q_pos = jax.lax.broadcasted_iota(jnp.int32, (1, q.shape[0]), 1) + (q_tile * tile + queries.start)
        return jnp.where((k_pos >= lo_ref[0, :, queries]) & (k_pos <= q_pos), s, _NEG_BIG)

    return scores, functools.partial(_walk, tile=tile, sub=sub, cuts=_DIAGONAL, by_lo=True)


def _split_more(refs, shared: bool, n_out: int, n_scratch: int):
    """A two-width kernel's refs as the bodies take them, and the second
    product's: its two inputs lead, its output follows the kernel's own
    ``n_out`` outputs and its sums the ``n_scratch`` scratch buffers."""
    if not shared:
        return refs, None
    q_more, k_shared, *rest = refs
    n_in = len(rest) - n_out - n_scratch - 2
    out_more, acc_more = rest[n_in + n_out], rest[-1]
    own = rest[:n_in + n_out] + rest[n_in + n_out + 1:-1]
    return own, (q_more, k_shared, out_more, acc_more)


def _iv_fwd_kernel(row_ref, col_ref, kind_ref, first_ref, last_ref, lo_ref, *refs, scale, tile, sub,
                   visits, shared=False):
    at = pl.program_id(0) * visits + pl.program_id(2)
    more = refs[:2] if shared else None
    _fwd_body(at, kind_ref, first_ref, last_ref, *refs[2 if shared else 0:],
              *_iv_mask(scale, tile, sub, lo_ref, row_ref[at], col_ref[at], more))


def _sums_of(acc, out, scale, of_rows):
    """The second product's (init, visit, finalize) for a backward body:
    ``acc`` sums ``dst`` against ``of_rows(queries, keys)`` and leaves for ``out``."""
    def init():
        acc[:] = jnp.zeros_like(acc)

    def finalize():
        out[0, 0] = (acc[:] * scale).astype(out.dtype)

    return init, of_rows, finalize


def _iv_dq_kernel(row_ref, col_ref, kind_ref, first_ref, last_ref, lo_ref, *refs, scale, tile, sub,
                  visits, shared=False):
    at = pl.program_id(0) * visits + pl.program_id(2)
    refs, more = _split_more(refs, shared, 1, 1)
    hooks = _NO_MORE
    if more:
        q_more, k_shared, dq_more, acc = more

        def visit(dst, queries, keys):  # dst (keys, queries)
            acc[queries] += jax.lax.dot_general(dst, k_shared[0, keys], (((0,), (0,)), ((), ())),
                                                preferred_element_type=jnp.float32,
                                                precision=_ONE_PASS)

        hooks = _sums_of(acc, dq_more, scale, visit)
    _dq_body(at, kind_ref, first_ref, last_ref, *refs,
             *_iv_mask(scale, tile, sub, lo_ref, row_ref[at], col_ref[at], more and more[:2]), scale,
             hooks)


def _iv_dkv_kernel(row_ref, col_ref, kind_ref, first_ref, last_ref, lo_ref, *refs, scale, tile, sub,
                   visits, group, shared=False):
    at = pl.program_id(0) * visits + pl.program_id(2)  # rows are k tiles here, columns q tiles
    refs, more = _split_more(refs, shared, 2, 2)
    hooks = _NO_MORE
    if more:
        q_more, k_shared, dk_more, acc = more

        def visit(dst, queries, keys):
            acc[keys] += jax.lax.dot_general(dst, q_more[0, 0, queries], (((1,), (0,)), ((), ())),
                                             preferred_element_type=jnp.float32,
                                             precision=_ONE_PASS)

        hooks = _sums_of(acc, dk_more, scale, visit)
    _dkv_body(at, kind_ref, first_ref, last_ref, *refs,
              *_iv_mask(scale, tile, sub, lo_ref, col_ref[at], row_ref[at], more and more[:2]), scale,
              group, hooks)


def _iv_plan(q, k, lo, tile):
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    if k.shape != (b, t, hkv, d) or hq % hkv or lo.shape != (b, t):
        raise ValueError(f"q {q.shape}, k {k.shape} and lo {lo.shape} do not fit each other")
    tile = min(tile, t)
    if t % tile or tile % 8 or d % 128:
        raise ValueError(f"the length {t} must be a multiple of the tile {tile}, the tile of 8, "
                         f"and the head size {d} of 128")
    return tile, _sub_tile(tile, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _iv_attention(q, k, v, lo, more, window, scale, tile, interpret):
    return _iv_attention_fwd(q, k, v, lo, more, window, scale, tile, interpret)[0]


def _iv_attention_fwd(q, k, v, lo, more, window, scale, tile, interpret):
    tile, sub = _iv_plan(q, k, lo, tile)
    visits = interval_visits(q.shape[1] // tile, tile, window)
    lo = _interval_lo(lo, window)
    kinds = _interval_kinds(lo, tile)
    tables = _interval_tables(kinds, visits)
    tables_t = _interval_tables(jnp.swapaxes(kinds, 1, 2), visits)
    lo = lo[:, None, :]  # lane-dense, as the row statistics are
    kernel = functools.partial(_iv_fwd_kernel, scale=scale, tile=tile, sub=sub, visits=visits,
                               shared=more is not None)
    out, lse = _forward_call(kernel, "interval_attention", tables, visits, q, k, v, lo, tile,
                             interpret, more)
    # named as the block-diffusion kernels' are: a caller that recomputes its
    # layer keeps these two and does not run the forward kernel a second time
    out, lse = checkpoint_name(out, ATTENTION_OUT), checkpoint_name(lse, ATTENTION_LSE)
    return out, (q, k, v, out, lse, lo, tables, tables_t, more)


def _iv_attention_bwd(window, scale, tile, interpret, res, do):
    q, k, v, out, lse, lo, tables, tables_t, more = res
    tile, sub = _iv_plan(q, k, lo[:, 0], tile)
    visits = interval_visits(q.shape[1] // tile, tile, window)
    how = dict(scale=scale, tile=tile, sub=sub, visits=visits, shared=more is not None)
    dq, dk, dv, *d_more = _backward_call(
        functools.partial(_iv_dq_kernel, **how),
        functools.partial(_iv_dkv_kernel, group=q.shape[2] // k.shape[2], **how),
        "interval_attention", tables, tables_t, visits, q, k, v, out, lse, do, lo, tile, interpret, more)
    if more:  # the shared columns' gradient: every K/V head's part, summed in float32
        d_more = (d_more[0], jnp.sum(d_more[1], axis=1).astype(more[1].dtype))
    return dq, dk, dv, None, tuple(d_more) if more else None


_iv_attention.defvjp(_iv_attention_fwd, _iv_attention_bwd)


def interval_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lo: jax.Array,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    tile: int = BLOCK_DIFFUSION_TILE,
    interpret: bool = False,
    k_shared: Optional[jax.Array] = None,
) -> jax.Array:
    """Attention in which query ``i`` reads the keys ``[lo_i, i]``: q [B, T,
    Hq, D], k and v [B, T, Hkv, D], ``lo`` [B, T] int32 -> [B, T, Hq, D];
    query head ``g`` reads K/V head ``g // (Hq // Hkv)``. ``lo`` is data: 0
    for a causal sequence, the start of each position's document for packed
    documents. With ``window`` a query reads no key before ``i - window + 1``
    either (static: it also bounds the kernels' grids).

    Forward, dq and dk/dv are the block-diffusion kernels' bodies under
    another mask, over square tiles of ``tile`` positions. A tile pair's kind
    is reduced on the device from the q tile's least and greatest ``lo``
    (``_interval_kinds``) and the live pairs are compacted into a visit list
    a sequence (``_interval_tables``), handed to the kernels as prefetched
    scalars: a dead pair is neither fetched nor computed, a whole pair runs
    unmasked, the tile on the diagonal is walked by sub-tiles as the
    block-diffusion mask's clean diagonal is, and a tile that ``lo`` cuts (a
    window's trailing edge, a document's first token) takes its mask from
    ``lo`` and the positions on vectors. The grids run over the worst case
    (``interval_visits``: the triangle, or the window's reach); the steps past
    a sequence's live pairs stay on the last pair's blocks and do nothing.
    Nothing of size T x T is ever built: the backward recomputes the
    probabilities from the forward's log-sum-exp a row. Products take the
    inputs' dtype as operands and accumulate in float32; the softmax is
    float32, over scores scaled by ``scale`` (``D ** -0.5``).

    **Two widths** (latent attention): with ``k_shared`` [B, T, R], R further
    key columns that every K/V head shares, q is [B, T, Hq, D + R] and a score
    is 192 wide where a value is 128: ``q[..., :D] . k + q[..., D:] .
    k_shared``, scaled by ``(D + R) ** -0.5``. The kernels add the second
    product a tile; nothing is padded or repeated in HBM: q's further columns
    go head-major ([B, Hq, T, R], a block's last axis the whole R), and the
    shared columns' gradient leaves the dk/dv kernel a K/V head and is summed.
    """
    if q.ndim != 4:
        raise ValueError(f"expected [B, T, H, D], got shape {q.shape}")
    scale = q.shape[-1] ** -0.5 if scale is None else float(scale)
    more = None
    if k_shared is not None:
        d = k.shape[-1]
        q, more = q[..., :d], (jnp.swapaxes(q[..., d:], 1, 2), k_shared)
    return _iv_attention(q, k, v, lo, more, None if window is None else int(window), scale, int(tile),
                         interpret)
