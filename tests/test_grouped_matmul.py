"""The grouped products' kernels (``ops/grouped_matmul.py``) on the CPU under
the Pallas interpreter: each form against a plain loop over groups in float64
from the same bfloat16 operands, over splits that put boundaries on and off
the tile edges; the walk itself; the tile rule; and the expert layer's
hand-written backward against autodiff of the dense formula on a ragged
split. An interpreter's unwritten block reads NaN, so "exactly 0" below also
says that every block was written.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from persia_tpu.models import moe_tower
from persia_tpu.ops import grouped_matmul as gm
from persia_tpu.ops.grouped_matmul import grouped_matmul, grouped_outer, grouped_tiles

# name: (row tile, M, K, N, sizes)
SPLITS = {
    "even": (16, 64, 32, 24, [16, 16, 16, 16]),
    "one_group_holds_all": (16, 64, 32, 24, [0, 64, 0]),
    "empty_first_last_and_between": (16, 64, 32, 24, [0, 20, 0, 0, 30, 0]),
    "boundaries_off_the_tile_edge": (16, 64, 32, 24, [5, 11, 17, 3, 28]),
    "live_rows_end_inside_a_tile": (16, 64, 32, 24, [10, 13]),
    "whole_tiles_past_the_sum": (16, 96, 32, 24, [0, 16, 16, 0]),
    "many_groups_in_one_tile": (16, 32, 32, 24, [1, 1, 1, 1, 1, 1, 1]),
    "nothing_live": (16, 48, 32, 24, [0, 0, 0]),
    "m_of_one_tile": (16, 16, 32, 24, [3, 0, 9]),
    "m_of_less_than_a_tile": (512, 40, 32, 24, [7, 0, 21]),
    "m_no_whole_tiles": (16, 100, 32, 24, [13, 0, 30, 8]),
    "the_cells_tile": (512, 1536, 128, 256, [300, 0, 212, 600, 0]),
}


def _case(name):
    tile, m, k, n, sizes = SPLITS[name]
    rng = np.random.default_rng(sum(map(ord, name)))
    bf = lambda x: jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
    x, g = bf(rng.standard_normal((m, k))), bf(rng.standard_normal((m, n)))
    w = bf(rng.standard_normal((len(sizes), k, n)))
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    return tile, x, w, g, jnp.asarray(sizes, jnp.int32), [slice(a, b) for a, b in zip(bounds[:-1], bounds[1:])]


def _f64(a):
    return np.asarray(a.astype(jnp.float32), np.float64)


@pytest.fixture
def row_tile(monkeypatch):
    return lambda tile: monkeypatch.setattr(gm, "ROW_TILE", tile)


@pytest.mark.parametrize("transposed", [False, True], ids=["weights_as_given", "weights_transposed"])
@pytest.mark.parametrize("split", sorted(SPLITS))
def test_grouped_matmul_against_a_loop(row_tile, split, transposed):
    tile, x, w, g, sizes, groups = _case(split)
    row_tile(tile)
    if transposed:  # g (M, N) by each group's w^T, w contracted over its last axis
        want = np.zeros((x.shape[0], w.shape[1]))
        for i, rows in enumerate(groups):
            want[rows] = _f64(g)[rows] @ _f64(w)[i].T
        got = grouped_matmul(g, w, sizes, transposed=True, interpret=True)
    else:
        want = np.zeros((x.shape[0], w.shape[2]))
        for i, rows in enumerate(groups):
            want[rows] = _f64(x)[rows] @ _f64(w)[i]
        got = grouped_matmul(x, w, sizes, interpret=True)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    got = np.asarray(got)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-5)
    # rows of no group: exactly 0.0, no NaN, whatever the buffer held
    np.testing.assert_array_equal(got[groups[-1].stop:], 0.0)


@pytest.mark.parametrize("split", sorted(SPLITS))
def test_grouped_outer_against_a_loop(row_tile, split):
    tile, x, _, g, sizes, groups = _case(split)
    row_tile(tile)
    want = np.stack([_f64(x)[rows].T @ _f64(g)[rows] for rows in groups])
    got = grouped_outer(x, g, sizes, interpret=True)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    got = np.asarray(got)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-5)
    for i, rows in enumerate(groups):  # an empty group's block: exactly 0.0
        if rows.stop == rows.start:
            np.testing.assert_array_equal(got[i], 0.0)


@pytest.mark.parametrize("every_group", [False, True], ids=["every_tile_once", "every_group_once"])
@pytest.mark.parametrize("split", sorted(SPLITS))
def test_the_walk_costs_a_visit_a_live_tile_and_one_a_boundary_inside_a_tile(row_tile, split, every_group):
    tile, m, k, n, sizes = SPLITS[split]
    row_tile(tile)
    tm = grouped_tiles(m, k, n)[0]
    tiles = -(-m // tm)
    tile, group, lo, hi = (np.asarray(a) for a in gm._schedule(jnp.asarray(sizes, jnp.int32), tiles, tm, every_group))
    assert len(tile) == tiles + len(sizes) - 1  # the static grid
    ends = np.cumsum(sizes)
    begins = ends - sizes
    held = hi > lo
    # every row of every group is covered exactly once, by its own group
    seen = np.zeros(tiles * tm, int)
    for t, g, a, b in zip(tile[held], group[held], lo[held], hi[held]):
        assert begins[g] <= t * tm + a and t * tm + b <= ends[g]
        seen[t * tm + a:t * tm + b] += 1
    np.testing.assert_array_equal(seen, np.arange(tiles * tm) < ends[-1])
    # one visit a live tile, one more for each boundary that falls inside a tile
    live_tiles = -(-int(ends[-1]) // tm)
    inside = {int(b) for b, s in zip(begins, sizes) if s and b % tm and b > 0}
    assert held.sum() == live_tiles + len(inside)
    # blocks change in row order only (a result block is never come back to)
    assert (np.diff(tile) >= 0).all() and (np.diff(group) >= 0).all()
    if every_group:  # an empty group's block is written too: each group is some step's
        assert set(group) == set(range(len(sizes)))
    else:  # a tile past the live rows is written too: each tile is some step's
        assert set(tile) == set(range(tiles))
    # a step that holds no row fetches no rows and no weights: it names the step before's
    idle = ~held & (np.arange(len(tile)) > 0)
    before = np.flatnonzero(idle) - 1
    if every_group:  # an empty group's step has its own result block and reads no new rows
        assert (tile[idle] == tile[before]).all()
    else:  # a tile past the live rows has its own result block and reads no new weights
        assert (group[idle] == group[before]).all()


@pytest.mark.parametrize("shape,want", [
    ((36864, 2304, 896), (512, 2304, 896)),  # the packed cell: gate and up; down; the weights' gradients alike
    ((36864, 896, 2304), (512, 896, 2304)),
    ((18432, 2048, 768), (512, 2048, 768)),  # the SDAR cell
    ((18432, 768, 2048), (512, 768, 2048)),
    ((40, 32, 24), (48, 32, 24)),  # less than a tile: all rows, in whole sublane tiles of bfloat16
    ((4096, 8192, 8192), (512, 8192, 256)),  # blocks past the budget: a narrower N, in whole lanes
])
def test_tiles_are_a_function_of_the_shapes(shape, want):
    assert grouped_tiles(*shape) == want
    tm, k, tn = want
    assert shape[2] % tn == 0 and (tn == shape[2] or tn % 128 == 0)


def test_the_expert_layers_backward_against_autodiff_of_the_dense_formula(row_tile):
    """Three chunks over a ragged split (an empty expert, boundaries inside
    tiles, a last chunk that runs past the picks): the written-out backward
    of ``_held_experts`` beside ``jax.grad`` of the layer written densely
    with the same roundings to bfloat16."""
    row_tile(16)
    rng = np.random.default_rng(11)
    n, d, f, held, k, size = 24, 32, 16, 4, 2, 16
    m = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    gate, up = (jnp.asarray(rng.standard_normal((held, d, f)) * 0.3, jnp.float32) for _ in range(2))
    down = jnp.asarray(rng.standard_normal((held, f, d)) * 0.3, jnp.float32)
    flat_w = jnp.asarray(rng.random(n * k), jnp.float32)
    # pick i is token i // k on expert local[i]; ``held`` means another chip's expert; expert 1 gets none
    local = rng.choice([0, 2, 3, held], size=n * k, p=[0.5, 0.2, 0.1, 0.2]).astype(np.int32)
    sorted_e, order = jax.lax.sort((jnp.asarray(local), jnp.arange(n * k, dtype=jnp.int32)), num_keys=1)
    starts = jnp.searchsorted(sorted_e, jnp.arange(held + 1, dtype=jnp.int32)).astype(jnp.int32)
    assert int(starts[-1]) > 2 * size and int(starts[2] - starts[1]) == 0
    order = jnp.pad(order, (0, -(n * k) % size))
    target = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)

    def kernels(m, flat_w, gate, up, down):
        y = moe_tower._held_experts(m, flat_w, gate, up, down, order, starts, size, k, True)
        return jnp.sum(y * target)

    def dense(m, flat_w, gate, up, down):
        # rounded to bfloat16 on the way in, the gradient left float32 on the way back
        bf = lambda x: x + jax.lax.stop_gradient(x.astype(jnp.bfloat16).astype(jnp.float32) - x)
        x = bf(m)[jnp.arange(n * k) // k]  # a row a pick
        on = jax.nn.one_hot(local, held, dtype=jnp.float32)  # (picks, held); all 0 for another chip's
        g, u = (jnp.einsum("pd,edf,pe->pf", x, bf(w), on) for w in (gate, up))
        out = jnp.einsum("pf,efd,pe->pd", bf(jax.nn.silu(g) * u), bf(down), on)
        return jnp.sum((out * flat_w[:, None]).reshape(n, k, d).sum(1) * target)

    np.testing.assert_allclose(kernels(m, flat_w, gate, up, down), dense(m, flat_w, gate, up, down), rtol=1e-4)
    got = jax.grad(kernels, argnums=(0, 1, 2, 3, 4))(m, flat_w, gate, up, down)
    want = jax.grad(dense, argnums=(0, 1, 2, 3, 4))(m, flat_w, gate, up, down)
    for name, a, b in zip(("m", "flat_w", "gate", "up", "down"), got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert np.isfinite(a).all(), name
        # the backward rounds its operands (d_out, dg, du) to bfloat16: 2 ** -8 a factor
        assert np.linalg.norm(a - b) < 2e-2 * np.linalg.norm(b), name
    np.testing.assert_array_equal(np.asarray(got[2])[1], 0.0)  # the expert no pick took
    np.testing.assert_array_equal(np.asarray(got[1])[local == held], 0.0)  # another chip's picks
