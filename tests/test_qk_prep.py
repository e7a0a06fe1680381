"""``ops/qk_prep.py::qk_norm_rope`` in the Pallas interpreter against the dense
float32 formula written out here: per-head RMS norm, rotate-half RoPE, the
cast to bfloat16, and their gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from persia_tpu.ops.qk_prep import qk_norm_rope, qk_prep_tile

D, EPS = 128, 1e-6


def dense(x, w, cos, sin, n_heads):
    """The towers' formula before the op: float32 all the way, heads as an axis."""
    b, t, width = x.shape
    x = x.reshape(b, t, n_heads, width // n_heads)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * w
    half = y.shape[-1] // 2
    rotated = jnp.concatenate([-y[..., half:], y[..., :half]], axis=-1)
    return (y * cos[..., None, :] + rotated * sin[..., None, :]).reshape(b, t, width)


def inputs(n_heads, per_sequence, b=2, t=None, seed=0):
    t = t or (2048 if n_heads == 32 else 3072)  # two and three blocks of 1,024 positions a sequence
    kx, kw, ka, kg = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = 3.0 * jax.random.normal(kx, (b, t, n_heads * D), jnp.float32)
    w = 1.0 + 0.5 * jax.random.normal(kw, (D,), jnp.float32)
    # cos and sin of angles that differ between the two halves of the lanes
    # and, where the table is one a sequence, between sequences: nothing the
    # op may assume of a table
    angle = 7.0 * jax.random.uniform(ka, (b, t, D) if per_sequence else (t, D), jnp.float32)
    # a cotangent bfloat16 holds exactly: what the op is handed and what the formula is handed agree
    g = jax.random.normal(kg, x.shape, jnp.float32).astype(jnp.bfloat16).astype(jnp.float32)
    return x, w, 1.25 * jnp.cos(angle), 1.25 * jnp.sin(angle), g


CASES = [pytest.param(heads, per_sequence, id=f"{heads}-heads-{'a-table-a-sequence' if per_sequence else 'one-table'}")
         for heads in (32, 4, 1) for per_sequence in (False, True)]


@pytest.mark.parametrize("n_heads,per_sequence", CASES)
def test_forward_is_the_dense_formula_to_a_bfloat16_ulp(n_heads, per_sequence):
    x, w, cos, sin, _ = inputs(n_heads, per_sequence)
    got = qk_norm_rope(x, w, cos, sin, n_heads, EPS, interpret=True)
    assert got.dtype == jnp.bfloat16 and got.shape == x.shape
    want = dense(x, w, cos, sin, n_heads)
    got, nearest = np.asarray(got, np.float32), np.asarray(want.astype(jnp.bfloat16), np.float32)
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(nearest), 1e-30))) - 7)
    # beside the ulp, what float32 leaves of two terms of size 10 that cancel
    assert np.all(np.abs(got - nearest) <= ulp + 1e-6)
    assert np.mean(got == nearest) > 0.99  # a sum in another order moves few roundings


@pytest.mark.parametrize("n_heads,per_sequence", CASES)
def test_gradients_are_those_of_the_float32_formula(n_heads, per_sequence):
    x, w, cos, sin, g = inputs(n_heads, per_sequence, seed=1)

    def through_op(x, w):
        out = qk_norm_rope(x, w, cos, sin, n_heads, EPS, interpret=True)
        return jnp.sum(out.astype(jnp.float32) * g)

    dx, dw = jax.grad(through_op, argnums=(0, 1))(x, w)
    want_dx, want_dw = jax.grad(lambda x, w: jnp.sum(dense(x, w, cos, sin, n_heads) * g),
                                argnums=(0, 1))(x, w)
    assert dx.dtype == jnp.float32 and dx.shape == x.shape and dw.shape == w.shape
    np.testing.assert_allclose(dx, want_dx, rtol=1e-4, atol=2e-6)
    np.testing.assert_allclose(dw, want_dw, rtol=1e-5, atol=1e-5 * float(jnp.max(jnp.abs(want_dw))))


def test_a_block_holds_the_most_positions_that_divide_the_sequence():
    assert [qk_prep_tile(t) for t in (64, 1024, 1536, 3072, 8192, 16384, 8 * 251)] == [
        64, 1024, 768, 1024, 1024, 1024, 8]
    x, w, cos, sin, _ = inputs(4, True, t=1536, seed=2)  # two blocks of 768
    got = qk_norm_rope(x, w, cos, sin, 4, EPS, interpret=True)
    whole = qk_norm_rope(x[:, :768], w, cos[:, :768], sin[:, :768], 4, EPS, interpret=True)
    np.testing.assert_array_equal(got[:, :768], whole)


def test_no_table_gets_a_gradient():
    x, w, cos, sin, g = inputs(1, False, t=16, seed=3)
    d_cos, d_sin = jax.grad(
        lambda c, s: jnp.sum(qk_norm_rope(x, w, c, s, 1, EPS, interpret=True).astype(jnp.float32) * g),
        argnums=(0, 1))(cos, sin)
    assert not d_cos.any() and not d_sin.any()


@pytest.mark.parametrize("shape,n_heads,tables,what", [
    ((1, 16, 128), 2, (16, 64), "head size 64"),
    ((1, 12, 128), 1, (12, 128), "length 12"),
    ((1, 16, 384), 2, (16, 192), "head size 192"),
    ((1, 16, 128), 1, (8, 128), "tables"),
    ((1, 16, 100), 3, (16, 128), "heads"),
    ((16, 128), 1, (16, 128), "shape"),
], ids=["head-dim-64", "length-12", "head-dim-192", "a-table-of-another-length", "width-no-whole-heads", "no-batch-axis"])
def test_refuses_what_the_attention_kernels_refuse(shape, n_heads, tables, what):
    x = jnp.zeros(shape, jnp.float32)
    with pytest.raises(ValueError, match=what):
        qk_norm_rope(x, jnp.ones((tables[-1],), jnp.float32), jnp.zeros(tables), jnp.zeros(tables),
                     n_heads, EPS, interpret=True)
