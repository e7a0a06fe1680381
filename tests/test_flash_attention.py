"""Pallas flash attention (interpret mode on CPU) vs the dense oracle."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from persia_tpu import ops
from persia_tpu.parallel.sequence import reference_attention

# the kernel compiles by default (that needs a TPU); on CPU the suite asks
# for the Pallas interpreter itself
flash_attention = functools.partial(ops.flash_attention, interpret=True)


def _qkv(b=2, l=64, h=4, d=16, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((b, l, h, d)), dtype=dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_matches_reference(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_ragged_length_padding():
    """L not divisible by block size: padded keys must not contribute."""
    q, k, v = _qkv(l=37, seed=1)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_single_block():
    q, k, v = _qkv(l=8, seed=2)
    out = flash_attention(q, k, v)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_bf16_io():
    q, k, v = _qkv(seed=3, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2, rtol=3e-2
    )


def test_gradients_match_dense():
    q, k, v = _qkv(l=32, seed=4)

    def loss_f(f):
        return lambda q, k, v: jnp.sum(f(q, k, v) ** 2)

    g_flash = jax.grad(
        loss_f(lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=16, block_k=16)),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_ref = jax.grad(
        loss_f(lambda q, k, v: reference_attention(q, k, v, causal=True)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_under_jit():
    q, k, v = _qkv(seed=5)
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, block_q=16, block_k=16))(q, k, v)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_rejects_bad_rank():
    with pytest.raises(ValueError):
        flash_attention(jnp.zeros((2, 8, 4)), jnp.zeros((2, 8, 4)), jnp.zeros((2, 8, 4)))


@pytest.mark.parametrize("bq,bk", [(256, 512), (32, 16), (16, 48)])
def test_mismatched_blocks_cover_all_rows(bq, bk):
    """Regression: L not divisible by the smaller block must not drop rows."""
    q, k, v = _qkv(l=300 if bq >= 256 else 50, h=2, seed=6)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_default_is_compiled_not_interpreted():
    """No caller gets the interpreter without asking: off-TPU the default
    (compile with Mosaic) refuses instead of quietly interpreting."""
    q, k, v = _qkv(l=8)
    with pytest.raises(ValueError, match="interpret mode"):
        ops.flash_attention(q, k, v)
