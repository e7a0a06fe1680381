"""Pallas flash attention (interpret mode on CPU) vs the dense oracle."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from persia_tpu import ops
from persia_tpu.parallel.sequence import reference_attention

# the kernel compiles by default (that needs a TPU); on CPU the suite asks
# for the Pallas interpreter itself
flash_attention = functools.partial(ops.flash_attention, interpret=True)
KERNELS = importlib.import_module("persia_tpu.ops.flash_attention")  # the module, beside the function


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


# ---- how many query heads a grid step of the attention kernels carries ----

# a call at each sequence cell's shape: query heads, K/V heads (the latent kernels read one
# K/V head a query head), the shared key columns, whether the mask is the intervals', the
# sequences, the tile pairs a sequence the grid runs over; and the kernels' VMEM by the
# reckoning, forward, dq, dk/dv, at 8 heads a step
CELL_CALLS = {
    "joyai-flash-ep32-pack16k-mtp1": ((32, 32, 64, True, 1, 528), (17_596_416, 23_887_872, 30_179_328)),
    "kimi-linear-ep32-pack16k": ((32, 32, 64, True, 1, 528), (17_596_416, 23_887_872, 30_179_328)),
    "mellum2-ep4-pack16k": ((32, 4, 0, True, 1, 528), (11_567_104, 13_664_256, 10_518_528)),
    "mellum2-ep4-pack16k-sliding": ((32, 4, 0, True, 1, 93), (11_567_104, 13_664_256, 10_518_528)),
    "sdar-ep8-bd4-seq4k": ((32, 4, 0, False, 2, 80), (11_534_336, 13_631_488, 10_485_760)),
}


@pytest.mark.parametrize("cell", sorted(CELL_CALLS))
def test_heads_a_step_and_their_vmem_at_the_cells_shapes(cell):
    """8 query heads a grid step at every cell's shape, from the shapes
    alone: an eighth of the grid steps of a head a step. Each kernel's
    reckoning fits the budget and the limit the call asks Mosaic for lies
    above it (``tests/test_tpu_compile.py`` compiles the kernels under the
    reckoning alone); at the latent shape 16 heads would not fit."""
    fa = KERNELS
    (hq, hkv, shared, lo, batch, visits), want = CELL_CALLS[cell]
    assert fa.heads_a_step(hq, hkv, 128, 512, jnp.bfloat16, shared, lo) == 8
    assert fa.attention_grid_steps(batch, hq, hkv, 128, 512, visits, jnp.bfloat16, shared, lo) == \
        batch * 4 * visits
    group = hq // hkv
    got = tuple(fa._vmem_reckoning(k, 8, group, 128, shared, 512, 2, lo) for k in ("fwd", "dq", "dkv"))
    assert got == want and max(got) <= fa._VMEM_BUDGET
    for kernel, need in zip(("fwd", "dq", "dkv"), got):
        params = fa._compiler_params(kernel, 8, group, 128, shared, 512, jnp.bfloat16, lo, ("parallel",))
        assert params.vmem_limit_bytes == max(16 * 2**20, need + fa._VMEM_SLACK)
    if shared:
        assert fa._vmem_reckoning("dkv", 16, group, 128, shared, 512, 2, lo) > fa._VMEM_BUDGET


@pytest.mark.parametrize("hq,hkv,want", [(2, 2, 2), (6, 2, 6), (3, 1, 3), (11, 1, 1), (64, 2, 8), (12, 4, 6)])
def test_heads_a_step_is_whole_groups_or_part_of_one(hq, hkv, want):
    """A step's K/V block is whole heads: its query heads are whole groups or
    lie inside one (``flash_attention(causal=True)``'s two heads take 2; a
    group of 11 has no part of 2 to 8 heads and takes 1)."""
    assert KERNELS.heads_a_step(hq, hkv, 128, 256, jnp.float32) == want
