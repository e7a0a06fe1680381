"""Weights and table rows made from the seed by a counter hash.

Element ``j`` of row ``i`` of stream ``k`` is a pure function of
``(seed, k, i, j)``, so the harness can fill a whole table on the device in
one jitted call and the reference can compute the same values for only the
rows a batch touches. All arithmetic is uint32 (wrapping), which numpy and
jax.numpy compute alike on every backend.
"""

from __future__ import annotations

import numpy as np

_GOLD = 0x9E3779B9


def _mix32(x, xp):
    """lowbias32 finalizer over a uint32 array (numpy or jax.numpy)."""
    u = xp.uint32
    x = x ^ (x >> u(16))
    x = x * u(0x7FEB352D)
    x = x ^ (x >> u(15))
    x = x * u(0x846CA68B)
    x = x ^ (x >> u(16))
    return x


def seed_words(seed):
    """A seed of any size up to 64 bits as two uint32 words. Words given as an
    array (an argument of a jitted call, so that the seed is no constant of the
    compiled program and every seed finds it in the compile cache) pass through."""
    if hasattr(seed, "shape"):
        return seed[0], seed[1]
    seed = int(seed) & ((1 << 64) - 1)
    return np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32)


def hashed_uniform(seed_lo, seed_hi, stream, rows, dim: int, bound: float, xp=np):
    """(len(rows), dim) float32 uniform in [-bound, bound).

    ``rows`` is an integer array (< 2**32); ``stream`` names the table or the
    dense leaf. ``xp`` is numpy or jax.numpy; both give the same bits."""
    u = xp.uint32
    words = xp.stack([xp.asarray(seed_lo, dtype=xp.uint32),
                      xp.asarray(seed_hi, dtype=xp.uint32)])  # arrays wrap silently
    key = _mix32(words[:1] ^ _mix32(words[1:] + u(_GOLD), xp), xp)
    stream = xp.reshape(xp.asarray(stream).astype(xp.uint32), (-1,))
    key = _mix32(key ^ (stream * u(0x85EBCA6B) + u(1)), xp)  # (1,) or (n,)
    r = _mix32(xp.asarray(rows).astype(xp.uint32) ^ key, xp)
    cols = xp.arange(dim, dtype=xp.uint32) * u(_GOLD)
    h = _mix32(r[:, None] + cols[None, :] + key[:, None], xp)
    # an exact integer-to-float conversion and ONE rounding multiply: no
    # multiply-add for a compiler to fuse, so the bits match everywhere
    centred = (h >> u(8)).astype(xp.int32) - xp.int32(1 << 23)
    return centred.astype(xp.float32) * xp.float32(bound / float(1 << 23))


DENSE_STREAM0 = 1 << 20  # dense leaf l uses stream DENSE_STREAM0 + l


def dense_layer_sizes(config: dict):
    """[(fan_in, fan_out), ...] bottom MLP, then top MLP (its last layer is
    the 1-wide logit)."""
    d = config["embedding_dim"]
    n_vec = len(config["table_rows"]) + 1
    sizes, fan = [], config["num_dense"]
    for h in config["bottom_mlp"]:
        sizes.append((fan, h))
        fan = h
    fan = d + n_vec * (n_vec - 1) // 2
    for h in config["top_mlp"]:
        sizes.append((fan, h))
        fan = h
    return sizes


def dense_params(config: dict, seed, xp=np):
    """[(kernel (in, out), bias (out,)), ...] for every dense layer, made
    from the seed: kernels uniform in +-1/sqrt(fan_in), biases in +-0.01."""
    lo_w, hi_w = seed_words(seed)
    out = []
    for l, (fan_in, fan_out) in enumerate(dense_layer_sizes(config)):
        b = 1.0 / float(np.sqrt(fan_in))
        k = hashed_uniform(lo_w, hi_w, DENSE_STREAM0 + 2 * l, xp.arange(fan_in), fan_out, b, xp)
        bias = hashed_uniform(lo_w, hi_w, DENSE_STREAM0 + 2 * l + 1, xp.arange(1), fan_out, 0.01, xp)[0]
        out.append((k, bias))
    return out


TABLE_BOUND = 0.01


def table_rows_init(seed, slot, ids, dim: int, xp=np):
    """Initial rows of table ``slot`` for ``ids`` (pinned placements)."""
    lo_w, hi_w = seed_words(seed)
    return hashed_uniform(lo_w, hi_w, slot, ids, dim, TABLE_BOUND, xp)
