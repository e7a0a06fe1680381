"""Plain reference DLRM training step: float32 ``jax.numpy``, matrix products
at ``highest`` precision, a dictionary of rows in place of tables, cache and
parameter server. Imports nothing of ``persia_tpu``.

The model (Naumov et al. 2019, as MLPerf and facebookresearch/dlrm run it):
bottom MLP over the dense features (ReLU after every layer), dot interaction
of the bottom output with the pooled row of every table (all pairs i < j),
top MLP over [bottom | interactions] (ReLU after every layer but the 1-wide
logit), mean sigmoid cross-entropy. Sparse Adagrad on the rows a batch touches
(gradients of one key summed first), Adam on the dense parameters.

Departures from the published training recipe are the configuration's
(``assumed`` in its file): Adagrad/Adam in place of SGD.

``passes`` selects the arithmetic of every matrix product: 6 is float32 at
``highest`` (the reference proper); 3 and 1 emulate the TPU's ``high``
(three bfloat16 passes) and default (one pass) precisions from exact products
of bfloat16-rounded pieces, forward and backward, on any backend. They exist
for the control of ``perf/compare.py``: the reference computed one precision
below what the configuration states has to come out as not correct.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
_PAD = 8192  # a step's distinct rows are padded to a multiple of this


# ------------------------------------------------------------ row birth rule

_C1 = np.uint64(0x9E3779B97F4A7C15)
_C2 = np.uint64(0xBF58476D1CE4E5B9)
_C3 = np.uint64(0x94D049BB133111EB)


def _splitmix64(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint64) + _C1
    x ^= x >> np.uint64(30)
    x *= _C2
    x ^= x >> np.uint64(27)
    x *= _C3
    x ^= x >> np.uint64(31)
    return x


def splitmix_uniform_rows(signs: np.ndarray, ps_seed: int, dim: int,
                          lo: float = -0.01, hi: float = 0.01) -> np.ndarray:
    """The configuration's ``row_birth`` guarantee, written from its text:
    element j of sign s is lo + (hi - lo) * (splitmix64(splitmix64(s ^ seed)
    + j) >> 11) * 2**-53, rounded to float32."""
    bases = _splitmix64(np.asarray(signs, dtype=np.uint64) ^ np.uint64(ps_seed))
    states = _splitmix64(bases[:, None] + np.arange(dim, dtype=np.uint64)[None, :])
    unit = (states >> np.uint64(11)).astype(np.float64) * (1.0 / (1 << 53))
    return (lo + unit * (hi - lo)).astype(np.float32)


# ------------------------------------------------------------ matrix products

def _split_bf16(x, pieces: int):
    out, rest = [], x
    for _ in range(pieces):
        # reduce_precision, not a cast there and back: a compiler allowed excess
        # precision may drop such a pair of casts, and the rounding with it
        p = jax.lax.reduce_precision(rest, exponent_bits=8, mantissa_bits=7)
        out.append(p)
        rest = rest - p
    return out


def _mm_impl(a, b, passes: int):
    if passes >= 6:
        return jnp.matmul(a, b, precision=_HI)
    if passes == -3:  # the chip's own ``high``: three passes on the MXU (a no-op off the TPU)
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)
    if passes == 1:
        (a0,), (b0,) = _split_bf16(a, 1), _split_bf16(b, 1)
        return jnp.matmul(a0, b0, precision=_HI)
    if passes == 3:
        (a0, a1), (b0, b1) = _split_bf16(a, 2), _split_bf16(b, 2)
        return (jnp.matmul(a0, b1, precision=_HI) + jnp.matmul(a1, b0, precision=_HI)
                + jnp.matmul(a0, b0, precision=_HI))
    raise ValueError(f"passes must be 6, 3, -3 or 1, got {passes}")


def _t(x):
    return jnp.swapaxes(x, -1, -2)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _mm(a, b, passes):
    """a @ b (both 2-D or both 3-D) in the stated arithmetic, backward too."""
    return _mm_impl(a, b, passes)


def _mm_fwd(a, b, passes):
    return _mm_impl(a, b, passes), (a, b)


def _mm_bwd(passes, res, g):
    a, b = res
    return _mm_impl(g, _t(b), passes), _mm_impl(_t(a), g, passes)


_mm.defvjp(_mm_fwd, _mm_bwd)


# ------------------------------------------------------------------- forward

def forward(dense_params, emb, dense_x, n_bottom: int, passes: int = 6):
    """Logits (B, 1). ``dense_params``: [(kernel, bias), ...] bottom layers
    first; ``emb``: (B, S, d) pooled rows; ``dense_x``: (B, F)."""
    x = dense_x
    for k, b in dense_params[:n_bottom]:
        x = jax.nn.relu(_mm(x, k, passes) + b)
    feats = jnp.concatenate([x[:, None, :], emb], axis=1)  # (B, S+1, d)
    inter = _mm(feats, _t(feats), passes)  # (B, n, n)
    n = feats.shape[1]
    iu, ju = np.triu_indices(n, k=1)
    top = jnp.concatenate([x, inter[:, iu, ju]], axis=1)
    last = len(dense_params) - 1
    for i, (k, b) in enumerate(dense_params[n_bottom:], start=n_bottom):
        top = _mm(top, k, passes) + b
        if i < last:
            top = jax.nn.relu(top)
    return top


def bce_mean(logits, labels):
    """Mean over the batch of the sigmoid cross-entropy,
    -y log s(x) - (1 - y) log s(-x), with log s(x) = -softplus(-x)."""
    z = labels * jax.nn.softplus(-logits) + (1.0 - labels) * jax.nn.softplus(logits)
    return jnp.mean(z)


def loss_fn(dense_params, rows_u, inv, dense_x, labels, n_bottom, passes):
    s, b = inv.shape
    emb = jnp.transpose(rows_u[inv.reshape(-1)].reshape(s, b, -1), (1, 0, 2))
    return bce_mean(forward(dense_params, emb, dense_x, n_bottom, passes), labels)


@partial(jax.jit, static_argnames=("n_bottom", "passes", "sparse", "adam"))
def _train_step(dense_params, m, v, t, rows_u, acc_u, inv, dense_x, labels,
                n_bottom, passes, sparse, adam):
    loss, (g_dense, g_rows) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
        dense_params, rows_u, inv, dense_x, labels, n_bottom, passes)
    lr, eps = sparse
    acc_new = acc_u + g_rows * g_rows
    rows_new = rows_u - lr * g_rows / jnp.sqrt(acc_new + eps)
    alr, b1, b2, aeps = adam
    t = t + 1.0
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    m = jax.tree.map(lambda mm, g: b1 * mm + (1.0 - b1) * g, m, g_dense)
    v = jax.tree.map(lambda vv, g: b2 * vv + (1.0 - b2) * g * g, v, g_dense)
    dense_new = jax.tree.map(
        lambda p, mm, vv: p - alr * (mm / c1) / (jnp.sqrt(vv / c2) + aeps),
        dense_params, m, v)
    return loss, dense_new, m, v, t, rows_new, acc_new


class ReferenceDLRM:
    """The reference trainer. Rows live in a dictionary keyed by an opaque
    uint64 (a table-and-id code or a sign) that gives each key's place in two
    host arrays; ``row_birth(keys)`` gives the initial rows of keys never seen."""

    def __init__(self, config: dict, dense: List[Tuple[np.ndarray, np.ndarray]],
                 row_birth: Callable[[np.ndarray], np.ndarray],
                 passes: int = 6, adam_start: Optional[dict] = None):
        self.config = config
        self.dim = config["embedding_dim"]
        self.n_bottom = len(config["bottom_mlp"])
        self.passes = passes
        self.row_birth = row_birth
        so, do = config["sparse_optimizer"], config["dense_optimizer"]
        if so["kind"] != "adagrad" or do["kind"] != "adam":
            raise ValueError("the reference implements Adagrad rows and Adam dense")
        self.acc0 = float(so["initial_accumulator"])
        self._sparse = (float(so["lr"]), float(so["eps"]))
        self._adam = (float(do["lr"]), float(do["b1"]), float(do["b2"]), float(do["eps"]))
        self.dense = [(jnp.asarray(k), jnp.asarray(b)) for k, b in dense]
        self.m = jax.tree.map(jnp.zeros_like, self.dense)
        self.v = jax.tree.map(jnp.zeros_like, self.dense)
        self.t = jnp.zeros((), jnp.float32)
        if adam_start:  # Adam as a long-running job holds it: steps taken, second moment
            self.t = jnp.asarray(float(adam_start["count"]), jnp.float32)
            self.v = jax.tree.map(lambda x: jnp.full_like(x, float(adam_start["nu"])), self.v)
        self._slot: Dict[int, int] = {}  # key -> position in rows/acc: the dictionary of rows
        self.rows = np.empty((_PAD, self.dim), np.float32)
        self.acc = np.empty((_PAD, self.dim), np.float32)

    def _positions(self, keys: np.ndarray, create: bool) -> np.ndarray:
        """Where each key's row lives; -1 for a key never trained, unless
        ``create``, which gives it a place, its birth row and accumulator."""
        slot = self._slot
        pos = np.fromiter((slot.get(k, -1) for k in keys.tolist()), np.int64, len(keys))
        new = np.flatnonzero(pos < 0)
        if create and len(new):
            n = len(slot)
            while n + len(new) > len(self.rows):
                self.rows = np.concatenate([self.rows, np.empty_like(self.rows)])
                self.acc = np.concatenate([self.acc, np.empty_like(self.acc)])
            pos[new] = np.arange(n, n + len(new))
            self.rows[pos[new]] = self.row_birth(keys[new])
            self.acc[pos[new]] = self.acc0
            slot.update(zip(keys[new].tolist(), pos[new].tolist()))
        return pos

    def lookup(self, keys: np.ndarray):
        """(rows, acc) as held now; keys never trained read their birth rows."""
        keys = np.asarray(keys, np.uint64)
        pos = self._positions(keys, create=False)
        found = pos >= 0
        rows = np.empty((len(keys), self.dim), np.float32)
        acc = np.full((len(keys), self.dim), self.acc0, np.float32)
        rows[found], acc[found] = self.rows[pos[found]], self.acc[pos[found]]
        if (~found).any():
            rows[~found] = self.row_birth(keys[~found])
        return rows, acc

    def step(self, keys: np.ndarray, dense_x: np.ndarray, labels: np.ndarray) -> float:
        """One training step on ``keys`` (S, B) uint64, ``dense_x`` (B, F),
        ``labels`` (B, 1). Returns the loss."""
        s, b = keys.shape
        uniq, inv = np.unique(keys.reshape(-1), return_inverse=True)
        pos = self._positions(uniq, create=True)
        pad = -len(uniq) % _PAD  # few shapes over a run's steps: few compiles
        rows_p = np.concatenate([self.rows[pos], np.zeros((pad, self.dim), np.float32)])
        acc_p = np.concatenate([self.acc[pos], np.ones((pad, self.dim), np.float32)])
        out = _train_step(
            self.dense, self.m, self.v, self.t, jnp.asarray(rows_p), jnp.asarray(acc_p),
            jnp.asarray(inv.reshape(s, b).astype(np.int32)), jnp.asarray(dense_x),
            jnp.asarray(labels), n_bottom=self.n_bottom, passes=self.passes,
            sparse=self._sparse, adam=self._adam)
        loss, dense_new, m, v, t, rows_new, acc_new = out
        self.dense, self.m, self.v, self.t = dense_new, m, v, t
        self.rows[pos] = np.asarray(rows_new)[:len(uniq)]
        self.acc[pos] = np.asarray(acc_new)[:len(uniq)]
        return float(loss)
