"""The gated delta rule with a decay a channel (Kimi Delta Attention), chunked
over positions, for packed documents.

A head keeps a state ``S`` (``Dk x Dv``, float32), zero before a document's
first position. Position ``i`` with ``alpha_i = exp(g_i)`` in (0, 1]^Dk and
write strength ``beta_i``:

    S' = diag(alpha_i) S_{i-1};  S_i = S' + beta_i k_i (v_i - S'^T k_i)^T;  o_i = S_i^T q_i

**The chunked form.** With ``G`` the running sum of ``g`` inside a chunk of
``C`` positions and ``u_i = beta_i (v_i - S'^T k_i)`` the value actually
written, ``S_i = diag(e^{G_i}) S_0 + sum_{j<=i} diag(e^{G_i - G_j}) k_j
u_j^T`` for the state ``S_0`` that enters the chunk, so

    (I + A) U = beta (V - K+ S_0),   A_ij = beta_i sum_d k_id k_jd e^{G_id - G_jd}  (j < i)
    O = Q+ S_0 + P U,                P_ij = sum_d q_id k_jd e^{G_id - G_jd}         (j <= i)
    S_C = diag(e^{G_C}) S_0 + Ke^T U,  Ke_j = k_j e^{G_C - G_j}

with ``K+ = k e^G``, ``Q+ = q e^G``. ``T = (I + A)^-1`` does not depend on the
state, so ``W = T (beta K+)`` and ``U0 = T (beta V)`` are made for all chunks
at once and what is left to scan is linear in the state:

    U = U0 - W S;   O = Q+ S + P U;   S <- diag(gamma) S + Ke^T U

**Four Pallas kernels.** ``kda_prepare_fwd`` makes a chunk's operands (``W``,
``U0``, ``Q+``, ``P``, ``Ke``, ``gamma``) from q, k, v, g, beta and ``lo``: the
running sums, the pair products, the inverse; a grid step takes four chunks of
one head, a head a block of 128 columns of the (B, T, H x 128) inputs, and no
step depends on another. It also writes the inverse ``T`` itself (float32, 16
KB a chunk of 64: 33.5 MB for 8 heads over 16,384 positions), which is most of
the chunk's work and which only the backward reads. ``kda_prepare_bwd`` takes
``T`` from there, makes the chunk's other parts again (the running sums, the
pair products, the masks and exponentials: one-pass products and VPU work)
and takes the operands' cotangents back to q, k, v, g and beta by hand: of
the forward it keeps its inputs and ``T``. ``kda_chunk_fwd`` and ``kda_chunk_bwd``
run the scan, a chunk a grid step with the state (forward) or its gradient
(backward, the chunks in reverse) carried in VMEM. The forward writes the
state that enters each chunk (float32, ``B H (T / C) Dv Dk``: 537 MB at 16,384
positions and 32 heads of 128 x 128) and the backward reads it back,
recomputing ``U``. The operands between the two pairs are seven arrays of
the inputs' size or half it: a caller with many heads runs a group of heads at
a time and recomputes the group in its backward
(``models/kimi_linear_moe.py``).

**Documents.** ``lo`` (B, T) int32 gives each position the index at which its
document starts; it is data and may fall anywhere in a chunk. A pair (i, j)
counts in ``A`` and ``P`` only where ``lo_i == lo_j``; a position reads the
entering state only where its document began before the chunk; the leaving
state takes only the chunk's last document, and the entering state only where
that document began before the chunk. The running sum ``G`` runs through the
starts (every ``g`` is finite), and only differences inside one document are
ever used.

**Arithmetic.** ``g``, its running sums (one triangular product in float32:
the triangle of 0 and 1 against ``g`` in three bfloat16 parts, which is what a
product at ``highest`` sums once the terms that multiply zeros are left out),
the exponentials, ``T`` (a block-recursive inverse of the unit lower-triangular
``I + A``: the blocks of 2 are ``I - A`` there, then five levels of two float32
products at ``highest`` at a chunk of 64, which reorders forward substitution
and does not square ``A``), its gradient ``-T^T dT T^T`` and the state are
float32, and ``T`` crosses HBM as float32. Every other matrix
product takes bfloat16 operands and sums in float32: ``k e^{G - G_m}`` and
``k e^{G_m - G}`` into ``A`` and ``P`` (``G_m`` the running sum at the
chunk's middle, so that a pair's two factors stay in range: the form is exact
while no channel decays by more than e^-80 over half a chunk), ``T``, ``beta
K+`` and ``beta V`` into ``W`` and ``U0``, and in the kernels ``W``, ``Q+``,
``P``, ``Ke``, the state and ``U`` rounded where a product takes them.
Gradients pass the roundings straight through, and a product the forward
made on rounded operands is transposed on rounded operands.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KDA_CHUNK = 64
_ONE_PASS = jax.lax.Precision.DEFAULT
_HIGHEST = jax.lax.Precision.HIGHEST
_EXP_CAP = 80.0  # e^80 is finite in float32 and in bfloat16


def log_decay_floor(chunk: int = KDA_CHUNK) -> float:
    """The least log decay a position for which ``kda`` is exact at this
    chunk: half a chunk of them sum to ``_EXP_CAP``, past which a pair's
    factors about the chunk's middle are cut off, silently. A caller whose
    decays are learned holds them at or above it (``KimiLinearMoE._kda``)."""
    return -_EXP_CAP / max(chunk // 2, 1)


# ------------------------------------------------------------------ the kernels

def _dot(a, b, dims, precision=_ONE_PASS):
    return jax.lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=jnp.float32,
                               precision=precision)


def _rdot(a, b, dims):
    """Both operands rounded to bfloat16, one pass, summed in float32."""
    return _dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dims)


def _fdot(a, b, dims):
    return _dot(a, b, dims, _HIGHEST)


_NN, _TN, _NT = ((1,), (0,)), ((0,), (0,)), ((1,), (1,))  # a b, a^T b, a b^T


def unit_lower_inverse(a):
    """``(I + a)^-1`` in float32 for strictly lower-triangular ``a`` (C, C), C
    a power of two: the inverses of the diagonal blocks of size s give those
    of size 2s, ``X - X L X`` with ``L`` the blocks of ``a`` under them. The
    blocks of size 1 are the identity, so those of size 2 are ``I - L`` and
    cost no product."""
    c = a.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    under = lambda s: (row // (2 * s) == col // (2 * s)) & (row // s > col // s)
    x = (row == col).astype(a.dtype) - jnp.where(under(1), a, 0.0)
    s = 2
    while s < c:
        x = x - _fdot(_fdot(x, jnp.where(under(s), a, 0.0), _NN), x, _NN)
        s *= 2
    return x


def running_sums(x, reverse=False):
    """The sums of ``x`` (C, D) float32 over the rows up to and with each row
    (from each row on if ``reverse``), as a triangular product in float32: the
    triangle is 0 and 1, exact in bfloat16, so of the six passes of a product
    at ``highest`` the three that take its two lower parts multiply zeros.
    ``x`` goes in as three bfloat16 parts (24 bits), a pass each."""
    c = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    upto = (row >= col).astype(jnp.bfloat16)
    dims = _TN if reverse else _NN
    high = x.astype(jnp.bfloat16)
    rest = x - high.astype(jnp.float32)
    middle = rest.astype(jnp.bfloat16)
    low = (rest - middle.astype(jnp.float32)).astype(jnp.bfloat16)
    return _dot(upto, low, dims) + _dot(upto, middle, dims) + _dot(upto, high, dims)


# ---- a chunk's operands (``kda_prepare_fwd``, ``kda_prepare_bwd``): no state, every chunk alone

def _chunk_parts(q, k, g, beta, lo_col, lo_row, first, t_inv=None):
    """What both kernels make of a chunk on the way to its operands: q, k, g
    (C, Dk) float32, ``beta`` and ``lo_col`` (C, 1), ``lo_row`` (1, C),
    ``first`` the chunk's first position; ``t_inv`` the chunk's inverse where
    the caller has it (the backward: the forward wrote it), else made here."""
    c = q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    run = running_sums(g)
    middle = max(c // 2 - 1, 0)
    mid, end = run[middle:middle + 1], run[c - 1:c]
    rise, fall = run - mid, mid - run
    up, down = jnp.exp(jnp.minimum(rise, _EXP_CAP)), jnp.exp(jnp.minimum(fall, _EXP_CAP))
    same = lo_col == lo_row
    carried = (lo_col < first).astype(jnp.float32)  # the position's document began before the chunk
    tail = (lo_col == lo_col[c - 1:c]).astype(jnp.float32)  # the chunk's last document
    k_up, k_down = k * up, k * down
    # masked before anything multiplies it: a pair no position reads may overflow (both factors
    # past the chunk's middle on the large side), and 0 x inf in beta's gradient would be NaN
    below = same & (row > col)
    pairs = jnp.where(below, _rdot(k_up, k_down, _NT), 0.0)
    return dict(row=row, col=col, middle=middle, up=up, down=down, rising=rise < _EXP_CAP,
                falling=fall < _EXP_CAP, k_up=k_up, k_down=k_down, q_up=q * up, pairs=pairs, below=below,
                upto=same & (row >= col), t_inv=unit_lower_inverse(pairs * beta) if t_inv is None else t_inv,
                decay=jnp.exp(run) * carried,
                leave=jnp.exp(end - run) * tail, gamma=jnp.exp(end) * carried[c - 1:c])


def _column(x, lane):
    """Column ``lane`` (traced) of x (C, H) as (C, 1)."""
    at = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(at == lane, x, 0.0), axis=1, keepdims=True)


def _chunk_inputs(refs, s, chunk, sub):
    q_ref, k_ref, v_ref, g_ref, beta_ref, lo_col_ref, lo_row_ref = refs
    rows = slice(s * chunk, (s + 1) * chunk)
    q, k, v, g = (x[0, rows, :] for x in (q_ref, k_ref, v_ref, g_ref))
    beta = _column(beta_ref[0, rows, :], pl.program_id(1))
    first = (pl.program_id(2) * sub + s) * chunk
    return q, k, v, g, beta, lo_col_ref[0, s], lo_row_ref[0, s], first


def _prepare_fwd_kernel(*refs, chunk, sub):
    w_ref, u0_ref, qin_ref, p_ref, ke_ref, gamma_ref, t_ref = refs[7:]
    for s in range(sub):
        q, k, v, g, beta, lo_col, lo_row, first = _chunk_inputs(refs[:7], s, chunk, sub)
        x = _chunk_parts(q, k, g, beta, lo_col, lo_row, first)
        t_ref[0, 0, s] = x["t_inv"]
        w_ref[0, 0, s] = _rdot(x["t_inv"], beta * k * x["decay"], _NN).astype(w_ref.dtype)
        u0_ref[0, 0, s] = _rdot(x["t_inv"], beta * v, _NN)
        qin_ref[0, 0, s] = (q * x["decay"]).astype(qin_ref.dtype)
        p_ref[0, 0, s] = jnp.where(x["upto"], _rdot(x["q_up"], x["k_down"], _NT), 0.0).astype(p_ref.dtype)
        ke_ref[0, 0, s] = (k * x["leave"]).astype(ke_ref.dtype)
        gamma_ref[0, 0, s] = x["gamma"]


def _prepare_bwd_kernel(*refs, chunk, sub):
    """The chunk's parts made again but for the inverse, which the forward
    kept, then the operands' cotangents back to q, k, v, g and beta. The
    roundings pass gradients straight through; a product the forward made on
    bfloat16 operands is transposed on bfloat16 operands; the inverse's, ``dA
    = -T^T dT T^T`` under the diagonal, is float32."""
    t_ref, dw_ref, du0_ref, dqin_ref, dp_ref, dke_ref, dgamma_ref = refs[7:14]
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref = refs[14:]
    f32 = lambda ref, s: ref[0, 0, s].astype(jnp.float32)
    for s in range(sub):
        q, k, v, g, beta, lo_col, lo_row, first = _chunk_inputs(refs[:7], s, chunk, sub)
        x = _chunk_parts(q, k, g, beta, lo_col, lo_row, first, t_inv=t_ref[0, 0, s])
        row, col, t_inv, decay, up, down = x["row"], x["col"], x["t_inv"], x["decay"], x["up"], x["down"]
        dw, du0, dqin, dp, dke = (f32(r, s) for r in (dw_ref, du0_ref, dqin_ref, dp_ref, dke_ref))
        rows = slice(s * chunk, (s + 1) * chunk)

        d_keyed, d_valued = _rdot(t_inv, dw, _TN), _rdot(t_inv, du0, _TN)  # of beta k decay, of beta v
        dt = _rdot(dw, beta * k * decay, _NT) + _rdot(du0, beta * v, _NT)
        da = jnp.where(x["below"], -_fdot(_fdot(t_inv, dt, _TN), t_inv, _NT), 0.0)
        dpairs, dp = da * beta, jnp.where(x["upto"], dp, 0.0)
        dk_up = _rdot(dpairs, x["k_down"], _NN)
        dk_down = _rdot(dpairs, x["k_up"], _TN) + _rdot(dp, x["q_up"], _TN)
        dq_up = _rdot(dp, x["k_down"], _NN)

        dq_ref[0, rows, :] = dqin * decay + dq_up * up
        dk_ref[0, rows, :] = d_keyed * beta * decay + dk_up * up + dk_down * down + dke * x["leave"]
        dv_ref[0, rows, :] = d_valued * beta
        dbeta = (jnp.sum(da * x["pairs"], axis=1, keepdims=True)
                 + jnp.sum(d_valued * v + d_keyed * k * decay, axis=1, keepdims=True))
        dbeta_ref[0, 0, s] = jnp.sum(jnp.where(row == col, dbeta, 0.0), axis=0, keepdims=True)  # as a row

        # the running sums: through the decays, the factors about the middle and the leaving state
        about = (jnp.where(x["rising"], (dk_up * k + dq_up * q) * up, 0.0)
                 - jnp.where(x["falling"], dk_down * k * down, 0.0))
        leaving = dke * k * x["leave"]
        drun = (d_keyed * beta * k + dqin * q) * decay + about - leaving
        dend = jnp.sum(leaving, axis=0, keepdims=True) + dgamma_ref[0, 0, s] * x["gamma"]
        at = row[:, :1]
        drun = (drun - jnp.where(at == x["middle"], jnp.sum(about, axis=0, keepdims=True), 0.0)
                + jnp.where(at == chunk - 1, dend, 0.0))
        dg_ref[0, rows, :] = running_sums(drun, reverse=True)


def _sub_chunks(n):
    return next(s for s in (4, 2, 1) if n % s == 0)


def _prepare_call(kernel, name, q, k, v, g, beta, lo, chunk, by_chunk_ins, out_shapes, interpret):
    """One of the two kernels over a grid of (batch, head, ``sub`` chunks): q,
    k, v, g and 3-d results as (B, T, H x width) with a head a block of
    columns, ``beta`` whole (a step takes its head's column), ``lo`` as a
    column and as a row a chunk, everything else (B, H, N, ., .) a chunk."""
    b, t, h, dk = q.shape
    n = t // chunk
    sub = _sub_chunks(n)
    flat = lambda x: x.astype(jnp.float32).reshape(b, t, -1)
    lo = lo.astype(jnp.int32).reshape(b, n, chunk)
    by_head = lambda width: pl.BlockSpec((1, sub * chunk, width), lambda bi, hi, ni: (bi, ni, hi))
    by_chunk = lambda shape: pl.BlockSpec((1, 1, sub) + shape[3:], lambda bi, hi, ni: (bi, hi, ni, 0, 0))
    spec = lambda x: by_head(x.shape[-1] // h) if len(x.shape) == 3 else by_chunk(x.shape)
    ins = [flat(q), flat(k), flat(v), flat(g)]
    in_specs = ([spec(x) for x in ins]
                + [pl.BlockSpec((1, sub * chunk, h), lambda bi, hi, ni: (bi, ni, 0)),
                   pl.BlockSpec((1, sub, chunk, 1), lambda bi, hi, ni: (bi, ni, 0, 0)),
                   pl.BlockSpec((1, sub, 1, chunk), lambda bi, hi, ni: (bi, ni, 0, 0))]
                + [spec(x) for x in by_chunk_ins])
    return pl.pallas_call(
        functools.partial(kernel, chunk=chunk, sub=sub), grid=(b, h, n // sub),
        in_specs=in_specs, out_specs=[spec(x) for x in out_shapes], out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",) * 3),
        interpret=interpret, name=name,
    )(*ins, beta.astype(jnp.float32), lo[..., None], lo[:, :, None, :], *by_chunk_ins)


def _prepare_fwd(q, k, v, g, beta, lo, chunk, interpret):
    """The chunk's operands for the scan, head-major: ``W``, ``Q+``, ``Ke``
    (B, H, N, C, Dk) and ``P`` (B, H, N, C, C) bfloat16; ``U0`` (B, H, N, C,
    Dv) and ``gamma`` (B, H, N, 1, Dk) float32; and the chunk's inverse ``T``
    (B, H, N, C, C) float32, which the scan does not take and the backward
    does. One kernel writes all seven whoever calls: a call that is not
    differentiated throws ``T`` away."""
    b, t, h, dk = q.shape
    n, dv = t // chunk, v.shape[-1]
    shape = lambda *last, dtype=jnp.bfloat16: jax.ShapeDtypeStruct((b, h, n) + last, dtype)
    outs = [shape(chunk, dk), shape(chunk, dv, dtype=jnp.float32), shape(chunk, dk), shape(chunk, chunk),
            shape(chunk, dk), shape(1, dk, dtype=jnp.float32), shape(chunk, chunk, dtype=jnp.float32)]
    *operands, t_inv = _prepare_call(_prepare_fwd_kernel, "kda_prepare_fwd", q, k, v, g, beta, lo, chunk, [], outs,
                                     interpret)
    return tuple(operands), t_inv


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _prepare(q, k, v, g, beta, lo, chunk, interpret):
    return _prepare_fwd(q, k, v, g, beta, lo, chunk, interpret)[0]


def _prepare_vjp_fwd(q, k, v, g, beta, lo, chunk, interpret):
    operands, t_inv = _prepare_fwd(q, k, v, g, beta, lo, chunk, interpret)
    return operands, (q, k, v, g, beta, lo, t_inv)


def _prepare_vjp_bwd(chunk, interpret, res, cts):
    q, k, v, g, beta, lo, t_inv = res
    b, t, h, dk = q.shape
    flat = lambda x: jax.ShapeDtypeStruct((b, t, h * x.shape[-1]), jnp.float32)
    outs = [flat(q), flat(k), flat(v), flat(g), jax.ShapeDtypeStruct((b, h, t // chunk, 1, chunk), jnp.float32)]
    dq, dk_, dv, dg, dbeta = _prepare_call(_prepare_bwd_kernel, "kda_prepare_bwd", q, k, v, g, beta, lo, chunk,
                                           [t_inv, *cts], outs, interpret)
    dbeta = jnp.moveaxis(dbeta.reshape(b, h, t), 1, 2)
    return dq.reshape(q.shape), dk_.reshape(k.shape), dv.reshape(v.shape), dg.reshape(g.shape), dbeta, None


_prepare.defvjp(_prepare_vjp_fwd, _prepare_vjp_bwd)


# ---- the scan over chunks (``kda_chunk_fwd``, ``kda_chunk_bwd``)

# The kernels keep the state transposed, (Dv, Dk): the decay of a key channel
# then scales a column, which a (1, Dk) row does by broadcasting over sublanes.

def _chunk_values(w_ref, u0_ref, st):
    sb = st.astype(jnp.bfloat16)
    u = u0_ref[0, 0, 0] - _dot(w_ref[0, 0, 0], sb, _NT)
    return sb, u.astype(jnp.bfloat16)


def _fwd_kernel(w_ref, u0_ref, q_ref, p_ref, ke_ref, gamma_ref, o_ref, states_ref, s_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_ref[:] = jnp.zeros_like(s_ref)

    st = s_ref[:]
    states_ref[0, 0, 0] = st
    sb, ub = _chunk_values(w_ref, u0_ref, st)
    o_ref[0, 0, 0] = _dot(q_ref[0, 0, 0], sb, _NT) + _dot(p_ref[0, 0, 0], ub, _NN)
    s_ref[:] = gamma_ref[0, 0, 0] * st + _dot(ub, ke_ref[0, 0, 0], _TN)


def _bwd_kernel(w_ref, u0_ref, q_ref, p_ref, ke_ref, gamma_ref, states_ref, do_ref,
                dw_ref, du0_ref, dq_ref, dp_ref, dke_ref, dgamma_ref, ds_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_ref[:] = jnp.zeros_like(ds_ref)

    st, dst = states_ref[0, 0, 0], ds_ref[:]
    sb, ub = _chunk_values(w_ref, u0_ref, st)
    dsb, dob = dst.astype(jnp.bfloat16), do_ref[0, 0, 0].astype(jnp.bfloat16)
    du = _dot(p_ref[0, 0, 0], dob, _TN) + _dot(ke_ref[0, 0, 0], dsb, _NT)
    dub = du.astype(jnp.bfloat16)
    du0_ref[0, 0, 0] = du
    dw_ref[0, 0, 0] = (-_dot(dub, sb, _NN)).astype(dw_ref.dtype)
    dq_ref[0, 0, 0] = _dot(dob, sb, _NN).astype(dq_ref.dtype)
    dp_ref[0, 0, 0] = _dot(dob, ub, _NT).astype(dp_ref.dtype)
    dke_ref[0, 0, 0] = _dot(ub, dsb, _NN).astype(dke_ref.dtype)
    dgamma_ref[0, 0, 0] = jnp.sum(st * dst, axis=0, keepdims=True)
    ds_ref[:] = (_dot(dob, q_ref[0, 0, 0], _TN) + gamma_ref[0, 0, 0] * dst
                 - _dot(dub, w_ref[0, 0, 0], _TN))


def _specs(shapes, at):
    return [pl.BlockSpec((1, 1, 1) + s[3:], at) for s in shapes]


def _scan_fwd(operands, interpret):
    w, u0, q_in, p, k_end, gamma = operands
    b, h, n, c, dk = w.shape
    dv = u0.shape[-1]
    at = lambda bi, hi, ni: (bi, hi, ni, 0, 0)
    out_shapes = [jax.ShapeDtypeStruct((b, h, n, c, dv), jnp.float32),
                  jax.ShapeDtypeStruct((b, h, n, dv, dk), jnp.float32)]
    return pl.pallas_call(
        _fwd_kernel, grid=(b, h, n),
        in_specs=_specs([x.shape for x in operands], at),
        out_specs=_specs([s.shape for s in out_shapes], at),
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="kda_chunk_fwd",
    )(*operands)


def _scan_bwd(operands, states, do, interpret):
    b, h, n, c, dk = operands[0].shape
    dv = do.shape[-1]
    back = lambda bi, hi, ni: (bi, hi, n - 1 - ni, 0, 0)  # the chunks in reverse
    ins = list(operands) + [states, do]
    # each gradient in its operand's dtype: what was rounded going in comes back rounded
    out_shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in operands]
    return pl.pallas_call(
        _bwd_kernel, grid=(b, h, n),
        in_specs=_specs([x.shape for x in ins], back),
        out_specs=_specs([s.shape for s in out_shapes], back),
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="kda_chunk_bwd",
    )(*ins)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(w, u0, q_in, p, k_end, gamma, interpret):
    return _scan_fwd((w, u0, q_in, p, k_end, gamma), interpret)[0]


def _scan_vjp_fwd(w, u0, q_in, p, k_end, gamma, interpret):
    operands = (w, u0, q_in, p, k_end, gamma)
    o, states = _scan_fwd(operands, interpret)
    return o, (operands, states)


def _scan_vjp_bwd(interpret, res, do):
    operands, states = res
    return tuple(_scan_bwd(operands, states, do, interpret))


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def kda_chunk(t: int, chunk: int = KDA_CHUNK) -> int:
    """The scan's chunk for ``t`` positions: ``chunk``, or a short sequence whole."""
    return min(chunk, t)


def kda(q, k, v, g, beta, lo, chunk: int = KDA_CHUNK, interpret: bool = False):
    """The gated delta rule over packed documents: q, k (B, T, H, Dk), v (B,
    T, H, Dv), ``g`` (B, T, H, Dk) the log decays (<= 0), ``beta`` (B, T, H),
    ``lo`` (B, T) int32 -> o (B, T, H, Dv) float32. See the module's
    docstring for the form, what runs where and what is rounded."""
    b, t, h, dk = q.shape
    chunk = kda_chunk(t, chunk)
    if t % chunk or chunk & (chunk - 1):
        raise ValueError(f"{t} positions are no whole chunks of {chunk}, a power of two")
    o = _scan(*_prepare(q, k, v, g, beta, lo, chunk, interpret), interpret)  # (B, H, N, C, Dv)
    return jnp.transpose(o, (0, 2, 3, 1, 4)).reshape(b, t, h, v.shape[-1])


def kda_recurrence(q, k, v, g, beta, lo):
    """The plain recurrence, position by position, float32 at ``highest``:
    what ``kda`` is held to in the tests and in ``chip_smoke.py``."""
    b, t, h, dk = q.shape
    at = jnp.arange(t, dtype=jnp.int32)

    def one(s, xs):
        qi, ki, vi, gi, bi, start = xs  # (B, H, D) ..., (B, H), (B,)
        s = jnp.where(start[:, None, None, None], 0.0, s)
        s = jnp.exp(gi)[..., None] * s
        read = jnp.einsum("bhkv,bhk->bhv", s, ki, precision=_HIGHEST)
        s = s + jnp.einsum("bhk,bhv->bhkv", ki, bi[..., None] * (vi - read), precision=_HIGHEST)
        return s, jnp.einsum("bhkv,bhk->bhv", s, qi, precision=_HIGHEST)

    first = jnp.moveaxis(lo == at[None, :], 1, 0)
    xs = tuple(jnp.moveaxis(x.astype(jnp.float32), 1, 0) for x in (q, k, v, g, beta)) + (first,)
    s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    return jnp.moveaxis(jax.lax.scan(one, s0, xs)[1], 0, 1)
