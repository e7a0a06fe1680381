"""The ``kimi_linear_moe`` tower's initial weights and token rows from the seed,
for the entry and the reference alike: the counter hash of ``perf/weights.py``,
uniform with deviation 0.02 (bound 0.02 sqrt 3) for every product, norm
weights 1, and for the leaves the family adds, the public implementation's
laws in a form that can be recomputed:

- ``conv_q``, ``conv_k``, ``conv_v`` (taps, channels): uniform in +-1/sqrt(taps),
  a depthwise convolution's default;
- ``a_log`` (a head): log of a value uniform in [1, 16);
- ``dt_bias`` (a channel): the inverse softplus of ``dt``, ``log dt`` uniform
  in [log 0.001, log 0.1): a step's log decay starts between -0.001 and -1.6.

Leaves are named ``L<l>.<leaf>`` (``l`` from 0: the leading layer), ``norm_f``
and ``head``; as the tower holds them they lie under ``lead`` (a tuple of the
leading layers' leaves), ``layers[kind]`` (each kind's layers stacked along a
first axis, in their order) and at the top.

**The router's law** (``router_law`` in the configuration): 256 columns over
32 shares of 8 cannot be ``repeated_columns`` as the accepted towers' are (a
token's 8 picks would be 8 of 32 exact ties). Column e is share ``e // held``'s
slot ``e % held``. ``mirrored_copies``: 4 base columns; a share's slots j and
j + 4 hold base j plus and minus the share's own draw (at ``COPY_SPREAD`` of
the base's deviation), so a token's 8 picks are the 8 shares
whose draw has the largest product with its residual, whatever the sign: what
all tokens have in common (a third of the router's input on the chip, and it
shifts every copy's odds) moves a share's load only in the second order.
``plain`` draws every column alone. ``PERF.md`` section 7 row 5 has the
readings.
"""

from __future__ import annotations

import numpy as np

from perf import weights

BOUND = 0.02 * float(np.sqrt(3.0))
TOP_LEAVES = ("norm_f", "head")
TABLE_STREAM = 0
LAWS = ("mirrored_copies", "plain")
COPY_SPREAD = 0.25  # a share's own draw against the base column's deviation
KDA, MLA = "kda", "mla"
# every leaf a layer may hold, in the order that numbers its stream
LEAVES = ("norm1", "wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "wf_a", "wf_b", "a_log", "dt_bias",
          "wb", "o_norm", "wg_a", "wg_b", "wkv_a", "kv_norm", "wkv_b", "wo", "norm2", "router", "gate",
          "up", "down", "shared_gate", "shared_up", "shared_down", "dense_gate", "dense_up", "dense_down",
          "router_copies")
STREAMS_A_LAYER = 32


def layer_kinds(config: dict) -> list:
    """``(attention kind, mlp)`` of each layer held, layer 1 first."""
    lin = config["linear_attn_config"]
    return [(KDA if l in lin["kda_layers"] else MLA,
             "dense" if l <= int(config["first_k_dense_replace"]) else "shared_experts")
            for l in range(1, int(config["num_hidden_layers"]) + 1)]


def layer_shapes(config: dict, kind: str, mlp: str) -> dict:
    """A layer's dense leaves; the experts' carry the held experts first."""
    d, h, hd = config["hidden_size"], config["num_attention_heads"], config["v_head_dim"]
    lin = config["linear_attn_config"]
    r, taps = lin["head_dim"], lin["short_conv_kernel_size"]
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    if kind == MLA:
        out = {"norm1": (d,), "wq": (d, h * (config["qk_nope_head_dim"] + rope)), "wkv_a": (d, rank + rope),
               "kv_norm": (rank,), "wkv_b": (rank, h * (config["qk_nope_head_dim"] + hd)), "wo": (h * hd, d)}
    else:
        out = {"norm1": (d,), "wq": (d, h * hd), "wk": (d, h * hd), "wv": (d, h * hd),
               "conv_q": (taps, h * hd), "conv_k": (taps, h * hd), "conv_v": (taps, h * hd),
               "wf_a": (d, r), "wf_b": (r, h * hd), "a_log": (h,), "dt_bias": (h * hd,), "wb": (d, h),
               "o_norm": (hd,), "wg_a": (d, r), "wg_b": (r, h * hd), "wo": (h * hd, d)}
    out["norm2"] = (d,)
    if mlp == "dense":
        w = config["intermediate_size"]
        out.update(dense_gate=(d, w), dense_up=(d, w), dense_down=(w, d))
    else:
        e, f = config["num_experts"], config["moe_intermediate_size"]
        out.update(router=(d, config["router_width"]), gate=(e, d, f), up=(e, d, f), down=(e, f, d),
                   shared_gate=(d, f), shared_up=(d, f), shared_down=(f, d))
    return out


def top_shapes(config: dict) -> dict:
    return {"norm_f": (config["hidden_size"],), "head": (config["hidden_size"], config["vocab_size"])}


def leaf_shapes(config: dict) -> dict:
    """Every dense leaf's shape by the name snapshots use."""
    out = {f"L{l}.{n}": s for l, (kind, mlp) in enumerate(layer_kinds(config))
           for n, s in layer_shapes(config, kind, mlp).items()}
    out.update(top_shapes(config))
    return out


def leaf_names(config: dict) -> list:
    return list(leaf_shapes(config))


def _uniform(seed, stream, shape, bound, xp):
    lo, hi = weights.seed_words(seed)
    rows = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
    return weights.hashed_uniform(lo, hi, stream, xp.arange(rows), shape[-1], bound, xp).reshape(shape)


def leaf(config: dict, seed, name: str, xp=np):
    """One dense leaf, float32, from the seed."""
    shape = leaf_shapes(config)[name]
    if name in TOP_LEAVES:
        stream = weights.DENSE_STREAM0 + TOP_LEAVES.index(name)
        short = name
    else:
        layer, _, short = name.partition(".")
        stream = weights.DENSE_STREAM0 + STREAMS_A_LAYER * (int(layer[1:]) + 1) + LEAVES.index(short)
    if "norm" in short:
        return xp.ones(shape, xp.float32)
    if short.startswith("conv_"):
        return _uniform(seed, stream, shape, 1.0 / float(np.sqrt(shape[0])), xp)
    if short == "a_log":
        return xp.log(8.5 + 7.5 * _uniform(seed, stream, shape, 1.0, xp))
    if short == "dt_bias":
        mid, half = 0.5 * (np.log(0.1) + np.log(0.001)), 0.5 * (np.log(0.1) - np.log(0.001))
        dt = xp.exp(xp.float32(mid) + xp.float32(half) * _uniform(seed, stream, shape, 1.0, xp))
        return dt + xp.log(-xp.expm1(-dt))
    if short == "router":
        law = config.get("router_law", LAWS[0])
        if law not in LAWS:
            raise ValueError(f"router_law {law!r}; known: {LAWS}")
        held = config["num_experts"]
        copies = stream - LEAVES.index("router") + LEAVES.index("router_copies")
        if law == "mirrored_copies":
            half, shares = held // 2, shape[1] // held
            base = _uniform(seed, stream, (shape[0], 1, half), BOUND, xp)
            own = _uniform(seed, copies, (shape[0], shares * half), BOUND * COPY_SPREAD, xp).reshape(shape[0], shares, half)
            return xp.concatenate([base + own, base - own], axis=2).reshape(shape)
    return _uniform(seed, stream, shape, BOUND, xp)


def token_rows(config: dict, seed, ids, xp=np):
    """Initial rows of the token table for ``ids``."""
    lo, hi = weights.seed_words(seed)
    return weights.hashed_uniform(lo, hi, TABLE_STREAM, ids, config["hidden_size"], BOUND, xp)


def dense_tree(config: dict, seed, xp=np) -> dict:
    """Every dense leaf from the seed as the tower holds them."""
    kinds = layer_kinds(config)
    n_lead = int(config["first_k_dense_replace"])
    make = lambda l: {n: leaf(config, seed, f"L{l}.{n}", xp) for n in layer_shapes(config, *kinds[l])}
    layers = {}
    for kind in {k for k, _ in kinds[n_lead:]}:
        mine = [make(l) for l in range(n_lead, len(kinds)) if kinds[l][0] == kind]
        layers[kind] = {n: xp.stack([m[n] for m in mine]) for n in mine[0]}
    out = {name: leaf(config, seed, name, xp) for name in TOP_LEAVES}
    return dict(out, lead=tuple(make(l) for l in range(n_lead)), layers=layers)


def leaves_by_name(tree, config: dict) -> dict:
    """Host copies of such a tree's leaves by the names snapshots use."""
    kinds = layer_kinds(config)
    n_lead = int(config["first_k_dense_replace"])
    out = {f"L{l}.{n}": np.asarray(x) for l, leaves in enumerate(tree["lead"]) for n, x in leaves.items()}
    for kind, stacked in tree["layers"].items():
        where = [l for l in range(n_lead, len(kinds)) if kinds[l][0] == kind]
        for n, x in stacked.items():
            host = np.asarray(x)
            for i, l in enumerate(where):
                out[f"L{l}.{n}"] = host[i]
    for name in TOP_LEAVES:
        out[name] = np.asarray(tree[name])
    return out


def zeros_by_name(config: dict) -> dict:
    """Adam's first moment before any step, by leaf name (``np.zeros`` pages
    are not resident until written)."""
    return {n: np.zeros(s, np.float32) for n, s in leaf_shapes(config).items()}
