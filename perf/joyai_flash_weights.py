"""The ``joyai_flash_moe`` tower's initial weights and token rows from the seed,
for the entry and the reference alike: the counter hash of ``perf/weights.py``,
uniform with deviation ``initial_deviation`` (0.02 where the configuration
states none: bound 0.02 sqrt 3) for every product, norm weights 1 (the
accepted files' law; a rehearsal's preset states a larger one, so that at a
width of 128 the attention's scores are no rounding of zero and a rotation
left out shows).

Leaves are named ``L<l>.<leaf>`` (``l`` from 0, as the family numbers its
layers: layer 0 is the leading one), ``mtp.<leaf>`` (the prediction module's
own leaves and its layer's), ``norm_f`` and ``head``; as the tower holds them
they lie under ``lead`` (a tuple of the leading layers' leaves), ``layers``
(the scanned layers' stacked along a first axis), ``after`` (a tuple: the
module's layer), ``mtp`` (``norm_e``, ``norm_h``, ``merge``, ``norm_s``) and
at the top.

**The router's law** (``router_law`` in the configuration) is
``perf/kimi_linear_weights.py``'s, whose docstring gives it and its reason:
``mirrored_copies`` (4 base columns; a share's slots j and j + 4 hold base j
plus and minus the share's own draw) or ``plain``.
"""

from __future__ import annotations

import numpy as np

from perf import weights
from perf.kimi_linear_weights import COPY_SPREAD, LAWS

TOP_LEAVES = ("norm_f", "head")
TABLE_STREAM = 0
MODULE = "mtp"
MODULE_LEAVES = ("norm_e", "norm_h", "merge", "norm_s")
# every leaf a block may hold, in the order that numbers its stream
LEAVES = ("norm1", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo", "norm2", "router", "gate",
          "up", "down", "shared_gate", "shared_up", "shared_down", "dense_gate", "dense_up", "dense_down",
          "router_copies") + MODULE_LEAVES
STREAMS_A_BLOCK = 32


def bound(config: dict) -> float:
    """The uniform law's bound: deviation x sqrt 3."""
    return float(config.get("initial_deviation", 0.02)) * float(np.sqrt(3.0))


def n_layers(config: dict) -> int:
    return int(config["num_hidden_layers"])


def has_module(config: dict) -> bool:
    return int(config["num_nextn_predict_layers"]) > 0


def layer_shapes(config: dict, mlp: str) -> dict:
    """A layer's dense leaves; the experts' carry the held experts first."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    nope, rope, hd = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    q_rank, rank = config["q_lora_rank"], config["kv_lora_rank"]
    out = {"norm1": (d,), "wq_a": (d, q_rank), "q_norm": (q_rank,), "wq_b": (q_rank, h * (nope + rope)),
           "wkv_a": (d, rank + rope), "kv_norm": (rank,), "wkv_b": (rank, h * (nope + hd)), "wo": (h * hd, d),
           "norm2": (d,)}
    if mlp == "dense":
        w = config["intermediate_size"]
        out.update(dense_gate=(d, w), dense_up=(d, w), dense_down=(w, d))
    else:
        e, f = config["n_routed_experts"], config["moe_intermediate_size"]
        out.update(router=(d, config["router_width"]), gate=(e, d, f), up=(e, d, f), down=(e, f, d),
                   shared_gate=(d, f), shared_up=(d, f), shared_down=(f, d))
    return out


def mlp_of(config: dict, l: int) -> str:
    return "dense" if l < int(config["first_k_dense_replace"]) else "shared_experts"


def module_shapes(config: dict) -> dict:
    """The module's own leaves and its layer's."""
    d = config["hidden_size"]
    return dict({"norm_e": (d,), "norm_h": (d,), "merge": (2 * d, d), "norm_s": (d,)},
                **layer_shapes(config, "shared_experts"))


def top_shapes(config: dict) -> dict:
    return {"norm_f": (config["hidden_size"],), "head": (config["hidden_size"], config["vocab_size"])}


def leaf_shapes(config: dict) -> dict:
    """Every dense leaf's shape by the name snapshots use."""
    out = {f"L{l}.{n}": s for l in range(n_layers(config))
           for n, s in layer_shapes(config, mlp_of(config, l)).items()}
    if has_module(config):
        out.update({f"{MODULE}.{n}": s for n, s in module_shapes(config).items()})
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
        stream, short = weights.DENSE_STREAM0 + TOP_LEAVES.index(name), name
    else:
        block, _, short = name.partition(".")
        at = n_layers(config) if block == MODULE else int(block[1:])  # the module numbers after the layers
        stream = weights.DENSE_STREAM0 + STREAMS_A_BLOCK * (at + 1) + LEAVES.index(short)
    if "norm" in short:
        return xp.ones(shape, xp.float32)
    if short == "router":
        law = config.get("router_law", LAWS[0])
        if law not in LAWS:
            raise ValueError(f"router_law {law!r}; known: {LAWS}")
        if law == "mirrored_copies":
            held = config["n_routed_experts"]
            half, shares = held // 2, shape[1] // held
            copies = stream - LEAVES.index("router") + LEAVES.index("router_copies")
            base = _uniform(seed, stream, (shape[0], 1, half), bound(config), xp)
            own = _uniform(seed, copies, (shape[0], shares * half), bound(config) * COPY_SPREAD, xp).reshape(
                shape[0], shares, half)
            return xp.concatenate([base + own, base - own], axis=2).reshape(shape)
    return _uniform(seed, stream, shape, bound(config), xp)


def token_rows(config: dict, seed, ids, xp=np):
    """Initial rows of the token table for ``ids``."""
    lo, hi = weights.seed_words(seed)
    return weights.hashed_uniform(lo, hi, TABLE_STREAM, ids, config["hidden_size"], bound(config), xp)


def dense_tree(config: dict, seed, xp=np) -> dict:
    """Every dense leaf from the seed as the tower holds them."""
    n, n_lead = n_layers(config), int(config["first_k_dense_replace"])
    make = lambda l: {m: leaf(config, seed, f"L{l}.{m}", xp) for m in layer_shapes(config, mlp_of(config, l))}
    scanned = [make(l) for l in range(n_lead, n)]
    out = {name: leaf(config, seed, name, xp) for name in TOP_LEAVES}
    out.update(lead=tuple(make(l) for l in range(n_lead)),
               layers={m: xp.stack([x[m] for x in scanned]) for m in scanned[0]})
    if has_module(config):
        mine = {m: leaf(config, seed, f"{MODULE}.{m}", xp) for m in module_shapes(config)}
        out.update(after=({m: x for m, x in mine.items() if m not in MODULE_LEAVES},),
                   mtp={m: mine[m] for m in MODULE_LEAVES})
    return out


def leaves_by_name(tree, config: dict) -> dict:
    """Host copies of such a tree's leaves by the names snapshots use."""
    n_lead = int(config["first_k_dense_replace"])
    out = {f"L{l}.{m}": np.asarray(x) for l, leaves in enumerate(tree["lead"]) for m, x in leaves.items()}
    for m, x in tree["layers"].items():
        host = np.asarray(x)
        for i in range(host.shape[0]):
            out[f"L{n_lead + i}.{m}"] = host[i]
    if has_module(config):
        for m, x in dict(tree["after"][0], **tree["mtp"]).items():
            out[f"{MODULE}.{m}"] = np.asarray(x)
    for name in TOP_LEAVES:
        out[name] = np.asarray(tree[name])
    return out


def zeros_by_name(config: dict) -> dict:
    """Adam's first moment before any step, by leaf name (``np.zeros`` pages
    are not resident until written)."""
    return {m: np.zeros(s, np.float32) for m, s in leaf_shapes(config).items()}
