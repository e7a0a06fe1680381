"""The ``sdar_moe`` tower's initial weights and token rows from the seed, by
the counter hash of ``perf/weights.py``: the entry fills the program's state
with them on the device in one jitted call, the reference makes the same
values for itself. Uniform with the deviation 0.02 that the family
initialises its products with (bound 0.02 * sqrt(3)); norm weights are 1.

**The router starts with one column a held expert, repeated over the shares**
(column e equals column e mod ``num_experts``; with 16 of 128 held, 8 copies).
At initialisation experts are exchangeable, and a random tower's residual
stream is one common direction (the mean over the context that attention
writes is 17 times the token rows' deviation: every token then picks the same
8 experts, and how many of those 8 a chip holds, 0 to 3, is the seed's luck:
28 to 32 steps in 20 s on two seeds, my chip runs, PR 33). With the columns
repeated, a token's 8 picks are the 8 copies of its best column, one on every
share: each share gets exactly the load an even router sends it (k x held / E
picks a token), on every seed, which is what a deployment's balanced placement
aims at and what the cell is sized on. The copies receive different gradients
and part from the first step on.
"""

from __future__ import annotations

import numpy as np

from perf import weights

BOUND = 0.02 * float(np.sqrt(3.0))
LAYER_LEAVES = ("norm1", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "norm2", "router",
                "gate", "up", "down")
TOP_LEAVES = ("norm_f", "head")
TABLE_STREAM = 0


def layer_shapes(config: dict) -> dict:
    """A layer's dense leaves; the experts' carry the held experts first."""
    d, hd = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    e, f = config["num_experts"], config["moe_intermediate_size"]
    return {"norm1": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv), "q_norm": (hd,),
            "k_norm": (hd,), "wo": (q, d), "norm2": (d,), "router": (d, config["router_width"]),
            "gate": (e, d, f), "up": (e, d, f), "down": (e, f, d)}


def top_shapes(config: dict) -> dict:
    return {"norm_f": (config["hidden_size"],), "head": (config["hidden_size"], config["vocab_size"])}


def leaf_names(config: dict):
    """Every dense leaf by the name snapshots use: ``L<l>.<leaf>``, then the top's."""
    return [f"L{l}.{n}" for l in range(config["num_hidden_layers"]) for n in LAYER_LEAVES] + list(TOP_LEAVES)


def leaf(config: dict, seed, name: str, xp=np):
    """One dense leaf, float32, from the seed."""
    if name in TOP_LEAVES:
        shape, stream = top_shapes(config)[name], weights.DENSE_STREAM0 + TOP_LEAVES.index(name)
    else:
        layer, _, short = name.partition(".")
        shape = layer_shapes(config)[short]
        stream = weights.DENSE_STREAM0 + 16 * (int(layer[1:]) + 1) + LAYER_LEAVES.index(short)
    if "norm" in name:
        return xp.ones(shape, xp.float32)
    lo, hi = weights.seed_words(seed)
    if name.endswith("router"):
        # one column a held expert, repeated over the shares (see the module's docstring)
        held = config["num_experts"]
        base = weights.hashed_uniform(lo, hi, stream, xp.arange(shape[0]), held, BOUND, xp)
        return xp.tile(base, (1, shape[1] // held))
    rows = int(np.prod(shape[:-1]))
    return weights.hashed_uniform(lo, hi, stream, xp.arange(rows), shape[-1], BOUND, xp).reshape(shape)


def token_rows(config: dict, seed, ids, xp=np):
    """Initial rows of the token table for ``ids``."""
    lo, hi = weights.seed_words(seed)
    return weights.hashed_uniform(lo, hi, TABLE_STREAM, ids, config["hidden_size"], BOUND, xp)


def dense_tree(config: dict, seed, xp=np) -> dict:
    """Every dense leaf from the seed as the tower holds them: the layers'
    stacked along a first axis under ``layers``, the top's beside them."""
    n = int(config["num_hidden_layers"])
    layers = {name: xp.stack([leaf(config, seed, f"L{l}.{name}", xp) for l in range(n)])
              for name in LAYER_LEAVES}
    return dict({name: leaf(config, seed, name, xp) for name in TOP_LEAVES}, layers=layers)


def leaves_by_name(tree) -> dict:
    """Host copies of such a tree's leaves by the names snapshots use:
    ``L<l>.<leaf>``, ``norm_f``, ``head``."""
    out = {}
    for name, stacked in tree["layers"].items():
        host = np.asarray(stacked)
        for l in range(host.shape[0]):
            out[f"L{l}.{name}"] = host[l]
    for name in TOP_LEAVES:
        out[name] = np.asarray(tree[name])
    return out


def zeros_by_name(config: dict) -> dict:
    """Adam's first moment before any step, by leaf name: zeros the host
    never has to hold (``np.zeros`` pages are not resident until written),
    where a copy from the device would be 2.4 GB of them at the cell's size."""
    shapes = dict(layer_shapes(config))
    out = {f"L{l}.{n}": np.zeros(shapes[n], np.float32)
           for l in range(int(config["num_hidden_layers"])) for n in LAYER_LEAVES}
    out.update({n: np.zeros(shape, np.float32) for n, shape in top_shapes(config).items()})
    return out
