"""The ``mellum_moe`` tower's initial weights and token rows from the seed:
``perf/sdar_weights.py``'s law (the counter hash of ``perf/weights.py``,
uniform with deviation 0.02, norm weights 1; the leaves have the same names
and the configuration the same keys), shared by the entry and the reference.

**The router's law is the configuration's to state** (``router_law``):
``repeated_columns`` is ``perf/sdar_weights.py``'s (one column a held expert,
repeated over the shares: here 16 columns in 4 copies, so that a token's 8
picks are the 4 copies of its two best columns and 2 of them fall on every
share: exactly the even router's load, whatever the seed); ``plain`` draws
every one of the 64 columns alone, like every other product. Both were run on
the chip before the cell was sized; ``PERF.md`` section 7 row 5 has the
readings and the configuration's ``assumed`` says which was taken and why.
"""

from __future__ import annotations

import numpy as np

from perf import sdar_weights, weights
from perf.sdar_weights import (  # noqa: F401  (the entry and the reference take them from here)
    BOUND, LAYER_LEAVES, TOP_LEAVES, layer_shapes, leaf_names, leaves_by_name, token_rows,
    top_shapes, zeros_by_name,
)

LAWS = ("repeated_columns", "plain")


def leaf(config: dict, seed, name: str, xp=np):
    """One dense leaf, float32, from the seed."""
    law = config.get("router_law", LAWS[0])
    if law not in LAWS:
        raise ValueError(f"router_law {law!r}; known: {LAWS}")
    if name.endswith("router") and law == "plain":
        shape = layer_shapes(config)["router"]
        stream = (weights.DENSE_STREAM0 + 16 * (int(name.partition(".")[0][1:]) + 1)
                  + LAYER_LEAVES.index("router"))
        lo, hi = weights.seed_words(seed)
        return weights.hashed_uniform(lo, hi, stream, xp.arange(shape[0]), shape[1], BOUND, xp)
    return sdar_weights.leaf(config, seed, name, xp)


def dense_tree(config: dict, seed, xp=np) -> dict:
    """Every dense leaf from the seed as the tower holds them: the layers'
    stacked along a first axis under ``layers``, the top's beside them."""
    n = int(config["num_hidden_layers"])
    layers = {name: xp.stack([leaf(config, seed, f"L{l}.{name}", xp) for l in range(n)])
              for name in LAYER_LEAVES}
    return dict({name: leaf(config, seed, name, xp) for name in TOP_LEAVES}, layers=layers)
