"""The grouped products of the experts a chip holds.

``rows`` (M, K) are sorted by group: the first ``sizes[0]`` rows belong to
group 0, the next ``sizes[1]`` to group 1, and so on; rows past ``sum(sizes)``
belong to none. The products themselves are ``jax.lax.ragged_dot`` and
``ragged_dot_general``, which the TPU's compiler lowers to a grouped matmul
kernel of its own over the live row tiles (Mosaic custom calls named
``ragged-dot``; 0.5 ms a live chunk's product at the cell's widths, PERF.md PR
33): the repo brings no kernel for them. What this module fixes is the
arithmetic, operands as given (bfloat16) in one pass whatever the process's
default matmul precision, sums and results float32, and the two
forms the expert layer's forward and hand-written backward need. (Left to
autodiff, the gradient of a bfloat16-operand product comes back rounded to
bfloat16, the weights' among them; and a transposed weight operand misses the
compiler's kernel.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# x (M, K) by dy (M, N), contracted over each group's rows -> (G, K, N)
_OVER_GROUP_ROWS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def grouped_matmul(rows, weights, sizes):
    """rows (M, K) x weights (G, K, N) by ``sizes`` (G,) int32 -> (M, N)
    float32: row i of group g meets ``weights[g]``; rows of no group give 0.
    For the product with the transposed weights pass them transposed, as an
    array of their own."""
    return jax.lax.ragged_dot(rows, weights, sizes, precision=jax.lax.Precision.DEFAULT,
                              preferred_element_type=jnp.float32)


def grouped_outer(rows, grads, sizes):
    """rows (M, K), grads (M, N) -> (G, K, N) float32: ``rows_g^T grads_g``
    over each group's rows, the weights' gradient of ``grouped_matmul``."""
    return jax.lax.ragged_dot_general(rows, grads, sizes, _OVER_GROUP_ROWS,
                                      precision=jax.lax.Precision.DEFAULT,
                                      preferred_element_type=jnp.float32)
