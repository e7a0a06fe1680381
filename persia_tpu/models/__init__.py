"""Dense-model zoo (flax). Every model takes the framework's standard inputs:

    model.apply(variables, non_id_features, embeddings, train=...)

where ``non_id_features`` is a list of (B, F) arrays and ``embeddings`` is a
list aligned with the batch's slot order: pooled slots contribute a (B, dim)
array; raw (sequence) slots contribute a ``(gathered, mask)`` pair with
``gathered`` (B, L, dim) and boolean ``mask`` (B, L). Models return logits
(loss applies the sigmoid — unlike the reference models which bake
``nn.Sigmoid`` into ``forward``, e.g.
`/root/reference/examples/src/adult-income/model.py:40`).

``SDARMoE`` is the one tower that is no click model: a block-diffusion
mixture-of-experts transformer over one raw slot of token rows, which states
its own loss and outputs (``models/sdar_moe.py``).
"""

from persia_tpu.models.dnn import DNN  # noqa: F401
from persia_tpu.models.dlrm import DLRM  # noqa: F401
from persia_tpu.models.deepfm import DeepFM  # noqa: F401
from persia_tpu.models.dcn import DCNv2  # noqa: F401
from persia_tpu.models.din import DIN  # noqa: F401
from persia_tpu.models.sdar_moe import SDARMoE  # noqa: F401
