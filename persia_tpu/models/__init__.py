"""Dense-model zoo (flax). Every model takes the framework's standard inputs:

    model.apply(variables, non_id_features, embeddings, train=...)

where ``non_id_features`` is a list of (B, F) arrays and ``embeddings`` is a
list aligned with the batch's slot order: pooled slots contribute a (B, dim)
array; raw (sequence) slots contribute a ``(gathered, mask)`` pair with
``gathered`` (B, L, dim) and boolean ``mask`` (B, L). Models return logits
(loss applies the sigmoid — unlike the reference models which bake
``nn.Sigmoid`` into ``forward``, e.g.
`/root/reference/examples/src/adult-income/model.py:40`).

Four towers are no click models: mixture-of-experts sequence models over one
raw slot of token rows that state their own loss and outputs, on one shared
tower (``models/moe_tower.py``: norms, grouped-query attention up to its
kernel, the expert layer that is told which experts it holds, the scan over
periods of layers; a tower states its kinds of layer, their leaves, a leading
layer, the MLP and the router's law). ``SDARMoE`` (``models/sdar_moe.py``) trains by block
diffusion over ``[noised | clean]`` under the block-diffusion mask;
``MellumMoE`` (``models/mellum_moe.py``) trains causally over packed
documents, window and full layers in one period under RoPE tables of their
own, an int32 side input (each position's document start) in ``dense``, head
and loss in chunks of positions (``train_loss``). ``KimiLinearMoE``
(``models/kimi_linear_moe.py``) trains the same objective on the same batches
with gated delta-rule layers (a state a head, ``ops/delta_rule.py``) and
latent-attention layers (scores 192 wide, values 128), a leading dense layer,
a shared expert beside sigmoid-routed ones. ``JoyAIFlashMoE``
(``models/joyai_flash_moe.py``) has that latent attention in every layer
(``moe_tower.latent_attention``, shared with the ``kimi_linear`` family) with
a low-rank query and a rotation of the query's last and the shared key's
columns by the position inside the document, and trains a second objective:
a multi-token-prediction module after the scan reads the tower's normed
stream beside the next token's row (the gathered slot shifted by a position)
and goes through the tower's head a second time (``NextTokenTower.objectives``).
"""

from persia_tpu.models.dnn import DNN  # noqa: F401
from persia_tpu.models.dlrm import DLRM  # noqa: F401
from persia_tpu.models.deepfm import DeepFM  # noqa: F401
from persia_tpu.models.dcn import DCNv2  # noqa: F401
from persia_tpu.models.din import DIN  # noqa: F401
from persia_tpu.models.sdar_moe import SDARMoE  # noqa: F401
from persia_tpu.models.mellum_moe import MellumMoE  # noqa: F401
from persia_tpu.models.kimi_linear_moe import KimiLinearMoE  # noqa: F401
from persia_tpu.models.joyai_flash_moe import JoyAIFlashMoE  # noqa: F401
