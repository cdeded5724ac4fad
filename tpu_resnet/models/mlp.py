"""One-hidden-layer softmax MLP — the reference's debug/sanity model
(reference logist_model.py:14-87, ``LRNet``).

Parity: flatten image → dense(hidden_units, trunc-normal std 1/image_size)
→ relu → dense(num_classes, trunc-normal std 1/sqrt(hidden)) → logits
(reference logist_model.py:36-59). The reference bakes softmax + clipped
log-loss into the graph; here the model returns logits and the loss lives in
the train step like every other model.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from tpu_resnet.models.resnet import image_dataset


class MLP(nn.Module):
    hidden_units: int = 100
    num_classes: int = 10
    image_size: int = 32
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        del train  # no BN/dropout — accepted for train-step API uniformity
        x = jnp.asarray(x, self.dtype)
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(
            self.hidden_units,
            kernel_init=nn.initializers.truncated_normal(1.0 / self.image_size),
            name="hidden")(x)
        x = nn.relu(x)
        x = nn.Dense(
            self.num_classes,
            kernel_init=nn.initializers.truncated_normal(
                1.0 / math.sqrt(self.hidden_units)),
            name="softmax_linear")(x)
        return jnp.asarray(x, jnp.float32)


# What models/__init__.py registers as the family ``mlp``.
def build(cfg) -> MLP:
    return MLP(hidden_units=cfg.model.mlp_hidden_units,
               num_classes=cfg.data.num_classes,
               image_size=cfg.data.resolved_image_size)


def spell(cfg):
    return image_dataset(cfg), "mlp"
