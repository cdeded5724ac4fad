"""Model registry — replaces the reference's per-trainer hardcoded
cifar/imagenet dispatch (reference resnet_model.py:71-74) and the abandoned
config-driven registry sketch (reference models/__init__.py:1-21)."""

from __future__ import annotations

import jax.numpy as jnp

from tpu_resnet.models.mlp import MLP
from tpu_resnet.models.resnet import (
    ResNetV2,
    cifar_resnet_v2,
    imagenet_resnet_v2,
)

__all__ = [
    "MLP",
    "ResNetV2",
    "cifar_resnet_v2",
    "imagenet_resnet_v2",
    "build_model",
]


# The fused bottleneck family's verdict on the chip (v5e, jax 0.9.0,
# 2026-09-26, `tools/pallas_compile_smoke.py --family bottleneck`). Its
# switch stays, but on a TPU backend it raises with what the compiler
# said instead of training on kernels that do not build (ROADMAP C4).
_BOTTLENECK_REFUSAL = (
    "model.fused_blocks with a bottleneck ResNet (ImageNet rn50/101/152/"
    "200) does not build on a TPU backend yet: the forward kernels "
    "compile and match their reference at f=64/128/256, but Mosaic "
    "refuses the backward and the four-pass train backward at f=128 and "
    "f=256 (\"RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem "
    "while allocating on stack for %tpu_custom_call\" under the default "
    "16 MiB scoped limit), and the f=64 train backward compiles but "
    "disagrees with bottleneck_train_fwd_reference by 5.9e-2 (tolerance "
    "2e-2). Run with model.fused_blocks=false; see ROADMAP C4.")


def build_model(cfg):
    """Build the model from a ``RunConfig`` (tpu_resnet.config.RunConfig)."""
    dtype = jnp.dtype(cfg.model.compute_dtype)
    if cfg.model.name == "mlp":
        return MLP(hidden_units=cfg.model.mlp_hidden_units,
                   num_classes=cfg.data.num_classes,
                   image_size=cfg.data.resolved_image_size)
    if cfg.model.name != "resnet":
        raise ValueError(f"unknown model {cfg.model.name!r}")
    epilogue = getattr(cfg.model, "fused_epilogue", "off")
    if epilogue not in ("off", "on", "auto"):
        raise ValueError(f"model.fused_epilogue must be off|on|auto, "
                         f"got {epilogue!r}")
    if cfg.data.dataset == "imagenet":
        # fused_blocks: bottleneck sizes dispatch to the halo-tiled
        # kernel family (FusedBottleneckBlock; f=512 blocks stay XLA);
        # 18/34 basic blocks get VMEM-derived tile plans
        # (ops.fused_block.auto_batch_tile), with the planless 7²x512
        # stage likewise staying XLA.
        from tpu_resnet import ops
        from tpu_resnet.models.resnet import _IMAGENET_PARAMS

        if (cfg.model.fused_blocks and ops.is_tpu_backend()
                and _IMAGENET_PARAMS[cfg.model.resnet_size][0]):
            raise NotImplementedError(_BOTTLENECK_REFUSAL)
        return imagenet_resnet_v2(
            cfg.model.resnet_size, cfg.data.num_classes, dtype=dtype,
            stem_space_to_depth=cfg.model.stem_space_to_depth,
            remat=cfg.model.remat, fused_blocks=cfg.model.fused_blocks,
            fused_epilogue=epilogue)
    if cfg.model.fused_blocks and cfg.model.width_multiplier > 1:
        # Wide-ResNet channels (160/320/640 at WRN-28-10) put the default
        # tile far past core VMEM, and no A/B has measured those shapes —
        # fail loudly rather than ship an untested kernel configuration.
        raise ValueError("model.fused_blocks is only measured/tiled for "
                         "width_multiplier=1 (16/32/64-channel stages)")
    return cifar_resnet_v2(cfg.model.resnet_size, cfg.data.num_classes,
                           width_multiplier=cfg.model.width_multiplier,
                           dtype=dtype, remat=cfg.model.remat,
                           fused_blocks=cfg.model.fused_blocks,
                           fused_block_tile=cfg.model.fused_block_tile,
                           fused_epilogue=epilogue)
