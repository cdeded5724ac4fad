"""Model registry — replaces the reference's per-trainer hardcoded
cifar/imagenet dispatch (reference resnet_model.py:71-74) and the abandoned
config-driven registry sketch (reference models/__init__.py:1-21)."""

from __future__ import annotations

import jax.numpy as jnp

from tpu_resnet.models.afmoe import Afmoe, Arch
from tpu_resnet.models.mlp import MLP
from tpu_resnet.models.resnet import (
    ResNetV2,
    cifar_resnet_v2,
    imagenet_resnet_v2,
)

__all__ = [
    "Afmoe",
    "MLP",
    "ResNetV2",
    "cifar_resnet_v2",
    "imagenet_resnet_v2",
    "build_model",
    "sample_input",
    "require_image_model",
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


def sample_input(cfg):
    """What a fresh state's weights are drawn on: one image of the data
    set's size or, for token data, one short sequence of ids (no leaf's
    shape depends on the length)."""
    if cfg.data.dataset == "tokens":
        return jnp.zeros((1, 8), jnp.int32)
    size = cfg.data.resolved_image_size
    return jnp.zeros((1, size, size, 3), jnp.float32)


def require_image_model(cfg, what: str) -> None:
    """Evaluation, serving and export carry image classifiers only: a
    token model has no evaluation split, no cache for its attention and
    no serving path (ROADMAP B-I)."""
    if cfg.data.dataset == "tokens" or cfg.model.name == "afmoe":
        raise NotImplementedError(
            f"{what} is not supported for a token model "
            f"(model.name={cfg.model.name!r}, data.dataset="
            f"{cfg.data.dataset!r}): only `train` carries it; evaluation "
            f"needs a held-out token split and serving a cache for "
            f"attention, and neither exists yet")


def build_model(cfg):
    """Build the model from a ``RunConfig`` (tpu_resnet.config.RunConfig)."""
    dtype = jnp.dtype(cfg.model.compute_dtype)
    if cfg.model.name == "afmoe":
        a = cfg.afmoe
        return Afmoe(Arch(
            layers=tuple(a.layers), hidden=a.hidden, heads=a.heads,
            kv_heads=a.kv_heads, head_dim=a.head_dim, window=a.window,
            dense_width=a.dense_width, expert_width=a.expert_width,
            experts_total=a.experts_total,
            experts_held=(a.experts_first, a.experts_held), top_k=a.top_k,
            shared=a.shared, vocab_rows=cfg.data.num_classes,
            rope_theta=a.rope_theta, eps=a.rms_eps,
            route_scale=a.route_scale, balance_coeff=a.balance_coeff,
            remat=cfg.model.remat, dtype=dtype))
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
