"""On-device, jit-compatible data augmentation.

The reference augments on the host inside 16 queue-runner threads
(reference cifar_input.py:70-100). On TPU the idiomatic split is: the host
streams raw uint8 batches; augmentation runs *inside the compiled train step*
on the VPU, fused by XLA with the rest of the step. That removes the host
CPU from the per-step critical path entirely.

CIFAR semantics match reference cifar_input.py:70-79 exactly:
pad to 36×36 (symmetric — resize_image_with_crop_or_pad(36,36) pads 2 px per
side), random 32×32 crop, random horizontal flip, per-image standardization
with TF's ``adjusted_stddev = max(std, 1/sqrt(num_elements))``. Crop and
flip are selects over the whole batch, so the step holds no per-image
operation.

ImageNet device-side ops cover the tail of the VGG pipeline: random flip and
mean subtraction (reference vgg_preprocessing.py:284-314; the RGB means are
divided by 255 because images arrive as floats in [0,1],
vgg_preprocessing.py:37-39). Decode/resize/crop are host-side
(tpu_resnet.data.imagenet) since JPEG sizes are dynamic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Reference vgg_preprocessing.py:37-39 — means already divided by 255.
VGG_MEANS_01 = (123.68 / 255.0, 116.78 / 255.0, 103.94 / 255.0)


def per_image_standardization(images: jnp.ndarray) -> jnp.ndarray:
    """tf.image.per_image_standardization over a batch
    (reference cifar_input.py:79, :91)."""
    images = images.astype(jnp.float32)
    n = images[0].size
    mean = jnp.mean(images, axis=(1, 2, 3), keepdims=True)
    std = jnp.std(images, axis=(1, 2, 3), keepdims=True)
    adjusted = jnp.maximum(std, 1.0 / jnp.sqrt(jnp.float32(n)))
    return (images - mean) / adjusted


def _random_crop_batch(rng: jax.Array, images: jnp.ndarray,
                       pad: int) -> jnp.ndarray:
    """Pad symmetrically then take a per-image random crop of original size.

    Each axis is a batch-wide select among its ``2*pad+1`` static shifts:
    elementwise along the batch, so XLA fuses it (no gather, no per-image
    ``dynamic-update-slice``) and it shards over ``data`` with no collective.
    A select copies values, so the rows are the bits a per-image
    ``dynamic_slice`` at ``(off_h, off_w)`` gives.
    """
    b, h, w, _ = images.shape
    padded = jnp.pad(images, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    rng_h, rng_w = jax.random.split(rng)
    off_h = jax.random.randint(rng_h, (b,), 0, 2 * pad + 1)
    off_w = jax.random.randint(rng_w, (b,), 0, 2 * pad + 1)
    off_h = off_h[:, None, None, None]
    off_w = off_w[:, None, None, None]

    rows = padded[:, 0:h]
    for k in range(1, 2 * pad + 1):
        rows = jnp.where(off_h == k, padded[:, k:k + h], rows)
    out = rows[:, :, 0:w]
    for k in range(1, 2 * pad + 1):
        out = jnp.where(off_w == k, rows[:, :, k:k + w], out)
    return out


def _random_flip_batch(rng: jax.Array, images: jnp.ndarray) -> jnp.ndarray:
    b = images.shape[0]
    flip = jax.random.bernoulli(rng, 0.5, (b, 1, 1, 1))
    return jnp.where(flip, images[:, :, ::-1, :], images)


def cifar_train_augment(rng: jax.Array, images: jnp.ndarray) -> jnp.ndarray:
    """uint8 [B,32,32,3] → standardized float32, training path
    (reference cifar_input.py:70-79: crop_or_pad 36 → random_crop 32 → flip →
    standardize)."""
    rng_crop, rng_flip = jax.random.split(rng)
    images = images.astype(jnp.float32)
    images = _random_crop_batch(rng_crop, images, pad=2)
    images = _random_flip_batch(rng_flip, images)
    return per_image_standardization(images)


def cifar_eval_preprocess(images: jnp.ndarray) -> jnp.ndarray:
    """Eval path: standardization only (reference cifar_input.py:87-91)."""
    return per_image_standardization(images)


def imagenet_train_augment(rng: jax.Array, images: jnp.ndarray) -> jnp.ndarray:
    """uint8 [B,224,224,3] (already random-resized+cropped on host) →
    flip + mean-subtract, in [0,1] scale (vgg_preprocessing.py:284-314)."""
    images = images.astype(jnp.float32) / 255.0
    images = _random_flip_batch(rng, images)
    return images - jnp.asarray(VGG_MEANS_01).reshape(1, 1, 1, 3)


def imagenet_eval_preprocess(images: jnp.ndarray) -> jnp.ndarray:
    """Host already did aspect-preserving resize + central crop
    (vgg_preprocessing.py:317-333)."""
    images = images.astype(jnp.float32) / 255.0
    return images - jnp.asarray(VGG_MEANS_01).reshape(1, 1, 1, 3)


def get_augment_fns(dataset: str):
    """(train_augment(rng, imgs), eval_preprocess(imgs)) for a dataset."""
    if dataset == "imagenet":
        return imagenet_train_augment, imagenet_eval_preprocess
    if dataset in ("cifar10", "cifar100", "synthetic"):
        return cifar_train_augment, cifar_eval_preprocess
    raise ValueError(f"unknown dataset {dataset!r}")
