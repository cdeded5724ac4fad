"""Plain reference for the benchmark's training cells: pre-activation
ResNet-v2 (He et al. 2016, "Identity Mappings in Deep Residual Networks";
Zagoruyko & Komodakis 2016 for the wide basic-block variant), its loss, its
gradients and the momentum-SGD update, in straightforward ``jax.numpy``.

Imports nothing of ``tpu_resnet``. Everything is float32 with matrix
products at ``highest`` precision (on a TPU a float32 product is otherwise
run in bf16 passes). The architecture, the optimizer and the preprocessing
come from the benchmark's configuration file; parameters are a flat dict
``{"block_layer1/block0/conv1/conv/kernel": array, ...}``.

``quantize="fp8"`` is the control of the comparison that decides
``correct`` (benchmarks/lib/check.py): the same mathematics computed in
float8_e4m3, the nearest precision below the bf16 the configurations
state. As the program keeps every activation in bf16, the control keeps
every activation in fp8: the inputs and the output of every convolution and
of the dense layer, every BN+ReLU output and every residual sum are rounded
(per tensor scaled to the format's range, straight-through gradient).
``quantize="bf16"`` rounds the same places to bfloat16, the precision the
configurations state: the reference's own picture of what the program's
rounding alone costs, for the look into a number that reads far off
(PERF.md); it decides nothing.

Departures from the published description, all shared with the system
under test because they define the job and not the implementation:
BN momentum 0.997 and epsilon 1e-5, L2 penalty ``wd * sum(w**2)/2`` over
every trainable leaf added to the loss, explicit (k-1)//2 padding on
strided convolutions, global mean pooling before the dense layer.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
VGG_MEANS_01 = (123.68 / 255.0, 116.78 / 255.0, 103.94 / 255.0)
FP8_MAX = 448.0  # largest finite float8_e4m3fn


# ------------------------------------------------------------ quantization
def _fp8_round(x):
    """Round to float8_e4m3 after scaling the tensor's largest magnitude
    onto the format's largest number; gradient passes straight through."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


def _bf16_round(x):
    q = x.astype(jnp.bfloat16).astype(jnp.float32)
    return x + lax.stop_gradient(q - x)


_ROUNDERS = {"none": lambda x: x, "fp8": _fp8_round, "bf16": _bf16_round}


# ------------------------------------------------------------------ layers
def _conv(x, w, stride: int, rnd):
    k = w.shape[0]
    if stride > 1:
        beg = (k - 1) // 2
        padding = [(beg, k - 1 - beg)] * 2
    else:
        padding = "SAME"
    return rnd(lax.conv_general_dilated(
        rnd(x), rnd(w), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST))


def _bn_relu(x, params, stats, new_stats, name: str, arch, rnd):
    scale, bias = params[f"{name}/bn/scale"], params[f"{name}/bn/bias"]
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    m = arch["bn_momentum"]
    new_stats[f"{name}/bn/mean"] = (m * stats[f"{name}/bn/mean"]
                                    + (1 - m) * mean)
    new_stats[f"{name}/bn/var"] = m * stats[f"{name}/bn/var"] + (1 - m) * var
    y = (x - mean) * lax.rsqrt(var + arch["bn_epsilon"]) * scale + bias
    return rnd(jnp.maximum(y, 0.0))


def _block(x, params, stats, name: str, filters: int, stride: int,
           project: bool, arch, rnd):
    """One pre-activation residual block; returns (y, new stats of its
    BN sites). The projection shortcut convolves the pre-activated input."""
    new_stats: Dict[str, jnp.ndarray] = {}
    bottleneck = arch["block"] == "bottleneck"
    shortcut = x
    x = _bn_relu(x, params, stats, new_stats, f"{name}/preact", arch, rnd)
    if project:
        shortcut = _conv(x, params[f"{name}/proj/conv/kernel"], stride, rnd)
    if bottleneck:
        x = _conv(x, params[f"{name}/conv1/conv/kernel"], 1, rnd)
        x = _bn_relu(x, params, stats, new_stats, f"{name}/bnrelu1", arch, rnd)
        x = _conv(x, params[f"{name}/conv2/conv/kernel"], stride, rnd)
        x = _bn_relu(x, params, stats, new_stats, f"{name}/bnrelu2", arch, rnd)
        x = _conv(x, params[f"{name}/conv3/conv/kernel"], 1, rnd)
    else:
        x = _conv(x, params[f"{name}/conv1/conv/kernel"], stride, rnd)
        x = _bn_relu(x, params, stats, new_stats, f"{name}/bnrelu1", arch, rnd)
        x = _conv(x, params[f"{name}/conv2/conv/kernel"], 1, rnd)
    return rnd(x + shortcut), new_stats


def forward(params, stats, images, arch, quantize: str = "none"
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Training-mode forward pass: float32 logits and the new BN running
    statistics. ``images`` are preprocessed floats, NHWC."""
    rnd = _ROUNDERS[quantize]
    new_stats: Dict[str, jnp.ndarray] = {}
    x = images.astype(jnp.float32)
    stem_w = params["initial_conv/conv/kernel"]
    if arch["stem"] == "imagenet":
        x = _conv(x, stem_w, 2, rnd)  # 7x7/2, padding (3, 3)
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    else:
        x = _conv(x, stem_w, 1, rnd)  # 3x3/1
    for i, (f, n, s) in enumerate(zip(arch["stage_filters"],
                                      arch["stage_blocks"],
                                      arch["stage_strides"])):
        for j in range(n):
            name = f"block_layer{i + 1}/block{j}"
            # Rematerialized block by block, so that the float32
            # activations of the whole batch fit beside nothing else.
            blk = jax.checkpoint(functools.partial(
                _block, name=name, filters=f, stride=s if j == 0 else 1,
                project=j == 0, arch=arch, rnd=rnd))
            sub_p = {k: v for k, v in params.items()
                     if k.startswith(name + "/")}
            sub_s = {k: v for k, v in stats.items()
                     if k.startswith(name + "/")}
            x, blk_stats = blk(x, sub_p, sub_s)
            new_stats.update(blk_stats)
    x = _bn_relu(x, params, stats, new_stats, "final_bnrelu", arch, rnd)
    x = jnp.mean(x, axis=(1, 2))
    logits = jnp.dot(rnd(x), rnd(params["final_dense/kernel"]),
                     precision=HIGHEST) + params["final_dense/bias"]
    return logits, new_stats


# ----------------------------------------------------------- preprocessing
def preprocess(kind: str, rng, images):
    """The on-device half of the input pipeline, as the job states it.
    ``imagenet_flip_meansub``: [0,1] scale, random horizontal flip, VGG
    mean subtraction. ``cifar_crop_flip_standardize``: pad 2, random 32x32
    crop, random flip, per-image standardization."""
    x = images.astype(jnp.float32)
    b = x.shape[0]
    if kind == "imagenet_flip_meansub":
        x = x / 255.0
        flip = jax.random.bernoulli(rng, 0.5, (b, 1, 1, 1))
        x = jnp.where(flip, x[:, :, ::-1, :], x)
        return x - jnp.asarray(VGG_MEANS_01).reshape(1, 1, 1, 3)
    if kind == "cifar_crop_flip_standardize":
        rng_crop, rng_flip = jax.random.split(rng)
        _, h, w, c = x.shape
        padded = jnp.pad(x, ((0, 0), (2, 2), (2, 2), (0, 0)))
        rng_h, rng_w = jax.random.split(rng_crop)
        off_h = jax.random.randint(rng_h, (b,), 0, 5)
        off_w = jax.random.randint(rng_w, (b,), 0, 5)
        x = jax.vmap(lambda im, oh, ow: lax.dynamic_slice(
            im, (oh, ow, 0), (h, w, c)))(padded, off_h, off_w)
        flip = jax.random.bernoulli(rng_flip, 0.5, (b, 1, 1, 1))
        x = jnp.where(flip, x[:, :, ::-1, :], x)
        n = h * w * c
        mean = jnp.mean(x, axis=(1, 2, 3), keepdims=True)
        std = jnp.std(x, axis=(1, 2, 3), keepdims=True)
        return (x - mean) / jnp.maximum(std, 1.0 / jnp.sqrt(jnp.float32(n)))
    raise ValueError(f"unknown preprocessing {kind!r}")


# ------------------------------------------------------------ loss and step
def learning_rate(job, step):
    lr = job["lr"]
    if lr["kind"] == "constant":
        return jnp.float32(lr["value"])
    if lr["kind"] == "linear_warmup":
        frac = jnp.minimum(step, lr["steps"]) / lr["steps"]
        return jnp.float32(lr["init"] + (lr["peak"] - lr["init"]) * frac)
    raise ValueError(f"unknown learning-rate rule {lr['kind']!r}")


def loss_fn(params, stats, images, labels, arch, job, quantize="none"):
    logits, new_stats = forward(params, stats, images, arch, quantize)
    logp = jax.nn.log_softmax(logits, axis=-1)
    xent = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    l2 = sum(jnp.sum(jnp.square(v)) for v in params.values()) / 2
    return xent + job["weight_decay"] * l2, new_stats


def train_step(carry, batch, step_rng, arch, job, quantize="none"):
    """One step of momentum SGD (heavy ball, as optax.sgd: ``m = g +
    mu*m; p -= lr(step)*m``). The per-step preprocessing key is
    ``fold_in(step_rng, step)``."""
    params, stats, mom, step = carry
    images, labels = batch
    x = preprocess(job["preprocess"], jax.random.fold_in(step_rng, step),
                   images)
    (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, stats, x, labels, arch, job, quantize)
    mu = job["momentum"]
    lr = learning_rate(job, step)
    new_mom = {k: grads[k] + mu * mom[k] for k in params}
    new_params = {k: params[k] - lr * new_mom[k] for k in params}
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    return (new_params, new_stats, new_mom, step + 1), (loss, gnorm)


def follow(params, stats, mom, images, labels, arch, job, seed: int,
           quantize: str = "none", start_step: int = 0):
    """Follow ``len(images)`` steps from ``(params, stats, mom)`` at step
    ``start_step`` on the staged feed ``images[k], labels[k]``. Returns the
    final ``(params, stats, mom)`` and per-step ``losses, grad_norms``.

    ``seed`` derives the preprocessing keys the way the job states:
    ``step_rng = split(PRNGKey(seed))[1]``. One step is one jitted call,
    so that only a step's activations are live at a time."""
    step_rng = jax.random.split(jax.random.PRNGKey(seed))[1]
    # the key is an argument, not a constant of the compiled step: one
    # compiled program serves every seed
    step_fn = jax.jit(functools.partial(
        train_step, arch=arch, job=job, quantize=quantize),
        donate_argnums=(0,))
    carry = (params, stats, mom, jnp.asarray(start_step, jnp.int32))
    losses, gnorms = [], []
    for k in range(len(images)):
        carry, (loss, gnorm) = step_fn(carry, (images[k], labels[k]),
                                       step_rng)
        losses.append(float(loss))
        gnorms.append(float(gnorm))
    return carry[0], carry[1], carry[2], losses, gnorms
