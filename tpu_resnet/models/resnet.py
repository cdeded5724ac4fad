"""Pre-activation ResNet-v2 in Flax — TPU-native rebuild of the reference
model (reference: resnet_model_official.py).

Parity notes (reference file:line):
- BatchNorm momentum 0.997, epsilon 1e-5, scale+center
  (resnet_model_official.py:37-48). TF ``fused=True`` is irrelevant here —
  XLA:TPU fuses BN into neighboring ops automatically.
- ``fixed_padding`` for strided convs: explicit (k-1)//2 padding so the
  padding depends only on kernel size, not input size
  (resnet_model_official.py:53-91).
- Building block / bottleneck block with BN+ReLU *before* convs and the
  projection shortcut taken from the pre-activated input
  (resnet_model_official.py:94-175).
- CIFAR generator: 6n+2 sizing (``resnet_size % 6 == 2``), 3×3/1 stem with
  16 filters, three stages 16/32/64 with strides 1/2/2, final BN+ReLU +
  global average pool + dense (resnet_model_official.py:217-278).
- ImageNet generator: 7×7/2 stem with 64 filters + 3×3/2 'SAME' max-pool,
  four stages 64/128/256/512 with strides 1/2/2/2, sizes
  18/34/50/101/152/200 (resnet_model_official.py:281-366).
- Conv init: variance_scaling(scale=1.0, fan_in, truncated_normal) — the
  tf.variance_scaling_initializer() default (resnet_model_official.py:90).
  Dense init: glorot_uniform (tf.layers.dense default).

TPU-first deviations from the reference design (not behavior):
- Always NHWC; no data_format flag. XLA:TPU picks layouts itself; the
  reference's channels_first/cuDNN vs channels_last/MKL switch
  (resnet_cifar_train.py:80-81) is a GPU/CPU artifact with no TPU analog.
- Mixed precision: conv/matmul compute in ``compute_dtype`` (bfloat16 on the
  MXU), parameters and BN statistics in float32, logits returned in float32.
- The final average pool is a global spatial mean — identical to the
  reference's 8×8 (CIFAR) / 7×7 (ImageNet) VALID pool at native resolutions
  (resnet_model_official.py:269-274, :337-344) and well-defined at others.
- ``width_multiplier`` generalizes the CIFAR net to Wide-ResNet (WRN-28-10 =
  resnet_size 28, width 10).
- Optional ``bn_axis_name`` enables cross-replica (synced) BatchNorm under
  ``shard_map``; default None matches the reference's per-replica BN
  statistics (resnet_model.py:120-122).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

Dtype = Any

_BATCH_NORM_MOMENTUM = 0.997
_BATCH_NORM_EPSILON = 1e-5

conv_kernel_init = nn.initializers.variance_scaling(
    1.0, "fan_in", "truncated_normal")
dense_kernel_init = nn.initializers.xavier_uniform()


class BatchNormRelu(nn.Module):
    """BN (fp32 stats/params) then ReLU, computing in ``dtype``.

    ``epilogue`` != "off" executes the site as the fused Pallas conv
    epilogue (tpu_resnet/ops/epilogue.py): batch/running moments are
    folded to a scale/bias affine (one XLA reduction in training; free
    at eval) and the scale-bias-ReLU chain runs as ONE VMEM pass over
    the conv output. The parameter/stat tree is IDENTICAL to
    nn.BatchNorm (same paths/shapes/inits via _BNVars), so checkpoints
    interchange and ``model.fused_epilogue`` can flip on a restore.
    "auto" consults the compile-time A/B cache (ops/autotune.py) per
    shape — unprofitable shapes keep the identical XLA math."""

    dtype: Dtype = jnp.float32
    axis_name: Optional[str] = None
    epilogue: str = "off"

    @nn.compact
    def __call__(self, x, *, train: bool):
        if self.epilogue == "off":
            x = nn.BatchNorm(
                use_running_average=not train,
                momentum=_BATCH_NORM_MOMENTUM,
                epsilon=_BATCH_NORM_EPSILON,
                dtype=self.dtype,
                param_dtype=jnp.float32,
                axis_name=self.axis_name if train else None,
                name="bn",
            )(x)
            return nn.relu(x)
        if self.epilogue not in ("on", "auto"):
            raise ValueError(f"fused_epilogue must be off|on|auto, got "
                             f"{self.epilogue!r}")
        if self.axis_name is not None:
            raise ValueError("fused_epilogue does not implement sync-BN "
                             "(bn_axis_name); unset one of the two")
        from tpu_resnet.ops import autotune
        from tpu_resnet.ops import epilogue as ep

        gamma, beta, ra_mean, ra_var = _BNVars(x.shape[-1], name="bn")()
        if train:
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, axis=(0, 1, 2))
            # Fast (single-pass) variance, matching flax BatchNorm's
            # use_fast_variance=True; clamped so rsqrt can't NaN under
            # fp32 cancellation.
            var = jnp.maximum(
                jnp.mean(jnp.square(xf), axis=(0, 1, 2))
                - jnp.square(mean), 0.0)
            if not self.is_initializing():
                m = _BATCH_NORM_MOMENTUM  # flax EMA convention
                ra_mean.value = m * ra_mean.value + (1 - m) * mean
                ra_var.value = m * ra_var.value + (1 - m) * var
        else:
            mean, var = ra_mean.value, ra_var.value
        scale = gamma * jax.lax.rsqrt(var + _BATCH_NORM_EPSILON)
        bias = beta - mean * scale
        use_kernel = (self.epilogue == "on"
                      or autotune.use_pallas(ep.OP_SBR,
                                             ep.sbr_key(x.shape)))
        if use_kernel:
            return ep.scale_bias_relu(x, scale, bias)
        return ep.scale_bias_relu_reference(x, scale, bias)


class ConvFixedPadding(nn.Module):
    """Strided conv with input-size-independent explicit padding
    (reference resnet_model_official.py:53-91)."""

    filters: int
    kernel_size: int
    strides: int
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        k, s = self.kernel_size, self.strides
        if s > 1:
            pad_total = k - 1
            pad_beg = pad_total // 2
            pad_end = pad_total - pad_beg
            padding = [(pad_beg, pad_end), (pad_beg, pad_end)]
        else:
            padding = "SAME"
        return nn.Conv(
            features=self.filters,
            kernel_size=(k, k),
            strides=(s, s),
            padding=padding,
            use_bias=False,
            kernel_init=conv_kernel_init,
            dtype=self.dtype,
            param_dtype=jnp.float32,
            name="conv",
        )(x)


class SpaceToDepthStem(nn.Module):
    """The ImageNet 7×7/2 stem executed as a 4×4/1 conv over
    space-to-depth(2) input — the canonical TPU ResNet optimization (the
    7×7 conv over 3 input channels leaves the 128-lane MXU mostly idle;
    over 12 s2d channels utilization quadruples).

    The PARAMETER stays the reference's 7×7×C×F kernel (same name, shape,
    init as the plain stem — checkpoints, param counts and the tfprof
    golden are unchanged); at apply time it is zero-padded to 8×8 and
    reshaped to 4×4×4C×F, which makes the s2d conv mathematically
    identical to the original: output rows use input rows
    2i-3..2i+3 either way (pad (3,3) + 7×7/2 ≡ pad (4,2) + 8×8/2 with a
    leading zero row/col ≡ pad (2,1) + 4×4/1 on s2d(2)).
    Equivalence is asserted by tests/test_models.py."""

    filters: int
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        import jax

        b, h, w, c = x.shape
        kernel = _StemKernel(self.filters, name="conv")(c)
        if h % 2 or w % 2:  # odd inputs: plain 7×7/2 form, same params
            return jax.lax.conv_general_dilated(
                x.astype(self.dtype), kernel.astype(self.dtype), (2, 2),
                [(3, 3), (3, 3)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        # 7×7 → 8×8 with a zero leading row/col, then (2a'+a, 2b'+b2, c)
        # → (a', b', (a, b2, c)): the 4×4×4C equivalent kernel.
        k8 = jnp.pad(kernel, ((1, 0), (1, 0), (0, 0), (0, 0)))
        k4 = k8.reshape(4, 2, 4, 2, c, self.filters).transpose(
            0, 2, 1, 3, 4, 5).reshape(4, 4, 4 * c, self.filters)
        # space-to-depth(2) with matching (a, b2, c) channel order
        xs = x.reshape(b, h // 2, 2, w // 2, 2, c).transpose(
            0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2, 4 * c)
        return jax.lax.conv_general_dilated(
            xs.astype(self.dtype), k4.astype(self.dtype), (1, 1),
            [(2, 1), (2, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))


class _StemKernel(nn.Module):
    """Declares the stem kernel at the same tree path
    (initial_conv/conv/kernel) and shape as ConvFixedPadding's nn.Conv."""

    filters: int

    @nn.compact
    def __call__(self, in_channels: int):
        return self.param("kernel", conv_kernel_init,
                          (7, 7, in_channels, self.filters), jnp.float32)


class _BNVars(nn.Module):
    """nn.BatchNorm's exact parameter/stat tree (params scale/bias,
    batch_stats mean/var, same names, shapes, inits, fp32) for a BN whose
    math runs inside the fused Pallas kernel instead of a flax layer."""

    features: int

    @nn.compact
    def __call__(self):
        f = self.features
        scale = self.param("scale", nn.initializers.ones_init(), (f,),
                           jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(), (f,),
                          jnp.float32)
        mean = self.variable("batch_stats", "mean",
                             lambda: jnp.zeros((f,), jnp.float32))
        var = self.variable("batch_stats", "var",
                            lambda: jnp.ones((f,), jnp.float32))
        return scale, bias, mean, var


class _BNSite(nn.Module):
    """Wraps _BNVars one scope deeper (child name 'bn') so the tree path
    matches BatchNormRelu's nn.BatchNorm exactly (e.g. preact/bn/scale)."""

    features: int

    @nn.compact
    def __call__(self):
        return _BNVars(self.features, name="bn")()


class _ConvKernel(nn.Module):
    features: int
    in_features: int
    kernel_size: int = 3

    @nn.compact
    def __call__(self):
        k = self.kernel_size
        return self.param("kernel", conv_kernel_init,
                          (k, k, self.in_features, self.features),
                          jnp.float32)


class _ConvSite(nn.Module):
    """Wraps _ConvKernel at child name 'conv' — path matches
    ConvFixedPadding's nn.Conv (e.g. conv1/conv/kernel)."""

    features: int
    in_features: int
    kernel_size: int = 3

    @nn.compact
    def __call__(self):
        return _ConvKernel(self.features, self.in_features,
                           self.kernel_size, name="conv")()


class FusedBuildingBlock(nn.Module):
    """BuildingBlock (stride 1, identity shortcut) executed as the fused
    Pallas residual-block kernel family (tpu_resnet/ops/fused_block.py):
    one VMEM-resident program per block — scale-bias, ReLU, two 3×3 convs,
    residual add — instead of XLA's several sequential fused loops, built
    to harvest the CIFAR step's measured ~3.7× overhead-above-roofline gap
    (docs/PERF.md "CIFAR is overhead-bound").

    The parameter/stat tree is IDENTICAL to BuildingBlock (same paths,
    shapes, inits — asserted by tests/test_fused_model.py), so checkpoints
    are interchangeable and ``model.fused_blocks`` can flip on a restore.

    Training uses ``block_train_apply`` (live batch moments, custom-VJP
    backward with full BN correction terms) and updates the running-stats
    EMA exactly like nn.BatchNorm (momentum 0.997). Eval folds the running
    stats to scale/bias and uses ``block_apply``.

    BN semantics: batch moments are taken over the batch the kernel sees.
    Single-device (the CIFAR headline config) that equals global-batch BN.
    Multi-chip, the supported dispatch is shard_map-EXPLICIT (VERDICT r4
    item 5): ``model.sync_bn=false`` routes the step through
    ``train.step.shard_step(per_replica_bn=True)``, so each replica's
    kernel call gets its concrete local shard — per-replica BN, exactly
    the reference's semantics (resnet_model.py:120-122). The train loop
    raises on the unsupported combination (fused + sync-BN + data>1), and
    sync-BN via ``bn_axis_name`` raises at construction. Validated by
    dryrun path 5 (``__graft_entry__.dryrun_multichip``) and the 8-device
    shard_map equivalence test (tests/test_fused_model.py); the
    single-real-chip non-interpret shard_map smoke is battery stage 57.
    """

    filters: int
    dtype: Dtype = jnp.float32
    batch_tile: int = 16

    @nn.compact
    def __call__(self, x, train: bool):
        from tpu_resnet.ops import fused_block as fb

        f = self.filters
        gamma1, beta1, mean1, var1 = _BNSite(f, name="preact")()
        w1 = _ConvSite(f, f, name="conv1")()
        gamma2, beta2, mean2, var2 = _BNSite(f, name="bnrelu1")()
        w2 = _ConvSite(f, f, name="conv2")()

        # VMEM-derived tile plan (auto_batch_tile): reproduces the
        # measured bt=16 at the CIFAR shapes and sizes the ImageNet
        # rn18/34 shapes (56²x64 → bt~2-3 etc.) under the same budget;
        # config's fused_block_tile remains the cap.
        bt = fb.auto_batch_tile(x.shape, cap=self.batch_tile)

        if train:
            y, (bm1, bv1, bm2, bv2) = fb.block_train_apply(
                x, w1, w2, gamma1, beta1, gamma2, beta2,
                _BATCH_NORM_EPSILON, bt, None)
            if not self.is_initializing():
                m = _BATCH_NORM_MOMENTUM  # flax EMA convention
                mean1.value = m * mean1.value + (1 - m) * bm1
                var1.value = m * var1.value + (1 - m) * bv1
                mean2.value = m * mean2.value + (1 - m) * bm2
                var2.value = m * var2.value + (1 - m) * bv2
            return y
        s1, b1 = fb._fold(gamma1, beta1, mean1.value, var1.value,
                          _BATCH_NORM_EPSILON)
        s2, b2 = fb._fold(gamma2, beta2, mean2.value, var2.value,
                          _BATCH_NORM_EPSILON)
        return fb.block_apply(x, w1, w2, s1, b1, s2, b2, bt)


def _check_fused_bn_axis(fused_blocks: bool, bn_axis_name) -> None:
    """Fail-loud convention (ADVICE r4): the fused kernels compute batch
    moments per replica with no cross-device axis sync — a sync-BN
    request combined with ``fused_blocks`` must raise, not silently
    degrade to per-replica BN."""
    if fused_blocks and bn_axis_name is not None:
        raise ValueError("fused_blocks does not implement sync-BN "
                         "(bn_axis_name); unset one of the two")


def _check_epilogue(fused_epilogue: str, bn_axis_name) -> None:
    """Same fail-loud convention for the fused BN+ReLU epilogues: a typo
    must not mean "off" while the operator believes the kernels run, and
    the manual-moments epilogue path computes batch statistics per
    replica with no cross-device axis sync — sync-BN via ``bn_axis_name``
    must raise, not silently degrade (mirrors _check_fused_bn_axis)."""
    if fused_epilogue not in ("off", "on", "auto"):
        raise ValueError(f"fused_epilogue must be off|on|auto, got "
                         f"{fused_epilogue!r}")
    if fused_epilogue != "off" and bn_axis_name is not None:
        raise ValueError("fused_epilogue does not implement sync-BN "
                         "(bn_axis_name); unset one of the two")


class BuildingBlock(nn.Module):
    """Basic 3×3+3×3 pre-activation block
    (reference resnet_model_official.py:94-130)."""

    filters: int
    strides: int
    use_projection: bool
    dtype: Dtype = jnp.float32
    bn_axis_name: Optional[str] = None
    epilogue: str = "off"

    @nn.compact
    def __call__(self, x, train: bool):
        shortcut = x
        x = BatchNormRelu(self.dtype, self.bn_axis_name, self.epilogue,
                          name="preact")(x, train=train)
        if self.use_projection:
            # Projection comes after the first BN+ReLU: it convolves the
            # pre-activated input (resnet_model_official.py:117-120).
            shortcut = ConvFixedPadding(
                self.filters, 1, self.strides, self.dtype, name="proj")(x)
        x = ConvFixedPadding(
            self.filters, 3, self.strides, self.dtype, name="conv1")(x)
        x = BatchNormRelu(self.dtype, self.bn_axis_name, self.epilogue,
                          name="bnrelu1")(x, train=train)
        x = ConvFixedPadding(self.filters, 3, 1, self.dtype, name="conv2")(x)
        return x + shortcut


class BottleneckBlock(nn.Module):
    """1×1 → 3×3 → 1×1(4f) pre-activation bottleneck
    (reference resnet_model_official.py:133-175)."""

    filters: int
    strides: int
    use_projection: bool
    dtype: Dtype = jnp.float32
    bn_axis_name: Optional[str] = None
    epilogue: str = "off"

    @nn.compact
    def __call__(self, x, train: bool):
        shortcut = x
        x = BatchNormRelu(self.dtype, self.bn_axis_name, self.epilogue,
                          name="preact")(x, train=train)
        if self.use_projection:
            shortcut = ConvFixedPadding(
                4 * self.filters, 1, self.strides, self.dtype, name="proj")(x)
        x = ConvFixedPadding(self.filters, 1, 1, self.dtype, name="conv1")(x)
        x = BatchNormRelu(self.dtype, self.bn_axis_name, self.epilogue,
                          name="bnrelu1")(x, train=train)
        x = ConvFixedPadding(
            self.filters, 3, self.strides, self.dtype, name="conv2")(x)
        x = BatchNormRelu(self.dtype, self.bn_axis_name, self.epilogue,
                          name="bnrelu2")(x, train=train)
        x = ConvFixedPadding(4 * self.filters, 1, 1, self.dtype, name="conv3")(x)
        return x + shortcut


class BlockLayer(nn.Module):
    """A stage of blocks; only the first block projects/strides
    (reference resnet_model_official.py:178-214)."""

    filters: int
    blocks: int
    strides: int
    bottleneck: bool
    dtype: Dtype = jnp.float32
    bn_axis_name: Optional[str] = None
    remat: bool = False
    # Fused Pallas kernel for the stride-1 identity blocks (hybrid
    # dispatch: block0 — the strided/projection transition — always stays
    # on the XLA path; see FusedBuildingBlock). Basic blocks only.
    fused: bool = False
    fused_tile: int = 16
    # Fused Pallas BN+ReLU epilogues at the XLA-path BN sites
    # (ops/epilogue.py; off | on | auto — see BatchNormRelu).
    epilogue: str = "off"

    @nn.compact
    def __call__(self, x, *, train: bool):
        block_cls = BottleneckBlock if self.bottleneck else BuildingBlock
        fused_cls = FusedBuildingBlock
        if self.remat:
            # Rematerialize per block: activations are recomputed in the
            # backward pass instead of stored — trades ~33% more FLOPs in
            # the block for O(depth) activation memory, buying the larger
            # batches that raise MXU utilization (pallas_guide: HBM is
            # the usual ceiling). static_argnums: (self, x, train) — the
            # bool must stay a Python static.
            block_cls = nn.remat(block_cls, static_argnums=(2,))
            fused_cls = nn.remat(fused_cls, static_argnums=(2,))
        # Hybrid dispatch: only the stride-1 identity basic blocks fuse,
        # and only at widths with a VMEM-sized tile plan per
        # auto_batch_tile (which rejects f=512 ImageNet blocks: weights
        # alone ~18.9 MB). The checked shape is the STAGE shape — block0
        # (projection/stride) runs first, so probe with its output
        # geometry.
        fuse = self.fused and not self.bottleneck
        if fuse:
            from tpu_resnet.ops.fused_block import auto_batch_tile
            try:
                auto_batch_tile(
                    (x.shape[0],
                     (x.shape[1] + self.strides - 1) // self.strides,
                     (x.shape[2] + self.strides - 1) // self.strides,
                     self.filters),
                    cap=self.fused_tile)
            except ValueError:
                fuse = False   # no VMEM plan at this width: stay on XLA
        _check_fused_bn_axis(fuse, self.bn_axis_name)
        _check_epilogue(self.epilogue, self.bn_axis_name)
        x = block_cls(self.filters, self.strides, True, self.dtype,
                      self.bn_axis_name, self.epilogue,
                      name="block0")(x, train)
        for i in range(1, self.blocks):
            if fuse:
                x = fused_cls(self.filters, self.dtype, self.fused_tile,
                              name=f"block{i}")(x, train)
            else:
                x = block_cls(self.filters, 1, False, self.dtype,
                              self.bn_axis_name, self.epilogue,
                              name=f"block{i}")(x, train)
        return x


class ResNetV2(nn.Module):
    """Generic pre-activation ResNet-v2 over NHWC inputs.

    ``stem='cifar'``: 3×3/1 conv, no max-pool; ``stem='imagenet'``:
    7×7/2 conv + 3×3/2 SAME max-pool.
    """

    stage_filters: Sequence[int]
    stage_blocks: Sequence[int]
    stage_strides: Sequence[int]
    bottleneck: bool
    num_classes: int
    stem: str = "imagenet"
    stem_filters: int = 64
    dtype: Dtype = jnp.bfloat16
    bn_axis_name: Optional[str] = None
    # Execute the ImageNet stem as a space-to-depth conv (identical math
    # and identical parameters — see SpaceToDepthStem; safe default).
    stem_space_to_depth: bool = True
    # Rematerialize residual blocks in the backward pass (activation
    # memory O(depth) instead of O(depth·width)): enables the larger
    # batches that raise MXU utilization. Off by default — at b128/b256
    # the activations fit and remat only adds recompute FLOPs.
    remat: bool = False
    # Hybrid fused-Pallas dispatch for stride-1 identity basic blocks
    # (FusedBuildingBlock); transition blocks stay XLA. Off by default —
    # gated on battery stage 05_fused_block_ab's A/B.
    fused_blocks: bool = False
    fused_block_tile: int = 16
    # Fused Pallas BN+ReLU epilogues at every XLA-path BN site
    # (ops/epilogue.py; off | on | auto — "auto" takes the per-shape
    # compile-time A/B cache). Off by default: flips per shape on a
    # measured win, the xent-kernel policy.
    fused_epilogue: str = "off"

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        x = jnp.asarray(x, self.dtype)
        if self.stem == "cifar":
            x = ConvFixedPadding(self.stem_filters, 3, 1, self.dtype,
                                 name="initial_conv")(x)
        elif self.stem == "imagenet":
            if self.stem_space_to_depth:
                x = SpaceToDepthStem(self.stem_filters, self.dtype,
                                     name="initial_conv")(x)
            else:
                x = ConvFixedPadding(self.stem_filters, 7, 2, self.dtype,
                                     name="initial_conv")(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        else:
            raise ValueError(f"unknown stem {self.stem!r}")

        for i, (f, b, s) in enumerate(zip(self.stage_filters,
                                          self.stage_blocks,
                                          self.stage_strides)):
            x = BlockLayer(f, b, s, self.bottleneck, self.dtype,
                           self.bn_axis_name, self.remat,
                           self.fused_blocks, self.fused_block_tile,
                           self.fused_epilogue,
                           name=f"block_layer{i + 1}")(x, train=train)

        x = BatchNormRelu(self.dtype, self.bn_axis_name,
                          self.fused_epilogue, name="final_bnrelu")(
            x, train=train)
        # Global spatial mean == the reference's full-extent VALID avg-pool
        # (resnet_model_official.py:269-274, :337-344).
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, kernel_init=dense_kernel_init,
                     dtype=self.dtype, param_dtype=jnp.float32,
                     name="final_dense")(x)
        return jnp.asarray(x, jnp.float32)


def cifar_resnet_v2(resnet_size: int, num_classes: int,
                    width_multiplier: int = 1,
                    dtype: Dtype = jnp.bfloat16,
                    bn_axis_name: Optional[str] = None,
                    remat: bool = False,
                    fused_blocks: bool = False,
                    fused_block_tile: int = 16,
                    fused_epilogue: str = "off") -> ResNetV2:
    """6n+2 CIFAR ResNet-v2 (reference resnet_model_official.py:217-278).

    'ResNet-50' on CIFAR means n=8 basic blocks per stage with filters
    16/32/64 — not the ImageNet bottleneck net (SURVEY.md §2.1).

    With ``width_multiplier`` > 1, the Wide-ResNet 6n+4 depth convention is
    also accepted (WRN-28-10 = size 28, n=4, width 10).
    """
    if resnet_size % 6 == 2:
        n = (resnet_size - 2) // 6
    elif resnet_size % 6 == 4 and width_multiplier > 1:
        n = (resnet_size - 4) // 6
    else:
        raise ValueError(f"resnet_size must be 6n+2 (or 6n+4 for wide), "
                         f"got {resnet_size}")
    if fused_blocks and width_multiplier > 1:
        # Wide-ResNet channels (160/320/640 at WRN-28-10) put the default
        # tile far past core VMEM, and no A/B has measured those shapes —
        # fail loudly rather than ship an untested kernel configuration.
        raise ValueError("fused_blocks is only measured/tiled for "
                         "width_multiplier=1 (16/32/64-channel stages)")
    _check_fused_bn_axis(fused_blocks, bn_axis_name)
    _check_epilogue(fused_epilogue, bn_axis_name)
    w = width_multiplier
    return ResNetV2(
        stage_filters=(16 * w, 32 * w, 64 * w),
        stage_blocks=(n, n, n),
        stage_strides=(1, 2, 2),
        bottleneck=False,
        num_classes=num_classes,
        stem="cifar",
        stem_filters=16,
        dtype=dtype,
        bn_axis_name=bn_axis_name,
        remat=remat,
        fused_blocks=fused_blocks,
        fused_block_tile=fused_block_tile,
        fused_epilogue=fused_epilogue,
    )


_IMAGENET_PARAMS = {
    # size: (bottleneck, stage_blocks) — resnet_model_official.py:352-358
    18: (False, (2, 2, 2, 2)),
    34: (False, (3, 4, 6, 3)),
    50: (True, (3, 4, 6, 3)),
    101: (True, (3, 4, 23, 3)),
    152: (True, (3, 8, 36, 3)),
    200: (True, (3, 24, 36, 3)),
}


def imagenet_resnet_v2(resnet_size: int, num_classes: int,
                       dtype: Dtype = jnp.bfloat16,
                       bn_axis_name: Optional[str] = None,
                       stem_space_to_depth: bool = True,
                       remat: bool = False,
                       fused_blocks: bool = False,
                       fused_epilogue: str = "off") -> ResNetV2:
    """ImageNet ResNet-v2 18/34/50/101/152/200
    (reference resnet_model_official.py:350-366)."""
    if resnet_size not in _IMAGENET_PARAMS:
        raise ValueError(
            f"invalid resnet_size {resnet_size}; have {sorted(_IMAGENET_PARAMS)}")
    bottleneck, blocks = _IMAGENET_PARAMS[resnet_size]
    if fused_blocks and bottleneck:
        raise ValueError(f"fused_blocks covers basic blocks only "
                         f"(ImageNet rn18/rn34); rn{resnet_size} is built "
                         f"of bottleneck blocks")
    _check_fused_bn_axis(fused_blocks, bn_axis_name)
    _check_epilogue(fused_epilogue, bn_axis_name)
    return ResNetV2(
        stage_filters=(64, 128, 256, 512),
        stage_blocks=blocks,
        stage_strides=(1, 2, 2, 2),
        bottleneck=bottleneck,
        num_classes=num_classes,
        stem="imagenet",
        stem_filters=64,
        dtype=dtype,
        bn_axis_name=bn_axis_name,
        stem_space_to_depth=stem_space_to_depth,
        remat=remat,
        fused_blocks=fused_blocks,
        fused_epilogue=fused_epilogue,
    )


# ------------------------------------------------------------------ family
# What models/__init__.py registers as the family ``resnet``: the glue
# between a RunConfig and the constructors above. The constructors hold
# every guard; nothing here repeats one.
def build(cfg) -> ResNetV2:
    m = cfg.model
    dtype = jnp.dtype(m.compute_dtype)
    if cfg.data.dataset == "imagenet":
        # fused_blocks: rn18/34 basic blocks get VMEM-derived tile plans
        # (ops.fused_block.auto_batch_tile); the planless 7²x512 stage
        # stays XLA. Bottleneck sizes refuse the switch.
        return imagenet_resnet_v2(
            m.resnet_size, cfg.data.num_classes, dtype=dtype,
            stem_space_to_depth=m.stem_space_to_depth, remat=m.remat,
            fused_blocks=m.fused_blocks, fused_epilogue=m.fused_epilogue)
    return cifar_resnet_v2(
        m.resnet_size, cfg.data.num_classes,
        width_multiplier=m.width_multiplier, dtype=dtype, remat=m.remat,
        fused_blocks=m.fused_blocks, fused_block_tile=m.fused_block_tile,
        fused_epilogue=m.fused_epilogue)


def image_dataset(cfg) -> str:
    """The data part of an image family's program key: the data set's
    name, with the class count where ``synthetic`` is not at its 10 (the
    head's shape follows it)."""
    if cfg.data.dataset == "synthetic" and cfg.data.synthetic_classes != 10:
        return f"synthetic{cfg.data.synthetic_classes}"
    return cfg.data.dataset


def spell(cfg):
    m = cfg.model
    name = (f"wrn{m.resnet_size}_{m.width_multiplier}"
            if m.width_multiplier != 1 else f"rn{m.resnet_size}")
    return image_dataset(cfg), name


def variants(cfg):
    """The kernel switches that change the traced program, as the
    suffixes a key carries before and after ``_remat`` (the order is that
    of the keys already written). ``fused_epilogue=auto`` spells like
    ``off``: its dispatch is the probe's."""
    m = cfg.model
    return ("_fused" if m.fused_blocks else "",
            ("_ep" if m.fused_epilogue == "on" else "")
            + ("_nos2d" if cfg.data.dataset == "imagenet"
               and not m.stem_space_to_depth else ""))


def train_flops_per_example(cfg, xla_counted: bool = True):
    """None: XLA's count of the lowered step is this family's. Where XLA
    gave none, ResNet-50 on ImageNet has its analytic count (another size
    reports no number rather than that one's)."""
    if xla_counted or (cfg.data.dataset, cfg.model.resnet_size) != (
            "imagenet", 50):
        return None
    from tpu_resnet.obs.mfu import analytic_resnet50_flops

    return analytic_resnet50_flops(1, cfg.data.resolved_image_size)
