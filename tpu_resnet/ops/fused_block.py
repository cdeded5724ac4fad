"""EXPERIMENTAL: the ResNet-v2 basic block as fused Pallas TPU kernels —
forward, backward, and live-batch-stats training variants.

Motivation (docs/PERF.md "CIFAR step is overhead-bound"): the CIFAR
ResNet's 16/32/64-channel convolutions run ~3.7× above even the HBM
bandwidth roofline — per-fused-op fixed costs dominate when ops are this
small. XLA executes a v2 basic block as several sequential fused loops
(BN, conv, BN, conv, add), each paying pipeline fill/drain; this kernel
executes the whole block — scale-bias, ReLU, two 3×3 convs (as 9-tap
shifted matmuls), residual add — in a single VMEM-resident program, one
HBM round trip per block.

Scope: stride 1, equal in/out channels (22 of the CIFAR ResNet-50's 24
blocks), BN folded to scale/bias (stats supplied — the cross-batch stats
reduction is an orthogonal pass either way). ``block_apply`` is the full
differentiable primitive: Pallas forward + Pallas backward via
``jax.custom_vjp``, with the backward kernel recomputing the forward
chain in VMEM from ``x`` alone — no residual tensors ever touch HBM.
Battery stage 05_fused_block_ab A/Bs both directions against XLA's compilation of the
identical math (`block_fwd_reference`) at CIFAR shapes on a live window.
A win green-lights model integration (batch stats + strided/projection
variants); a loss gets recorded next to the xent kernel's negative
result (docs/PERF.md) and this file stays an exemplar.

Reference block semantics: v2 preactivation residual block,
reference resnet_model_official.py:144-186 (building_block_v2).

Training-path integration (round 4: REALIZED, config-gated): live batch
stats fold into this design as a two-pass block. BN1's stats are
moments of the block input x (available before the kernel); BN2's are
moments of conv1's output c1, which is produced inside the block — so
pass A runs the tile grid accumulating c1's sum/sum-of-squares (c1 is
recomputed, never written to HBM), pass B runs this kernel with both
stats folded to scale/bias. HBM traffic: two reads of x + one write of
y per block, still far below XLA's per-op materialization. The backward
gains the standard BN batch-stats correction terms (dmean/dvar chain)
in the same recompute style. Eval-path integration needs no new math:
inference BN is exactly the folded scale/bias this kernel already takes
(scale = gamma/sqrt(var+eps), bias = beta - gamma*mean/sqrt(var+eps)).
The model-side dispatch is ``models/resnet.py::FusedBuildingBlock``
behind ``model.fused_blocks`` (default off until the A/B), equivalence-
tested against the XLA path in tests/test_fused_model.py; battery stage
15_fused_model_ab measures it end to end on the headline config.
"""

from __future__ import annotations

import logging
import functools


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_resnet.ops.softmax_xent import is_tpu_backend

# The epilogue math (scale-bias-ReLU) and the init-or-accumulate grid
# idiom live with the standalone epilogue kernels (ops/epilogue.py); the
# block kernels here apply the same epilogue between their convs.
from tpu_resnet.ops.epilogue import _acc_out
from tpu_resnet.ops.epilogue import scale_bias_relu_math as _scale_bias_relu
from tpu_resnet.ops.epilogue import vmem_row_bytes


def _conv3x3_taps(h_pad, w, bt, h, wdt, c):
    """3×3 SAME conv over the padded [Bt, H+2, W+2, C] input as 9 shifted
    (Bt·H·W, C) @ (C, C) matmuls accumulating in fp32 — each tap is an MXU
    dot over the flattened pixel rows."""
    acc = jnp.zeros((bt * h * wdt, c), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            patch = h_pad[:, dy:dy + h, dx:dx + wdt, :].reshape(
                bt * h * wdt, c)
            acc = acc + jnp.dot(patch, w[dy, dx],
                                preferred_element_type=jnp.float32)
    return acc.reshape(bt, h, wdt, c)


def _block_kernel(x_ref, w1_ref, w2_ref, s1_ref, b1_ref, s2_ref, b2_ref,
                  o_ref):
    bt, h, wdt, c = x_ref.shape
    x = x_ref[...].astype(jnp.float32)
    pre1 = _scale_bias_relu(x, s1_ref[...], b1_ref[...])
    pre1 = jnp.pad(pre1, ((0, 0), (1, 1), (1, 1), (0, 0)))
    mid = _conv3x3_taps(pre1, w1_ref[...].astype(jnp.float32),
                        bt, h, wdt, c)
    pre2 = _scale_bias_relu(mid, s2_ref[...], b2_ref[...])
    pre2 = jnp.pad(pre2, ((0, 0), (1, 1), (1, 1), (0, 0)))
    out = _conv3x3_taps(pre2, w2_ref[...].astype(jnp.float32),
                        bt, h, wdt, c)
    o_ref[...] = (x + out).astype(o_ref.dtype)


def auto_batch_tile(shape, cap: int = 16,
                    budget_bytes: int = 10 * 2 ** 20):
    """VMEM-derived forward batch tile for a basic-block input ``shape``
    (B, H, W, C) — the tile plan machinery behind ImageNet rn18/34 fused
    basic blocks (VERDICT r4 item 8), shared with the CIFAR shapes where
    it reproduces the measured default (bt=16 at 32²x16 under the 16
    cap).

    The forward kernel's live set is ~4 fp32 spatial slabs per batch row
    (x, pre/pad, mid, out — _block_kernel) plus both 3x3xCxC weights;
    the budget leaves headroom under the ~16 MB core VMEM for Mosaic's
    own buffers. Returns the largest batch divisor within cap and
    budget, or raises if even one batch row cannot fit (f=512 ImageNet
    blocks: weights alone are ~18.9 MB — callers keep those on XLA)."""
    b, h, w, c = shape
    # Sized as Mosaic lays arrays out (minor dims tiled to (8, 128)), not
    # by logical bytes: at 16 channels the two differ eightfold.
    weight_bytes = 2 * 9 * vmem_row_bytes(1, c, c)
    per_row = vmem_row_bytes(h, w, c) * 4
    avail = budget_bytes - weight_bytes
    if avail < per_row:
        raise ValueError(
            f"fused basic block does not fit VMEM at {h}x{w}x{c}: "
            f"weights {weight_bytes / 2**20:.1f} MB + one batch row "
            f"{per_row / 2**20:.1f} MB exceed the {budget_bytes / 2**20:.0f}"
            f" MB plan budget — keep this width on the XLA path")
    bt = max(1, min(cap, b, avail // per_row))
    while b % bt:
        bt -= 1
    return int(bt)


def _default_bwd_tile(batch: int, fwd_tile: int) -> int:
    """Largest divisor of ``batch`` that is <= fwd_tile // 2 (the backward
    kernels keep ~2-3x the forward's live set, and the tile must divide
    the batch or _plumbing raises at jax.grad time).

    A batch with no divisor near the target (e.g. a prime batch size)
    silently degrades toward batch_tile=1 — a fully sequential per-example
    backward grid, correct but very slow. That pathology must be visible
    in unattended A/B logs (ADVICE r3), hence the warning."""
    target = max(1, min(batch, fwd_tile // 2))
    chosen = target
    while batch % chosen:
        chosen -= 1
    if chosen < max(1, target // 2):
        logging.getLogger("tpu_resnet").warning(
            "fused_block backward tile degraded to %d (target %d) for "
            "batch %d — no divisor near fwd_tile//2; the backward grid is "
            "near-sequential and will be slow", chosen, target, batch)
    return chosen


def _plumbing(x, batch_tile, interpret):
    """Shared pallas_call scaffolding for the fwd and bwd kernels:
    (resolved interpret, batch tile, grid, tile BlockSpec, whole-array
    BlockSpec factory, compiler kwargs)."""
    if interpret is None:
        interpret = not is_tpu_backend()
    b, h, wdt, c = x.shape
    bt = min(batch_tile, b)
    if b % bt:
        raise ValueError(f"batch {b} not divisible by batch_tile {bt}")
    grid = (b // bt,)
    tile = pl.BlockSpec((bt, h, wdt, c), lambda i: (i, 0, 0, 0))
    full = lambda *shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    return interpret, bt, grid, tile, full, kwargs


def block_fwd(x, w1, w2, s1, b1, s2, b2, *, batch_tile: int = 16,
              interpret: bool | None = None):
    """Fused v2 basic-block forward.

    x [B,H,W,C]; w1,w2 [3,3,C,C]; s1,b1,s2,b2 [C] (folded BN).
    Returns x + conv2(relu(sb2(conv1(relu(sb1(x)))))), same dtype as x.
    """
    interpret, bt, grid, tile, full, kwargs = _plumbing(
        x, batch_tile, interpret)
    c = x.shape[-1]
    return pl.pallas_call(
        _block_kernel,
        grid=grid,
        in_specs=[tile, full(3, 3, c, c), full(3, 3, c, c),
                  full(c), full(c), full(c), full(c)],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        **kwargs,
    )(x, w1, w2, s1, b1, s2, b2)


@jax.jit
def block_fwd_reference(x, w1, w2, s1, b1, s2, b2):
    """The identical math as XLA compiles it (the A/B's other arm and the
    correctness oracle for tests)."""
    xf = x.astype(jnp.float32)
    dn = ("NHWC", "HWIO", "NHWC")
    pre1 = _scale_bias_relu(xf, s1, b1)
    mid = jax.lax.conv_general_dilated(
        pre1, w1.astype(jnp.float32), (1, 1), "SAME", dimension_numbers=dn)
    pre2 = _scale_bias_relu(mid, s2, b2)
    out = jax.lax.conv_general_dilated(
        pre2, w2.astype(jnp.float32), (1, 1), "SAME", dimension_numbers=dn)
    return (xf + out).astype(x.dtype)


# --------------------------------------------------------------------------
# Backward: one Pallas kernel, activations recomputed in VMEM from x alone
# --------------------------------------------------------------------------
#
# Forward chain (per tile, all VMEM):
#   a1 = s1·x + b1 ; r1 = relu(a1) ; c1 = conv(r1, w1)
#   a2 = s2·c1 + b2 ; r2 = relu(a2) ; c2 = conv(r2, w2) ; y = x + c2
# Backward, given gy (= dL/dy):
#   dr2 = convT(gy, w2)            da2 = dr2 ⊙ [a2>0]
#   dc1 = s2·da2                   ds2 = Σ da2⊙c1 ;  db2 = Σ da2
#   dw2[t] = r2_patch(t)ᵀ @ gy     (9 taps)
#   dr1 = convT(dc1, w1)           da1 = dr1 ⊙ [a1>0]
#   dx  = gy + s1·da1              ds1 = Σ da1⊙x ;  db1 = Σ da1
#   dw1[t] = r1_patch(t)ᵀ @ dc1
# convT (transposed SAME 3×3) = taps with spatially-flipped, C-transposed
# weights. Nothing but x, gy and the params is read from HBM; no residual
# tensors are ever materialized there — the bandwidth-minimal design the
# CIFAR analysis calls for. Weight/scale/bias grads accumulate across the
# sequential batch-tile grid into their output refs.


def _transpose_weights(w):
    """Weights of the transposed SAME 3×3 conv: spatial flip + IO-channel
    swap, so convT(d, w) == _conv3x3_taps(d_pad, _transpose_weights(w)).
    Applied OUTSIDE the kernels (the flip is a ``rev``, which Mosaic does
    not lower): the backward kernels take the result as an input."""
    return w[::-1, ::-1].transpose(0, 1, 3, 2)


def _wgrad_taps(r_pad, d, bt, h, wdt, c):
    """dw[dy,dx] = r_patch(dy,dx)ᵀ @ d — nine (C, M)@(M, C) matmuls."""
    dm = d.reshape(bt * h * wdt, c)
    rows = []
    for dy in range(3):
        row = []
        for dx in range(3):
            patch = r_pad[:, dy:dy + h, dx:dx + wdt, :].reshape(
                bt * h * wdt, c)
            row.append(jnp.dot(patch.T, dm,
                               preferred_element_type=jnp.float32))
        rows.append(jnp.stack(row))
    return jnp.stack(rows)  # [3,3,C,C]


def _block_bwd_kernel(x_ref, gy_ref, w1_ref, w2_ref, w1t_ref, w2t_ref,
                      s1_ref, b1_ref, s2_ref, b2_ref,
                      dx_ref, dw1_ref, dw2_ref, ds1_ref, db1_ref,
                      ds2_ref, db2_ref):
    bt, h, wdt, c = x_ref.shape
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    gy = gy_ref[...].astype(jnp.float32)
    w1 = w1_ref[...].astype(jnp.float32)
    w2 = w2_ref[...].astype(jnp.float32)
    w1t = w1t_ref[...].astype(jnp.float32)
    w2t = w2t_ref[...].astype(jnp.float32)
    s1, b1 = s1_ref[...], b1_ref[...]
    s2, b2 = s2_ref[...], b2_ref[...]

    # Recompute the forward chain in VMEM.
    a1 = x * s1 + b1
    r1 = jnp.maximum(a1, 0.0)
    r1p = jnp.pad(r1, ((0, 0), (1, 1), (1, 1), (0, 0)))
    c1 = _conv3x3_taps(r1p, w1, bt, h, wdt, c)
    a2 = c1 * s2 + b2
    r2 = jnp.maximum(a2, 0.0)
    r2p = jnp.pad(r2, ((0, 0), (1, 1), (1, 1), (0, 0)))

    # Backward chain (convT = taps over the flipped/IO-swapped weights).
    gyp = jnp.pad(gy, ((0, 0), (1, 1), (1, 1), (0, 0)))
    dr2 = _conv3x3_taps(gyp, w2t, bt, h, wdt, c)
    da2 = jnp.where(a2 > 0, dr2, 0.0)
    dc1 = da2 * s2
    dc1p = jnp.pad(dc1, ((0, 0), (1, 1), (1, 1), (0, 0)))
    dr1 = _conv3x3_taps(dc1p, w1t, bt, h, wdt, c)
    da1 = jnp.where(a1 > 0, dr1, 0.0)
    dx_ref[...] = (gy + da1 * s1).astype(dx_ref.dtype)

    # Parameter grads: accumulate across the sequential batch-tile grid.
    dw1 = _wgrad_taps(r1p, dc1, bt, h, wdt, c)
    dw2 = _wgrad_taps(r2p, gy, bt, h, wdt, c)
    ds1 = jnp.sum(da1 * x, axis=(0, 1, 2))
    db1 = jnp.sum(da1, axis=(0, 1, 2))
    ds2 = jnp.sum(da2 * c1, axis=(0, 1, 2))
    db2 = jnp.sum(da2, axis=(0, 1, 2))

    _acc_out(i == 0, (dw1_ref, dw2_ref, ds1_ref, db1_ref, ds2_ref, db2_ref),
             (dw1, dw2, ds1, db1, ds2, db2))


def _block_bwd_call(x, gy, w1, w2, s1, b1, s2, b2, *, batch_tile: int,
                    interpret: bool):
    interpret, bt, grid, tile, full, kwargs = _plumbing(
        x, batch_tile, interpret)
    c = x.shape[-1]
    f32 = jnp.float32
    return pl.pallas_call(
        _block_bwd_kernel,
        grid=grid,
        in_specs=[tile, tile] + [full(3, 3, c, c)] * 4
                 + [full(c), full(c), full(c), full(c)],
        out_specs=[tile, full(3, 3, c, c), full(3, 3, c, c),
                   full(c), full(c), full(c), full(c)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((3, 3, c, c), f32),
                   jax.ShapeDtypeStruct((3, 3, c, c), f32),
                   jax.ShapeDtypeStruct((c,), f32),
                   jax.ShapeDtypeStruct((c,), f32),
                   jax.ShapeDtypeStruct((c,), f32),
                   jax.ShapeDtypeStruct((c,), f32)],
        interpret=interpret,
        **kwargs,
    )(x, gy, w1, w2, _transpose_weights(w1), _transpose_weights(w2),
      s1, b1, s2, b2)


# --------------------------------------------------------------------------
# Training-path backward: BN batch-stats corrections, three tile passes
# --------------------------------------------------------------------------
#
# With live moments, BN's VJP carries batch-wide correction terms: for
# z = γ·(u-m)/σ + β (biased variance, N elements/channel),
#   du = γ/σ · (dz − ΣB dz / N − ẑ · ΣB dz⊙ẑ / N),
# and the two sums are exactly dβ and dγ. The sums are over the WHOLE
# batch, so the sequential tile grid needs a pass boundary before using
# them. Three passes, each recomputing the forward chain in VMEM from
# (x, params, saved moments):
#   pass 1: accumulate T1=Σdz2, T2=Σdz2⊙ẑ2 and dw2   (dγ2=T2, dβ2=T1)
#   pass 2: finish dc1 with T1/T2; accumulate U1=Σdz1, U2=Σdz1⊙ẑ1 and
#           dw1                                        (dγ1=U2, dβ1=U1)
#   pass 3: finish dx with U1/U2.
# The moments output of block_train_fwd gets a zero cotangent by
# convention: running-stats EMA updates are stop-gradient in BN training
# semantics (flax's mutable batch_stats likewise).


def _recompute_train(x, w1, g1, b1, g2, b2, m1, i1, m2, i2,
                     bt, h, wdt, c):
    """Forward chain from the block input and SAVED moments (i = 1/σ);
    shared by all three backward passes."""
    z1hat = (x - m1) * i1
    z1 = g1 * z1hat + b1
    r1 = jnp.maximum(z1, 0.0)
    r1p = jnp.pad(r1, ((0, 0), (1, 1), (1, 1), (0, 0)))
    c1 = _conv3x3_taps(r1p, w1, bt, h, wdt, c)
    z2hat = (c1 - m2) * i2
    z2 = g2 * z2hat + b2
    r2 = jnp.maximum(z2, 0.0)
    r2p = jnp.pad(r2, ((0, 0), (1, 1), (1, 1), (0, 0)))
    return z1, z1hat, r1p, z2, z2hat, r2p


def _train_bwd_calls(x, gy, w1, w2, g1, b1, g2, b2, moments, eps, *,
                     batch_tile, interpret):
    m1, v1, m2, v2 = moments
    i1 = jax.lax.rsqrt(v1 + eps)
    i2 = jax.lax.rsqrt(v2 + eps)
    interpret, bt, grid, tile, full, kwargs = _plumbing(
        x, batch_tile, interpret)
    b, h, wdt, c = x.shape
    n = float(b * h * wdt)
    f32 = jnp.float32
    # x, gy, w1, w2, their convT forms w1t, w2t, then the 8 [C] vectors
    # g1,b1,g2,b2,m1,i1,m2,i2
    base_in = [tile, tile] + [full(3, 3, c, c)] * 4 + [full(c)] * 8
    base_ops = (x, gy, w1, w2, _transpose_weights(w1),
                _transpose_weights(w2), g1, b1, g2, b2, m1, i1, m2, i2)
    wshape = jax.ShapeDtypeStruct((3, 3, c, c), f32)
    cshape = jax.ShapeDtypeStruct((c,), f32)

    def load(refs):
        (x_ref, gy_ref, w1_ref, w2_ref, w1t_ref, w2t_ref, g1_ref, b1_ref,
         g2_ref, b2_ref, m1_ref, i1_ref, m2_ref, i2_ref) = refs
        return (x_ref[...].astype(f32), gy_ref[...].astype(f32),
                w1_ref[...].astype(f32), w2_ref[...].astype(f32),
                w1t_ref[...].astype(f32), w2t_ref[...].astype(f32),
                g1_ref[...], b1_ref[...], g2_ref[...], b2_ref[...],
                m1_ref[...], i1_ref[...], m2_ref[...], i2_ref[...])

    def pass1(*refs):
        (t1_ref, t2_ref, dw2_ref) = refs[-3:]
        (xv, gyv, w1v, w2v, w1tv, w2tv, g1v, b1v, g2v, b2v, m1v, i1v, m2v,
         i2v) = load(refs[:-3])
        _, _, _, z2, z2hat, r2p = _recompute_train(
            xv, w1v, g1v, b1v, g2v, b2v, m1v, i1v, m2v, i2v, bt, h, wdt, c)
        gyp = jnp.pad(gyv, ((0, 0), (1, 1), (1, 1), (0, 0)))
        dr2 = _conv3x3_taps(gyp, w2tv, bt, h, wdt, c)
        dz2 = jnp.where(z2 > 0, dr2, 0.0)
        _acc_out(pl.program_id(0) == 0, (t1_ref, t2_ref, dw2_ref),
                 (jnp.sum(dz2, axis=(0, 1, 2)),
                  jnp.sum(dz2 * z2hat, axis=(0, 1, 2)),
                  _wgrad_taps(r2p, gyv, bt, h, wdt, c)))

    t1, t2, dw2 = pl.pallas_call(
        pass1, grid=grid, in_specs=base_in,
        out_specs=[full(c), full(c), full(3, 3, c, c)],
        out_shape=[cshape, cshape, wshape],
        interpret=interpret, **kwargs,
    )(*base_ops)

    def _dc1(z2, z2hat, gyv, w2tv, g2v, i2v, t1v, t2v):
        dr2 = _conv3x3_taps(
            jnp.pad(gyv, ((0, 0), (1, 1), (1, 1), (0, 0))),
            w2tv, bt, h, wdt, c)
        dz2 = jnp.where(z2 > 0, dr2, 0.0)
        return g2v * i2v * (dz2 - t1v / n - z2hat * (t2v / n))

    def pass2(*refs):
        (u1_ref, u2_ref, dw1_ref) = refs[-3:]
        t1_ref, t2_ref = refs[-5:-3]
        (xv, gyv, w1v, w2v, w1tv, w2tv, g1v, b1v, g2v, b2v, m1v, i1v, m2v,
         i2v) = load(refs[:-5])
        z1, z1hat, r1p, z2, z2hat, _ = _recompute_train(
            xv, w1v, g1v, b1v, g2v, b2v, m1v, i1v, m2v, i2v, bt, h, wdt, c)
        dc1 = _dc1(z2, z2hat, gyv, w2tv, g2v, i2v, t1_ref[...],
                   t2_ref[...])
        dr1 = _conv3x3_taps(
            jnp.pad(dc1, ((0, 0), (1, 1), (1, 1), (0, 0))),
            w1tv, bt, h, wdt, c)
        dz1 = jnp.where(z1 > 0, dr1, 0.0)
        _acc_out(pl.program_id(0) == 0, (u1_ref, u2_ref, dw1_ref),
                 (jnp.sum(dz1, axis=(0, 1, 2)),
                  jnp.sum(dz1 * z1hat, axis=(0, 1, 2)),
                  _wgrad_taps(r1p, dc1, bt, h, wdt, c)))

    u1, u2, dw1 = pl.pallas_call(
        pass2, grid=grid, in_specs=base_in + [full(c), full(c)],
        out_specs=[full(c), full(c), full(3, 3, c, c)],
        out_shape=[cshape, cshape, wshape],
        interpret=interpret, **kwargs,
    )(*base_ops, t1, t2)

    def pass3(*refs):
        dx_ref = refs[-1]
        t1_ref, t2_ref, u1_ref, u2_ref = refs[-5:-1]
        (xv, gyv, w1v, w2v, w1tv, w2tv, g1v, b1v, g2v, b2v, m1v, i1v, m2v,
         i2v) = load(refs[:-5])
        z1, z1hat, _, z2, z2hat, _ = _recompute_train(
            xv, w1v, g1v, b1v, g2v, b2v, m1v, i1v, m2v, i2v, bt, h, wdt, c)
        dc1 = _dc1(z2, z2hat, gyv, w2tv, g2v, i2v, t1_ref[...],
                   t2_ref[...])
        dr1 = _conv3x3_taps(
            jnp.pad(dc1, ((0, 0), (1, 1), (1, 1), (0, 0))),
            w1tv, bt, h, wdt, c)
        dz1 = jnp.where(z1 > 0, dr1, 0.0)
        dx = gyv + g1v * i1v[None, None, None, :] * (
            dz1 - u1_ref[...] / n - z1hat * (u2_ref[...] / n))
        dx_ref[...] = dx.astype(dx_ref.dtype)

    dx = pl.pallas_call(
        pass3, grid=grid,
        in_specs=base_in + [full(c), full(c), full(c), full(c)],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret, **kwargs,
    )(*base_ops, t1, t2, u1, u2)

    # dγ2 = T2, dβ2 = T1, dγ1 = U2, dβ1 = U1 — the correction sums.
    return dx, dw1, dw2, u2, u1, t2, t1


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def block_train_apply(x, w1, w2, gamma1, beta1, gamma2, beta2,
                      eps=1e-5, batch_tile=16, interpret=None):
    """Differentiable live-batch-stats fused block (training semantics):
    Pallas two-pass forward + three-pass backward with the full BN
    batch-moment correction terms. Returns ``(y, moments)``; the moments
    output is stop-gradient (running-stats EMA convention)."""
    return block_train_fwd(x, w1, w2, gamma1, beta1, gamma2, beta2, eps,
                           batch_tile=batch_tile, interpret=interpret)


def _block_train_fwd_rule(x, w1, w2, gamma1, beta1, gamma2, beta2, eps,
                          batch_tile, interpret):
    y, moments = block_train_fwd(x, w1, w2, gamma1, beta1, gamma2, beta2,
                                 eps, batch_tile=batch_tile,
                                 interpret=interpret)
    return (y, moments), (x, w1, w2, gamma1, beta1, gamma2, beta2, moments)


def _block_train_bwd_rule(eps, batch_tile, interpret, res, cot):
    gy, _gmoments = cot  # moments cotangent dropped: EMA is stop-gradient
    x, w1, w2, gamma1, beta1, gamma2, beta2, moments = res
    bwd_tile = _default_bwd_tile(x.shape[0], batch_tile or 16)
    dx, dw1, dw2, dg1, db1, dg2, db2 = _train_bwd_calls(
        x, gy.astype(jnp.float32), w1, w2, gamma1, beta1, gamma2, beta2,
        moments, eps, batch_tile=bwd_tile, interpret=interpret)
    return (dx.astype(x.dtype), dw1.astype(w1.dtype), dw2.astype(w2.dtype),
            dg1.astype(gamma1.dtype), db1.astype(beta1.dtype),
            dg2.astype(gamma2.dtype), db2.astype(beta2.dtype))


block_train_apply.defvjp(_block_train_fwd_rule, _block_train_bwd_rule)


# --------------------------------------------------------------------------
# Training forward with LIVE batch stats: the two-pass block
# --------------------------------------------------------------------------
#
# BN1 normalizes the block input x — its moments are one cheap XLA
# reduction. BN2 normalizes conv1's output c1, which this design never
# materializes in HBM: pass A (_stats_kernel) recomputes c1 per tile and
# accumulates its per-channel sum / sum-of-squares across the sequential
# grid; pass B is the folded-scale/bias block kernel above. HBM traffic
# per block: three reads of x (BN1 moments, stats pass, apply pass) and
# one write of y — still far below per-op materialization, and the BN1
# reduction could later fold into the previous block's epilogue.


def _stats_kernel(x_ref, w1_ref, s1_ref, b1_ref, sum_ref, sumsq_ref):
    bt, h, wdt, c = x_ref.shape
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    pre1 = _scale_bias_relu(x, s1_ref[...], b1_ref[...])
    pre1 = jnp.pad(pre1, ((0, 0), (1, 1), (1, 1), (0, 0)))
    c1 = _conv3x3_taps(pre1, w1_ref[...].astype(jnp.float32),
                       bt, h, wdt, c)
    _acc_out(i == 0, (sum_ref, sumsq_ref),
             (jnp.sum(c1, axis=(0, 1, 2)),
              jnp.sum(c1 * c1, axis=(0, 1, 2))))


def _c1_moments(x, w1, s1, b1, *, batch_tile, interpret):
    interpret, bt, grid, tile, full, kwargs = _plumbing(
        x, batch_tile, interpret)
    c = x.shape[-1]
    f32 = jnp.float32
    s, ss = pl.pallas_call(
        _stats_kernel,
        grid=grid,
        in_specs=[tile, full(3, 3, c, c), full(c), full(c)],
        out_specs=[full(c), full(c)],
        out_shape=[jax.ShapeDtypeStruct((c,), f32),
                   jax.ShapeDtypeStruct((c,), f32)],
        interpret=interpret,
        **kwargs,
    )(x, w1, s1, b1)
    n = x.shape[0] * x.shape[1] * x.shape[2]
    mean = s / n
    # Single-pass variance can go slightly negative under fp32
    # cancellation (large mean, tiny variance); clamped so rsqrt(var+eps)
    # can't NaN where the two-pass jnp.var wouldn't.
    var = jnp.maximum(ss / n - mean * mean, 0.0)
    return mean, var


def _fold(gamma, beta, mean, var, eps):
    scale = gamma * jax.lax.rsqrt(var + eps)
    return scale, beta - mean * scale


def block_train_fwd(x, w1, w2, gamma1, beta1, gamma2, beta2,
                    eps: float = 1e-5, *, batch_tile: int = 16,
                    interpret: bool | None = None):
    """Fused v2 basic block with LIVE batch-norm statistics (training
    semantics, biased variance like flax BatchNorm's batch moments).

    Returns ``(y, (mean1, var1, mean2, var2))`` — the moments feed the
    caller's running-stats EMA exactly as the unfused BN layers would
    (reference resnet_model.py:39-57 batch_norm_relu)."""
    xf = x.astype(jnp.float32)
    mean1 = jnp.mean(xf, axis=(0, 1, 2))
    var1 = jnp.var(xf, axis=(0, 1, 2))
    s1, b1 = _fold(gamma1, beta1, mean1, var1, eps)
    mean2, var2 = _c1_moments(x, w1, s1, b1, batch_tile=batch_tile,
                              interpret=interpret)
    s2, b2 = _fold(gamma2, beta2, mean2, var2, eps)
    y = block_fwd(x, w1, w2, s1, b1, s2, b2, batch_tile=batch_tile,
                  interpret=interpret)
    return y, (mean1, var1, mean2, var2)


@jax.jit
def block_train_fwd_reference(x, w1, w2, gamma1, beta1, gamma2, beta2,
                              eps: float = 1e-5):
    """XLA oracle: the same training-BN block with batch moments."""
    xf = x.astype(jnp.float32)
    dn = ("NHWC", "HWIO", "NHWC")
    mean1 = jnp.mean(xf, axis=(0, 1, 2))
    var1 = jnp.var(xf, axis=(0, 1, 2))
    pre1 = jnp.maximum(
        (xf - mean1) * jax.lax.rsqrt(var1 + eps) * gamma1 + beta1, 0.0)
    c1 = jax.lax.conv_general_dilated(
        pre1, w1.astype(jnp.float32), (1, 1), "SAME", dimension_numbers=dn)
    mean2 = jnp.mean(c1, axis=(0, 1, 2))
    var2 = jnp.var(c1, axis=(0, 1, 2))
    pre2 = jnp.maximum(
        (c1 - mean2) * jax.lax.rsqrt(var2 + eps) * gamma2 + beta2, 0.0)
    out = jax.lax.conv_general_dilated(
        pre2, w2.astype(jnp.float32), (1, 1), "SAME", dimension_numbers=dn)
    return (xf + out).astype(x.dtype), (mean1, var1, mean2, var2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def block_apply(x, w1, w2, s1, b1, s2, b2, batch_tile=16, interpret=None,
                bwd_batch_tile=None):
    """Differentiable fused block: Pallas forward + Pallas backward with
    in-kernel activation recompute (only ``x`` is saved — no residual
    tensors in HBM). Drop-in for ``block_fwd_reference`` under
    ``jax.grad``.

    ``bwd_batch_tile`` (default: ``batch_tile`` // 2, min 1) sizes the
    backward kernel's tile separately — its VMEM live set is ~2-3× the
    forward's (recomputed chain + gradient chain + wgrad accumulators),
    so a forward-tuned tile can exceed the ~16 MB core VMEM."""
    return block_fwd(x, w1, w2, s1, b1, s2, b2, batch_tile=batch_tile,
                     interpret=interpret)


def _block_apply_fwd(x, w1, w2, s1, b1, s2, b2, batch_tile, interpret,
                     bwd_batch_tile):
    y = block_fwd(x, w1, w2, s1, b1, s2, b2, batch_tile=batch_tile,
                  interpret=interpret)
    return y, (x, w1, w2, s1, b1, s2, b2)


def _block_apply_bwd(batch_tile, interpret, bwd_batch_tile, res, gy):
    x, w1, w2, s1, b1, s2, b2 = res
    if bwd_batch_tile is None:
        bwd_batch_tile = _default_bwd_tile(x.shape[0], batch_tile)
    dx, dw1, dw2, ds1, db1, ds2, db2 = _block_bwd_call(
        x, gy, w1, w2, s1, b1, s2, b2, batch_tile=bwd_batch_tile,
        interpret=interpret)
    return (dx, dw1.astype(w1.dtype), dw2.astype(w2.dtype),
            ds1.astype(s1.dtype), db1.astype(b1.dtype),
            ds2.astype(s2.dtype), db2.astype(b2.dtype))


block_apply.defvjp(_block_apply_fwd, _block_apply_bwd)
