"""EXPERIMENTAL: the ResNet-v2 bottleneck block as a halo-tiled fused
Pallas TPU kernel — the ImageNet analog of ``ops/fused_block.py``.

Motivation (docs/PERF.md "ImageNet MFU"): XLA never fuses convolutions
into each other, so each bottleneck block materializes its 1×1→3×3→1×1
intermediates to HBM (~hundreds of MB per block at 224²-scale); measured
AI is 80 FLOP/byte vs the ~240 a v5e needs, parking MFU at ~37%. This
kernel executes the whole stride-1 identity bottleneck — scale-bias,
ReLU, 1×1 reduce, BN-ReLU, 3×3, BN-ReLU, 1×1 expand, residual add — in
one VMEM-resident program per (batch, row-band) tile: one read of x and
one write of y per block.

Halo tiling: the single 3×3 needs one neighbor row per side. Pallas
BlockSpecs can't overlap, so the halo rows ride separate single-row
input specs whose index maps are row-granular (block H = 1 ⇒ block index
= row index), clamped at the image boundary and zero-masked in-kernel so
SAME-conv padding semantics are exact. The backward reads an x halo of
two rows (the recomputed chain needs mid at ±1, hence p2 at ±2) via
2-row specs, and a gy halo of one row.

Scope: stride 1, identity shortcut, folded BN (stats supplied as
scale/bias — eval semantics; the live-batch-stats training variant
follows ops/fused_block.py's staging and is deferred until the A/B).
Channel plans f ∈ {64, 128, 256} cover 10 of ResNet-50's 12 identity
bottlenecks; f=512 (7²×2048) is excluded — its three weight matrices
alone (3·3·512² + 2·512·2048 fp32 ≈ 17.8 MB) exceed the ~16 MB core
VMEM. ``bottleneck_apply`` is differentiable (custom VJP, backward
recomputes the forward chain in VMEM from x alone).

Battery stage 55 A/Bs both directions against XLA's compilation of the
identical math (``bottleneck_fwd_reference``) at the rn50 stage shapes,
gated on the basic-block A/B (stage 05) having proven block fusion.

Reference block semantics: v2 preactivation bottleneck,
reference resnet_model_official.py:133-175 (bottleneck_block_v2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_resnet.ops.fused_block import (_acc_out, _conv3x3_taps,
                                        _transpose_weights, _wgrad_taps,
                                        is_tpu_backend)

# (batch_tile, row_tile) by bottleneck width f — sized so the backward's
# recomputed chain + gradient chain + weight-grad accumulators stay under
# VMEM at the rn50 stage shapes (56²/28²/14² @ H=W).
_DEFAULT_TILES = {64: (1, 14), 128: (2, 14), 256: (4, 14)}


def _tiles_for(f: int, b: int, h: int, batch_tile=None, row_tile=None):
    if f not in _DEFAULT_TILES and (batch_tile is None or row_tile is None):
        raise ValueError(
            f"no default tile plan for f={f} (have {sorted(_DEFAULT_TILES)}"
            "); pass batch_tile/row_tile explicitly")
    dbt, dht = _DEFAULT_TILES.get(f, (None, None))
    bt = batch_tile or dbt
    ht = row_tile or dht
    bt = min(bt, b)
    ht = min(ht, h)
    if row_tile is None:
        # Default plans are sized for the rn50 stage heights (56/28/14);
        # other heights (64² inputs → 16, tiny test shapes) take the
        # largest even divisor at or under the default.
        while ht > 1 and (h % ht or ht % 2):
            ht -= 1
    if b % bt:
        raise ValueError(f"batch {b} not divisible by batch_tile {bt}")
    if h % ht:
        raise ValueError(f"height {h} not divisible by row_tile {ht}")
    if ht % 2:
        # 2-row backward halo specs index in 2-row blocks; odd tiles would
        # misalign them.
        raise ValueError(f"row_tile must be even (height {h} has no even "
                         f"divisor <= {min(dht or h, h)})"
                         if row_tile is None else
                         f"row_tile must be even, got {ht}")
    return bt, ht


def _row_mask(rows, lo, hi, x):
    """Zero rows whose global index falls outside [lo, hi)."""
    valid = (rows >= lo) & (rows < hi)
    return jnp.where(valid[None, :, None, None], x, 0.0)


def _specs(bt, ht, wdt, c, n_h):
    """(center, top1, bot1) BlockSpecs for a [B,H,W,C] operand with a
    one-row halo. Boundary clamping leaves garbage rows that callers must
    mask by global row index."""
    center = pl.BlockSpec((bt, ht, wdt, c),
                          lambda bi, hi: (bi, hi, 0, 0))
    top = pl.BlockSpec((bt, 1, wdt, c),
                       lambda bi, hi: (bi, jnp.maximum(hi * ht - 1, 0),
                                       0, 0))
    bot = pl.BlockSpec((bt, 1, wdt, c),
                       lambda bi, hi: (bi,
                                       jnp.minimum((hi + 1) * ht,
                                                   n_h * ht - 1), 0, 0))
    return center, top, bot


def _specs2(bt, ht, wdt, c, n_h):
    """(top2, bot2) 2-row halo specs (block H = 2 ⇒ index in 2-row
    units; ht is even so the halo start ht·hi − 2 is always aligned)."""
    top = pl.BlockSpec((bt, 2, wdt, c),
                       lambda bi, hi: (bi,
                                       jnp.maximum(hi * ht - 2, 0) // 2,
                                       0, 0))
    bot = pl.BlockSpec((bt, 2, wdt, c),
                       lambda bi, hi: (bi,
                                       jnp.minimum((hi + 1) * ht,
                                                   n_h * ht - 2) // 2,
                                       0, 0))
    return top, bot


def _global_rows(hi, ht, halo):
    """Global row indices of an (ht + 2·halo)-row extended tile (2-D
    iota then squeeze — TPU Pallas rejects 1-D iota)."""
    start = hi * ht - halo
    n = ht + 2 * halo
    return start + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)[:, 0]


def _chain_fwd(x_ext, rows, height, w1, s1, b1, s2, b2):
    """Recompute the pre-3×3 chain on an extended row band: returns
    (m1, p1, c1, m2, p2_masked) where p2 is zero at out-of-image rows
    (exact SAME-conv padding)."""
    m1 = x_ext * s1 + b1
    p1 = jnp.maximum(m1, 0.0)
    bt, hext, wdt, _ = x_ext.shape
    f = w1.shape[-1]
    c1 = jnp.dot(p1.reshape(bt * hext * wdt, -1), w1,
                 preferred_element_type=jnp.float32).reshape(
                     bt, hext, wdt, f)
    m2 = c1 * s2 + b2
    p2 = _row_mask(rows, 0, height, jnp.maximum(m2, 0.0))
    return m1, p1, c1, m2, p2


def _fwd_kernel(height, x_c_ref, x_t_ref, x_b_ref, w1_ref, w2_ref,
                w3_ref, s1_ref, b1_ref, s2_ref, b2_ref, s3_ref, b3_ref,
                o_ref):
    bt, ht, wdt, c4 = x_c_ref.shape
    hi = pl.program_id(1)
    x_ext = jnp.concatenate([
        x_t_ref[...], x_c_ref[...], x_b_ref[...]], axis=1).astype(
            jnp.float32)
    rows = _global_rows(hi, ht, 1)
    w2 = w2_ref[...].astype(jnp.float32)
    _, _, _, _, p2 = _chain_fwd(
        x_ext, rows, height, w1_ref[...].astype(jnp.float32),
        s1_ref[...], b1_ref[...], s2_ref[...], b2_ref[...])
    f = p2.shape[-1]
    p2p = jnp.pad(p2, ((0, 0), (0, 0), (1, 1), (0, 0)))
    mid = _conv3x3_taps(p2p, w2, bt, ht, wdt, f)
    m3 = mid * s3_ref[...] + b3_ref[...]
    p3 = jnp.maximum(m3, 0.0)
    r = jnp.dot(p3.reshape(bt * ht * wdt, f), w3_ref[...].astype(
        jnp.float32), preferred_element_type=jnp.float32).reshape(
            bt, ht, wdt, c4)
    o_ref[...] = (x_c_ref[...].astype(jnp.float32) + r).astype(o_ref.dtype)


def _plumb(x, batch_tile, row_tile, interpret, f):
    if interpret is None:
        interpret = not is_tpu_backend()
    b, h, wdt, c4 = x.shape
    bt, ht = _tiles_for(f, b, h, batch_tile, row_tile)
    grid = (b // bt, h // ht)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"))
    full = lambda *shape: pl.BlockSpec(
        shape, lambda bi, hi: (0,) * len(shape))
    return interpret, bt, ht, grid, full, kwargs


def bottleneck_fwd(x, w1, w2, w3, s1, b1, s2, b2, s3, b3, *,
                   batch_tile: int | None = None,
                   row_tile: int | None = None,
                   interpret: bool | None = None):
    """Fused v2 bottleneck forward (stride 1, identity shortcut).

    x [B,H,W,4f]; w1 [4f,f]; w2 [3,3,f,f]; w3 [f,4f]; s/b pairs are the
    three folded BNs ([4f], [f], [f]). Returns the same dtype as x.
    """
    f = w1.shape[-1]
    interpret, bt, ht, grid, full, kwargs = _plumb(
        x, batch_tile, row_tile, interpret, f)
    b, h, wdt, c4 = x.shape
    n_h = grid[1]
    center, top, bot = _specs(bt, ht, wdt, c4, n_h)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, h),
        grid=grid,
        in_specs=[center, top, bot,
                  full(c4, f), full(3, 3, f, f), full(f, c4),
                  full(c4), full(c4), full(f), full(f), full(f), full(f)],
        out_specs=center,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        **kwargs,
    )(x, x, x, w1, w2, w3, s1, b1, s2, b2, s3, b3)


@jax.jit
def bottleneck_fwd_reference(x, w1, w2, w3, s1, b1, s2, b2, s3, b3):
    """The identical math as XLA compiles it (the A/B's other arm and the
    correctness oracle for tests)."""
    xf = x.astype(jnp.float32)
    p1 = jnp.maximum(xf * s1 + b1, 0.0)
    c1 = jnp.einsum("bhwc,cf->bhwf", p1, w1.astype(jnp.float32))
    p2 = jnp.maximum(c1 * s2 + b2, 0.0)
    mid = jax.lax.conv_general_dilated(
        p2, w2.astype(jnp.float32), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    p3 = jnp.maximum(mid * s3 + b3, 0.0)
    r = jnp.einsum("bhwf,fc->bhwc", p3, w3.astype(jnp.float32))
    return (xf + r).astype(x.dtype)


# --------------------------------------------------------------------------
# Backward: one kernel, chain recomputed in VMEM from a 2-row x halo
# --------------------------------------------------------------------------

def _transposed(w1, w2, w3):
    """(w1ᵀ, convT form of w2, w3ᵀ) — computed by XLA outside the
    kernels and handed to the backward kernels as inputs."""
    return w1.T, _transpose_weights(w2), w3.T


def _bwd_kernel(height, x_c_ref, x_t_ref, x_b_ref, gy_c_ref, gy_t_ref,
                gy_b_ref, w1_ref, w2_ref, w3_ref, w1t_ref, w2t_ref, w3t_ref,
                s1_ref, b1_ref, s2_ref, b2_ref, s3_ref, b3_ref, dx_ref, dw1_ref, dw2_ref, dw3_ref,
                ds1_ref, db1_ref, ds2_ref, db2_ref, ds3_ref, db3_ref):
    bt, ht, wdt, c4 = x_c_ref.shape
    bi, hi = pl.program_id(0), pl.program_id(1)
    f = w1_ref.shape[-1]
    w1 = w1_ref[...].astype(jnp.float32)
    w2 = w2_ref[...].astype(jnp.float32)
    w3 = w3_ref[...].astype(jnp.float32)
    # Transposed forms arrive as inputs (_transposed): the 3×3 flip is a
    # ``rev``, which Mosaic does not lower.
    w1t = w1t_ref[...].astype(jnp.float32)
    w2t = w2t_ref[...].astype(jnp.float32)
    w3t = w3t_ref[...].astype(jnp.float32)
    s1, b1 = s1_ref[...], b1_ref[...]
    s2, b2 = s2_ref[...], b2_ref[...]
    s3, b3 = s3_ref[...], b3_ref[...]

    # Extended bands: x at ±2 rows, gy at ±1.
    x_ext = jnp.concatenate([x_t_ref[...], x_c_ref[...], x_b_ref[...]],
                            axis=1).astype(jnp.float32)
    gy_ext = jnp.concatenate([gy_t_ref[...], gy_c_ref[...], gy_b_ref[...]],
                             axis=1).astype(jnp.float32)
    rows2 = _global_rows(hi, ht, 2)          # ht + 4 rows
    rows1 = _global_rows(hi, ht, 1)          # ht + 2 rows
    gy_ext = _row_mask(rows1, 0, height, gy_ext)

    # Recompute the pre-3×3 chain on the ±2 band.
    m1, p1, c1, m2, p2 = _chain_fwd(x_ext, rows2, height, w1,
                                    s1, b1, s2, b2)
    # mid on the ±1 band (valid-H conv of the ±2 band).
    p2p = jnp.pad(p2, ((0, 0), (0, 0), (1, 1), (0, 0)))
    mid_ext = _conv3x3_taps(p2p, w2, bt, ht + 2, wdt, f)
    m3_ext = mid_ext * s3 + b3
    p3_ext = jnp.maximum(m3_ext, 0.0)

    # dmid on the ±1 band (gy halo is zero-masked outside the image).
    dp3 = jnp.dot(gy_ext.reshape(bt * (ht + 2) * wdt, c4), w3t,
                  preferred_element_type=jnp.float32).reshape(
                      bt, ht + 2, wdt, f)
    dm3 = jnp.where(m3_ext > 0, dp3, 0.0)
    dmid_ext = dm3 * s3

    # dp2 at center rows via the transposed 3×3 over the dmid band.
    dmid_p = jnp.pad(dmid_ext, ((0, 0), (0, 0), (1, 1), (0, 0)))
    dp2 = _conv3x3_taps(dmid_p, w2t, bt, ht, wdt, f)
    m2_c = m2[:, 2:2 + ht]
    dm2 = jnp.where(m2_c > 0, dp2, 0.0)
    dc1 = dm2 * s2

    # dx at center rows.
    dp1 = jnp.dot(dc1.reshape(bt * ht * wdt, f), w1t,
                  preferred_element_type=jnp.float32).reshape(
                      bt, ht, wdt, c4)
    m1_c = m1[:, 2:2 + ht]
    dm1 = jnp.where(m1_c > 0, dp1, 0.0)
    gy_c = gy_ext[:, 1:1 + ht]
    dx_ref[...] = (gy_c + dm1 * s1).astype(dx_ref.dtype)

    # Parameter grads, position-assigned to center rows (each global
    # position is the center of exactly one tile). dw2's input patches
    # span the ±1 p2 band; its output positions are the center mid rows.
    dmid_c = dmid_ext[:, 1:1 + ht]
    mid_c = mid_ext[:, 1:1 + ht]
    dm3_c = dm3[:, 1:1 + ht]
    p3_c = p3_ext[:, 1:1 + ht]
    p2_band = p2[:, 1:1 + ht + 2]            # rows ±1
    p2_band_p = jnp.pad(p2_band, ((0, 0), (0, 0), (1, 1), (0, 0)))
    x_c = x_ext[:, 2:2 + ht]
    c1_c = c1[:, 2:2 + ht]
    p1_c = p1[:, 2:2 + ht]

    dw1 = jnp.dot(p1_c.reshape(bt * ht * wdt, c4).T,
                  dc1.reshape(bt * ht * wdt, f),
                  preferred_element_type=jnp.float32)
    dw2 = _wgrad_taps(p2_band_p, dmid_c, bt, ht, wdt, f)
    dw3 = jnp.dot(p3_c.reshape(bt * ht * wdt, f).T,
                  gy_c.reshape(bt * ht * wdt, c4),
                  preferred_element_type=jnp.float32)
    ds1 = jnp.sum(dm1 * x_c, axis=(0, 1, 2))
    db1 = jnp.sum(dm1, axis=(0, 1, 2))
    ds2 = jnp.sum(dm2 * c1_c, axis=(0, 1, 2))
    db2 = jnp.sum(dm2, axis=(0, 1, 2))
    ds3 = jnp.sum(dm3_c * mid_c, axis=(0, 1, 2))
    db3 = jnp.sum(dm3_c, axis=(0, 1, 2))

    _acc_out((bi == 0) & (hi == 0),
          (dw1_ref, dw2_ref, dw3_ref, ds1_ref, db1_ref, ds2_ref, db2_ref,
           ds3_ref, db3_ref),
          (dw1, dw2, dw3, ds1, db1, ds2, db2, ds3, db3))


def _bwd_call(x, gy, w1, w2, w3, s1, b1, s2, b2, s3, b3, *,
              batch_tile, row_tile, interpret):
    f = w1.shape[-1]
    interpret, bt, ht, grid, full, kwargs = _plumb(
        x, batch_tile, row_tile, interpret, f)
    b, h, wdt, c4 = x.shape
    n_h = grid[1]
    center, gy_top, gy_bot = _specs(bt, ht, wdt, c4, n_h)
    x_top2, x_bot2 = _specs2(bt, ht, wdt, c4, n_h)
    f32 = jnp.float32
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, h),
        grid=grid,
        in_specs=[center, x_top2, x_bot2, center, gy_top, gy_bot,
                  full(c4, f), full(3, 3, f, f), full(f, c4),
                  full(f, c4), full(3, 3, f, f), full(c4, f),
                  full(c4), full(c4), full(f), full(f), full(f), full(f)],
        out_specs=[center,
                   full(c4, f), full(3, 3, f, f), full(f, c4),
                   full(c4), full(c4), full(f), full(f), full(f), full(f)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((c4, f), f32),
                   jax.ShapeDtypeStruct((3, 3, f, f), f32),
                   jax.ShapeDtypeStruct((f, c4), f32),
                   jax.ShapeDtypeStruct((c4,), f32),
                   jax.ShapeDtypeStruct((c4,), f32),
                   jax.ShapeDtypeStruct((f,), f32),
                   jax.ShapeDtypeStruct((f,), f32),
                   jax.ShapeDtypeStruct((f,), f32),
                   jax.ShapeDtypeStruct((f,), f32)],
        interpret=interpret,
        **kwargs,
    )(x, x, x, gy, gy, gy, w1, w2, w3, *_transposed(w1, w2, w3),
      s1, b1, s2, b2, s3, b3)
    return outs


@functools.partial(jax.custom_vjp, nondiff_argnums=(10, 11, 12))
def bottleneck_apply(x, w1, w2, w3, s1, b1, s2, b2, s3, b3,
                     batch_tile=None, row_tile=None, interpret=None):
    """Differentiable fused bottleneck: Pallas forward + Pallas backward
    with in-kernel chain recompute (only ``x`` is saved — no bottleneck
    intermediates ever reach HBM). Drop-in for
    ``bottleneck_fwd_reference`` under ``jax.grad``."""
    return bottleneck_fwd(x, w1, w2, w3, s1, b1, s2, b2, s3, b3,
                          batch_tile=batch_tile, row_tile=row_tile,
                          interpret=interpret)


def _apply_fwd(x, w1, w2, w3, s1, b1, s2, b2, s3, b3, batch_tile,
               row_tile, interpret):
    y = bottleneck_fwd(x, w1, w2, w3, s1, b1, s2, b2, s3, b3,
                       batch_tile=batch_tile, row_tile=row_tile,
                       interpret=interpret)
    return y, (x, w1, w2, w3, s1, b1, s2, b2, s3, b3)


def _apply_bwd(batch_tile, row_tile, interpret, res, gy):
    x, w1, w2, w3, s1, b1, s2, b2, s3, b3 = res
    dx, dw1, dw2, dw3, ds1, db1, ds2, db2, ds3, db3 = _bwd_call(
        x, gy.astype(jnp.float32), w1, w2, w3, s1, b1, s2, b2, s3, b3,
        batch_tile=batch_tile, row_tile=row_tile, interpret=interpret)
    return (dx, dw1.astype(w1.dtype), dw2.astype(w2.dtype),
            dw3.astype(w3.dtype), ds1.astype(s1.dtype),
            db1.astype(b1.dtype), ds2.astype(s2.dtype),
            db2.astype(b2.dtype), ds3.astype(s3.dtype),
            db3.astype(b3.dtype))


bottleneck_apply.defvjp(_apply_fwd, _apply_bwd)


# --------------------------------------------------------------------------
# Training path: live batch-norm statistics
# --------------------------------------------------------------------------
#
# Forward is staged like ops/fused_block.py's two-pass design, extended to
# the bottleneck's three BNs: BN1's moments are one cheap XLA reduction
# over x; BN2 normalizes c1 (pointwise + 1×1 — no halo), whose moments
# pass A accumulates; BN3 normalizes mid (the 3×3 output — 1-row halo),
# whose moments pass B accumulates; the apply pass is the folded forward
# kernel above. c1 and mid are recomputed, never written to HBM.
#
# Backward: with live moments each BN's VJP carries batch-wide correction
# sums (du = γ/σ·(dz − ΣB dz/N − ẑ·ΣB dz⊙ẑ/N); the sums are exactly
# dβ/dγ). Three BNs chain, so the sums cascade across FOUR tile passes,
# each recomputing the chain in VMEM from (x, params, saved moments):
#   pass 1: T3 = (Σdm3, Σdm3⊙m̂) and dw3           (x halo 2, gy halo 1)
#   pass 2: finish dmid with T3; T2 = (Σdm2, Σdm2⊙ĉ) and dw2
#   pass 3: finish dc1 with T2; T1 = (Σdm1, Σdm1⊙x̂) and dw1
#   pass 4: finish dx with T1.
# The moments output of bottleneck_train_fwd gets a zero cotangent
# (running-stats EMA is stop-gradient, flax convention).


def _fold_bn(g, be, mean, inv):
    return g * inv, be - mean * g * inv


def _chain_train(x_ext, rows, height, w1, g1, be1, mu1, i1, g2, be2,
                 mu2, i2):
    """Training-chain recompute on an extended band with RAW BN params
    (normalized forms are needed for the correction sums): returns
    (x̂1, m1, p1, c1, ĉ, m2, p2_masked)."""
    x1hat = (x_ext - mu1) * i1
    m1 = g1 * x1hat + be1
    p1 = jnp.maximum(m1, 0.0)
    bt, hext, wdt, _ = x_ext.shape
    f = w1.shape[-1]
    c1 = jnp.dot(p1.reshape(bt * hext * wdt, -1), w1,
                 preferred_element_type=jnp.float32).reshape(
                     bt, hext, wdt, f)
    chat = (c1 - mu2) * i2
    m2 = g2 * chat + be2
    p2 = _row_mask(rows, 0, height, jnp.maximum(m2, 0.0))
    return x1hat, m1, p1, c1, chat, m2, p2


def _stats_a_kernel(x_ref, w1_ref, g1_ref, be1_ref, mu1_ref, i1_ref,
                    sum_ref, sumsq_ref):
    """c1 sum / sum-of-squares over center rows (no conv upstream of c1,
    so no halo)."""
    bt, ht, wdt, c4 = x_ref.shape
    bi, hi = pl.program_id(0), pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    p1 = jnp.maximum(g1_ref[...] * (x - mu1_ref[...]) * i1_ref[...]
                     + be1_ref[...], 0.0)
    f = w1_ref.shape[-1]
    c1 = jnp.dot(p1.reshape(bt * ht * wdt, c4),
                 w1_ref[...].astype(jnp.float32),
                 preferred_element_type=jnp.float32).reshape(
                     bt, ht, wdt, f)
    _acc_out((bi == 0) & (hi == 0), (sum_ref, sumsq_ref),
             (jnp.sum(c1, axis=(0, 1, 2)),
              jnp.sum(c1 * c1, axis=(0, 1, 2))))


def _stats_b_kernel(height, x_c_ref, x_t_ref, x_b_ref, w1_ref, w2_ref,
                    g1_ref, be1_ref, mu1_ref, i1_ref, g2_ref, be2_ref,
                    mu2_ref, i2_ref, sum_ref, sumsq_ref):
    """mid sum / sum-of-squares over center rows (one conv upstream —
    1-row halo)."""
    bt, ht, wdt, c4 = x_c_ref.shape
    bi, hi = pl.program_id(0), pl.program_id(1)
    x_ext = jnp.concatenate([x_t_ref[...], x_c_ref[...], x_b_ref[...]],
                            axis=1).astype(jnp.float32)
    rows = _global_rows(hi, ht, 1)
    w2 = w2_ref[...].astype(jnp.float32)
    _, _, _, _, _, _, p2 = _chain_train(
        x_ext, rows, height, w1_ref[...].astype(jnp.float32),
        g1_ref[...], be1_ref[...], mu1_ref[...], i1_ref[...],
        g2_ref[...], be2_ref[...], mu2_ref[...], i2_ref[...])
    f = p2.shape[-1]
    p2p = jnp.pad(p2, ((0, 0), (0, 0), (1, 1), (0, 0)))
    mid = _conv3x3_taps(p2p, w2, bt, ht, wdt, f)
    _acc_out((bi == 0) & (hi == 0), (sum_ref, sumsq_ref),
             (jnp.sum(mid, axis=(0, 1, 2)),
              jnp.sum(mid * mid, axis=(0, 1, 2))))


def bottleneck_train_fwd(x, w1, w2, w3, g1, be1, g2, be2, g3, be3,
                         eps: float = 1e-5, *,
                         batch_tile: int | None = None,
                         row_tile: int | None = None,
                         interpret: bool | None = None):
    """Fused v2 bottleneck with LIVE batch-norm statistics (training
    semantics, biased variance like flax BatchNorm's batch moments).

    Returns ``(y, (m1, v1, m2, v2, m3, v3))`` — the moments feed the
    caller's running-stats EMA exactly as the unfused BN layers would."""
    f = w1.shape[-1]
    interpret, bt, ht, grid, full, kwargs = _plumb(
        x, batch_tile, row_tile, interpret, f)
    b, h, wdt, c4 = x.shape
    n_h = grid[1]
    center, top, bot = _specs(bt, ht, wdt, c4, n_h)
    f32 = jnp.float32
    n = float(b * h * wdt)

    xf32 = x.astype(f32)
    mu1 = jnp.mean(xf32, axis=(0, 1, 2))
    v1 = jnp.var(xf32, axis=(0, 1, 2))
    i1 = jax.lax.rsqrt(v1 + eps)

    s_c1, ss_c1 = pl.pallas_call(
        _stats_a_kernel, grid=grid,
        in_specs=[center, full(c4, f)] + [full(c4)] * 4,
        out_specs=[full(f), full(f)],
        out_shape=[jax.ShapeDtypeStruct((f,), f32)] * 2,
        interpret=interpret, **kwargs,
    )(x, w1, g1, be1, mu1, i1)
    mu2 = s_c1 / n
    # Single-pass variance clamped: fp32 cancellation (large mean, tiny
    # variance) must not NaN the rsqrt (same guard as fused_block).
    v2 = jnp.maximum(ss_c1 / n - mu2 * mu2, 0.0)
    i2 = jax.lax.rsqrt(v2 + eps)

    s_m, ss_m = pl.pallas_call(
        functools.partial(_stats_b_kernel, h), grid=grid,
        in_specs=([center, top, bot, full(c4, f), full(3, 3, f, f)]
                  + [full(c4)] * 4 + [full(f)] * 4),
        out_specs=[full(f), full(f)],
        out_shape=[jax.ShapeDtypeStruct((f,), f32)] * 2,
        interpret=interpret, **kwargs,
    )(x, x, x, w1, w2, g1, be1, mu1, i1, g2, be2, mu2, i2)
    mu3 = s_m / n
    v3 = jnp.maximum(ss_m / n - mu3 * mu3, 0.0)
    i3 = jax.lax.rsqrt(v3 + eps)

    s1, b1 = _fold_bn(g1, be1, mu1, i1)
    s2, b2 = _fold_bn(g2, be2, mu2, i2)
    s3, b3 = _fold_bn(g3, be3, mu3, i3)
    y = bottleneck_fwd(x, w1, w2, w3, s1, b1, s2, b2, s3, b3,
                       batch_tile=batch_tile, row_tile=row_tile,
                       interpret=interpret)
    return y, (mu1, v1, mu2, v2, mu3, v3)


@jax.jit
def bottleneck_train_fwd_reference(x, w1, w2, w3, g1, be1, g2, be2, g3,
                                   be3, eps: float = 1e-5):
    """XLA oracle: the same training-BN bottleneck with batch moments."""
    xf = x.astype(jnp.float32)
    mu1 = jnp.mean(xf, axis=(0, 1, 2))
    v1 = jnp.var(xf, axis=(0, 1, 2))
    p1 = jnp.maximum(
        g1 * (xf - mu1) * jax.lax.rsqrt(v1 + eps) + be1, 0.0)
    c1 = jnp.einsum("bhwc,cf->bhwf", p1, w1.astype(jnp.float32))
    mu2 = jnp.mean(c1, axis=(0, 1, 2))
    v2 = jnp.var(c1, axis=(0, 1, 2))
    p2 = jnp.maximum(
        g2 * (c1 - mu2) * jax.lax.rsqrt(v2 + eps) + be2, 0.0)
    mid = jax.lax.conv_general_dilated(
        p2, w2.astype(jnp.float32), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    mu3 = jnp.mean(mid, axis=(0, 1, 2))
    v3 = jnp.var(mid, axis=(0, 1, 2))
    p3 = jnp.maximum(
        g3 * (mid - mu3) * jax.lax.rsqrt(v3 + eps) + be3, 0.0)
    r = jnp.einsum("bhwf,fc->bhwc", p3, w3.astype(jnp.float32))
    return (xf + r).astype(x.dtype), (mu1, v1, mu2, v2, mu3, v3)


def _chain_train_full(x_ext, rows2, height, w1, w2, g1, be1, mu1, i1,
                      g2, be2, mu2, i2, g3, be3, mu3, i3):
    """Training-chain recompute through the 3×3 on a ±2 band: everything
    the backward passes need. mid/m3/m̂/p3 come out on the ±1 band."""
    bt = x_ext.shape[0]
    wdt = x_ext.shape[2]
    ht = x_ext.shape[1] - 4
    f = w1.shape[-1]
    x1hat, m1, p1, c1, chat, m2, p2 = _chain_train(
        x_ext, rows2, height, w1, g1, be1, mu1, i1, g2, be2, mu2, i2)
    p2p = jnp.pad(p2, ((0, 0), (0, 0), (1, 1), (0, 0)))
    mid_ext = _conv3x3_taps(p2p, w2, bt, ht + 2, wdt, f)
    mhat_ext = (mid_ext - mu3) * i3
    m3_ext = g3 * mhat_ext + be3
    p3_ext = jnp.maximum(m3_ext, 0.0)
    return (x1hat, m1, p1, c1, chat, m2, p2, mid_ext, mhat_ext, m3_ext,
            p3_ext)


def _train_bwd_calls(x, gy, w1, w2, w3, g1, be1, g2, be2, g3, be3,
                     moments, eps, *, batch_tile, row_tile, interpret):
    mu1, v1, mu2, v2, mu3, v3 = moments
    i1 = jax.lax.rsqrt(v1 + eps)
    i2 = jax.lax.rsqrt(v2 + eps)
    i3 = jax.lax.rsqrt(v3 + eps)
    f = w1.shape[-1]
    interpret, bt, ht, grid, full, kwargs = _plumb(
        x, batch_tile, row_tile, interpret, f)
    b, h, wdt, c4 = x.shape
    n_h = grid[1]
    n = float(b * h * wdt)
    f32 = jnp.float32
    center, gy_top, gy_bot = _specs(bt, ht, wdt, c4, n_h)
    x_top2, x_bot2 = _specs2(bt, ht, wdt, c4, n_h)

    # x (center, ±2 halo), gy (center, ±1 halo), 3 weights and their
    # transposed forms, 12 BN vectors
    base_in = ([center, x_top2, x_bot2, center, gy_top, gy_bot,
                full(c4, f), full(3, 3, f, f), full(f, c4),
                full(f, c4), full(3, 3, f, f), full(c4, f)]
               + [full(c4)] * 4 + [full(f)] * 8)
    base_ops = (x, x, x, gy, gy, gy, w1, w2, w3, *_transposed(w1, w2, w3),
                g1, be1, mu1, i1, g2, be2, mu2, i2, g3, be3, mu3, i3)
    fshape = jax.ShapeDtypeStruct((f,), f32)
    c4shape = jax.ShapeDtypeStruct((c4,), f32)

    def load(refs):
        (x_c, x_t, x_b, gy_c, gy_t, gy_b, w1_r, w2_r, w3_r,
         w1t_r, w2t_r, w3t_r,
         g1_r, be1_r, mu1_r, i1_r, g2_r, be2_r, mu2_r, i2_r,
         g3_r, be3_r, mu3_r, i3_r) = refs
        hi = pl.program_id(1)
        x_ext = jnp.concatenate(
            [x_t[...], x_c[...], x_b[...]], axis=1).astype(f32)
        gy_ext = jnp.concatenate(
            [gy_t[...], gy_c[...], gy_b[...]], axis=1).astype(f32)
        rows2 = _global_rows(hi, ht, 2)
        rows1 = _global_rows(hi, ht, 1)
        gy_ext = _row_mask(rows1, 0, h, gy_ext)
        chain = _chain_train_full(
            x_ext, rows2, h, w1_r[...].astype(f32),
            w2_r[...].astype(f32), g1_r[...], be1_r[...], mu1_r[...],
            i1_r[...], g2_r[...], be2_r[...], mu2_r[...], i2_r[...],
            g3_r[...], be3_r[...], mu3_r[...], i3_r[...])
        return (x_ext, gy_ext, rows1, w1t_r[...].astype(f32),
                w2t_r[...].astype(f32), w3t_r[...].astype(f32),
                g1_r[...], i1_r[...], g2_r[...], i2_r[...],
                g3_r[...], i3_r[...], chain)

    def _dm3_ext(gy_ext, m3_ext, w3tv):
        bte, hext, _, _ = gy_ext.shape
        dp3 = jnp.dot(gy_ext.reshape(bte * hext * wdt, c4), w3tv,
                      preferred_element_type=f32).reshape(
                          bte, hext, wdt, f)
        return jnp.where(m3_ext > 0, dp3, 0.0)

    # -- pass 1: T3 sums + dw3 (all from center rows) ----------------------
    def pass1(*refs):
        t3a_ref, t3b_ref, dw3_ref = refs[-3:]
        (x_ext, gy_ext, rows1, w1tv, w2tv, w3tv, g1v, i1v, g2v, i2v, g3v,
         i3v, chain) = load(refs[:-3])
        (_, _, _, _, _, _, _, _, mhat_ext, m3_ext, p3_ext) = chain
        dm3 = _dm3_ext(gy_ext, m3_ext, w3tv)
        dm3_c = dm3[:, 1:1 + ht]
        mhat_c = mhat_ext[:, 1:1 + ht]
        p3_c = p3_ext[:, 1:1 + ht]
        gy_c = gy_ext[:, 1:1 + ht]
        dw3 = jnp.dot(p3_c.reshape(bt * ht * wdt, f).T,
                      gy_c.reshape(bt * ht * wdt, c4),
                      preferred_element_type=f32)
        bi, hi = pl.program_id(0), pl.program_id(1)
        _acc_out((bi == 0) & (hi == 0), (t3a_ref, t3b_ref, dw3_ref),
                 (jnp.sum(dm3_c, axis=(0, 1, 2)),
                  jnp.sum(dm3_c * mhat_c, axis=(0, 1, 2)), dw3))

    t3a, t3b, dw3 = pl.pallas_call(
        pass1, grid=grid, in_specs=base_in,
        out_specs=[full(f), full(f), full(f, c4)],
        out_shape=[fshape, fshape, jax.ShapeDtypeStruct((f, c4), f32)],
        interpret=interpret, **kwargs,
    )(*base_ops)

    def _dmid_ext(gy_ext, m3_ext, mhat_ext, rows1, w3tv, g3v, i3v,
                  t3av, t3bv):
        dm3 = _dm3_ext(gy_ext, m3_ext, w3tv)
        dmid = g3v * i3v * (dm3 - t3av / n - mhat_ext * (t3bv / n))
        # The correction sums are nonzero even where dm3 is zero — the
        # out-of-image halo rows must be re-masked or they pollute dp2.
        return _row_mask(rows1, 0, h, dmid)

    # -- pass 2: T2 sums + dw2 --------------------------------------------
    def pass2(*refs):
        t2a_ref, t2b_ref, dw2_ref = refs[-3:]
        t3a_r, t3b_r = refs[-5:-3]
        (x_ext, gy_ext, rows1, w1tv, w2tv, w3tv, g1v, i1v, g2v, i2v, g3v,
         i3v, chain) = load(refs[:-5])
        (_, _, _, c1, chat, m2, p2, _, mhat_ext, m3_ext, _) = chain
        dmid = _dmid_ext(gy_ext, m3_ext, mhat_ext, rows1, w3tv, g3v, i3v,
                         t3a_r[...], t3b_r[...])
        dmid_p = jnp.pad(dmid, ((0, 0), (0, 0), (1, 1), (0, 0)))
        dp2 = _conv3x3_taps(dmid_p, w2tv, bt, ht, wdt, f)
        m2_c = m2[:, 2:2 + ht]
        chat_c = chat[:, 2:2 + ht]
        dm2 = jnp.where(m2_c > 0, dp2, 0.0)
        p2_band_p = jnp.pad(p2[:, 1:1 + ht + 2],
                            ((0, 0), (0, 0), (1, 1), (0, 0)))
        dmid_c = dmid[:, 1:1 + ht]
        dw2 = _wgrad_taps(p2_band_p, dmid_c, bt, ht, wdt, f)
        bi, hi = pl.program_id(0), pl.program_id(1)
        _acc_out((bi == 0) & (hi == 0), (t2a_ref, t2b_ref, dw2_ref),
                 (jnp.sum(dm2, axis=(0, 1, 2)),
                  jnp.sum(dm2 * chat_c, axis=(0, 1, 2)), dw2))

    t2a, t2b, dw2 = pl.pallas_call(
        pass2, grid=grid, in_specs=base_in + [full(f), full(f)],
        out_specs=[full(f), full(f), full(3, 3, f, f)],
        out_shape=[fshape, fshape,
                   jax.ShapeDtypeStruct((3, 3, f, f), f32)],
        interpret=interpret, **kwargs,
    )(*base_ops, t3a, t3b)

    def _dm1_c(x_ext, gy_ext, rows1, chain, w1tv, w2tv, w3tv, g2v, i2v,
               g3v, i3v, t3av, t3bv, t2av, t2bv):
        (x1hat, m1, p1, c1, chat, m2, p2, _, mhat_ext, m3_ext, _) = chain
        dmid = _dmid_ext(gy_ext, m3_ext, mhat_ext, rows1, w3tv, g3v, i3v,
                         t3av, t3bv)
        dmid_p = jnp.pad(dmid, ((0, 0), (0, 0), (1, 1), (0, 0)))
        dp2 = _conv3x3_taps(dmid_p, w2tv, bt, ht, wdt, f)
        m2_c = m2[:, 2:2 + ht]
        chat_c = chat[:, 2:2 + ht]
        dm2 = jnp.where(m2_c > 0, dp2, 0.0)
        dc1 = g2v * i2v * (dm2 - t2av / n - chat_c * (t2bv / n))
        dp1 = jnp.dot(dc1.reshape(bt * ht * wdt, f), w1tv,
                      preferred_element_type=f32).reshape(
                          bt, ht, wdt, c4)
        m1_c = m1[:, 2:2 + ht]
        dm1 = jnp.where(m1_c > 0, dp1, 0.0)
        return dm1, dc1, x1hat[:, 2:2 + ht], p1[:, 2:2 + ht]

    # -- pass 3: T1 sums + dw1 --------------------------------------------
    def pass3(*refs):
        t1a_ref, t1b_ref, dw1_ref = refs[-3:]
        t3a_r, t3b_r, t2a_r, t2b_r = refs[-7:-3]
        (x_ext, gy_ext, rows1, w1tv, w2tv, w3tv, g1v, i1v, g2v, i2v, g3v,
         i3v, chain) = load(refs[:-7])
        dm1, dc1, x1hat_c, p1_c = _dm1_c(
            x_ext, gy_ext, rows1, chain, w1tv, w2tv, w3tv, g2v, i2v, g3v,
            i3v, t3a_r[...], t3b_r[...], t2a_r[...], t2b_r[...])
        dw1 = jnp.dot(p1_c.reshape(bt * ht * wdt, c4).T,
                      dc1.reshape(bt * ht * wdt, f),
                      preferred_element_type=f32)
        bi, hi = pl.program_id(0), pl.program_id(1)
        _acc_out((bi == 0) & (hi == 0), (t1a_ref, t1b_ref, dw1_ref),
                 (jnp.sum(dm1, axis=(0, 1, 2)),
                  jnp.sum(dm1 * x1hat_c, axis=(0, 1, 2)), dw1))

    t1a, t1b, dw1 = pl.pallas_call(
        pass3, grid=grid, in_specs=base_in + [full(f)] * 4,
        out_specs=[full(c4), full(c4), full(c4, f)],
        out_shape=[c4shape, c4shape,
                   jax.ShapeDtypeStruct((c4, f), f32)],
        interpret=interpret, **kwargs,
    )(*base_ops, t3a, t3b, t2a, t2b)

    # -- pass 4: dx --------------------------------------------------------
    def pass4(*refs):
        dx_ref = refs[-1]
        t3a_r, t3b_r, t2a_r, t2b_r, t1a_r, t1b_r = refs[-7:-1]
        (x_ext, gy_ext, rows1, w1tv, w2tv, w3tv, g1v, i1v, g2v, i2v, g3v,
         i3v, chain) = load(refs[:-7])
        dm1, _, x1hat_c, _ = _dm1_c(
            x_ext, gy_ext, rows1, chain, w1tv, w2tv, w3tv, g2v, i2v, g3v,
            i3v, t3a_r[...], t3b_r[...], t2a_r[...], t2b_r[...])
        gy_c = gy_ext[:, 1:1 + ht]
        dx = gy_c + g1v * i1v * (
            dm1 - t1a_r[...] / n - x1hat_c * (t1b_r[...] / n))
        dx_ref[...] = dx.astype(dx_ref.dtype)

    dx = pl.pallas_call(
        pass4, grid=grid,
        in_specs=base_in + [full(f)] * 4 + [full(c4)] * 2,
        out_specs=center,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret, **kwargs,
    )(*base_ops, t3a, t3b, t2a, t2b, t1a, t1b)

    # dγ_i / dβ_i are exactly the correction sums.
    return dx, dw1, dw2, dw3, t1b, t1a, t2b, t2a, t3b, t3a


@functools.partial(jax.custom_vjp, nondiff_argnums=(10, 11, 12, 13))
def bottleneck_train_apply(x, w1, w2, w3, g1, be1, g2, be2, g3, be3,
                           eps=1e-5, batch_tile=None, row_tile=None,
                           interpret=None):
    """Differentiable live-batch-stats fused bottleneck (training
    semantics): staged Pallas forward + four-pass Pallas backward with
    the full BN batch-moment correction cascade. Returns ``(y,
    moments)``; the moments output is stop-gradient (running-stats EMA
    convention)."""
    return bottleneck_train_fwd(x, w1, w2, w3, g1, be1, g2, be2, g3, be3,
                                eps, batch_tile=batch_tile,
                                row_tile=row_tile, interpret=interpret)


def _train_apply_fwd(x, w1, w2, w3, g1, be1, g2, be2, g3, be3, eps,
                     batch_tile, row_tile, interpret):
    y, moments = bottleneck_train_fwd(
        x, w1, w2, w3, g1, be1, g2, be2, g3, be3, eps,
        batch_tile=batch_tile, row_tile=row_tile, interpret=interpret)
    return (y, moments), (x, w1, w2, w3, g1, be1, g2, be2, g3, be3,
                          moments)


def _train_apply_bwd(eps, batch_tile, row_tile, interpret, res, cot):
    gy, _gmoments = cot  # moments cotangent dropped: EMA is stop-gradient
    x, w1, w2, w3, g1, be1, g2, be2, g3, be3, moments = res
    dx, dw1, dw2, dw3, dg1, db1, dg2, db2, dg3, db3 = _train_bwd_calls(
        x, gy.astype(jnp.float32), w1, w2, w3, g1, be1, g2, be2, g3, be3,
        moments, eps, batch_tile=batch_tile, row_tile=row_tile,
        interpret=interpret)
    return (dx.astype(x.dtype), dw1.astype(w1.dtype),
            dw2.astype(w2.dtype), dw3.astype(w3.dtype),
            dg1.astype(g1.dtype), db1.astype(be1.dtype),
            dg2.astype(g2.dtype), db2.astype(be2.dtype),
            dg3.astype(g3.dtype), db3.astype(be3.dtype))


bottleneck_train_apply.defvjp(_train_apply_fwd, _train_apply_bwd)
