"""Fused conv epilogues as Pallas TPU kernels — BN-affine → ReLU, and the
residual-add variant — with custom VJPs and an autotune-guarded dispatch.

The where-the-time-goes analysis (docs/PERF.md) shows XLA never fuses
convolutions into each other: every conv's output round-trips HBM before
its BatchNorm/ReLU epilogue reads it back. These kernels close the small
half of that gap — the epilogue chain itself runs as ONE VMEM pass over
the conv output:

- ``scale_bias_relu(x, s, b)``       = relu(x * s + b)
- ``scale_bias_relu_add(x, s, b, r)`` = relu(x * s + b) + r

``s``/``b`` are the folded BN affine (scale = gamma/sqrt(var+eps), bias
= beta - mean*scale) — the inference fold, and equally the training-path
form once the batch moments are in hand (the moments reduction is an
orthogonal XLA pass either way; see models/resnet.py EpilogueBatchNorm
integration). Backward recomputes the mask from ``x`` in VMEM — no
pre-activation residual is ever materialized in HBM — and accumulates
the per-channel ``ds``/``db`` sums across the sequential batch-tile grid
(the ``_acc_out`` idiom shared with ops/fused_block.py).

Every entry point here is A/B-guarded: ``*_auto`` dispatches to the
Pallas lowering only for shapes where :mod:`tpu_resnet.ops.autotune`
recorded a measured win, falling back to the identical XLA math
otherwise — the policy the xent kernel's negative result (0.90x, now
retuned; docs/PERF.md) made mandatory for every Pallas path.

``interpret=True`` (auto on non-TPU backends) runs the same kernels
under the Pallas interpreter for CPU parity tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_resnet.ops import autotune
from tpu_resnet.ops.softmax_xent import is_tpu_backend

# Autotune op ids (the keys under which decisions persist).
OP_SBR = "epilogue_sbr"
OP_SBR_ADD = "epilogue_sbr_add"


def scale_bias_relu_math(x, scale, bias):
    """The epilogue math itself — shared in-kernel helper (also imported
    by ops/fused_block.py, whose block kernels apply the same epilogue
    between their convs)."""
    return jnp.maximum(x * scale + bias, 0.0)


def _acc_out(first, refs, vals):
    """Init-or-accumulate outputs across a sequential grid; ``first`` is
    the predicate marking the first grid step. Canonical home of the
    idiom ops/fused_block.py imports."""
    @pl.when(first)
    def _init():
        for ref, v in zip(refs, vals):
            ref[...] = v

    @pl.when(jnp.logical_not(first))
    def _acc():
        for ref, v in zip(refs, vals):
            ref[...] += v


def vmem_row_bytes(h: int, w: int, c: int, itemsize: int = 4) -> int:
    """VMEM bytes of one [h, w, c] batch row as Mosaic lays it out: the
    two minor dims tile to (8, 128), so a 16-channel CIFAR stage occupies
    eight times its logical size (measured on the chip: the 32x32x16
    epilogue overflowed VMEM under a plan that counted logical bytes)."""
    return h * (-(-w // 8) * 8) * (-(-c // 128) * 128) * itemsize


def auto_batch_tile(shape, budget_bytes: int = 8 * 2 ** 20) -> int:
    """Largest batch divisor whose forward live set (~3 fp32 slabs: x,
    activation, out/residual) fits the VMEM plan budget. Epilogues are
    elementwise so any divisor is correct; bigger tiles amortize grid
    overhead."""
    b, h, w, c = shape
    per_row = vmem_row_bytes(h, w, c) * 3
    bt = max(1, min(b, budget_bytes // max(per_row, 1)))
    while b % bt:
        bt -= 1
    return int(bt)


def _plumbing(x, batch_tile, interpret):
    if interpret is None:
        interpret = not is_tpu_backend()
    b, h, w, c = x.shape
    bt = auto_batch_tile(x.shape) if batch_tile is None \
        else min(batch_tile, b)
    if b % bt:
        raise ValueError(f"batch {b} not divisible by batch_tile {bt}")
    grid = (b // bt,)
    tile = pl.BlockSpec((bt, h, w, c), lambda i: (i, 0, 0, 0))
    full = lambda *shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    return interpret, grid, tile, full, kwargs


# ------------------------------------------------------------------ forward
def _sbr_kernel(x_ref, s_ref, b_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = scale_bias_relu_math(
        x, s_ref[...], b_ref[...]).astype(o_ref.dtype)


def _sbr_add_kernel(x_ref, s_ref, b_ref, r_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    r = r_ref[...].astype(jnp.float32)
    o_ref[...] = (scale_bias_relu_math(x, s_ref[...], b_ref[...])
                  + r).astype(o_ref.dtype)


def _sbr_call(x, scale, bias, *, batch_tile, interpret):
    interpret, grid, tile, full, kwargs = _plumbing(x, batch_tile,
                                                    interpret)
    c = x.shape[-1]
    return pl.pallas_call(
        _sbr_kernel, grid=grid,
        in_specs=[tile, full(c), full(c)],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret, **kwargs,
    )(x, scale, bias)


def _sbr_add_call(x, scale, bias, residual, *, batch_tile, interpret):
    interpret, grid, tile, full, kwargs = _plumbing(x, batch_tile,
                                                    interpret)
    c = x.shape[-1]
    return pl.pallas_call(
        _sbr_add_kernel, grid=grid,
        in_specs=[tile, full(c), full(c), tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret, **kwargs,
    )(x, scale, bias, residual)


# ----------------------------------------------------------------- backward
# Given g (= dL/dy) and the saved conv output x:
#   mask = [x*s + b > 0]
#   dx = g ⊙ mask · s      ds = Σ_{B,H,W} g ⊙ mask ⊙ x    db = Σ g ⊙ mask
#   (add variant additionally: dr = g, handled outside — it is identity)
# One kernel produces dx per tile and accumulates ds/db across the
# sequential grid; only x and g are read from HBM.


def _sbr_bwd_kernel(x_ref, s_ref, b_ref, g_ref, dx_ref, ds_ref, db_ref):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    mask = (x * s_ref[...] + b_ref[...]) > 0
    gm = jnp.where(mask, g, 0.0)
    dx_ref[...] = (gm * s_ref[...]).astype(dx_ref.dtype)
    _acc_out(pl.program_id(0) == 0, (ds_ref, db_ref),
             (jnp.sum(gm * x, axis=(0, 1, 2)),
              jnp.sum(gm, axis=(0, 1, 2))))


def _sbr_bwd_call(x, scale, bias, g, *, batch_tile, interpret):
    interpret, grid, tile, full, kwargs = _plumbing(x, batch_tile,
                                                    interpret)
    c = x.shape[-1]
    f32 = jnp.float32
    return pl.pallas_call(
        _sbr_bwd_kernel, grid=grid,
        in_specs=[tile, full(c), full(c), tile],
        out_specs=[tile, full(c), full(c)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((c,), f32),
                   jax.ShapeDtypeStruct((c,), f32)],
        interpret=interpret, **kwargs,
    )(x, scale, bias, g)


# --------------------------------------------------- differentiable wrappers
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def scale_bias_relu(x, scale, bias, batch_tile=None, interpret=None):
    """Fused conv epilogue: ``relu(x * scale + bias)`` in one VMEM pass.

    x [B,H,W,C] (any float dtype; math in fp32), scale/bias [C] fp32 —
    the folded BN affine. Differentiable; the backward kernel recomputes
    the ReLU mask from ``x`` (no residual tensors in HBM)."""
    return _sbr_call(x, scale, bias, batch_tile=batch_tile,
                     interpret=interpret)


def _sbr_fwd(x, scale, bias, batch_tile, interpret):
    y = _sbr_call(x, scale, bias, batch_tile=batch_tile,
                  interpret=interpret)
    return y, (x, scale, bias)


def _sbr_bwd(batch_tile, interpret, res, g):
    x, scale, bias = res
    dx, ds, db = _sbr_bwd_call(x, scale, bias, g, batch_tile=batch_tile,
                               interpret=interpret)
    return dx, ds.astype(scale.dtype), db.astype(bias.dtype)


scale_bias_relu.defvjp(_sbr_fwd, _sbr_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def scale_bias_relu_add(x, scale, bias, residual, batch_tile=None,
                        interpret=None):
    """Residual-add epilogue variant: ``relu(x * scale + bias) +
    residual`` in one VMEM pass (the block-tail fusion: conv output,
    folded BN, ReLU and the shortcut join never round-trip HBM
    separately). ``residual`` has x's shape; its gradient is the
    cotangent unchanged."""
    return _sbr_add_call(x, scale, bias, residual, batch_tile=batch_tile,
                         interpret=interpret)


def _sbr_add_fwd(x, scale, bias, residual, batch_tile, interpret):
    y = _sbr_add_call(x, scale, bias, residual, batch_tile=batch_tile,
                      interpret=interpret)
    return y, (x, scale, bias)


def _sbr_add_bwd(batch_tile, interpret, res, g):
    x, scale, bias = res
    dx, ds, db = _sbr_bwd_call(x, scale, bias, g, batch_tile=batch_tile,
                               interpret=interpret)
    return (dx, ds.astype(scale.dtype), db.astype(bias.dtype),
            g.astype(x.dtype))


scale_bias_relu_add.defvjp(_sbr_add_fwd, _sbr_add_bwd)


# ------------------------------------------------------------ XLA references
def scale_bias_relu_reference(x, scale, bias):
    """The identical math as XLA compiles it (A/B arm + test oracle)."""
    return scale_bias_relu_math(
        x.astype(jnp.float32), scale, bias).astype(x.dtype)


def scale_bias_relu_add_reference(x, scale, bias, residual):
    return (scale_bias_relu_math(x.astype(jnp.float32), scale, bias)
            + residual.astype(jnp.float32)).astype(x.dtype)


# -------------------------------------------------------- guarded dispatch
def sbr_key(shape) -> str:
    return autotune.shape_key(*shape)


def scale_bias_relu_auto(x, scale, bias):
    """Trace-time guarded dispatch: the Pallas lowering only for shapes
    autotune measured a win on (probe via :func:`probe_epilogue`);
    everything else takes the XLA math. Pure lookup — safe inside jit."""
    if autotune.use_pallas(OP_SBR, sbr_key(x.shape)):
        return scale_bias_relu(x, scale, bias)
    return scale_bias_relu_reference(x, scale, bias)


def scale_bias_relu_add_auto(x, scale, bias, residual):
    if autotune.use_pallas(OP_SBR_ADD, sbr_key(x.shape)):
        return scale_bias_relu_add(x, scale, bias, residual)
    return scale_bias_relu_add_reference(x, scale, bias, residual)


# ------------------------------------------------------------------ probing
def probe_epilogue(shape, dtype=jnp.float32, iters: int = 50,
                   interpret=None, force: bool = False,
                   include_add: bool = True):
    """A/B the epilogue op(s) at one (B,H,W,C) shape — value+grad, the
    training hot path — recording autotune decisions. Host code; run
    before compiling the step (the loop charges it to the compile
    window). ``include_add=False`` probes only OP_SBR (what the model
    integration dispatches). Returns the decision list."""
    key = autotune.shape_key(*shape)
    k1 = jax.random.PRNGKey(hash(key) & 0x7FFFFFFF)
    kx, kr, ks, kb = jax.random.split(k1, 4)
    x = jax.random.normal(kx, shape, dtype)
    r = jax.random.normal(kr, shape, dtype)
    c = shape[-1]
    s = jax.random.uniform(ks, (c,), jnp.float32, 0.5, 1.5)
    b = jax.random.normal(kb, (c,), jnp.float32)

    def grad_of(fn, *args):
        return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
                        argnums=tuple(range(len(args))))(*args)

    out = [autotune.probe(
        OP_SBR, key,
        lambda xx, ss, bb: grad_of(
            lambda a, s2, b2: scale_bias_relu(a, s2, b2, None, interpret),
            xx, ss, bb),
        lambda xx, ss, bb: grad_of(scale_bias_relu_reference, xx, ss, bb),
        (x, s, b), iters=iters, force=force)]
    if include_add:
        out.append(autotune.probe(
            OP_SBR_ADD, key,
            lambda xx, ss, bb, rr: grad_of(
                lambda a, s2, b2, r2: scale_bias_relu_add(
                    a, s2, b2, r2, None, interpret),
                xx, ss, bb, rr),
            lambda xx, ss, bb, rr: grad_of(scale_bias_relu_add_reference,
                                           xx, ss, bb, rr),
            (x, s, b, r), iters=iters, force=force))
    return out


def model_epilogue_shapes(cfg, local_batch: int):
    """The (B,H,W,C) set a ResNet's BN+ReLU sites see for this config —
    what ``probe_model_epilogues`` sweeps. Derived from the stage
    geometry (models/resnet.py): per stage both the block width f and,
    for bottlenecks, the 4f block output."""
    size = cfg.data.resolved_image_size
    w = cfg.model.width_multiplier
    shapes = set()
    if cfg.data.dataset == "imagenet":
        from tpu_resnet.models.resnet import _IMAGENET_PARAMS

        bottleneck, _ = _IMAGENET_PARAMS[cfg.model.resnet_size]
        hw = size // 4  # stem /2 + maxpool /2
        prev_hw = None
        for f in (64, 128, 256, 512):
            shapes.add((local_batch, hw, hw, f))
            if bottleneck:
                shapes.add((local_batch, hw, hw, 4 * f))
                if prev_hw is not None:
                    # Downsampling block0: conv1 is 1x1/1 and conv2
                    # carries the stride, so its bnrelu1 runs at the
                    # INPUT resolution with this stage's width.
                    shapes.add((local_batch, prev_hw, prev_hw, f))
            prev_hw = hw
            hw = max(1, hw // 2)
    else:
        hw = size
        for f in (16 * w, 32 * w, 64 * w):
            shapes.add((local_batch, hw, hw, f))
            hw = max(1, hw // 2)
    return sorted(shapes)


def probe_model_epilogues(cfg, local_batch: int, iters: int = 30):
    """Probe every epilogue shape of the configured model (the
    ``model.fused_epilogue="auto"`` setup pass). Only OP_SBR is probed —
    the model's BN sites dispatch nothing else; the add variant is
    library/A-B surface (probe_epilogue include_add). Returns the
    decision list; a shape whose kernel fails to compile raises
    (autotune.probe)."""
    dtype = jnp.dtype(cfg.model.compute_dtype)
    out = []
    for shape in model_epilogue_shapes(cfg, local_batch):
        out.extend(probe_epilogue(shape, dtype=dtype, iters=iters,
                                  include_add=False))
    return out
