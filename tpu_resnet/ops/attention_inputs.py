"""Attention's inputs in one pass each way: what lies between the q/k/v
projections and the fused kernel (``ops/attention.py``) as one function
with a backward pass of its own.

The composed chain it stands for (``models/transformer.py``: ``rms_norm``,
then ``rotary``, then ``ops/attention.py::_heads_first``) is, a row of
``D`` columns of each head, in float32 from the bf16 projection:

    y = x * rsqrt(mean(x * x) + eps) * scale      q/k norm, where given
    y = y * cos + half_turn(y) * sin_signed       rotary, where given
    y = y * (1 / sqrt(D))                         q only
    out = cast(y, dtype), heads first             the kernel's layout

``half_turn`` swaps the two halves of the row, and ``sin_signed`` is the
sine with the first half's sign turned: ``concatenate([-y2, y1]) * sin``
of the rotate-half form, to the bit, as one multiply-add.
``rotary_table`` computes cosine and sine from the positions once a
layer, outside both passes. A partial rotary (``rotary_dim`` under ``D``)
rotates the row's first ``rotary_dim`` columns and passes the rest: the
tables hold 1 and 0 past them, and the turn (``part_turn``) swaps the two
halves of those columns and gives 0 past them, a permutation that is its
own transpose. A family that declares no q/k norm (``scale`` None) gets
the chain without it: the row is the projection in float32, and nothing
is normed, not even by a weight of 1, whose ``rsqrt`` would change the
numbers.

Forward, each projection is read once and written once, ``q`` as ``(B,
KV, G, S, D)`` and ``k`` as ``(B, KV, S, D)``: the two rounding points are
the composed chain's (the bf16 projection, the one cast to ``dtype``), and
the result is its result to the bit. The residual is the projection,
nothing in float32 of the full width; the backward pass computes the
norm's ``rsqrt`` again from it. Backward, the kernel's cotangent heads
first and the saved projection are read, and the norm, rotary and scale
are applied transposed in float32 in one pass, which writes the
projection's cotangent in its own dtype and layout and the scale's float32
gradient: what autodiff of the composed chain computes, with the sums in
another order. ``v`` is a cast and a change of layout, which autodiff
transposes as it is.

At heads of a multiple of the 128 lanes each pass is a Pallas kernel
(``attention_inputs_fwd``, ``attention_inputs_bwd``): a tile of
``TILE`` positions of one head a step, the head's columns a lane-aligned
block of the projection as the product wrote it, the half-turn a roll of
the lanes, the output block written where the kernel's layout puts it.
In plain JAX, XLA cuts the half-turn out of its fusion: it writes the two
halves of the normed rows to HBM in float32, each padded to the 128 lanes,
and the change of layout is a copy of its own (as XLA compiles it for a
TPU v5e, where it took 2.9 ms a layer forward and backward at the
block-diffusion cell's shapes for 0.5 of bytes, PERF.md section 5). At
heads of 64
the same pass is plain JAX under the same backward (two heads share the
lanes, and a roll would cross them).

Each pass is a ``jit`` of its own, so that every layer and both copies of
the step in a chunk program share one trace (PERF.md section 6).
Where attention takes the scan (``ops/attention.py::attention_path``), the
model runs the composed chain itself, the reference of the tests.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_f32 = jnp.float32
_LANES = 128
TILE = 1024        # positions a step of the kernels


def rotary_table(theta: float, positions: Optional[jax.Array], seq_len: int,
                 head_dim: int, rot: int = 0) -> Tuple[jax.Array, jax.Array]:
    """``(cos, sin_signed)``, both ``(P, S, D)`` float32, of ``positions``
    ``(P, S)`` (``P`` 1 or the batch; None counts from the sequence's
    start), computed as ``models/transformer.py::rotary`` computes its
    tables: the cosine over both halves, the sine with the first half's
    sign turned. With ``rot`` (0: the whole head) the tables are of the
    first ``rot`` columns, and 1 and 0 past them."""
    width = rot or head_dim
    inv = 1.0 / (theta ** (jnp.arange(0, width, 2, dtype=_f32) / width))
    if positions is None:
        positions = jnp.arange(seq_len)[None]
    ang = positions.astype(_f32)[:, :, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos, sin = (jnp.concatenate([cos, cos], -1),
                jnp.concatenate([-sin, sin], -1))
    if width == head_dim:
        return cos, sin
    rest = ang.shape[:2] + (head_dim - width,)
    return (jnp.concatenate([cos, jnp.ones(rest, _f32)], -1),
            jnp.concatenate([sin, jnp.zeros(rest, _f32)], -1))


def half_turn(x):
    """The two halves of the last axis swapped."""
    d = x.shape[-1] // 2
    return jnp.concatenate([x[..., d:], x[..., :d]], -1)


def _jnp_roll(x, shift):
    return jnp.roll(x, shift, axis=x.ndim - 1)


def part_turn(x, roll=_jnp_roll, *, rot: int):
    """The two halves of the first ``rot`` columns of the last axis
    swapped, 0 past them: two rolls of the row, each column taking the
    one whose source is its partner (a rolled column index says which,
    whichever way ``roll`` turns)."""
    d, half = x.shape[-1], rot // 2
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    partner = jnp.where(lane < half, lane + half, lane - half)
    ahead = roll(lane, half) == partner
    return jnp.where(lane < rot, jnp.where(ahead, roll(x, half),
                                           roll(x, d - half)), 0.0)


def _norm(x, eps: float):
    """``x`` in float32 and its ``rsqrt(mean(x * x) + eps)`` a row, as
    ``models/transformer.py::rms_norm`` computes them."""
    x = x.astype(_f32)
    return x, jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _prepare(x, scale, cos, sin, turn, eps: float, factor: float):
    """The forward chain of a row block in float32 (the docstring); no
    norm where ``scale`` is None."""
    if scale is None:
        y = x.astype(_f32)
    else:
        x, r = _norm(x, eps)
        y = x * r * scale
    if cos is not None:
        y = y * cos + turn(y) * sin
    return y * factor if factor != 1.0 else y


def _transposed(x, g, scale, cos, sin, turn, eps: float, factor: float):
    """The projection's cotangent (float32) and the scale's gradient
    summed over the block's rows (None where there is no norm), from the
    prepared rows' cotangent ``g``."""
    g = g.astype(_f32)
    if factor != 1.0:
        g = g * factor
    if cos is not None:
        g = g * cos + turn(g * sin)          # rotary transposed
    if scale is None:
        return g, None
    x, r = _norm(x, eps)
    n = x * r
    dn = g * scale
    return (r * (dn - n * jnp.mean(dn * n, -1, keepdims=True)),
            jnp.sum(g * n, axis=tuple(range(g.ndim - 1))))


# ------------------------------------------------------ heads of 128 lanes
def _roll_half(y):
    return pltpu.roll(y, y.shape[-1] // 2, axis=y.ndim - 1)


def _lane_roll(y, shift):
    return pltpu.roll(y, shift, axis=y.ndim - 1)


def _turn(rot: int, whole, roll):
    """The turn of a row: ``whole`` for a rotary over the whole head
    (``rot`` 0), ``part_turn`` by ``roll`` for a partial one."""
    return functools.partial(part_turn, roll=roll, rot=rot) if rot else whole


def _fwd_kernel(*refs, eps, factor, rotary, rot, norm):
    x_ref, *rest, out_ref = refs
    scale_ref = rest.pop(0) if norm else None
    cos, sin = (t[0] for t in rest) if rotary else (None, None)
    out_ref[0, 0] = _prepare(x_ref[0], scale_ref[...] if norm else None,
                             cos, sin, _turn(rot, _roll_half, _lane_roll),
                             eps, factor).astype(out_ref.dtype)


def _bwd_kernel(*refs, eps, factor, rotary, rot, norm):
    x_ref, g_ref, *rest = refs
    scale_ref = rest.pop(0) if norm else None
    *table, dx_ref, d_scale_ref = rest if norm else rest + [None]
    cos, sin = (t[0] for t in table) if rotary else (None, None)
    dx, d_scale = _transposed(x_ref[0], g_ref[0, 0],
                              scale_ref[...] if norm else None, cos, sin,
                              _turn(rot, _roll_half, _lane_roll), eps,
                              factor)
    dx_ref[0] = dx.astype(dx_ref.dtype)
    if norm:
        d_scale_ref[...] = d_scale.reshape(d_scale_ref.shape)


def _specs(x, table):
    """The grid (batch, tiles of positions, heads: the table's block is
    fetched once for all heads of a tile) and the block specs of the
    projection ``(B, S, H * D)``, of the cotangent or output heads first
    ``(B, H, S, D)``, of the scale ``(1, D)`` and of the tables."""
    b, s, h, d = x.shape
    t = math.gcd(s, TILE)
    rows = pl.BlockSpec((1, t, d), lambda i, j, k: (i, j, k))
    heads = pl.BlockSpec((1, 1, t, d), lambda i, j, k: (i, k, j, 0))
    scale = pl.BlockSpec((1, d), lambda i, j, k: (0, 0))
    tables = []
    if table is not None:
        one = table[0].shape[0] == 1
        tables = [pl.BlockSpec((1, t, d), lambda i, j, k: (
            0 if one else i, j, 0))] * 2
    return (b, s // t, h), rows, heads, scale, tables


def _kernel_forward(x, scale, table, *, eps, factor, rot, dtype, interpret):
    b, s, h, d = x.shape
    grid, rows, heads, scale_spec, tables = _specs(x, table)
    norm = scale is not None
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, factor=factor,
                          rotary=table is not None, rot=rot, norm=norm),
        grid=grid, in_specs=[rows, *[scale_spec] * norm, *tables],
        out_specs=heads,
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        name="attention_inputs_fwd", interpret=interpret,
    )(x.reshape(b, s, h * d), *([scale.reshape(1, d)] if norm else []),
      *(table if table is not None else ()))


def _kernel_backward(x, scale, table, g, *, eps, factor, rot, interpret):
    b, s, h, d = x.shape
    grid, rows, heads, scale_spec, tables = _specs(x, table)
    norm = scale is not None
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, factor=factor,
                          rotary=table is not None, rot=rot, norm=norm),
        grid=grid, in_specs=[rows, heads, *[scale_spec] * norm, *tables],
        out_specs=[rows, pl.BlockSpec((1, 1, 1, 1, d),
                                      lambda i, j, k: (i, j, k, 0, 0))
                   ][:1 + norm],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * d), x.dtype),
                   jax.ShapeDtypeStruct((*grid, 1, d), _f32)][:1 + norm],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        name="attention_inputs_bwd", interpret=interpret,
    )(x.reshape(b, s, h * d), g.reshape(b, h, s, d),
      *([scale.reshape(1, d)] if norm else []),
      *(table if table is not None else ()))
    if not norm:
        return out[0].reshape(x.shape), None
    dx, d_scale = out
    return dx.reshape(x.shape), jnp.sum(d_scale, axis=(0, 1, 2, 3))


# ----------------------------------------------------------- both passes
def _rows(table):
    return (None, None) if table is None else (t[:, :, None] for t in table)


@functools.partial(jax.jit, static_argnames=("eps", "factor", "rot", "kv",
                                             "dtype", "kernel", "interpret"))
def _forward(x, scale, table, *, eps, factor, rot, kv, dtype, kernel,
             interpret):
    """The prepared projection as ``(B, KV, H / KV, S, D)``."""
    b, s, h, d = x.shape
    if kernel:
        out = _kernel_forward(x, scale, table, eps=eps, factor=factor,
                              rot=rot, dtype=dtype, interpret=interpret)
        return out.reshape(b, kv, h // kv, s, d)
    y = _prepare(x, scale, *_rows(table), _turn(rot, half_turn, _jnp_roll),
                 eps, factor)
    return jnp.transpose(y.astype(dtype).reshape(b, s, kv, h // kv, d),
                         (0, 2, 3, 1, 4))


@functools.partial(jax.jit, static_argnames=("eps", "factor", "rot", "kernel",
                                             "interpret"))
def _backward(x, scale, table, g, *, eps, factor, rot, kernel, interpret):
    """The projection's cotangent in its dtype and the scale's float32
    gradient, from the cotangent ``g`` of the prepared projection."""
    if kernel:
        return _kernel_backward(x, scale, table, g, eps=eps, factor=factor,
                                rot=rot, interpret=interpret)
    g = jnp.transpose(g, (0, 3, 1, 2, 4)).reshape(x.shape)
    dx, d_scale = _transposed(x, g, scale, *_rows(table),
                              _turn(rot, half_turn, _jnp_roll), eps, factor)
    return dx.astype(x.dtype), d_scale


def _path(x):
    """Whether the passes are the kernels (heads of a multiple of the 128
    lanes), and whether in Pallas' interpret mode (any backend but a
    TPU): static arguments of both ``jit``s."""
    return dict(kernel=x.shape[-1] % _LANES == 0,
                interpret=jax.default_backend() != "tpu")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _prepared(x, scale, table, eps, factor, rot, kv, dtype):
    return _forward(x, scale, table, eps=eps, factor=factor, rot=rot, kv=kv,
                    dtype=dtype, **_path(x))


def _prepared_fwd(x, scale, table, eps, factor, rot, kv, dtype):
    out = _forward(x, scale, table, eps=eps, factor=factor, rot=rot, kv=kv,
                   dtype=dtype, **_path(x))
    return out, (x, scale, table)


def _prepared_bwd(eps, factor, rot, kv, dtype, residuals, g):
    x, scale, table = residuals
    dx, d_scale = _backward(x, scale, table, g, eps=eps, factor=factor,
                            rot=rot, **_path(x))
    return dx, d_scale, None


_prepared.defvjp(_prepared_fwd, _prepared_bwd)


def attention_inputs(q, k, v, q_scale, k_scale, rotary, dtype, eps: float,
                     rotary_dim: int = 0):
    """``q`` ``(B, S, H, D)`` and ``k``, ``v`` ``(B, S, KV, D)``, the
    projections, as the fused kernel takes them: ``q`` normed, rotated,
    scaled by ``1 / sqrt(D)`` and cast to ``dtype`` as ``(B, KV, H / KV,
    S, D)``; ``k`` normed, rotated and cast as ``(B, KV, S, D)``; ``v``
    cast as ``(B, KV, S, D)`` (the module docstring). ``q_scale`` and
    ``k_scale`` are the norms' ``(D,)`` float32 weights (both None: no
    norm), ``rotary`` None or
    ``(theta, positions)`` with ``positions`` ``(P, S)`` or None, over the
    first ``rotary_dim`` columns of a head (0: all)."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    rot = rotary_dim if 0 < rotary_dim < d else 0
    table = None if rotary is None else rotary_table(*rotary, s, d, rot)
    return (_prepared(q, q_scale, table, eps, 1.0 / math.sqrt(d), rot, kv,
                      dtype),
            _prepared(k, k_scale, table, eps, 1.0, rot, kv, dtype).reshape(
                b, kv, s, d),
            jnp.transpose(v.astype(dtype), (0, 2, 1, 3)))
