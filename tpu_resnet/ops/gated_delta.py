"""Gated DeltaNet's recurrence (Yang et al. 2024, arXiv:2412.06464) over
packed sequences, in the chunked form of the paper's section 3.

Per value head, with state ``S`` of ``(dk, dv)`` from 0:

    S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

where ``S_{t-1}`` reads 0 at every position that begins a document
(``reset``), so that a document of a packed sequence gets what it would get
alone. Value heads share key heads in groups: value head ``h`` reads key
head ``h // (Hv / Hk)``.

**The chunked form.** Cut the sequence into chunks of ``C`` positions. Within
a chunk let ``G_i`` be the sum of ``g`` over its positions up to ``i`` and
``R_i`` the number of documents begun there up to ``i``; ``Gam_ij = exp(G_i -
G_j)`` where ``j <= i`` lie in one document, else 0, is the decay from ``j``
to ``i`` (the difference, never ``exp(G)`` and ``exp(-G)`` apart, which
overflow at strong decay). With ``S0`` the state the chunk starts from,
``c_i = exp(G_i)`` where no document begins in the chunk up to ``i`` (else 0)
the carried state's reach, ``e_j = exp(G_C - G_j)`` where ``j`` lies in the
chunk's last document (else 0) and ``z = exp(G_C)`` where none begins in the
chunk (else 0):

    N = diag(beta) ((K K^T) . Gam), strictly below the diagonal
    U = (I + N)^-1 diag(beta) (V - diag(c) K S0)      the UT transform
    O = diag(c) Q S0 + ((Q K^T) . Gam) U
    S1 = z S0 + (diag(e) K)^T U

(``U``'s rows are the delta rule's corrections ``beta_i (v_i - S'^T k_i)``,
which the unit lower-triangular system gives all at once: the WY
representation.) ``(I + N)^-1`` is the product ``(I - N)(I + N^2)(I + N^4)
...`` up to the power past ``C``, ``N`` being nilpotent, in float32.

**Numerics.** The products take ``dtype`` operands (bf16) with float32
accumulation; the inverse's products are float32 at ``HIGHEST``; the state is
float32 from chunk to chunk.

**Paths** (``recurrence_path``). On one TPU chip, three ``pallas_call``s
each over a layer's whole sequence: ``gated_delta_fwd_inverse``, a grid
whose steps are independent, writes ``(I + N)^-1`` of every chunk and value
head in ``dtype`` (the inverse depends on ``k``, ``beta``, ``G`` and the
documents, never on the state, and both kernels below read it only as a
``dtype`` operand, so the copy gives every use the bits it had);
``gated_delta_fwd``, a grid over batch and value heads that goes through
the chunks in order with the state in VMEM, writing each chunk's output and
the state it started from; and ``gated_delta_bwd``, which goes through the
chunks in reverse with the state's cotangent in VMEM, computes a chunk's
forward again from its inputs, the inverse and the saved state, and writes
the cotangents of ``q``, ``k``, ``v``, ``beta`` and ``G`` (those of ``k``,
``beta`` and ``G`` through the inverse too). What a layer saves for its
backward pass beside its inputs is the inverses, ``B x Hv x S / C x C x
C`` in ``dtype``, and the states at the chunks' starts: ``B x Hv x S / C x
dk x dv`` float32. The inverse carries the name ``INVERSE``, so that a
caller's ``jax.checkpoint`` policy can keep it and run only the forward
kernel again. Everywhere else the same chunk algebra in plain JAX, a
``lax.scan`` over the chunks whose body is under ``jax.checkpoint``, the
inverse computed inside each chunk, and autodiff backward.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_f32 = jnp.float32
CHUNK = 128            # positions a chunk (PERF.md section 5 has the sweep)
UNITS = 4              # (chunk, value head) units a step of the inverse's grid
INVERSE = "recurrence_inverse"       # the kernel path's inverses, by name
_NN = (((1,), (0,)), ((), ()))       # a @ b
_NT = (((1,), (1,)), ((), ()))       # a @ b.T
_TN = (((0,), (0,)), ((), ()))       # a.T @ b


def recurrence_path(backend: str, devices: int) -> str:
    """``kernel`` or ``scan``, from the backend's name and the number of its
    devices alone: the kernels where Mosaic compiles them and the step is
    one device's program (an auto-partitioned ``jit`` over several refuses
    a Mosaic kernel, ROADMAP B-I 4)."""
    return "kernel" if backend == "tpu" and devices == 1 else "scan"


def doc_chunks_frac(reset, chunk: int = CHUNK):
    """Of the (sequence, chunk) pairs, the share in which a document begins
    after the chunk's first position: the chunks whose masks cut the
    decay inside them. ``reset`` is ``(B, S)`` bool."""
    b, s = reset.shape
    inside = reset.reshape(b, s // chunk, chunk)[:, :, 1:]
    return jnp.mean(jnp.any(inside, axis=-1).astype(_f32))


# ------------------------------------------------------------- one chunk
def _mm(a, b, dims, dtype):
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                               preferred_element_type=_f32)


def _mm32(a, b):
    return jax.lax.dot_general(a, b, _NN, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_f32)


def _unit_lower_inverse(n):
    """``(I + n)^-1`` of a strictly lower-triangular ``n`` ``(C, C)``:
    ``(I - n)(I + n^2)(I + n^4) ...`` until the power reaches ``C``."""
    size = n.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, n.shape, 0)
    j = jax.lax.broadcasted_iota(jnp.int32, n.shape, 1)
    eye = (i == j).astype(_f32)
    inv, power, reach = eye - n, n, 2
    while reach < size:
        power = _mm32(power, power)
        inv = _mm32(inv, eye + power)
        reach *= 2
    return inv


def _last(col, axis_len: int = 0):
    """A column ``(C, 1)``'s last entry as a column of ``C`` copies or, with
    ``axis_len``, as a row ``(1, axis_len)``: a masked sum, then a
    broadcast one way (Mosaic broadcasts a single entry one way at a
    time)."""
    size = col.shape[0]
    at = jax.lax.broadcasted_iota(jnp.int32, col.shape, 0) == size - 1
    if axis_len:
        wide = jnp.broadcast_to(jnp.where(at, col, 0.0), (size, axis_len))
        return jnp.sum(wide, axis=0, keepdims=True)
    return jnp.broadcast_to(jnp.sum(jnp.where(at, col, 0.0), axis=0,
                                    keepdims=True), col.shape)


def _decays(gc, gr, rc, rr):
    """``(incl, strict, gam)``: the chunk's decays under its documents (the
    module docstring) from ``G`` and ``R`` as a column ``(C, 1)`` and a row
    ``(1, C)``."""
    size = gc.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (size, size), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (size, size), 1)
    same = rc == rr
    incl = same & (i >= j)
    strict = same & (i > j)
    gam = jnp.where(incl, jnp.exp(jnp.where(incl, gc - gr, 0.0)), 0.0)
    return incl, strict, gam


def _masks(gc, gr, rc, rr, width: int):
    """``_decays``' three and the carried state's ``(c, e, z)``; ``z`` as a
    row ``(1, width)``."""
    incl, strict, gam = _decays(gc, gr, rc, rr)
    c = jnp.where(rc == 0, jnp.exp(gc), 0.0)
    e = jnp.where(rc == _last(rc), jnp.exp(_last(gc) - gc), 0.0)
    z = jnp.where(_last(rc, width) == 0, jnp.exp(_last(gc, width)), 0.0)
    return incl, strict, gam, c, e, z


def _inverse(kk, beta, strict, gam):
    """The UT transform's ``(I + N)^-1`` float32 from ``K K^T``."""
    return _unit_lower_inverse(beta * jnp.where(strict, kk * gam, 0.0))


def _chunk_parts(q, k, v, beta, gc, gr, rc, rr, s0, dtype, a=None):
    """The parts of a chunk's forward; the inverse ``a`` computed here
    unless given."""
    incl, strict, gam, c, e, z = _masks(gc, gr, rc, rr, s0.shape[1])
    qk = _mm(q, k, _NT, dtype)
    if a is None:
        a = _inverse(_mm(k, k, _NT, dtype), beta, strict, gam)
    ks = _mm(k, s0, _NN, dtype)
    x = beta * (v.astype(_f32) - c * ks)
    u = _mm(a, x, _NN, dtype)
    return dict(incl=incl, strict=strict, gam=gam, c=c, e=e, z=z, qk=qk,
                a=a, ks=ks, x=x, u=u)


def chunk_forward(q, k, v, beta, gc, gr, rc, rr, s0, dtype, a=None):
    """``(o, s1)`` of one chunk: ``q``, ``k`` ``(C, dk)``, ``v`` ``(C,
    dv)``, ``beta``, ``G`` and ``R`` as columns ``(C, 1)`` and ``G``, ``R``
    also as rows ``(1, C)``, the state ``s0`` ``(dk, dv)`` float32; the
    inverse ``a`` ``(C, C)`` computed here unless given."""
    p = _chunk_parts(q, k, v, beta, gc, gr, rc, rr, s0, dtype, a)
    o = p["c"] * _mm(q, s0, _NN, dtype) + _mm(p["qk"] * p["gam"], p["u"],
                                              _NN, dtype)
    s1 = p["z"] * s0 + _mm(p["e"] * k.astype(_f32), p["u"], _TN, dtype)
    return o, s1


def chunk_backward(q, k, v, beta, gc, gr, rc, rr, s0, do, ds1, dtype, a):
    """The cotangents ``(dq, dk, dv, dbeta, dG_col, dG_row, ds0)`` of one
    chunk from those of its output ``do`` and its end state ``ds1``, given
    the chunk's inverse ``a``: ``dG`` is the sum of a column ``(C, 1)`` and
    a row ``(1, C)``. Those of ``k``, ``beta`` and ``G`` hold what moves
    them through the inverse."""
    kk = _mm(k, k, _NT, dtype)
    p = _chunk_parts(q, k, v, beta, gc, gr, rc, rr, s0, dtype, a)
    gam, c, e, z, u = p["gam"], p["c"], p["e"], p["z"], p["u"]
    qf, kf, do = q.astype(_f32), k.astype(_f32), do.astype(_f32)
    pg = p["qk"] * gam
    du = _mm(pg, do, _TN, dtype) + _mm(e * kf, ds1, _NN, dtype)
    dx = _mm(p["a"], du, _TN, dtype)
    dn = -jnp.where(p["strict"], _mm(dx, u, _NT, dtype), 0.0)
    dpg = jnp.where(p["incl"], _mm(do, u, _NT, dtype), 0.0) * gam
    dlg = beta * dn * gam
    u_ds = _mm(u, ds1, _NT, dtype)                       # (C, dk)
    cbdx = c * beta * dx
    dq = _mm(dpg, k, _NN, dtype) + c * _mm(do, s0, _NT, dtype)
    dk = (_mm(dlg, k, _NN, dtype) + _mm(dlg, k, _TN, dtype)
          + _mm(dpg, q, _TN, dtype) + e * u_ds - _mm(cbdx, s0, _NT, dtype))
    dv = beta * dx
    dbeta = (jnp.sum(dn * kk * gam, axis=1, keepdims=True)
             + jnp.sum(dx * (v.astype(_f32) - c * p["ks"]), axis=1,
                       keepdims=True))
    dc = (jnp.sum(do * _mm(q, s0, _NN, dtype), axis=1, keepdims=True)
          - jnp.sum(beta * dx * p["ks"], axis=1, keepdims=True))
    de_e = jnp.sum(kf * u_ds, axis=1, keepdims=True) * e
    f = dpg * p["qk"] + dlg * kk
    size = gc.shape[0]
    # what G's last entry moves: e's and z's, both summed into a row
    dz = jnp.sum(jnp.broadcast_to(jnp.sum(s0 * ds1, axis=1, keepdims=True),
                                  (s0.shape[0], size)), axis=0, keepdims=True)
    z_row = jnp.where(_last(rc, size) == 0, jnp.exp(_last(gc, size)), 0.0)
    at_last = jax.lax.broadcasted_iota(jnp.int32, (1, size), 1) == size - 1
    dg_col = jnp.sum(f, axis=1, keepdims=True) + dc * c - de_e
    dg_row = -jnp.sum(f, axis=0, keepdims=True) + jnp.where(
        at_last, jnp.sum(jnp.broadcast_to(de_e, (size, size)), axis=0,
                         keepdims=True) + dz * z_row, 0.0)
    ds0 = z * ds1 + _mm(qf, c * do, _TN, dtype) - _mm(kf, cbdx, _TN, dtype)
    return dq, dk, dv, dbeta, dg_col, dg_row, ds0


# ------------------------------------------------------------ the kernels
def _inverse_kernel(k_ref, beta_ref, gc_ref, gr_ref, rc_ref, rr_ref, a_ref, *,
                    dtype):
    """The inverses of a step's units, consecutive chunks of one value
    head, each its own chain of products."""
    units, size = a_ref.shape[2], a_ref.shape[3]
    for n in range(units):
        at = slice(n * size, (n + 1) * size)
        _, strict, gam = _decays(gc_ref[0, 0, at], gr_ref[0, 0, n],
                                 rc_ref[0, 0, at], rr_ref[0, 0, n])
        k = k_ref[0, at]
        a_ref[0, 0, n] = _inverse(_mm(k, k, _NT, dtype), beta_ref[0, 0, at],
                                  strict, gam).astype(a_ref.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, beta_ref, gc_ref, gr_ref, rc_ref, rr_ref,
                a_ref, o_ref, s_ref, state, *, dtype):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, _f32)

    s0 = state[...]
    s_ref[0, 0, 0] = s0
    o, s1 = chunk_forward(q_ref[0], k_ref[0], v_ref[0], beta_ref[0, 0],
                          gc_ref[0, 0], gr_ref[0, 0, 0], rc_ref[0, 0],
                          rr_ref[0, 0, 0], s0, dtype, a_ref[0, 0, 0])
    o_ref[0] = o.astype(o_ref.dtype)
    state[...] = s1


def _bwd_kernel(q_ref, k_ref, v_ref, beta_ref, gc_ref, gr_ref, rc_ref, rr_ref,
                a_ref, s_ref, do_ref, dq_ref, dk_ref, dv_ref, dbeta_ref,
                dgc_ref, dgr_ref, d_state, *, dtype):
    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state[...] = jnp.zeros(d_state.shape, _f32)

    dq, dk, dv, dbeta, dgc, dgr, ds0 = chunk_backward(
        q_ref[0], k_ref[0], v_ref[0], beta_ref[0, 0], gc_ref[0, 0],
        gr_ref[0, 0, 0], rc_ref[0, 0], rr_ref[0, 0, 0], s_ref[0, 0, 0],
        do_ref[0], d_state[...], dtype, a_ref[0, 0, 0])
    dq_ref[0] = dq
    dk_ref[0] = dk
    dv_ref[0] = dv
    dbeta_ref[0, 0] = dbeta
    dgc_ref[0, 0] = dgc
    dgr_ref[0, 0, 0] = dgr
    d_state[...] = ds0


def _specs(shape, group: int, chunk: int, reverse: bool, units: int = 1):
    """The block specs of the grid (batch, value heads, steps of ``units``
    chunks), the steps in reverse where ``reverse``: rows of a key head's
    ``(B, S, Hk * dk)``, of a value head's ``(B, S, Hv * d)``, a column
    ``(B, Hv, S, 1)``, a row ``(B, Hv, S / C, 1, C)``, a state ``(B, Hv, S
    / C, dk, dv)`` and an inverse ``(B, Hv, S / C, C, C)``."""
    b, s, hv, dk, dv = shape
    steps, rows = s // (chunk * units), chunk * units

    def at(c):
        return steps - 1 - c if reverse else c

    return dict(
        key=pl.BlockSpec((1, rows, dk),
                         lambda i, h, c: (i, at(c), h // group)),
        value=lambda d: pl.BlockSpec((1, rows, d),
                                     lambda i, h, c: (i, at(c), h)),
        col=pl.BlockSpec((1, 1, rows, 1), lambda i, h, c: (i, h, at(c), 0)),
        row=pl.BlockSpec((1, 1, units, 1, chunk),
                         lambda i, h, c: (i, h, at(c), 0, 0)),
        state=pl.BlockSpec((1, 1, units, dk, dv),
                           lambda i, h, c: (i, h, at(c), 0, 0)),
        inverse=pl.BlockSpec((1, 1, units, chunk, chunk),
                             lambda i, h, c: (i, h, at(c), 0, 0)),
        grid=(b, hv, steps))


def _params(chunk_order: str = "arbitrary"):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", chunk_order))


# Each pass is a jit of its own: every layer and both copies of the step in
# a chunk program then trace and lower the kernel once.
@functools.partial(jax.jit,
                   static_argnames=("chunk", "dtype", "units", "interpret"))
def _kernel_inverse(k, cols, rows, *, chunk, dtype, units, interpret):
    """The inverses ``(B, Hv, S / C, C, C)`` in ``dtype``, ``units`` (a
    divisor of the chunks) a grid step; no step waits on another."""
    b, s, hk, dk = k.shape
    hv = cols[0].shape[1]
    sp = _specs((b, s, hv, dk, dk), hv // hk, chunk, reverse=False,
                units=units)
    return pl.pallas_call(
        functools.partial(_inverse_kernel, dtype=dtype),
        grid=sp["grid"],
        in_specs=[sp["key"], sp["col"], sp["col"], sp["row"], sp["col"],
                  sp["row"]],
        out_specs=sp["inverse"],
        out_shape=jax.ShapeDtypeStruct((b, hv, s // chunk, chunk, chunk),
                                       dtype),
        compiler_params=_params("parallel"), name="gated_delta_fwd_inverse",
        interpret=interpret,
    )(k.reshape(b, s, hk * dk), cols[0], cols[1], rows[0], cols[2], rows[1])


@functools.partial(jax.jit, static_argnames=("chunk", "dtype", "interpret"))
def _kernel_forward(q, k, v, cols, rows, a, *, chunk, dtype, interpret):
    """``(o, states)``: ``o`` ``(B, S, Hv * dv)`` in ``dtype``, the state
    each chunk starts from ``(B, Hv, S / C, dk, dv)`` float32."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    sp = _specs((b, s, hv, dk, dv), hv // hk, chunk, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, dtype=dtype),
        grid=sp["grid"],
        in_specs=[sp["key"], sp["key"], sp["value"](dv), sp["col"],
                  sp["col"], sp["row"], sp["col"], sp["row"],
                  sp["inverse"]],
        out_specs=[sp["value"](dv), sp["state"]],
        out_shape=[jax.ShapeDtypeStruct((b, s, hv * dv), dtype),
                   jax.ShapeDtypeStruct((b, hv, s // chunk, dk, dv), _f32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), _f32)],
        compiler_params=_params(), name="gated_delta_fwd",
        interpret=interpret,
    )(q.reshape(b, s, hk * dk), k.reshape(b, s, hk * dk),
      v.reshape(b, s, hv * dv), cols[0], cols[1], rows[0], cols[2], rows[1],
      a)


@functools.partial(jax.jit, static_argnames=("chunk", "dtype", "interpret"))
def _kernel_backward(q, k, v, cols, rows, a, states, do, *, chunk, dtype,
                     interpret):
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    sp = _specs((b, s, hv, dk, dv), hv // hk, chunk, reverse=True)
    nc = s // chunk
    dq, dk_, dv_, dbeta, dgc, dgr = pl.pallas_call(
        functools.partial(_bwd_kernel, dtype=dtype),
        grid=sp["grid"],
        in_specs=[sp["key"], sp["key"], sp["value"](dv), sp["col"],
                  sp["col"], sp["row"], sp["col"], sp["row"], sp["inverse"],
                  sp["state"], sp["value"](dv)],
        out_specs=[sp["value"](dk), sp["value"](dk), sp["value"](dv),
                   sp["col"], sp["col"], sp["row"]],
        out_shape=[jax.ShapeDtypeStruct((b, s, hv * dk), _f32),
                   jax.ShapeDtypeStruct((b, s, hv * dk), _f32),
                   jax.ShapeDtypeStruct((b, s, hv * dv), _f32),
                   jax.ShapeDtypeStruct((b, hv, s, 1), _f32),
                   jax.ShapeDtypeStruct((b, hv, s, 1), _f32),
                   jax.ShapeDtypeStruct((b, hv, nc, 1, chunk), _f32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), _f32)],
        compiler_params=_params(), name="gated_delta_bwd",
        interpret=interpret,
    )(q.reshape(b, s, hk * dk), k.reshape(b, s, hk * dk),
      v.reshape(b, s, hv * dv), cols[0], cols[1], rows[0], cols[2], rows[1],
      a, states, do.reshape(b, s, hv * dv))

    def by_key_head(x):
        """A value head's cotangent summed into the key head it reads."""
        return jnp.sum(x.reshape(b, s, hk, hv // hk, dk), axis=3)

    dg = dgc[..., 0] + dgr.reshape(b, hv, s)
    return (by_key_head(dq), by_key_head(dk_), dv_.reshape(b, s, hv, dv),
            jnp.swapaxes(dbeta[..., 0], 1, 2), jnp.swapaxes(dg, 1, 2))


def _layouts(beta, g_cum, resets, chunk):
    """``beta``, ``G`` and ``R`` ``(B, S, Hv)`` as the kernels read them:
    columns ``(B, Hv, S, 1)`` of the three, rows ``(B, Hv, S / C, 1, C)``
    of ``G`` and ``R``."""
    b, s, hv = beta.shape
    heads_first = [jnp.swapaxes(x, 1, 2) for x in (beta, g_cum, resets)]
    cols = [x[..., None] for x in heads_first]
    rows = [x.reshape(b, hv, s // chunk, 1, chunk) for x in heads_first[1:]]
    return cols, rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _kernels(q, k, v, beta, g_cum, resets, a, chunk, dtype, interpret):
    cols, rows = _layouts(beta, g_cum, resets, chunk)
    return _kernel_forward(q, k, v, cols, rows, a, chunk=chunk, dtype=dtype,
                           interpret=interpret)[0]


def _kernels_fwd(q, k, v, beta, g_cum, resets, a, chunk, dtype, interpret):
    cols, rows = _layouts(beta, g_cum, resets, chunk)
    o, states = _kernel_forward(q, k, v, cols, rows, a, chunk=chunk,
                                dtype=dtype, interpret=interpret)
    return o, (q, k, v, beta, g_cum, resets, a, states)


def _kernels_bwd(chunk, dtype, interpret, residuals, do):
    """The inverse ``a`` is a function of ``k``, ``beta``, ``G`` and ``R``:
    the backward kernel's cotangents of those hold what moves them through
    it, and ``a`` itself takes none."""
    q, k, v, beta, g_cum, resets, a, states = residuals
    cols, rows = _layouts(beta, g_cum, resets, chunk)
    dq, dk, dv, dbeta, dg = _kernel_backward(
        q, k, v, cols, rows, a, states, do, chunk=chunk, dtype=dtype,
        interpret=interpret)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dbeta, dg, None, None)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


# --------------------------------------------------------------- the scan
def _scan(q, k, v, beta, g_cum, resets, chunk, dtype):
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    nc = s // chunk

    def chunks(x):
        """``(B, S, H, ...)`` as ``(S / C, B, Hv, C, ...)``, a key head
        repeated for each value head that reads it."""
        x = jnp.repeat(x, hv // x.shape[2], axis=2)
        x = x.reshape(b, nc, chunk, hv, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)

    xs = (chunks(q), chunks(k), chunks(v),
          *(chunks(x[..., None]) for x in (beta, g_cum, resets)),
          *(jnp.swapaxes(chunks(x[..., None]), -1, -2)
            for x in (g_cum, resets)))
    one = jax.vmap(jax.vmap(functools.partial(chunk_forward, dtype=dtype)))

    @jax.checkpoint
    def body(state, x):
        qc, kc, vc, bc, gc, rc, gr, rr = x
        o, s1 = one(qc, kc, vc, bc, gc, gr, rc, rr, state)
        return s1, o.astype(dtype)

    _, out = jax.lax.scan(body, jnp.zeros((b, hv, dk, dv), _f32), xs)
    # (S / C, B, Hv, C, dv) -> (B, S, Hv * dv)
    return jnp.moveaxis(out, 2, 3).swapaxes(0, 1).reshape(b, s, hv * dv)


# ------------------------------------------------------------------ entry
def _running(g, reset, hv: int, chunk: int):
    """``(G, R)`` ``(B, S, Hv)`` float32: chunk-local running sums over the
    positions of ``g`` and of the documents begun."""
    b, s = reset.shape

    def in_chunks(x):
        return jnp.cumsum(x.reshape(b, s // chunk, chunk, *x.shape[2:]),
                          axis=2).reshape(x.shape)

    return (in_chunks(g.astype(_f32)),
            jnp.broadcast_to(in_chunks(reset.astype(_f32))[..., None],
                             (b, s, hv)))


def _inverses(k, beta, g_cum, resets, chunk, dtype, units):
    cols, rows = _layouts(beta, g_cum, resets, chunk)
    return _kernel_inverse(k, cols, rows, chunk=chunk, dtype=jnp.dtype(dtype),
                           units=math.gcd(units, k.shape[1] // chunk),
                           interpret=jax.default_backend() != "tpu")


def inverses(k, beta, g, reset, *, dtype, chunk: int = CHUNK,
             units: int = UNITS):
    """The UT transform's ``(I + N)^-1`` of every sequence, value head and
    chunk, ``(B, Hv, S / C, C, C)`` in ``dtype``, as the kernel path
    computes them once a call: the ``pallas_call``
    ``gated_delta_fwd_inverse``, ``units`` chunks a grid step (the largest
    divisor of the chunks up to it), in Pallas' interpreter on any backend
    but a TPU. The arguments are ``gated_delta``'s."""
    g_cum, resets = _running(g, reset, beta.shape[2], chunk)
    return _inverses(k.astype(dtype), beta.astype(_f32), g_cum, resets,
                     chunk, dtype, units)


def gated_delta(q, k, v, beta, g, reset, *, dtype, chunk: int = CHUNK,
                path: str = ""):
    """The recurrence's outputs ``(B, S, Hv * dv)`` in ``dtype``.

    ``q``, ``k`` ``(B, S, Hk, dk)`` and ``v`` ``(B, S, Hv, dv)`` (``q``
    normed and scaled as the layer wants it); ``beta`` and the log-decay
    ``g`` (at most 0) ``(B, S, Hv)`` float32; ``reset`` ``(B, S)`` bool,
    where a document begins. ``path`` is ``recurrence_path``'s here unless
    given; the kernels run in Pallas' interpreter on any backend but a TPU
    (tests)."""
    b, s, hk, dk = q.shape
    hv = v.shape[2]
    if s % chunk:
        raise ValueError(f"a sequence of {s} positions is not a whole number "
                         f"of chunks of {chunk}")
    if hv % hk:
        raise ValueError(f"{hv} value heads do not share {hk} key heads "
                         f"evenly")
    path = path or recurrence_path(jax.default_backend(), jax.device_count())
    g_cum, resets = _running(g, reset, hv, chunk)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    beta = beta.astype(_f32)
    if path == "kernel":
        resets = jax.lax.stop_gradient(resets)
        # the backward kernel differentiates through the inverse itself
        a = checkpoint_name(_inverses(
            *(jax.lax.stop_gradient(x) for x in (k, beta, g_cum)), resets,
            chunk, dtype, UNITS), INVERSE)
        return _kernels(q, k, v, beta, g_cum, resets, a, chunk,
                        jnp.dtype(dtype), jax.default_backend() != "tpu")
    return _scan(q, k, v, beta, g_cum, resets, chunk, dtype)
