"""What the token families share (``afmoe``, ``sdar_moe``, ``lfm2_moe``,
``qwen3_next``, ``smallthinker``): the numerics of a matrix product,
RMSNorm, the SwiGLU MLP, the rotary embedding by position ids (over a whole
head or its first columns), attention by whichever path the backend and the
shapes give (with an output gate taken from the query's product where a
family asks), the causal short convolution within documents, the sigmoid
router with its bias and the softmax router, and the dispatch of an expert
layer's assignments to the experts this chip holds. A family keeps what is
its own: its token mixers, its norms' places, its masks, its objective.

**The softmax router** (``softmax_router``; ``sdar_moe``, ``qwen3_next``
and ``smallthinker``, which runs it on the layer's input before
attention): ``s = softmax(x Wr)`` over all experts, top-k, the chosen
renormalised to 1. **The sigmoid router** (``sigmoid_router``,
``balanced_bias``; ``afmoe`` and ``lfm2_moe`` call it, each with its two
numbers): ``s = sigmoid(x
Wr)`` in float32 over ALL ``experts_total``; chosen = top-k of ``s + b``,
``b`` the layer's ``expert_bias``, state without a gradient that chooses
and does not weigh; ``w = s[chosen] / (sum s[chosen] + eps) * scale``. In
training, after the step's routing, ``b += c - mean(c)`` with ``c = coeff
* sign(mean(n) - n)``, ``n`` the assignments per expert (the
auxiliary-loss-free balancing of Wang et al. 2024, arXiv:2408.15664).

**The dispatch** (``dispatch_experts``). A family's router hands over ``(x,
chosen, weight)``: for each of ``N`` tokens the ``top_k`` experts chosen of
``experts_total`` and their weights, routed on ``x`` or on another tensor
of the layer; an expert is ``(activation(x W_gate) * (x W_up)) W_down``,
SwiGLU by default and ReGLU where a family passes ReLU. No token is
dropped, and the layer does the work its routing fills: one stable sort of
the ``N * top_k`` assignments by their expert here puts those that fall on
a held expert (``experts_held = (first, count)``) first, grouped by expert
and in token order within an expert. The first ``rows`` of them
(``rows_slack`` x what an even routing sends to all held experts together,
in whole tiles) are one buffer: a gather of the tokens by the sorted order,
three grouped products whose work follows the groups' sizes
(``ops/grouped.py``, which also says how its path is chosen and what its
kernel never writes: the buffer's rows past the last assignment come back
0, in the result and in every gradient), the weighting, and the sum of each
token's rows back into its token. The gather and that sum are one
transposed pair (``ops/rows_to_tokens.py``, which also says how their path
is chosen): on one TPU chip the sum, forward in the combine and backward in
the dispatch, is a kernel that reads only the rows an assignment filled, in
the runs an expert's rows make within a tile of tokens; elsewhere it is the
scatter-add. ``expert_paths`` names both paths, and each family says them
once, as the event ``expert_path``. Whatever sorted positions lie beyond
the buffer go through the same code a tier of ``rows`` at a time, only the
tiers that hold an assignment, under a ``lax.cond`` that is false while the
held experts together take no more than the buffer. What the absent experts
would add is left out and that partial result goes on. The counters say
what happened (``moe_dropped_frac`` reads 0 by that construction and is
counted from the groups' sizes all the same; ``moe_overflow_frac`` says
whether the tiers beyond ran, ``moe_rows_filled_frac`` how much of the
buffer carried an assignment).

**Attention** (``self_attention``, which all five families call: the q/k/v
projections, the q/k norms where a family has them, rotary where it asks
for it, and attention) never builds a ``(B, H, S, S)`` score tensor. On one
TPU chip, at heads of a multiple of 128, or of 64, and sequences its blocks
divide, it is one fused kernel that keeps each tile of scores on the chip
(``ops/attention.py``, which also says how the path is chosen and what a
mask is), fed by one pass that norms, rotates, scales, casts and lays out
its inputs, with a backward pass of its own (``ops/attention_inputs.py``);
everywhere else the norms and rotary as composed here and a block of
queries at a time against the keys its mask can reach, as a scan whose body
is under ``jax.checkpoint`` (``blocked_attention``).
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from tpu_resnet.ops import rows_to_tokens
from tpu_resnet.ops.attention import (BlockDiffusion, attention_path,
                                      heads_first_attention)
from tpu_resnet.ops.attention_inputs import attention_inputs
from tpu_resnet.ops.grouped import grouped_dot, grouped_path, row_tile

COUNTERS = ("moe_dropped_frac", "moe_load_max_over_mean", "moe_here_frac",
            "moe_overflow_frac", "moe_rows_filled_frac")

_init = nn.initializers.normal(0.02)
_f32 = jnp.float32
# What ``remat`` keeps of a layer for its backward pass: the results of its
# matrix products and of attention; norms, rotary, gates and activations
# are computed again.
_KEEP = jax.checkpoint_policies.save_from_both_policies(
    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    jax.checkpoint_policies.save_only_these_names("attention", "experts"))


def _dot(x, w, dtype, out=None):
    """``x @ w`` over the last axis of ``x`` and the first of ``w``:
    operands in ``dtype``, accumulation in float32, the result in ``out``
    (``dtype`` unless said)."""
    return jax.lax.dot_general(
        x.astype(dtype), w.astype(dtype),
        (((x.ndim - 1,), (0,)), ((), ())), preferred_element_type=_f32
    ).astype(out or dtype)


def rms_norm(x, scale, eps: float):
    """``x * rsqrt(mean(x * x) + eps) * scale`` over the last axis, in
    float32."""
    x = x.astype(_f32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + eps) * scale


class NormScale(nn.Module):
    """The ``(width,)`` weight of an RMSNorm, ``<name>/scale``, for a norm
    that is computed elsewhere (``self_attention``'s q/k norms). A
    zero-centred norm's weight ``w`` starts at 0 and scales by ``1 + w``:
    what is returned is that scale."""
    zero_centred: bool = False

    @nn.compact
    def __call__(self, width: int):
        if self.zero_centred:
            return 1.0 + self.param("scale", nn.initializers.zeros, (width,),
                                    _f32)
        return self.param("scale", nn.initializers.ones, (width,), _f32)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           _f32)
        return rms_norm(x, scale, self.eps)


class SwiGLU(nn.Module):
    """``(silu(x W_gate) * (x W_up)) W_down``, no bias."""
    width: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        gate = _dot(x, self.param("gate", _init, (d, self.width), _f32),
                    self.dtype)
        up = _dot(x, self.param("up", _init, (d, self.width), _f32),
                  self.dtype)
        return _dot(jax.nn.silu(gate) * up,
                    self.param("down", _init, (self.width, d), _f32),
                    self.dtype)


def rotary(x, theta: float, positions=None, dims: int = 0):
    """Rotate-half rotary embedding; ``x`` is ``(B, S, H, D)`` float32.
    ``positions`` are ``(B, S)`` position ids; without them positions count
    from the sequence's start. ``dims`` (0: the whole head) rotates the
    first ``dims`` of each head and passes the rest through (a partial
    rotary factor), the frequencies over those ``dims``."""
    d = x.shape[-1]
    if dims and dims < d:
        return jnp.concatenate([rotary(x[..., :dims], theta, positions),
                                x[..., dims:]], -1)
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=_f32) / d))
    if positions is None:
        positions = jnp.arange(x.shape[1])[None]
    ang = positions.astype(_f32)[:, :, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


# ------------------------------------------------------- short convolution
def same_document(doc, back: int):
    """``(B, S)`` bool: whether position ``t - back`` exists and lies in
    ``t``'s document."""
    before = jnp.pad(doc, ((0, 0), (back, 0)),
                     constant_values=-1)[:, :doc.shape[1]]
    return before == doc


def taps_init(key, shape, dtype=_f32):
    """A depthwise filter ``(channels, K)`` as PyTorch's ``Conv1d`` draws
    it: uniform within ``1 / sqrt(K)`` (its fan-in is the taps of one
    channel). At the matrices' 0.02 a fresh filter would hand on a
    fiftieth of its input."""
    bound = 1.0 / math.sqrt(shape[-1])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def short_conv(u, taps, doc):
    """The causal depthwise convolution of ``u`` ``(B, S, d)`` by ``taps``
    ``(d, K)``, any ``K``, within documents: ``c_t = sum_j taps[:, K-1-j] *
    u_{t-j}`` with ``u_{t-j}`` 0 before the start of ``t``'s document.
    ``K`` shifted multiply-adds."""
    s, last = u.shape[1], taps.shape[1] - 1
    out = u * taps[:, last]
    for j in range(1, last + 1):
        back = jnp.pad(u, ((0, 0), (j, 0), (0, 0)))[:, :s]
        out = out + jnp.where(same_document(doc, j)[..., None], back,
                              0.0) * taps[:, last - j]
    return out


# --------------------------------------------------------------- attention
def _reach(seq_len: int, window: int, block: int) -> int:
    """The positions before a block's first query that the scan's every
    block takes keys from: the window, or on a full layer everything
    before the last block."""
    return min(window, seq_len - block) if window else seq_len - block


def blocked_attention(q, k, v, doc, mask, block: int, dtype):
    """Attention within documents under ``mask`` (``ops/attention.py``: a
    window, 0 for all that went before, or ``BlockDiffusion``). ``q`` is
    ``(B, S, KV, G, D)`` (G query heads a key/value head), ``k`` and ``v``
    ``(B, S, KV, D)``, ``doc`` ``(B, S)``. Returns ``(B, S, KV, G, D)`` in
    ``dtype``.

    A ``lax.scan`` over blocks of queries. Under a causal mask every block
    takes the same number of keys, ``reach + block``: the ``reach``
    positions before its first query that a mask can let it see (the
    window, or on a full layer everything before the last block) and its
    own. Keys and documents are padded in front by ``reach`` (document -1,
    which no query belongs to), so that the first blocks take that many
    too: one shape, one body, the scores of one block alive at a time.
    Under ``BlockDiffusion`` a noisy query sees clean keys that stand
    behind it, so every block takes all the keys."""
    b, s, kv, g, d = q.shape
    block = min(block, s)
    if s % block:
        raise ValueError(f"sequence length {s} is not a multiple of the "
                         f"attention block {block}")
    diffusion = isinstance(mask, BlockDiffusion)
    reach = 0 if diffusion else _reach(s, mask, block)
    span = s if diffusion else reach + block
    scale = 1.0 / math.sqrt(d)
    front = ((0, 0), (reach, 0))
    kp = jnp.pad(k.astype(dtype), front + ((0, 0), (0, 0)))
    vp = jnp.pad(v.astype(dtype), front + ((0, 0), (0, 0)))
    docp = jnp.pad(doc, front, constant_values=-1)

    @jax.checkpoint
    def one(qb, doc_q, q0):
        first = 0 if diffusion else q0       # of the span, in the padding
        kb, vb, doc_k = (jax.lax.dynamic_slice_in_dim(a, first, span, axis=1)
                         for a in (kp, vp, docp))
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb,
                        preferred_element_type=_f32)
        qi = q0 + jnp.arange(block)[:, None]
        kj = first - reach + jnp.arange(span)[None, :]
        if diffusion:
            ok = mask.allows(qi, kj)
        else:
            ok = kj <= qi
            if mask:
                ok &= qi - kj < mask
        ok = ok[None] & (doc_q[:, :, None] == doc_k[:, None, :])
        sc = jnp.where(ok[:, None, None], sc * scale, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(dtype), vb,
                          preferred_element_type=_f32).astype(dtype)

    nb = s // block
    _, out = jax.lax.scan(
        lambda _, x: (None, one(*x)), None,
        (jnp.moveaxis(q.astype(dtype).reshape(b, nb, block, kv, g, d), 1, 0),
         jnp.moveaxis(doc.reshape(b, nb, block), 1, 0),
         jnp.arange(nb, dtype=jnp.int32) * block))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, kv, g, d)


# What the event ``attention_path`` says of a layer's inputs on each path
INPUTS = {"kernel": "fused", "scan": "composed"}


def self_attention(module: nn.Module, x, doc, mask, *, heads: int,
                   kv_heads: int, head_dim: int, eps: float, rotary_of,
                   block: int, dtype, rotary_dim: int = 0,
                   zero_centred: bool = False, gated: bool = False,
                   qk_norm: bool = True):
    """Attention of ``x`` ``(B, S, d)`` over itself within documents
    ``doc`` under ``mask`` (``blocked_attention``) by whichever path
    ``attention_path`` gives here, before the output's product: ``q, k, v
    = x Wq, x Wk, x Wv`` (``module``'s ``wq``, ``wk``, ``wv``), per head
    ``q = RMSNorm(q)``, ``k = RMSNorm(k)`` (``q_norm/scale``,
    ``k_norm/scale``), the rotary embedding where ``rotary_of`` is
    ``(theta, positions)`` (positions ``(P, S)``, or None to count from
    the sequence's start; None for no rotary), over the first
    ``rotary_dim`` columns of a head (0: all). Returns ``(B, S, H * D)``
    in ``dtype``, named ``attention`` for a ``remat`` policy.

    ``zero_centred`` norms scale by ``1 + w`` (``NormScale``); without
    ``qk_norm`` there are no q/k norms and no weights for them. ``gated``:
    ``Wq`` gives each head its query and then a gate of as many columns,
    ``(d, H * 2D)``, and the result is ``attention * sigmoid(gate)``
    (scope ``gate_out``).

    On the kernel's path the norms, rotary, the scale and the kernel's
    layout are one pass each way (``ops/attention_inputs.py``); on the
    scan's they are the composed chain (``rms_norm``, ``rotary``), the
    reference of that pass. Scopes: ``qkv/project`` the products,
    ``qkv/prepare`` what lies between them and ``scores``."""
    b, s, d = x.shape
    h, kv, hd = heads, kv_heads, head_dim
    # the kernel's blocks have to divide the keys it is given, which under
    # the three-part mask are the clean copy's alone
    keys = mask.clean_len if isinstance(mask, BlockDiffusion) else s
    kernel = attention_path(jax.default_backend(), jax.device_count(), hd,
                            keys) == "kernel"
    with jax.named_scope("qkv"):
        with jax.named_scope("project"):
            q = _dot(x, module.param("wq", _init, (d, h * hd * (1 + gated)),
                                     _f32), dtype).reshape(b, s, h, -1)
            if gated:
                q, gate = q[..., :hd], q[..., hd:].reshape(b, s, h * hd)
            k = _dot(x, module.param("wk", _init, (d, kv * hd), _f32),
                     dtype).reshape(b, s, kv, hd)
            v = _dot(x, module.param("wv", _init, (d, kv * hd), _f32),
                     dtype).reshape(b, s, kv, hd)
        q_scale = k_scale = None
        if qk_norm:
            q_scale = NormScale(zero_centred, name="q_norm")(hd)
            k_scale = NormScale(zero_centred, name="k_norm")(hd)
        with jax.named_scope("prepare"):
            if kernel:
                q, k, v = attention_inputs(q, k, v, q_scale, k_scale,
                                           rotary_of, dtype, eps, rotary_dim)
            else:
                if qk_norm:
                    q, k = (rms_norm(q, q_scale, eps),
                            rms_norm(k, k_scale, eps))
                if rotary_of is not None:
                    q, k = (rotary(x, *rotary_of, dims=rotary_dim)
                            for x in (q, k))
    with jax.named_scope("scores"):
        if kernel:
            out = jnp.transpose(heads_first_attention(q, k, v, doc, mask),
                                (0, 3, 1, 2, 4))
        else:
            out = blocked_attention(q.reshape(b, s, kv, h // kv, hd), k, v,
                                    doc, mask, block, dtype)
        out = checkpoint_name(out.reshape(b, s, h * hd), "attention")
    if gated:
        with jax.named_scope("gate_out"):
            out = out * jax.nn.sigmoid(gate)
    return out


# ----------------------------------------------------------------- experts
def sigmoid_router(x, w_router, bias, top_k: int, *, eps: float,
                   scale: float):
    """``(chosen, weight)``, both ``(N, top_k)``, of ``x`` ``(N, d)``
    float32 under the router ``w_router`` ``(d, experts_total)`` and the
    layer's ``bias`` (the module docstring). The product is at
    ``HIGHEST``: a near-tie turns on its last bits."""
    scores = jax.nn.sigmoid(jnp.dot(
        x, w_router, precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    s = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, s / (jnp.sum(s, -1, keepdims=True) + eps) * scale


def softmax_router(x, w_router, top_k: int):
    """``(chosen, weight)``, both ``(N, top_k)``, of ``x`` ``(N, d)``
    float32 under the router ``w_router`` ``(d, experts_total)``: ``s =
    softmax(x Wr)`` in float32 over ALL experts, chosen = top-k of ``s``,
    ``w = s[chosen] / sum s[chosen]``. The product is at ``HIGHEST``, as
    the sigmoid router's."""
    scores = jax.nn.softmax(jnp.dot(
        x, w_router, precision=jax.lax.Precision.HIGHEST), axis=-1)
    s, chosen = jax.lax.top_k(scores, top_k)
    return chosen, s / jnp.sum(s, -1, keepdims=True)


def balanced_bias(bias, chosen, coeff: float):
    """The router's ``bias`` after a step that routed ``chosen``: one
    ``coeff`` up for an expert under the mean load, one down for one over
    it, the mean taken off."""
    per_expert = jnp.sum(jax.nn.one_hot(
        chosen.reshape(-1), bias.shape[0], dtype=_f32), axis=0)
    c = coeff * jnp.sign(jnp.mean(per_expert) - per_expert)
    return bias + c - jnp.mean(c)


def buffer_rows(n: int, top_k: int, held: int, total: int, slack: float,
                tile: int) -> int:
    """The buffer's rows for ``n`` tokens: ``slack`` x what an even
    routing sends to the ``held`` experts here all together, in whole
    tiles, and no more than every assignment there is."""
    even = n * top_k * held / total
    return -(-min(n * top_k, math.ceil(slack * even)) // tile) * tile


def expert_paths(backend: str, devices: int) -> Dict[str, str]:
    """The paths of the expert layer's two kinds of work here: its grouped
    products (``ops/grouped.py``) and the sum of its buffer's rows into
    their tokens (``ops/rows_to_tokens.py``). What ``train()`` says once,
    as the event ``expert_path``, for every family that calls
    ``dispatch_experts``."""
    return {"products": grouped_path(backend, devices),
            "rows_to_tokens": rows_to_tokens.rows_path(backend, devices)}


def dispatch_experts(x, chosen, weight, w_gate, w_up, w_down, *,
                     experts_total: int, experts_held: Tuple[int, int],
                     rows_slack: float, dtype,
                     activation: Callable = jax.nn.silu
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The partial result of the experts held here and what the routing
    did (``COUNTERS``), see the module docstring. ``x`` is ``(N, d)``
    float32, ``chosen`` and ``weight`` ``(N, top_k)`` from a router the
    caller ran, on ``x`` or on another tensor; the three weights are
    ``(count, d, width)``, ``(count, d, width)`` and ``(count, width, d)``
    float32. An expert is ``(activation(x W_gate) * (x W_up)) W_down``:
    SwiGLU by default, ReGLU with ``jax.nn.relu``."""
    n, d = x.shape
    k = chosen.shape[1]
    first, count = experts_held
    paths = expert_paths(jax.default_backend(), jax.device_count())
    path = paths["products"]
    rows = buffer_rows(n, k, count, experts_total, rows_slack,
                       row_tile(path))
    tiers = -(-n * k // rows)

    with jax.named_scope("dispatch"):
        # every assignment's expert here (``count`` = not held); one
        # stable sort puts the held ones first, grouped by expert, in
        # token order within an expert
        local = (chosen - first).reshape(-1)
        expert = jnp.where((local >= 0) & (local < count), local, count)
        load = jnp.sum(expert[:, None] == jnp.arange(count)[None, :],
                       axis=0, dtype=jnp.int32)    # per held expert
        ends = jnp.cumsum(load)
        here_n = ends[-1]
        order = jnp.pad(jnp.argsort(expert, stable=True),
                        (0, tiers * rows - n * k))

    def tier(lo, xb, weight, w_gate, w_up, w_down):
        """The partial result of the sorted positions ``lo .. lo +
        rows``, and how many of them hold an assignment. The positions
        past ``here_n`` lie past the groups' sum: the products leave
        them 0, in the result and in every gradient."""
        with jax.named_scope("dispatch"):
            at = jax.lax.dynamic_slice_in_dim(order, lo, rows)
            token = at // k
            sizes = (jnp.clip(ends, lo, lo + rows)
                     - jnp.clip(ends - load, lo, lo + rows))
            plan = rows_to_tokens.plan(
                token, n, paths["rows_to_tokens"], expert=expert,
                ends=ends, load=load, lo=lo, here=here_n)
            xs = rows_to_tokens.dispatch(xb, plan)
        with jax.named_scope("experts"):
            def mm(a, w, out=dtype):
                return grouped_dot(a.astype(dtype), w.astype(dtype), sizes,
                                   out, path)

            hidden = (checkpoint_name(mm(xs, w_gate), "experts"),
                      checkpoint_name(mm(xs, w_up), "experts"))
            y = mm(activation(hidden[0]) * hidden[1], w_down, _f32)
        with jax.named_scope("combine"):
            y = y * jnp.take(weight.reshape(-1), at)[:, None]
            return rows_to_tokens.combine(y, plan), jnp.sum(sizes)

    operands = (x.astype(dtype), weight, w_gate, w_up, w_down)
    out, computed = tier(0, *operands)
    if tiers > 1:
        # The positions beyond, a tier at a time and only the tiers
        # that hold an assignment. Recomputed backward, as a whole (a
        # cond hands on the residuals of both its branches: 3.5 GB of
        # temporaries in the benchmark's cell) and a tier at a time
        # within (the scan would stack every tier's: 0.9 GB).
        def nothing(*_):
            return jnp.zeros((n, d), _f32), jnp.zeros((), jnp.int32)

        @jax.checkpoint
        def beyond(*operands):
            def one(acc, lo):
                more, also = jax.lax.cond(
                    lo < here_n, jax.checkpoint(tier), nothing, lo,
                    *operands)
                return (acc[0] + more, acc[1] + also), None

            return jax.lax.scan(
                one, nothing(), jnp.arange(1, tiers, dtype=jnp.int32)
                * rows)[0]

        more, also = jax.lax.cond(here_n > rows, beyond, nothing,
                                  *operands)
        out, computed = out + more, computed + also

    with jax.named_scope("router"):
        here = here_n.astype(_f32)
        counters = {
            "moe_dropped_frac": (here - computed.astype(_f32))
            / jnp.maximum(here, 1.0),
            "moe_load_max_over_mean": jnp.max(load).astype(_f32) * count
            / jnp.maximum(here, 1.0),
            "moe_here_frac": here / (n * k),
            "moe_overflow_frac": (here_n > rows).astype(_f32),
            "moe_rows_filled_frac": jnp.minimum(here, rows) / rows,
        }
    return out, counters


def refuses(cfg, data_axis: int):
    """What of ``cfg`` neither token family trains with, beside what no
    token model does (train/step.py::check_step_config)."""
    refused = [
        ("mesh.partition=zero1 (no rule shards expert or attention "
         "leaves yet)", cfg.mesh.partition != "replicated"),
        ("model.fused_blocks / model.fused_epilogue (ResNet kernels)",
         cfg.model.fused_blocks or cfg.model.fused_epilogue != "off"),
    ]
    return [what for what, is_set in refused if is_set]


def sow_counters(module: nn.Module, counters: Dict[str, jax.Array]) -> None:
    """A layer's counters into the module's ``counters`` collection, where
    the train step finds a family's ``Family.counters``."""
    for name in COUNTERS:
        module.sow("counters", name, counters[name], init_fn=lambda: 0.0,
                   reduce_fn=lambda old, new: new)
