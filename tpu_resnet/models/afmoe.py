"""A sparse-expert transformer of the ``afmoe`` family as one chip of an
expert-parallel group sees it: windowed and full attention mixed, each with
a sigmoid output gate, leading dense layers, then layers of routed experts
beside a shared one. The first sequence model of this trainer; it goes
through ``train/loop.py`` like the image classifiers.

The equations (``d`` hidden size; RMSNorm with a weight everywhere):

- ``h = E[ids] * sqrt(d)``; logits ``= RMSNorm(h_L) W_head`` (untied).
- layer: ``h += post_attn_norm(Attn(input_norm(h)))``;
  ``h += post_mlp_norm(F(pre_mlp_norm(h)))``. ``F`` is a SwiGLU MLP in the
  dense layers and the expert layer in the others.
- attention: ``q, k, v, g = x Wq, x Wk, x Wv, x Wg`` (no biases); per head
  ``q = RMSNorm(q)``, ``k = RMSNorm(k)``; rotary embedding (rotate-half,
  whole head) on sliding layers only, full layers carry no position;
  scores ``q k^T / sqrt(head_dim)``, softmax over keys ``j <= i`` of the
  same document and, on sliding layers, ``i - j < window``;
  ``out = (softmax V) * sigmoid(g)``; ``Attn = out Wo``. A document begins
  at every id 0: ``doc = cumsum(ids == 0)``.
- expert layer: ``s = sigmoid(x Wr)`` in float32 over ALL
  ``experts_total``; chosen = top-k of ``s + b`` (``b`` is the layer's
  ``expert_bias``, state without a gradient); ``w = s[chosen] / (sum
  s[chosen] + 1e-20) * route_scale``; ``F(x) = Shared(x) + sum w_e
  Expert_e(x)`` over the chosen experts THIS CHIP HOLDS
  (``experts_held = (first, count)``). What the absent experts would add
  is left out and that partial result goes on. In training, after the
  step's routing, ``b += c - mean(c)`` with ``c = load_balance_coeff *
  sign(mean(n) - n)``, ``n`` the assignments per expert.

No token is dropped, and the layer does the work its routing fills: one
stable sort of the ``N * top_k`` assignments by their expert here puts
those that fall on a held expert first, grouped by expert and in token
order within an expert. The first ``rows`` of them (``rows_slack`` x what
an even routing sends to all held experts together, in whole tiles) are one
buffer: a gather by the sorted order, three grouped products whose work
follows the groups' sizes (``ops/grouped.py``, which also says how its path
is chosen and what its kernel never writes: the buffer's rows past the
last assignment come back 0, in the result and in every gradient, so none
of them reaches the weighted scatter-add), and the scatter-add. Whatever
sorted positions lie beyond the buffer go through the same code a tier of
``rows`` at a time, only the tiers that hold an assignment, under a
``lax.cond`` that is false while the held experts together take no more
than the buffer. The counters say what happened (``moe_dropped_frac`` reads
0 by that construction and is counted from the groups' sizes all the same;
``moe_overflow_frac`` says whether the tiers beyond ran,
``moe_rows_filled_frac`` how much of the buffer carried an assignment).

Attention never builds a ``(B, H, S, S)`` score tensor. On one TPU chip, at
heads of a multiple of 128 and sequences its blocks divide, it is one fused
kernel that keeps each tile of scores on the chip (``ops/attention.py``,
which also says how the path is chosen); everywhere else a block of
queries at a time against the keys its mask can reach, as a scan whose
body is under ``jax.checkpoint`` (``blocked_attention``). Parameters, the
residual stream, norms, router, softmax and logits are float32; matrix
products take ``dtype`` operands (bf16), accumulate in float32 and hand on
``dtype``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from tpu_resnet.ops.attention import (attention_path, fused_attention,
                                      key_blocks)
from tpu_resnet.ops.grouped import grouped_dot, grouped_path, row_tile

# layer kinds: what F is, and which mask attention takes
LAYER_KINDS = ("dense_sliding", "dense_full", "moe_sliding", "moe_full")
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


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           _f32)
        x = x.astype(_f32)
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + self.eps) * scale


def rotary(x, theta: float):
    """Rotate-half rotary embedding over the whole head; ``x`` is
    ``(B, S, H, D)`` float32, positions count from the sequence's start."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=_f32) / d))
    ang = jnp.arange(x.shape[1], dtype=_f32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _reach(seq_len: int, window: int, block: int) -> int:
    """The positions before a block's first query that the scan's every
    block takes keys from: the window, or on a full layer everything
    before the last block."""
    return min(window, seq_len - block) if window else seq_len - block


def blocked_attention(q, k, v, doc, window: int, block: int, dtype):
    """Causal attention within documents, ``window`` > 0 for a sliding
    layer. ``q`` is ``(B, S, KV, G, D)`` (G query heads a key/value head),
    ``k`` and ``v`` ``(B, S, KV, D)``, ``doc`` ``(B, S)``. Returns ``(B, S,
    KV, G, D)`` in ``dtype``.

    A ``lax.scan`` over blocks of queries. Every block takes the same
    number of keys, ``reach + block``: the ``reach`` positions before its
    first query that a mask can let it see (the window, or on a full layer
    everything before the last block) and its own. Keys and documents are
    padded in front by ``reach`` (document -1, which no query belongs to),
    so that the first blocks take that many too: one shape, one body, the
    scores of one block alive at a time."""
    b, s, kv, g, d = q.shape
    block = min(block, s)
    if s % block:
        raise ValueError(f"sequence length {s} is not a multiple of the "
                         f"attention block {block}")
    reach = _reach(s, window, block)
    span = reach + block
    scale = 1.0 / math.sqrt(d)
    front = ((0, 0), (reach, 0))
    kp = jnp.pad(k.astype(dtype), front + ((0, 0), (0, 0)))
    vp = jnp.pad(v.astype(dtype), front + ((0, 0), (0, 0)))
    docp = jnp.pad(doc, front, constant_values=-1)

    @jax.checkpoint
    def one(qb, doc_q, q0):
        kb, vb, doc_k = (jax.lax.dynamic_slice_in_dim(a, q0, span, axis=1)
                         for a in (kp, vp, docp))
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb,
                        preferred_element_type=_f32)
        qi = q0 + jnp.arange(block)[:, None]
        kj = q0 - reach + jnp.arange(span)[None, :]
        ok = kj <= qi
        if window:
            ok &= qi - kj < window
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


class Attention(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int
    window: int            # 0 = full attention, and then no rotary
    rope_theta: float
    eps: float
    block: int
    dtype: Any

    @nn.compact
    def __call__(self, x, doc):
        b, s, d = x.shape
        h, kv, hd = self.heads, self.kv_heads, self.head_dim
        with jax.named_scope("qkv"):
            q = _dot(x, self.param("wq", _init, (d, h * hd), _f32),
                     self.dtype).reshape(b, s, h, hd)
            k = _dot(x, self.param("wk", _init, (d, kv * hd), _f32),
                     self.dtype).reshape(b, s, kv, hd)
            v = _dot(x, self.param("wv", _init, (d, kv * hd), _f32),
                     self.dtype).reshape(b, s, kv, hd)
            gate = _dot(x, self.param("wg", _init, (d, h * hd), _f32),
                        self.dtype)
            q = RMSNorm(self.eps, name="q_norm")(q)
            k = RMSNorm(self.eps, name="k_norm")(k)
            if self.window:
                q, k = rotary(q, self.rope_theta), rotary(k, self.rope_theta)
        with jax.named_scope("scores"):
            q = q.reshape(b, s, kv, h // kv, hd)
            if attention_path(jax.default_backend(), jax.device_count(),
                              hd, s) == "kernel":
                out = fused_attention(q, k, v, doc, self.window, self.dtype)
            else:
                out = blocked_attention(q, k, v, doc, self.window,
                                        self.block, self.dtype)
            out = checkpoint_name(out.reshape(b, s, h * hd), "attention")
        with jax.named_scope("gate_out"):
            out = out * jax.nn.sigmoid(gate)
            return _dot(out, self.param("wo", _init, (h * hd, d), _f32),
                        self.dtype)


class SwiGLU(nn.Module):
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


class ExpertLayer(nn.Module):
    """The routed experts this chip holds, and the shared one."""
    width: int
    experts_total: int
    experts_held: Tuple[int, int]      # (first, count)
    top_k: int
    shared: int                        # shared experts (their width adds)
    route_scale: float
    balance_coeff: float
    rows_slack: float
    dtype: Any

    def _rows(self, n: int, tile: int) -> int:
        """The buffer's rows for ``n`` tokens: ``rows_slack`` x what an
        even routing sends to the experts held here all together, in whole
        tiles, and no more than every assignment there is."""
        even = n * self.top_k * self.experts_held[1] / self.experts_total
        return -(-min(n * self.top_k, math.ceil(self.rows_slack * even))
                 // tile) * tile

    @nn.compact
    def __call__(self, x, train: bool):
        shape = x.shape
        x = x.reshape(-1, shape[-1])                   # (N, d) float32
        n, d = x.shape
        first, count = self.experts_held
        k, total = self.top_k, self.experts_total
        bias = self.variable("batch_stats", "expert_bias",
                             lambda: jnp.zeros((total,), _f32))
        w_gate = self.param("gate", _init, (count, d, self.width), _f32)
        w_up = self.param("up", _init, (count, d, self.width), _f32)
        w_down = self.param("down", _init, (count, self.width, d), _f32)
        path = grouped_path(jax.default_backend(), jax.device_count())
        rows = self._rows(n, row_tile(path))
        tiers = -(-n * k // rows)

        with jax.named_scope("router"):
            scores = jax.nn.sigmoid(jnp.dot(
                x, self.param("router", _init, (d, total), _f32),
                precision=jax.lax.Precision.HIGHEST))
            _, chosen = jax.lax.top_k(
                scores + jax.lax.stop_gradient(bias.value), k)
            s = jnp.take_along_axis(scores, chosen, axis=-1)
            weight = s / (jnp.sum(s, -1, keepdims=True) + 1e-20) \
                * self.route_scale                     # (N, k) float32

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
                xs = jnp.take(xb, token, axis=0)
            with jax.named_scope("experts"):
                def mm(a, w, out=self.dtype):
                    return grouped_dot(a.astype(self.dtype),
                                       w.astype(self.dtype), sizes, out,
                                       path)

                hidden = (checkpoint_name(mm(xs, w_gate), "experts"),
                          checkpoint_name(mm(xs, w_up), "experts"))
                y = mm(jax.nn.silu(hidden[0]) * hidden[1], w_down, _f32)
            with jax.named_scope("combine"):
                y = y * jnp.take(weight.reshape(-1), at)[:, None]
                return (jnp.zeros((n, d), _f32).at[token].add(y),
                        jnp.sum(sizes))

        operands = (x.astype(self.dtype), weight, w_gate, w_up, w_down)
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

        if self.shared:
            with jax.named_scope("shared"):
                out = out + SwiGLU(self.width * self.shared, self.dtype,
                                   name="shared")(x)

        with jax.named_scope("router"):
            here = here_n.astype(_f32)
            for name, value in (
                    ("moe_dropped_frac", (here - computed.astype(_f32))
                     / jnp.maximum(here, 1.0)),
                    ("moe_load_max_over_mean", jnp.max(load).astype(_f32)
                     * count / jnp.maximum(here, 1.0)),
                    ("moe_here_frac", here / (n * k)),
                    ("moe_overflow_frac", (here_n > rows).astype(_f32)),
                    ("moe_rows_filled_frac",
                     jnp.minimum(here, rows) / rows)):
                self.sow("counters", name, value, init_fn=lambda: 0.0,
                         reduce_fn=lambda old, new: new)
            if train and not self.is_initializing():
                per_expert = jnp.sum(jax.nn.one_hot(
                    chosen.reshape(-1), total, dtype=_f32), axis=0)
                c = self.balance_coeff * jnp.sign(
                    jnp.mean(per_expert) - per_expert)
                bias.value = bias.value + c - jnp.mean(c)
        return out.reshape(shape)


class Layer(nn.Module):
    kind: str
    arch: Any

    @nn.compact
    def __call__(self, h, doc, train: bool):
        m = self.arch
        window = m.window if self.kind.endswith("_sliding") else 0
        with jax.named_scope("attention"):
            a = Attention(m.heads, m.kv_heads, m.head_dim, window,
                          m.rope_theta, m.eps, m.attn_block, m.dtype,
                          name="attn")(
                RMSNorm(m.eps, name="input_norm")(h), doc)
            h = h + RMSNorm(m.eps, name="post_attn_norm")(a)
        x = RMSNorm(m.eps, name="pre_mlp_norm")(h)
        if self.kind.startswith("dense"):
            with jax.named_scope("dense_mlp"):
                f = SwiGLU(m.dense_width, m.dtype, name="mlp")(x)
        else:
            with jax.named_scope("moe"):
                f = ExpertLayer(m.expert_width, m.experts_total,
                                tuple(m.experts_held), m.top_k, m.shared,
                                m.route_scale, m.balance_coeff,
                                m.rows_slack, m.dtype, name="moe")(x, train)
        return h + RMSNorm(m.eps, name="post_mlp_norm")(f)


@dataclasses.dataclass(frozen=True)
class Arch:
    """The model's fields. ``layers`` lists each layer's kind in order;
    ``experts_held = (first, count)`` are the routed experts this chip
    holds of ``experts_total``; ``vocab_rows`` the rows of the vocabulary
    it holds (ids, logits and the loss are over them)."""
    layers: Tuple[str, ...]
    hidden: int = 2048
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    window: int = 2048
    dense_width: int = 6144
    expert_width: int = 1024
    experts_total: int = 128
    experts_held: Tuple[int, int] = (0, 8)
    top_k: int = 8
    shared: int = 1
    vocab_rows: int = 25024
    rope_theta: float = 10000.0
    eps: float = 1e-5
    route_scale: float = 2.826
    balance_coeff: float = 0.001
    # the rows of the one buffer that holds the assignments of all held
    # experts, over the rows an even routing sends them together. Their
    # sum wanders far less than the busiest of them: a fresh router's held
    # share reads 0.64 to 1.36 x even (PERF.md section 6), so at 2 the
    # overflow tiers are rare; the products' cost follows the rows filled,
    # the gather's and the scatter-add's the buffer's.
    rows_slack: float = 2.0
    attn_block: int = 256              # queries a block of the scan path
    remat: bool = False                # each layer's backward keeps _KEEP
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "layers", tuple(self.layers))
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        bad = [k for k in self.layers if k not in LAYER_KINDS]
        first, count = self.experts_held
        if bad or not self.layers:
            raise ValueError(f"layer kinds must be of {LAYER_KINDS}, got "
                             f"{list(self.layers)}")
        if self.heads % self.kv_heads or self.head_dim % 2:
            raise ValueError("query heads must divide by key/value heads "
                             "and the head size by 2")
        if not (0 <= first and count >= 1
                and first + count <= self.experts_total
                and self.top_k <= self.experts_total):
            raise ValueError(f"experts_held {self.experts_held} does not "
                             f"lie in the {self.experts_total} experts")


class Afmoe(nn.Module):
    """``apply(variables, ids, train=...) -> logits`` of shape ``(B, S,
    vocab_rows)`` float32; ``ids`` are int32 in ``[0, vocab_rows)``. The
    mutable collections are ``batch_stats`` (each expert layer's
    ``expert_bias``) and ``counters`` (what the routing did this call)."""
    arch: Arch

    @nn.compact
    def __call__(self, ids, *, train: bool = False):
        m = self.arch
        ids = jnp.asarray(ids, jnp.int32)
        doc = jnp.cumsum((ids == 0).astype(jnp.int32), axis=1)
        with jax.named_scope("embed"):
            table = self.param("embed", _init, (m.vocab_rows, m.hidden),
                               _f32)
            h = jnp.take(table, ids, axis=0) * math.sqrt(m.hidden)
        layer = nn.remat(Layer, static_argnums=(3,),
                         policy=_KEEP) if m.remat else Layer
        for i, kind in enumerate(m.layers):
            h = layer(kind, m, name=f"layer_{i}")(h, doc, train)
        with jax.named_scope("head"):
            return _dot(RMSNorm(m.eps, name="final_norm")(h),
                        self.param("head", _init, (m.hidden, m.vocab_rows),
                                   _f32), m.dtype, out=_f32)


def attention_paths(model: Arch, seq_len: int, backend: str,
                    devices: int) -> List[Dict[str, object]]:
    """For each layer, the ``path`` its attention takes at ``seq_len`` on
    ``devices`` of ``backend`` and, in tiles of queries by keys, ``key_blocks_visited``
    of ``key_blocks_total``: the kernel's from its own mask table, the
    scan's from its uniform span of ``reach + block`` keys a block. What
    ``train()`` says once, as the event ``attention_path``."""
    path = attention_path(backend, devices, model.head_dim, seq_len)
    block = min(model.attn_block, seq_len)
    rows = []
    for i, kind in enumerate(model.layers):
        window = model.window if kind.endswith("_sliding") else 0
        if path == "kernel":
            visited, total = key_blocks(seq_len, window,
                                        model.heads // model.kv_heads)
        else:
            span = _reach(seq_len, window, block) + block
            visited = (seq_len // block) * -(-span // block)
            total = (seq_len // block) ** 2
        rows.append(dict(layer=i, kind=kind, path=path,
                         key_blocks_visited=visited, key_blocks_total=total))
    return rows


def multiply_adds_per_token(model: Arch, seq_len: int) -> float:
    """The multiply-adds one token meets in a forward pass here: every
    matrix it is multiplied by (``top_k * count / experts_total`` of an
    expert, the routing being even), and attention's scores and values
    over the entries its causal and window masks leave (document masks
    leave fewer)."""
    d, hd = model.hidden, model.heads * model.head_dim
    attn = 2 * d * hd + 2 * d * model.kv_heads * model.head_dim + hd * d
    total = d * model.vocab_rows
    for kind in model.layers:
        window = model.window if kind.endswith("_sliding") else 0
        live = sum(min(i + 1, window) if window else i + 1
                   for i in range(seq_len)) / seq_len
        total += attn + 2 * hd * live
        if kind.startswith("dense"):
            total += 3 * d * model.dense_width
        else:
            share = model.top_k * model.experts_held[1] / model.experts_total
            total += d * model.experts_total \
                + 3 * d * model.expert_width * (model.shared + share)
    return total


def train_flops_per_sequence(model: Arch, seq_len: int) -> float:
    """Forward and backward model FLOPs of one sequence: 3 x 2 x
    multiply-adds (nothing recomputed counts)."""
    return 6.0 * multiply_adds_per_token(model, seq_len) * seq_len


# ------------------------------------------------------------------ family
# What models/__init__.py registers as the family ``afmoe``, with
# ``COUNTERS`` above.
def build(cfg) -> Afmoe:
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
        remat=cfg.model.remat, dtype=jnp.dtype(cfg.model.compute_dtype)))


def spell(cfg):
    """Depth, the experts held of the router's width, and the sequence
    length each change the traced program."""
    a = cfg.afmoe
    return (f"tokens{cfg.data.seq_len}",
            f"afmoe{len(a.layers)}l_e{a.experts_held}of{a.experts_total}")


def train_flops_per_example(cfg, xla_counted: bool = True) -> float:
    """Counted from the shapes: XLA's count of the lowered step would
    hold what attention recomputes backward."""
    return train_flops_per_sequence(build(cfg).arch, cfg.data.seq_len)


def refuses(cfg, data_axis: int):
    """What of ``cfg`` this family does not train with, beside what no
    token model does (train/step.py::check_step_config)."""
    refused = [
        ("mesh.partition=zero1 (no rule shards expert or attention "
         "leaves yet)", cfg.mesh.partition != "replicated"),
        ("model.fused_blocks / model.fused_epilogue (ResNet kernels)",
         cfg.model.fused_blocks or cfg.model.fused_epilogue != "off"),
    ]
    return [what for what, is_set in refused if is_set]


def startup_events(model: Afmoe, cfg):
    """Static, so said once: the path each layer's attention takes here
    and the key blocks its mask leaves (docs/OBSERVABILITY.md)."""
    return {"attention_path": {"layers": attention_paths(
        model.arch, cfg.data.seq_len, jax.default_backend(),
        jax.device_count())}}
