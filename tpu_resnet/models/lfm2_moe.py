"""A conv-hybrid sparse-expert decoder of the ``lfm2_moe`` family (LFM2,
LiquidAI) as one chip of an expert-parallel group sees it: most layers mix
tokens by a gated short convolution, one in four by full attention (heads
of 64), the leading layers carry a dense MLP and the others routed experts
under a sigmoid router with a bias, and the output head is the embedding.

The equations (``d`` hidden size; RMSNorm with a weight; no bias):

- ``h = E[ids]`` (no scale); logits ``= RMSNorm(h_L) E^T``: the head is the
  embedding, one leaf used twice, and its gradient is the sum of both uses.
- layer: ``h += Mix(operator_norm(h))``; ``h += F(ffn_norm(h))``. ``Mix`` is
  the short convolution (``*_conv`` kinds) or attention (``*_full``); ``F`` a
  SwiGLU MLP (``dense_*``) or the expert layer (``moe_*``).
- gated short convolution (``conv_taps`` = ``K`` taps): ``[B, C, X] = x
  W_in`` (the three thirds in this order); ``u = B * X``; a causal depthwise
  convolution along the sequence, one filter a channel, the LAST tap on the
  current position: ``c_t = sum_j w[:, K-1-j] * u_{t-j}``, ``j = 0 .. K-1``;
  ``y = C * c``; ``Mix = y W_out``. **In a packed sequence ``u_{t-j}`` counts
  as 0 where position ``t-j`` lies before the start of ``t``'s document** (a
  document begins at every id 0: ``doc = cumsum(ids == 0)``, as attention's
  mask has it), so a document gets what it would get alone. The products
  hand on ``dtype`` (bf16); the elementwise part (``B * X``, the taps, ``C
  *``) is computed in float32 from them and rounded once, on the way into
  ``W_out``.
- attention: ``q, k, v = x Wq, x Wk, x Wv``; per head ``q = RMSNorm(q)``,
  ``k = RMSNorm(k)``; rotary embedding (rotate-half, whole head) on every
  attention layer; scores ``q k^T / sqrt(head_dim)``, softmax over the keys
  ``j <= i`` of the same document; ``Mix = (softmax V) Wo``. No gate, no
  window.
- expert layer: ``models/transformer.py``'s sigmoid router with the layer's
  ``expert_bias`` (``eps`` 1e-6, ``route_scale`` 1 here), then its dispatch:
  ``F(x) = sum w_e Expert_e(x)`` over the chosen experts THIS CHIP HOLDS
  (``experts_held = (first, count)``), ``Expert(x) = (silu(x W1) * (x W3))
  W2``. No shared expert, no auxiliary loss. What the absent experts would
  add is left out and that partial result goes on.

Attention by path, the dispatch with its five ``moe_*`` counters, the
router and its bias update, RMSNorm, SwiGLU, the rotary embedding and the
products' numerics are ``models/transformer.py``'s, shared with the other
token families, as is the short convolution with its document cut
(``short_conv``, which ``qwen3_next`` calls too); this module keeps what is
LFM2's own: the gated operator around it and ``conv_cut_taps_frac``, the layer
kinds that pair either mixer with either ``F``, two norms a layer, the tied
head. Parameters, the residual stream, norms, router, softmax, logits and
loss are float32; matrix products take ``dtype`` operands (bf16),
accumulate in float32 and hand on ``dtype``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_resnet.models import transformer
from tpu_resnet.models.transformer import (  # noqa: F401  (re-exported)
    INPUTS, RMSNorm, SwiGLU, _dot, _f32, _init, _KEEP, balanced_bias,
    dispatch_experts, same_document, self_attention, short_conv,
    sigmoid_router, sow_counters, taps_init)
from tpu_resnet.ops.attention import attention_path, key_blocks

# layer kinds: what F is, and which token mixer the layer takes
LAYER_KINDS = ("dense_conv", "dense_full", "moe_conv", "moe_full")
COUNTERS = transformer.COUNTERS + ("conv_cut_taps_frac",)


def cut_taps_frac(doc, taps: int):
    """Of the ``taps x tokens`` taps a conv operator reads, the share the
    document cut sets to 0: those that would reach a position of the
    sequence that lies in an earlier document (the positions before the
    sequence's start are the convolution's own padding and do not
    count)."""
    b, s = doc.shape
    cut = sum(jnp.sum(~same_document(doc, j)[:, j:]) for j in range(1, taps))
    return cut.astype(_f32) / (taps * b * s)


class ShortConv(nn.Module):
    """The gated short convolution (``Lfm2ShortConv``)."""
    taps: int
    dtype: Any

    @nn.compact
    def __call__(self, x, doc):
        d = x.shape[-1]
        with jax.named_scope("in_proj"):
            bcx = _dot(x, self.param("in_proj", _init, (d, 3 * d), _f32),
                       self.dtype)
        with jax.named_scope("gate_conv"):
            b, c, x = (a.astype(_f32) for a in jnp.split(bcx, 3, axis=-1))
            y = c * short_conv(
                b * x, self.param("conv", taps_init, (d, self.taps), _f32),
                doc)
        with jax.named_scope("out_proj"):
            return _dot(y, self.param("out_proj", _init, (d, d), _f32),
                        self.dtype)


class Attention(nn.Module):
    arch: "Arch"

    @nn.compact
    def __call__(self, x, doc):
        m = self.arch
        d = x.shape[-1]
        out = self_attention(
            self, x, doc, 0, heads=m.heads, kv_heads=m.kv_heads,
            head_dim=m.head_dim, eps=m.eps, rotary_of=(m.rope_theta, None),
            block=m.attn_block, dtype=m.dtype)
        with jax.named_scope("out"):
            return _dot(out, self.param(
                "wo", _init, (m.heads * m.head_dim, d), _f32), m.dtype)


class ExpertLayer(nn.Module):
    """The routed experts this chip holds, under the sigmoid router."""
    arch: "Arch"

    @nn.compact
    def __call__(self, x, train: bool):
        m = self.arch
        shape = x.shape
        x = x.reshape(-1, shape[-1])                   # (N, d) float32
        d, count = x.shape[1], m.experts_held[1]
        bias = self.variable("batch_stats", "expert_bias",
                             lambda: jnp.zeros((m.experts_total,), _f32))
        w_gate = self.param("gate", _init, (count, d, m.expert_width), _f32)
        w_up = self.param("up", _init, (count, d, m.expert_width), _f32)
        w_down = self.param("down", _init, (count, m.expert_width, d), _f32)
        with jax.named_scope("router"):
            chosen, weight = sigmoid_router(           # (N, k) float32
                x, self.param("router", _init, (d, m.experts_total), _f32),
                bias.value, m.top_k, eps=1e-6, scale=m.route_scale)
        out, counters = dispatch_experts(
            x, chosen, weight, w_gate, w_up, w_down,
            experts_total=m.experts_total, experts_held=m.experts_held,
            rows_slack=m.rows_slack, dtype=m.dtype)
        with jax.named_scope("router"):
            sow_counters(self, counters)
            if train and not self.is_initializing():
                bias.value = balanced_bias(bias.value, chosen,
                                           m.balance_coeff)
        return out.reshape(shape)


class Layer(nn.Module):
    kind: str
    arch: "Arch"

    @nn.compact
    def __call__(self, h, doc, train: bool):
        m = self.arch
        x = RMSNorm(m.eps, name="operator_norm")(h)
        if self.kind.endswith("_conv"):
            with jax.named_scope("conv"):
                h = h + ShortConv(m.conv_taps, m.dtype, name="conv")(x, doc)
        else:
            with jax.named_scope("attention"):
                h = h + Attention(m, name="attn")(x, doc)
        x = RMSNorm(m.eps, name="ffn_norm")(h)
        if self.kind.startswith("dense"):
            with jax.named_scope("dense_mlp"):
                return h + SwiGLU(m.dense_width, m.dtype, name="mlp")(x)
        with jax.named_scope("moe"):
            return h + ExpertLayer(m, name="moe")(x, train)


@dataclasses.dataclass(frozen=True)
class Arch:
    """The model's fields. ``layers`` lists each layer's kind in order;
    ``experts_held = (first, count)`` are the routed experts this chip
    holds of ``experts_total``; ``vocab_rows`` the rows of the (tied)
    vocabulary it holds (ids, logits and the loss are over them)."""
    layers: Tuple[str, ...]
    hidden: int = 2048
    heads: int = 32
    kv_heads: int = 8
    head_dim: int = 64
    conv_taps: int = 3
    dense_width: int = 11776
    expert_width: int = 1536
    experts_total: int = 64
    experts_held: Tuple[int, int] = (0, 8)
    top_k: int = 4
    vocab_rows: int = 8192
    rope_theta: float = 1e6
    eps: float = 1e-5
    route_scale: float = 1.0
    balance_coeff: float = 0.001
    rows_slack: float = 2.0            # transformer.py::buffer_rows
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
        if self.heads % self.kv_heads or self.head_dim % 2 \
                or self.conv_taps < 1:
            raise ValueError("query heads must divide by key/value heads, "
                             "the head size by 2, and a filter has a tap")
        if not (0 <= first and count >= 1
                and first + count <= self.experts_total
                and self.top_k <= self.experts_total):
            raise ValueError(f"experts_held {self.experts_held} does not "
                             f"lie in the {self.experts_total} experts")


class Lfm2Moe(nn.Module):
    """``apply(variables, ids, train=...) -> logits`` of shape ``(B, S,
    vocab_rows)`` float32; ``ids`` are int32 in ``[0, vocab_rows)``. The
    mutable collections are ``batch_stats`` (each expert layer's
    ``expert_bias``) and ``counters`` (what the routing and the document
    cut did this call)."""
    arch: Arch

    @nn.compact
    def __call__(self, ids, *, train: bool = False):
        m = self.arch
        ids = jnp.asarray(ids, jnp.int32)
        doc = jnp.cumsum((ids == 0).astype(jnp.int32), axis=1)
        with jax.named_scope("embed"):
            table = self.param("embed", _init, (m.vocab_rows, m.hidden),
                               _f32)
            h = jnp.take(table, ids, axis=0)
            self.sow("counters", "conv_cut_taps_frac",
                     cut_taps_frac(doc, m.conv_taps), init_fn=lambda: 0.0,
                     reduce_fn=lambda old, new: new)
        layer = nn.remat(Layer, static_argnums=(3,),
                         policy=_KEEP) if m.remat else Layer
        for i, kind in enumerate(m.layers):
            h = layer(kind, m, name=f"layer_{i}")(h, doc, train)
        with jax.named_scope("head"):
            return _dot(RMSNorm(m.eps, name="embedding_norm")(h), table.T,
                        m.dtype, out=_f32)


def token_mixers(model: Arch) -> List[Dict[str, object]]:
    """The kind of every layer and the mixer it takes. What ``train()``
    says once, as the event ``token_mixers``."""
    return [dict(layer=i, kind=kind,
                 mixer="conv" if kind.endswith("_conv") else "attention")
            for i, kind in enumerate(model.layers)]


def attention_paths(model: Arch, seq_len: int, backend: str,
                    devices: int) -> List[Dict[str, object]]:
    """For each ATTENTION layer, the ``path`` it takes at ``seq_len`` on
    ``devices`` of ``backend``, how its ``inputs`` are prepared on that
    path (``transformer.INPUTS``), with its ``head_dim`` (a head of 64 goes to
    the kernel as it is: no ``padded_to``) and, in tiles of queries by
    keys, ``key_blocks_visited`` of ``key_blocks_total``: the kernel's from
    its own mask table, the scan's from its span of every key before a
    block's last query. What ``train()`` says once, as the event
    ``attention_path``."""
    path = attention_path(backend, devices, model.head_dim, seq_len)
    if path == "kernel":
        visited, total = key_blocks(seq_len, 0,
                                    model.heads // model.kv_heads)
    else:
        visited = total = (seq_len // min(model.attn_block, seq_len)) ** 2
    return [dict(layer=i, kind=kind, path=path, inputs=INPUTS[path],
                 head_dim=model.head_dim,
                 key_blocks_visited=visited, key_blocks_total=total)
            for i, kind in enumerate(model.layers)
            if kind.endswith("_full")]


def multiply_adds_per_token(model: Arch, seq_len: int) -> float:
    """The multiply-adds one token meets in a forward pass here: every
    matrix it is multiplied by (``top_k * count / experts_total`` of an
    expert, the routing being even; the tied head once), and attention's
    scores and values over the entries its causal mask leaves (document
    masks leave fewer), and a filter's ``taps`` multiply-adds a channel.
    The convolution's two gates are elementwise and do not count."""
    d, hd = model.hidden, model.heads * model.head_dim
    share = model.top_k * model.experts_held[1] / model.experts_total
    total = d * model.vocab_rows
    for kind in model.layers:
        if kind.endswith("_conv"):
            total += d * 3 * d + d * model.conv_taps + d * d
        else:
            total += (2 * d * hd + 2 * d * model.kv_heads * model.head_dim
                      + 2 * hd * (seq_len + 1) / 2)
        if kind.startswith("dense"):
            total += 3 * d * model.dense_width
        else:
            total += d * model.experts_total \
                + 3 * d * model.expert_width * share
    return total


def train_flops_per_sequence(model: Arch, seq_len: int) -> float:
    """Forward and backward model FLOPs of one sequence: 3 x 2 x
    multiply-adds (nothing recomputed counts)."""
    return 6.0 * multiply_adds_per_token(model, seq_len) * seq_len


# ------------------------------------------------------------------ family
# What models/__init__.py registers as the family ``lfm2_moe``, with
# ``COUNTERS`` above and ``transformer.refuses`` (it trains with what the
# other token families train with).
def build(cfg) -> Lfm2Moe:
    a = cfg.lfm2_moe
    return Lfm2Moe(Arch(
        layers=tuple(a.layers), hidden=a.hidden, heads=a.heads,
        kv_heads=a.kv_heads, head_dim=a.head_dim, conv_taps=a.conv_taps,
        dense_width=a.dense_width, expert_width=a.expert_width,
        experts_total=a.experts_total,
        experts_held=(a.experts_first, a.experts_held), top_k=a.top_k,
        vocab_rows=cfg.data.num_classes, rope_theta=a.rope_theta,
        eps=a.rms_eps, route_scale=a.route_scale,
        balance_coeff=a.balance_coeff, remat=cfg.model.remat,
        dtype=jnp.dtype(cfg.model.compute_dtype)))


def spell(cfg):
    """The layers' kinds, the experts held of the router's width, and the
    sequence length each change the traced program."""
    a = cfg.lfm2_moe
    kinds = "".join(k[0] + k.split("_")[1][0] for k in a.layers)
    return (f"tokens{cfg.data.seq_len}",
            f"lfm2_{kinds}_e{a.experts_held}of{a.experts_total}")


def train_flops_per_example(cfg, xla_counted: bool = True) -> float:
    """Counted from the shapes: XLA's count of the lowered step would
    hold what attention recomputes backward."""
    return train_flops_per_sequence(build(cfg).arch, cfg.data.seq_len)


def startup_events(model: Lfm2Moe, cfg):
    """Static, so said once: the mixer of every layer, the path each
    attention layer takes here with the key blocks its mask leaves, and
    the expert layers' paths (docs/OBSERVABILITY.md)."""
    backend, devices = jax.default_backend(), jax.device_count()
    return {"token_mixers": {"layers": token_mixers(model.arch)},
            "attention_path": {"layers": attention_paths(
                model.arch, cfg.data.seq_len, backend, devices)},
            "expert_path": transformer.expert_paths(backend, devices)}
