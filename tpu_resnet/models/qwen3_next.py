"""A hybrid sparse-expert decoder of the ``qwen3_next`` family (Qwen3-Next,
Qwen) as one chip of an expert-parallel group sees it: three layers in four
mix tokens by Gated DeltaNet (a gated delta-rule recurrence, Yang et al.
2024, arXiv:2412.06464), one in four by gated full attention at heads of
256, and every layer's feed-forward is routed experts beside a shared one
behind a gate.

The equations (``d`` hidden size; every RMSNorm but the DeltaNet output's
is zero-centred, ``x / rms(x) * (1 + w)`` with ``w`` from 0; no bias):

- ``h = E[ids]``; logits ``= final_norm(h_L) W_head`` (untied).
- layer: ``h += Mix(input_norm(h))``; ``h += MoE(post_attention_norm(h))``.
  ``Mix`` is Gated DeltaNet on a ``linear`` layer and attention on a
  ``full`` one.
- Gated DeltaNet (``Hk`` key heads, ``Hv`` value heads, ``dk``, ``dv``):
  ``[q, k, v, z] = x W_qkvz`` (``Hk dk``, ``Hk dk``, ``Hv dv``, ``Hv dv``
  columns in this order); ``[b, a] = x W_ba`` (``Hv`` each); a causal
  depthwise convolution of ``conv_taps`` taps over the channels of ``[q,
  k, v]``, the last tap on the current position, then SiLU; ``q`` and ``k``
  L2-normed per head (``x * rsqrt(sum(x^2) + 1e-6)``), ``q`` scaled by
  ``1 / sqrt(dk)``; ``beta = sigmoid(b)``; ``g = -exp(A_log) * softplus(a
  + dt_bias)`` (one ``A_log`` and ``dt_bias`` a value head); the recurrence
  of ``ops/gated_delta.py`` (value heads ``2j`` and ``2j + 1`` read key
  head ``j``); ``y = RMSNorm_w(o) * silu(z)`` per value head (a plain
  weight from 1); ``Mix = y W_out``.
- attention: ``[q_h, gate_h] = x Wq`` per head, ``k, v = x Wk, x Wv``;
  zero-centred RMSNorm on ``q`` and ``k`` per head; rotary (rotate-half) on
  the first ``rotary_dim`` of each head, the rest passed through; softmax
  of ``q k^T / sqrt(head_dim)`` over the keys ``j <= i`` of the same
  document; ``Mix = ((softmax V) * sigmoid(gate)) Wo``.
- expert layer: ``models/transformer.py``'s softmax router over all
  ``experts_total``, top-k renormalised; ``MoE(x) = sigmoid(x W_sg)
  Shared(x) + sum w_e Expert_e(x)`` over the chosen experts THIS CHIP HOLDS
  (``experts_held = (first, count)``); SwiGLU experts. What the absent
  experts would add is left out and that partial result goes on. No
  auxiliary loss.

**Packing** (this repo's departure: the published forward pass knows no
packing): a document begins at every id 0, ``doc = cumsum(ids == 0)``;
attention's mask stays within documents, the convolution's taps read 0
before a document's start, and the recurrence's state is 0 at every
document's first position.

The convolution (``short_conv``), attention by path, the routers, the
dispatch with its ``moe_*`` counters and the products' numerics are
``models/transformer.py``'s; the recurrence is ``ops/gated_delta.py``'s,
with its path. Parameters, the residual stream, norms, gates, the router,
softmax, logits and loss are float32; matrix products take ``dtype``
operands (bf16), accumulate in float32 and hand on ``dtype``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_resnet.models import transformer
from tpu_resnet.models.transformer import (INPUTS, SwiGLU, _dot,
                                           _f32, _init, _KEEP,
                                           dispatch_experts, rms_norm,
                                           self_attention, short_conv,
                                           softmax_router, sow_counters,
                                           taps_init)
from tpu_resnet.ops import gated_delta
from tpu_resnet.ops.attention import attention_path, key_blocks

LAYER_KINDS = ("linear", "full")
COUNTERS = transformer.COUNTERS + ("gdn_doc_chunks_frac",)
# What remat keeps of a DeltaNet mixer: ``_KEEP``'s, and the recurrence's
# inverses, so that its backward runs the forward kernel again and not the
# inverse's.
_MIXER_KEEP = jax.checkpoint_policies.save_from_both_policies(
    _KEEP, jax.checkpoint_policies.save_only_these_names(gated_delta.INVERSE))


class Norm(nn.Module):
    """The zero-centred RMSNorm: ``x / rms(x) * (1 + w)``, ``w`` from 0."""
    eps: float

    @nn.compact
    def __call__(self, x):
        return rms_norm(x, 1.0 + self.param(
            "scale", nn.initializers.zeros, (x.shape[-1],), _f32), self.eps)


def l2_norm(x, eps: float = 1e-6):
    """``x * rsqrt(sum(x^2) + eps)`` over the last axis, in float32."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def _a_log_init(key, shape, dtype=_f32):
    """``log(A)``, ``A`` uniform in (0, 16): the published draw."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 0.0, 16.0))


class GatedDeltaNet(nn.Module):
    arch: "Arch"

    @nn.compact
    def __call__(self, x, doc, reset):
        m = self.arch
        b, s, d = x.shape
        hk, hv, dk, dv = m.key_heads, m.value_heads, m.key_dim, m.value_dim
        mixed = 2 * hk * dk + hv * dv            # the convolved channels
        with jax.named_scope("project"):
            qkvz = _dot(x, self.param("in_proj_qkvz", _init,
                                      (d, mixed + hv * dv), _f32), m.dtype)
            ba = _dot(x, self.param("in_proj_ba", _init, (d, 2 * hv), _f32),
                      m.dtype, out=_f32)
        with jax.named_scope("conv"):
            qkv = jax.nn.silu(short_conv(
                qkvz[..., :mixed].astype(_f32),
                self.param("conv", taps_init, (mixed, m.conv_taps), _f32),
                doc))
            q, k, v = jnp.split(qkv, [hk * dk, 2 * hk * dk], axis=-1)
            q = l2_norm(q.reshape(b, s, hk, dk)) / math.sqrt(dk)
            k = l2_norm(k.reshape(b, s, hk, dk))
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(self.param("A_log", _a_log_init, (hv,), _f32)) \
                * jax.nn.softplus(ba[..., hv:] + self.param(
                    "dt_bias", nn.initializers.ones, (hv,), _f32))
        with jax.named_scope("recurrence"):
            # the weights are drawn on a sequence of any length: the scan
            # computes what that needs
            o = gated_delta.gated_delta(
                q, k, v.reshape(b, s, hv, dv), beta, g, reset, dtype=m.dtype,
                chunk=min(m.chunk, s),
                path="scan" if self.is_initializing() else "")
        with jax.named_scope("gate_norm"):
            z = qkvz[..., mixed:].reshape(b, s, hv, dv).astype(_f32)
            y = rms_norm(o.reshape(b, s, hv, dv), self.param(
                "norm", nn.initializers.ones, (dv,), _f32), m.eps) \
                * jax.nn.silu(z)
        with jax.named_scope("out"):
            return _dot(y.reshape(b, s, hv * dv), self.param(
                "out_proj", _init, (hv * dv, d), _f32), m.dtype)


class Attention(nn.Module):
    arch: "Arch"

    @nn.compact
    def __call__(self, x, doc):
        m = self.arch
        out = self_attention(
            self, x, doc, 0, heads=m.heads, kv_heads=m.kv_heads,
            head_dim=m.head_dim, eps=m.eps, rotary_of=(m.rope_theta, None),
            block=m.attn_block, dtype=m.dtype, rotary_dim=m.rotary_dim,
            zero_centred=True, gated=True)
        with jax.named_scope("out"):
            return _dot(out, self.param(
                "wo", _init, (m.heads * m.head_dim, x.shape[-1]), _f32),
                m.dtype)


class ExpertLayer(nn.Module):
    """The routed experts this chip holds under the softmax router, and
    the shared expert behind its sigmoid gate."""
    arch: "Arch"

    @nn.compact
    def __call__(self, x):
        m = self.arch
        shape = x.shape
        x = x.reshape(-1, shape[-1])                   # (N, d) float32
        d, count = x.shape[1], m.experts_held[1]
        w_gate = self.param("gate", _init, (count, d, m.expert_width), _f32)
        w_up = self.param("up", _init, (count, d, m.expert_width), _f32)
        w_down = self.param("down", _init, (count, m.expert_width, d), _f32)
        with jax.named_scope("router"):
            chosen, weight = softmax_router(           # (N, k) float32
                x, self.param("router", _init, (d, m.experts_total), _f32),
                m.top_k)
        out, counters = dispatch_experts(
            x, chosen, weight, w_gate, w_up, w_down,
            experts_total=m.experts_total, experts_held=m.experts_held,
            rows_slack=m.rows_slack, dtype=m.dtype)
        with jax.named_scope("shared"):
            gate = jax.nn.sigmoid(_dot(x, self.param(
                "shared_gate", _init, (d, 1), _f32), m.dtype, out=_f32))
            out = out + gate * SwiGLU(m.shared_width, m.dtype,
                                      name="shared")(x)
        with jax.named_scope("router"):
            sow_counters(self, counters)
        return out.reshape(shape)


class Layer(nn.Module):
    kind: str
    arch: "Arch"

    @nn.compact
    def __call__(self, h, doc, reset):
        m = self.arch
        x = Norm(m.eps, name="input_norm")(h)
        if self.kind == "linear":
            mixer = nn.remat(GatedDeltaNet, policy=_MIXER_KEEP) if m.remat \
                else GatedDeltaNet
            with jax.named_scope("gdn"):
                h = h + mixer(m, name="linear_attn")(x, doc, reset)
        else:
            with jax.named_scope("attention"):
                h = h + Attention(m, name="attn")(x, doc)
        with jax.named_scope("moe"):
            return h + ExpertLayer(m, name="moe")(
                Norm(m.eps, name="post_attention_norm")(h))


@dataclasses.dataclass(frozen=True)
class Arch:
    """The model's fields. ``layers`` lists each layer's mixer in order
    (``linear``: Gated DeltaNet; ``full``: attention); ``experts_held =
    (first, count)`` are the routed experts this chip holds of
    ``experts_total``; ``vocab_rows`` the rows of the vocabulary it holds
    (ids, logits and the loss are over them)."""
    layers: Tuple[str, ...]
    hidden: int = 2048
    heads: int = 16
    kv_heads: int = 2
    head_dim: int = 256
    rotary_dim: int = 64               # partial_rotary_factor x head_dim
    key_heads: int = 16
    value_heads: int = 32
    key_dim: int = 128
    value_dim: int = 128
    conv_taps: int = 4
    expert_width: int = 512
    shared_width: int = 512
    experts_total: int = 512
    experts_held: Tuple[int, int] = (0, 32)
    top_k: int = 10
    vocab_rows: int = 18992
    rope_theta: float = 1e7
    eps: float = 1e-6
    rows_slack: float = 2.0            # transformer.py::buffer_rows
    attn_block: int = 256              # queries a block of the scan path
    chunk: int = gated_delta.CHUNK     # positions a chunk of the recurrence
    remat: bool = False                # a DeltaNet mixer's keeps _MIXER_KEEP
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "layers", tuple(self.layers))
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        bad = [k for k in self.layers if k not in LAYER_KINDS]
        first, count = self.experts_held
        if bad or not self.layers:
            raise ValueError(f"layer kinds must be of {LAYER_KINDS}, got "
                             f"{list(self.layers)}")
        if self.heads % self.kv_heads or self.value_heads % self.key_heads \
                or self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError("query heads must divide by key/value heads, "
                             "value heads by key heads, and the rotary "
                             "columns be even and within a head")
        if not (0 <= first and count >= 1
                and first + count <= self.experts_total
                and self.top_k <= self.experts_total):
            raise ValueError(f"experts_held {self.experts_held} does not "
                             f"lie in the {self.experts_total} experts")


class Qwen3Next(nn.Module):
    """``apply(variables, ids, train=...) -> logits`` of shape ``(B, S,
    vocab_rows)`` float32; ``ids`` are int32 in ``[0, vocab_rows)``. The
    mutable collection is ``counters`` (what the routing and the documents
    did this call)."""
    arch: Arch

    @nn.compact
    def __call__(self, ids, *, train: bool = False):
        del train                      # no state beside the parameters
        m = self.arch
        ids = jnp.asarray(ids, jnp.int32)
        reset = ids == 0
        doc = jnp.cumsum(reset.astype(jnp.int32), axis=1)
        with jax.named_scope("embed"):
            h = jnp.take(self.param("embed", _init, (m.vocab_rows, m.hidden),
                                    _f32), ids, axis=0)
            chunk = min(m.chunk, ids.shape[1])
            if ids.shape[1] % chunk == 0:
                self.sow("counters", "gdn_doc_chunks_frac",
                         gated_delta.doc_chunks_frac(reset, chunk),
                         init_fn=lambda: 0.0, reduce_fn=lambda old, new: new)
        for i, kind in enumerate(m.layers):
            h = Layer(kind, m, name=f"layer_{i}")(h, doc, reset)
        with jax.named_scope("head"):
            return _dot(Norm(m.eps, name="final_norm")(h),
                        self.param("head", _init, (m.hidden, m.vocab_rows),
                                   _f32), m.dtype, out=_f32)


def token_mixers(model: Arch) -> List[Dict[str, object]]:
    """The mixer of every layer. What ``train()`` says once, as the event
    ``token_mixers``."""
    return [dict(layer=i, kind=kind,
                 mixer="gated_delta" if kind == "linear" else "attention")
            for i, kind in enumerate(model.layers)]


def recurrence_paths(model: Arch, seq_len: int, backend: str,
                     devices: int) -> List[Dict[str, object]]:
    """For each DeltaNet layer, the ``path`` its recurrence takes here
    (``ops/gated_delta.py::recurrence_path``), its ``chunk``, the chunks of
    a sequence, and where its UT inverse is computed: ``once`` a layer and
    step by its own kernel on the kernel path, ``in_chunk`` on the scan's.
    What ``train()`` says once, as the event ``recurrence_path``."""
    path = gated_delta.recurrence_path(backend, devices)
    return [dict(layer=i, kind=kind, path=path, chunk=model.chunk,
                 chunks=seq_len // model.chunk,
                 inverse="once" if path == "kernel" else "in_chunk")
            for i, kind in enumerate(model.layers) if kind == "linear"]


def attention_paths(model: Arch, seq_len: int, backend: str,
                    devices: int) -> List[Dict[str, object]]:
    """For each attention layer, the ``path`` it takes at ``seq_len`` on
    ``devices`` of ``backend``, how its ``inputs`` are prepared there
    (``transformer.INPUTS``), its ``head_dim`` and ``rotary_dim``, and in
    tiles of queries by keys ``key_blocks_visited`` of
    ``key_blocks_total``. What ``train()`` says once, as the event
    ``attention_path``."""
    path = attention_path(backend, devices, model.head_dim, seq_len)
    if path == "kernel":
        visited, total = key_blocks(seq_len, 0,
                                    model.heads // model.kv_heads)
    else:
        visited = total = (seq_len // min(model.attn_block, seq_len)) ** 2
    return [dict(layer=i, kind=kind, path=path, inputs=INPUTS[path],
                 head_dim=model.head_dim, rotary_dim=model.rotary_dim,
                 key_blocks_visited=visited, key_blocks_total=total)
            for i, kind in enumerate(model.layers) if kind == "full"]


def multiply_adds_per_token(model: Arch, seq_len: int) -> float:
    """The multiply-adds one token meets in a forward pass here: every
    matrix it is multiplied by (``top_k * count / experts_total`` of a
    routed expert, the routing being even; the shared expert whole), a
    filter's ``taps`` a channel, the recurrence at its own ``3 dk dv`` a
    value head (decay and update of the state, the output's read; not the
    chunked form's), and attention's scores and values over the entries its
    causal mask leaves (document masks leave fewer)."""
    d = model.hidden
    hk, hv = model.key_heads * model.key_dim, model.value_heads * model.value_dim
    hq, hkv = model.heads * model.head_dim, model.kv_heads * model.head_dim
    share = model.top_k * model.experts_held[1] / model.experts_total
    total = d * model.vocab_rows
    for kind in model.layers:
        if kind == "linear":
            total += (d * (2 * hk + 2 * hv) + d * 2 * model.value_heads
                      + (2 * hk + hv) * model.conv_taps
                      + 3 * model.key_dim * hv + hv * d)
        else:
            total += (d * 2 * hq + 2 * d * hkv + hq * d
                      + 2 * hq * (seq_len + 1) / 2)
        total += (d * model.experts_total + d
                  + 3 * d * (model.expert_width * share + model.shared_width))
    return total


def train_flops_per_sequence(model: Arch, seq_len: int) -> float:
    """Forward and backward model FLOPs of one sequence: 3 x 2 x
    multiply-adds (nothing recomputed counts)."""
    return 6.0 * multiply_adds_per_token(model, seq_len) * seq_len


# ------------------------------------------------------------------ family
# What models/__init__.py registers as the family ``qwen3_next``, with
# ``COUNTERS`` above and ``refuses`` below.
def build(cfg) -> Qwen3Next:
    a = cfg.qwen3_next
    return Qwen3Next(Arch(
        layers=tuple(a.layers), hidden=a.hidden, heads=a.heads,
        kv_heads=a.kv_heads, head_dim=a.head_dim, rotary_dim=a.rotary_dim,
        key_heads=a.key_heads, value_heads=a.value_heads, key_dim=a.key_dim,
        value_dim=a.value_dim, conv_taps=a.conv_taps,
        expert_width=a.expert_width, shared_width=a.shared_width,
        experts_total=a.experts_total,
        experts_held=(a.experts_first, a.experts_held), top_k=a.top_k,
        vocab_rows=cfg.data.num_classes, rope_theta=a.rope_theta,
        eps=a.rms_eps, remat=cfg.model.remat,
        dtype=jnp.dtype(cfg.model.compute_dtype)))


def spell(cfg):
    """The layers' mixers, the experts held of the router's width and the
    sequence length each change the traced program."""
    a = cfg.qwen3_next
    kinds = "".join(k[0] for k in a.layers)
    return (f"tokens{cfg.data.seq_len}",
            f"qwen3next_{kinds}_e{a.experts_held}of{a.experts_total}")


def train_flops_per_example(cfg, xla_counted: bool = True) -> float:
    """Counted from the shapes: XLA's count of the lowered step would
    hold what attention and the recurrence recompute backward."""
    return train_flops_per_sequence(build(cfg).arch, cfg.data.seq_len)


def refuses(cfg, data_axis: int):
    """What of ``cfg`` this family does not train with, beside what no
    token family does: a sequence longer than a chunk that is not whole
    chunks (``ops/gated_delta.py::CHUNK``; a shorter one is one chunk)."""
    s = cfg.data.seq_len
    whole = s % min(gated_delta.CHUNK, s) == 0
    return transformer.refuses(cfg, data_axis) + ([] if whole else [
        f"data.seq_len={s} (whole chunks of {gated_delta.CHUNK} positions "
        f"of the recurrence)"])


def startup_events(model: Qwen3Next, cfg):
    """Static, so said once: the mixer of every layer, the path of each
    DeltaNet layer's recurrence and of each attention layer with the key
    blocks its mask leaves, and the expert layers' paths
    (docs/OBSERVABILITY.md)."""
    backend, devices = jax.default_backend(), jax.device_count()
    seq = cfg.data.seq_len
    return {"token_mixers": {"layers": token_mixers(model.arch)},
            "recurrence_path": {"layers": recurrence_paths(
                model.arch, seq, backend, devices)},
            "attention_path": {"layers": attention_paths(
                model.arch, seq, backend, devices)},
            "expert_path": transformer.expert_paths(backend, devices)}
