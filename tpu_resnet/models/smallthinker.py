"""A sparse-expert decoder of the ``smallthinker`` family (SmallThinker,
PowerInfer, arXiv:2507.20984) as one chip of an expert-parallel group sees
it: every layer routes its tokens BEFORE attention, from the same normed
input that attention reads, attends over a window of 4,096 with rotary or
over the whole sequence with no position at all (3:1), and sums ReGLU
experts with no shared one.

The equations (``d`` hidden size; RMSNorm ``x / sqrt(mean(x^2) + eps) *
w`` with ``w`` from 1; no bias anywhere):

- ``h = E[ids]``, no scale; logits ``= final_norm(h_L) W_head`` (untied).
  The embedding is drawn from ``normal(0, 1)`` (``_embed_init``), every
  other matrix from ``normal(0, 0.02)``.
- layer ``i``: ``x = input_norm(h)``; ``(chosen, w) = softmax_router(x
  W_r)`` (``models/transformer.py``: softmax in float32 over all
  ``experts_total``, top-k, renormalised); ``h += Attn_i(x)``; ``h +=
  sum over chosen ∩ held of w_e Expert_e(post_attention_norm(h))``,
  ``Expert(y) = (relu(y Wg) * (y Wu)) Wd``. What the absent experts would
  add is left out and that partial result goes on. No auxiliary loss.
- ``Attn``: ``q, k, v = x Wq, x Wk, x Wv`` (``heads`` query and
  ``kv_heads`` key/value heads of ``head_dim``); no q/k norm; a ``window``
  layer rotates every head whole (rotate-half, ``rope_theta``) and sees
  the keys ``0 <= i - j < window`` of the query's document, a ``global``
  layer carries no position and sees every ``j <= i`` of it; softmax of
  ``q k^T / sqrt(head_dim)``, then ``W_o``.

**Packing** (this repo's departure: the published forward pass knows no
packing): a document begins at every id 0, ``doc = cumsum(ids == 0)``,
and attention's mask stays within documents. ``window_cut_frac``, sown
once a call, says what the window does to such a batch: of the
same-document causal (query, key) pairs, the share a window layer drops.

Attention by path, the router, the dispatch with its ``moe_*`` counters
and the products' numerics are ``models/transformer.py``'s; the window's
key blocks that no query reaches are skipped by the fused kernel
(``ops/attention.py``). Parameters, the residual stream, norms, the
router, softmax, logits and loss are float32; matrix products take
``dtype`` operands (bf16), accumulate in float32 and hand on ``dtype``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_resnet.models import transformer
from tpu_resnet.models.transformer import (INPUTS, RMSNorm, _dot, _f32,
                                           _init, _KEEP, _reach,
                                           dispatch_experts, expert_paths,
                                           self_attention, softmax_router,
                                           sow_counters)
from tpu_resnet.ops.attention import attention_path, key_blocks

LAYER_KINDS = ("global", "window")
COUNTERS = transformer.COUNTERS + ("window_cut_frac",)


def _embed_init(key, shape, dtype=_f32):
    """The embedding, drawn at the scale a trained one has beside the
    layers' outputs. At the other leaves' 0.02 the residual stream of a
    fresh model past the global layer is attention's average over the
    document as much as the token, every position of a document routes
    alike, and the held experts' share of the assignments swings by
    document and by seed: at full width on one sequence of the cell's
    documents, ``moe_load_max_over_mean`` 7.1-7.5 on the third layer and
    ``moe_here_frac`` 0.04-0.18 for an even 0.125 (1.2-1.35 and
    0.118-0.127 at 1; PERF.md section 6)."""
    return jax.random.normal(key, shape, dtype)


def window_cut_frac(doc, window: int):
    """Of the same-document causal (query, key) pairs of ``doc`` ``(B,
    S)``, the share that a window of ``window`` keys drops: ``sum max(0,
    n_i - window) / sum n_i`` with ``n_i`` position ``i``'s offset in its
    document plus one (the keys its row sees without the window)."""
    pos = jnp.arange(doc.shape[1], dtype=jnp.int32)
    begins = jnp.concatenate([jnp.ones_like(doc[:, :1], bool),
                              doc[:, 1:] != doc[:, :-1]], axis=1)
    start = jax.lax.cummax(jnp.where(begins, pos, 0), axis=1)
    n = (pos - start + 1).astype(_f32)
    return jnp.sum(jnp.maximum(n - window, 0.0)) / jnp.sum(n)


class Attention(nn.Module):
    arch: "Arch"
    window: int            # 0 = global: every earlier key, no position

    @nn.compact
    def __call__(self, x, doc):
        m = self.arch
        out = self_attention(
            self, x, doc, self.window, heads=m.heads, kv_heads=m.kv_heads,
            head_dim=m.head_dim, eps=m.eps,
            rotary_of=(m.rope_theta, None) if self.window else None,
            block=m.attn_block, dtype=m.dtype, qk_norm=False)
        with jax.named_scope("out"):
            return _dot(out, self.param(
                "wo", _init, (m.heads * m.head_dim, x.shape[-1]), _f32),
                m.dtype)


class ExpertLayer(nn.Module):
    """The ReGLU experts this chip holds, under a routing computed
    before attention and handed in."""
    arch: "Arch"

    @nn.compact
    def __call__(self, y, chosen, weight):
        m = self.arch
        shape = y.shape
        y = y.reshape(-1, shape[-1])                   # (N, d) float32
        d, count = y.shape[1], m.experts_held[1]
        out, counters = dispatch_experts(
            y, chosen, weight,
            self.param("gate", _init, (count, d, m.expert_width), _f32),
            self.param("up", _init, (count, d, m.expert_width), _f32),
            self.param("down", _init, (count, m.expert_width, d), _f32),
            experts_total=m.experts_total, experts_held=m.experts_held,
            rows_slack=m.rows_slack, dtype=m.dtype, activation=jax.nn.relu)
        with jax.named_scope("router"):
            sow_counters(self, counters)
        return out.reshape(shape)


class Layer(nn.Module):
    kind: str
    arch: "Arch"

    @nn.compact
    def __call__(self, h, doc):
        m = self.arch
        x = RMSNorm(m.eps, name="input_norm")(h)
        with jax.named_scope("router"):
            chosen, weight = softmax_router(
                x.reshape(-1, m.hidden),
                self.param("router", _init, (m.hidden, m.experts_total),
                           _f32), m.top_k)
        window = m.window if self.kind == "window" else 0
        with jax.named_scope("attention"), jax.named_scope(self.kind):
            h = h + Attention(m, window, name="attn")(x, doc)
        experts = nn.remat(ExpertLayer, policy=_KEEP) if m.remat \
            else ExpertLayer
        with jax.named_scope("moe"):
            return h + experts(m, name="moe")(
                RMSNorm(m.eps, name="post_attention_norm")(h), chosen,
                weight)


@dataclasses.dataclass(frozen=True)
class Arch:
    """The model's fields. ``layers`` lists each layer's attention in
    order (``window``: rotary within ``window`` keys; ``global``: NoPE over
    all); ``experts_held = (first, count)`` are the routed experts this
    chip holds of ``experts_total``; ``vocab_rows`` the rows of the
    vocabulary it holds (ids, logits and the loss are over them)."""
    layers: Tuple[str, ...]
    hidden: int = 2560
    heads: int = 28
    kv_heads: int = 4
    head_dim: int = 128
    window: int = 4096
    expert_width: int = 768
    experts_total: int = 64
    experts_held: Tuple[int, int] = (0, 8)
    top_k: int = 6
    vocab_rows: int = 18992
    rope_theta: float = 1.5e6
    eps: float = 1e-6
    rows_slack: float = 2.0            # transformer.py::buffer_rows
    attn_block: int = 256              # queries a block of the scan path
    remat: bool = False                # an expert layer's keeps _KEEP
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


class SmallThinker(nn.Module):
    """``apply(variables, ids, train=...) -> logits`` of shape ``(B, S,
    vocab_rows)`` float32; ``ids`` are int32 in ``[0, vocab_rows)``. The
    mutable collection is ``counters`` (what the routing and the window
    did this call)."""
    arch: Arch

    @nn.compact
    def __call__(self, ids, *, train: bool = False):
        del train                      # no state beside the parameters
        m = self.arch
        ids = jnp.asarray(ids, jnp.int32)
        doc = jnp.cumsum((ids == 0).astype(jnp.int32), axis=1)
        with jax.named_scope("embed"):
            h = jnp.take(self.param("embed", _embed_init,
                                    (m.vocab_rows, m.hidden), _f32), ids,
                         axis=0)
            self.sow("counters", "window_cut_frac",
                     window_cut_frac(doc, m.window), init_fn=lambda: 0.0,
                     reduce_fn=lambda old, new: new)
        for i, kind in enumerate(m.layers):
            h = Layer(kind, m, name=f"layer_{i}")(h, doc)
        with jax.named_scope("head"):
            return _dot(RMSNorm(m.eps, name="final_norm")(h),
                        self.param("head", _init, (m.hidden, m.vocab_rows),
                                   _f32), m.dtype, out=_f32)


def attention_paths(model: Arch, seq_len: int, backend: str,
                    devices: int) -> List[Dict[str, object]]:
    """For each layer, the ``path`` its attention takes at ``seq_len`` on
    ``devices`` of ``backend``, how its ``inputs`` are prepared there
    (``transformer.INPUTS``), its ``window`` (0: global) and, in tiles of
    queries by keys, ``key_blocks_visited`` of ``key_blocks_total``: the
    kernel's from its own mask table, the scan's from its span of ``reach
    + block`` keys a block. What ``train()`` says once, as the event
    ``attention_path``."""
    path = attention_path(backend, devices, model.head_dim, seq_len)
    block = min(model.attn_block, seq_len)
    rows = []
    for i, kind in enumerate(model.layers):
        window = model.window if kind == "window" else 0
        if path == "kernel":
            visited, total = key_blocks(seq_len, window,
                                        model.heads // model.kv_heads)
        else:
            span = _reach(seq_len, window, block) + block
            visited = (seq_len // block) * -(-span // block)
            total = (seq_len // block) ** 2
        rows.append(dict(layer=i, kind=kind, path=path, inputs=INPUTS[path],
                         window=window, key_blocks_visited=visited,
                         key_blocks_total=total))
    return rows


def live_entries(seq_len: int, window: int) -> int:
    """The (query, key) pairs of a head that the causal mask leaves, and
    of them the last ``window`` keys of each row (0: all)."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def multiply_adds_per_token(model: Arch, seq_len: int) -> float:
    """The multiply-adds one token meets in a forward pass here: every
    matrix it is multiplied by (``top_k * count / experts_total`` of an
    expert, the routing being even), and attention's scores and values
    over the entries its causal and window masks leave (document masks
    leave fewer)."""
    d, hq = model.hidden, model.heads * model.head_dim
    hkv = model.kv_heads * model.head_dim
    share = model.top_k * model.experts_held[1] / model.experts_total
    total = d * model.vocab_rows
    for kind in model.layers:
        window = model.window if kind == "window" else 0
        total += (d * model.experts_total                  # the router
                  + d * (hq + 2 * hkv) + hq * d            # q, k, v; out
                  + 2 * hq * live_entries(seq_len, window) / seq_len
                  + 3 * d * model.expert_width * share)
    return total


def train_flops_per_sequence(model: Arch, seq_len: int) -> float:
    """Forward and backward model FLOPs of one sequence: 3 x 2 x
    multiply-adds (nothing recomputed counts)."""
    return 6.0 * multiply_adds_per_token(model, seq_len) * seq_len


# ------------------------------------------------------------------ family
# What models/__init__.py registers as the family ``smallthinker``, with
# ``COUNTERS`` above and ``transformer.refuses``.
def build(cfg) -> SmallThinker:
    a = cfg.smallthinker
    return SmallThinker(Arch(
        layers=tuple(a.layers), hidden=a.hidden, heads=a.heads,
        kv_heads=a.kv_heads, head_dim=a.head_dim, window=a.window,
        expert_width=a.expert_width, experts_total=a.experts_total,
        experts_held=(a.experts_first, a.experts_held), top_k=a.top_k,
        vocab_rows=cfg.data.num_classes, rope_theta=a.rope_theta,
        eps=a.rms_eps, remat=cfg.model.remat,
        dtype=jnp.dtype(cfg.model.compute_dtype)))


def spell(cfg):
    """The layers' kinds, the window, the experts held of the router's
    width and the sequence length each change the traced program."""
    a = cfg.smallthinker
    kinds = "".join(k[0] for k in a.layers)
    return (f"tokens{cfg.data.seq_len}",
            f"smallthinker_{kinds}_w{a.window}_e{a.experts_held}of"
            f"{a.experts_total}")


def train_flops_per_example(cfg, xla_counted: bool = True) -> float:
    """Counted from the shapes: XLA's count of the lowered step would
    hold what attention and the experts recompute backward."""
    return train_flops_per_sequence(build(cfg).arch, cfg.data.seq_len)


def startup_events(model: SmallThinker, cfg):
    """Static, so said once: the path each layer's attention takes here,
    its window and the key blocks its mask leaves, and the expert layers'
    paths (docs/OBSERVABILITY.md)."""
    backend, devices = jax.default_backend(), jax.device_count()
    return {"attention_path": {"layers": attention_paths(
        model.arch, cfg.data.seq_len, backend, devices)},
            "expert_path": expert_paths(backend, devices)}
