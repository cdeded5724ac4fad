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
- expert layer: ``models/transformer.py``'s sigmoid router (``s =
  sigmoid(x Wr)`` in float32 over ALL ``experts_total``; chosen = top-k of
  ``s + b``, ``b`` the layer's ``expert_bias``, state without a gradient;
  ``w = s[chosen] / (sum s[chosen] + eps) * scale``) with this family's
  two numbers, ``eps`` 1e-20 and ``route_scale`` 2.826; ``F(x) = Shared(x)
  + sum w_e Expert_e(x)`` over the chosen experts THIS CHIP HOLDS
  (``experts_held = (first, count)``). What the absent experts would add
  is left out and that partial result goes on. In training, after the
  step's routing, ``b += c - mean(c)`` with ``c = load_balance_coeff *
  sign(mean(n) - n)``, ``n`` the assignments per expert (the same module's
  ``balanced_bias``).

The sigmoid router and its bias update (shared with ``lfm2_moe`` since PR
36), the dispatch of the router's choices to the held experts (one sorted
buffer, grouped products, overflow tiers, the five ``moe_*`` counters),
attention by path (one fused kernel on a TPU chip, a scan over blocks of
queries everywhere else), RMSNorm, SwiGLU, the rotary embedding and the
products' numerics are ``models/transformer.py``'s, shared with the other
token families; this module keeps what is Trinity's own: the shared
expert beside the routed ones, the attention gate, four norms a layer,
windows, the embedding's ``sqrt(d)``. Parameters, the residual stream, norms, router, softmax
and logits are float32; matrix products take ``dtype`` operands (bf16),
accumulate in float32 and hand on ``dtype``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_resnet.models.transformer import (  # noqa: F401  (re-exported)
    COUNTERS, INPUTS, RMSNorm, SwiGLU, _dot, _f32, _init, _KEEP, _reach,
    balanced_bias, blocked_attention, dispatch_experts, expert_paths,
    refuses, rotary, self_attention, sigmoid_router, sow_counters)
from tpu_resnet.ops.attention import attention_path, key_blocks

# layer kinds: what F is, and which mask attention takes
LAYER_KINDS = ("dense_sliding", "dense_full", "moe_sliding", "moe_full")


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
        d, width = x.shape[-1], self.heads * self.head_dim
        out = self_attention(
            self, x, doc, self.window, heads=self.heads,
            kv_heads=self.kv_heads, head_dim=self.head_dim, eps=self.eps,
            rotary_of=(self.rope_theta, None) if self.window else None,
            block=self.block, dtype=self.dtype)
        with jax.named_scope("qkv"), jax.named_scope("project"):
            gate = _dot(x, self.param("wg", _init, (d, width), _f32),
                        self.dtype)
        with jax.named_scope("gate_out"):
            out = out * jax.nn.sigmoid(gate)
            return _dot(out, self.param("wo", _init, (width, d), _f32),
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

    @nn.compact
    def __call__(self, x, train: bool):
        shape = x.shape
        x = x.reshape(-1, shape[-1])                   # (N, d) float32
        d = x.shape[1]
        count = self.experts_held[1]
        k, total = self.top_k, self.experts_total
        bias = self.variable("batch_stats", "expert_bias",
                             lambda: jnp.zeros((total,), _f32))
        w_gate = self.param("gate", _init, (count, d, self.width), _f32)
        w_up = self.param("up", _init, (count, d, self.width), _f32)
        w_down = self.param("down", _init, (count, self.width, d), _f32)

        with jax.named_scope("router"):
            chosen, weight = sigmoid_router(           # (N, k) float32
                x, self.param("router", _init, (d, total), _f32),
                bias.value, k, eps=1e-20, scale=self.route_scale)

        out, counters = dispatch_experts(
            x, chosen, weight, w_gate, w_up, w_down, experts_total=total,
            experts_held=self.experts_held, rows_slack=self.rows_slack,
            dtype=self.dtype)

        if self.shared:
            with jax.named_scope("shared"):
                out = out + SwiGLU(self.width * self.shared, self.dtype,
                                   name="shared")(x)

        with jax.named_scope("router"):
            sow_counters(self, counters)
            if train and not self.is_initializing():
                bias.value = balanced_bias(bias.value, chosen,
                                           self.balance_coeff)
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
    ``devices`` of ``backend``, how its ``inputs`` are prepared on that
    path (``transformer.INPUTS``) and, in tiles of queries by keys, ``key_blocks_visited``
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
        rows.append(dict(layer=i, kind=kind, path=path, inputs=INPUTS[path],
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


def startup_events(model: Afmoe, cfg):
    """Static, so said once: the path each layer's attention takes here
    and the key blocks its mask leaves, and the expert layers' paths
    (docs/OBSERVABILITY.md)."""
    backend, devices = jax.default_backend(), jax.device_count()
    return {"attention_path": {"layers": attention_paths(
        model.arch, cfg.data.seq_len, backend, devices)},
            "expert_path": expert_paths(backend, devices)}
