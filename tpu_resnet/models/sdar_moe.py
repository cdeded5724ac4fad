"""A sparse-expert decoder of the ``sdar_moe`` family (SDAR, JetLM,
arXiv:2510.06303: a Qwen3-MoE backbone) trained by diffusion over blocks
(Arriola et al. 2025, arXiv:2503.09573), as one chip of an expert-parallel
group sees it. What makes the family is the job: the model is fed a noised
and a clean copy of every sequence under a three-part attention mask, and
the loss is on the masked positions only.

The step's input (``noise``, ``objective``): clean ids ``x0`` of ``(S,
L)``; blocks of ``block_length`` consecutive positions counted from the
sequence's start; one noise level a block, ``t = t_min + (1 - t_min) *
uniform(fold_in(r, 0), (S, L // B))``; each position masked by itself,
``masked = uniform(fold_in(r, 1), (S, L)) < repeat(t, B)``, with ``r`` the
step's key (``fold_in(base_rng, state.step)``); ``xt = where(masked,
mask_id, x0)``, ``mask_id`` the last row of the vocabulary held here. The
model is fed the ``2L`` positions ``[xt ; x0]`` with position ids ``[0 ..
L-1 ; 0 .. L-1]``.

The equations (``d`` hidden size; RMSNorm with a weight; no bias):

- ``h = E[ids]``; logits ``= RMSNorm(h_final) W_head`` (untied), computed
  at the ``L`` noisy positions only.
- every layer: ``h += Attn(input_norm(h))``;
  ``h += MoE(post_attention_norm(h))``.
- attention: ``q, k, v = x Wq, x Wk, x Wv``; per head ``q = RMSNorm(q)``,
  ``k = RMSNorm(k)``; rotary embedding (rotate-half, whole head) by the
  position ids on every layer; scores ``q k^T / sqrt(head_dim)``, softmax
  over the keys of the same document (documents from the CLEAN ids,
  ``cumsum(x0 == 0)``, for both copies) that ``BlockDiffusion(L, B)``
  allows (``ops/attention.py``): a noisy block sees itself, both ways, and
  the clean text strictly before it; the clean copy sees clean text up to
  and including its own block; nothing sees a noisy key of another block.
  ``Attn = (softmax V) Wo``.
- expert layer: ``models/transformer.py``'s softmax router (``s =
  softmax(x Wr)`` in float32 over ALL ``experts_total``; chosen = top-k of
  ``s``; ``w = s[chosen] / sum s[chosen]``); ``MoE(x) = sum w_e Expert_e(x)`` over the chosen experts
  THIS CHIP HOLDS (``experts_held = (first, count)``), ``Expert(x) =
  (silu(x Wg) * (x Wu)) Wd``. No shared expert, no bias, no auxiliary
  loss. The dispatch is ``models/transformer.py``'s, as are attention's
  two paths, RMSNorm, the rotary embedding and the products' numerics.
- loss: ``(1 / (S L)) sum over masked i of (1 / t_blk(i)) * (-log
  softmax(logits[i])[x0[i]])``: the logit of the noisy copy AT ``i``
  predicts the clean id at ``i`` (no shift); unmasked positions and the
  clean copy carry none; the data set's next-id labels are not read.

Parameters, the residual stream, norms, router, softmax, logits and loss
are float32; matrix products take ``dtype`` operands (bf16), accumulate in
float32 and hand on ``dtype``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_resnet.models import transformer
from tpu_resnet.models.transformer import (COUNTERS,  # noqa: F401
                                           INPUTS, RMSNorm, _dot, _f32,
                                           _init, _KEEP, dispatch_experts,
                                           self_attention, softmax_router,
                                           sow_counters)
from tpu_resnet.ops.attention import (BlockDiffusion, attention_path,
                                      diagonal_rows, key_blocks)

def _embed_init(key, shape, dtype=_f32):
    """The embedding, drawn at the scale a trained one has beside the
    layers' outputs, and its last row, the mask id's, at the mean of the
    others, where a token added to a vocabulary starts. The model adds no
    ``sqrt(d)`` to it: at the other leaves' 0.02 the residual stream of a
    fresh model is attention's averaged values and nothing of the token,
    and every position chooses the same experts
    (``moe_load_max_over_mean`` read 9.2 on the chip, PERF.md section 6);
    a mask row drawn like the others sends every masked position, a
    quarter of all, to one set of eight experts."""
    table = jax.random.normal(key, shape, dtype)
    return table.at[-1].set(jnp.mean(table[:-1], axis=0))


@dataclasses.dataclass(frozen=True)
class Arch:
    """The model's fields. ``experts_held = (first, count)`` are the
    routed experts this chip holds of ``experts_total``; ``vocab_rows``
    the rows of the vocabulary it holds (ids, logits and the loss are over
    them; the last is the mask id, which the data never draws)."""
    layers: int = 4
    hidden: int = 2048
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    expert_width: int = 768
    experts_total: int = 128
    experts_held: Tuple[int, int] = (0, 16)
    top_k: int = 8
    vocab_rows: int = 18992
    rope_theta: float = 1e6
    eps: float = 1e-6
    block_length: int = 4              # positions a block of the diffusion
    t_min: float = 1e-3                # the least noise level of a block
    rows_slack: float = 2.0            # transformer.py::buffer_rows
    attn_block: int = 256              # queries a block of the scan path
    remat: bool = False                # each layer's backward keeps _KEEP
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        first, count = self.experts_held
        if self.layers < 1 or self.block_length < 1:
            raise ValueError("layers and block_length must be at least 1")
        if self.heads % self.kv_heads or self.head_dim % 2:
            raise ValueError("query heads must divide by key/value heads "
                             "and the head size by 2")
        if not (0 <= first and count >= 1
                and first + count <= self.experts_total
                and self.top_k <= self.experts_total):
            raise ValueError(f"experts_held {self.experts_held} does not "
                             f"lie in the {self.experts_total} experts")

    @property
    def mask_id(self) -> int:
        return self.vocab_rows - 1


class Attention(nn.Module):
    arch: Arch

    @nn.compact
    def __call__(self, x, doc, positions):
        m = self.arch
        d = x.shape[-1]
        out = self_attention(
            self, x, doc, BlockDiffusion(x.shape[1] // 2, m.block_length),
            heads=m.heads, kv_heads=m.kv_heads, head_dim=m.head_dim,
            eps=m.eps, rotary_of=(m.rope_theta, positions),
            block=m.attn_block, dtype=m.dtype)
        with jax.named_scope("out"):
            return _dot(out, self.param(
                "wo", _init, (m.heads * m.head_dim, d), _f32), m.dtype)


class ExpertLayer(nn.Module):
    """The routed experts this chip holds, under a softmax router."""
    arch: Arch

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
        sow_counters(self, counters)
        return out.reshape(shape)


class Layer(nn.Module):
    arch: Arch

    @nn.compact
    def __call__(self, h, doc, positions):
        m = self.arch
        with jax.named_scope("attention"):
            h = h + Attention(m, name="attn")(
                RMSNorm(m.eps, name="input_norm")(h), doc, positions)
        with jax.named_scope("moe"):
            return h + ExpertLayer(m, name="moe")(
                RMSNorm(m.eps, name="post_attention_norm")(h))


class SdarMoe(nn.Module):
    """``apply(variables, ids, train=...) -> logits`` of shape ``(S, L,
    vocab_rows)`` float32; ``ids`` are ``(S, 2L)`` int32 in ``[0,
    vocab_rows)``, the noised copy of each sequence and then the clean one
    (``objective`` makes them). The mutable collection is ``counters``
    (what the routing did this call)."""
    arch: Arch

    @nn.compact
    def __call__(self, ids, *, train: bool = False):
        del train                      # no state beside the parameters
        m = self.arch
        ids = jnp.asarray(ids, jnp.int32)
        length = ids.shape[1] // 2
        if ids.shape[1] % 2 or length % m.block_length:
            raise ValueError(
                f"the model is fed a noised and a clean copy of whole "
                f"blocks of {m.block_length}, not {ids.shape[1]} positions")
        clean = ids[:, length:]
        doc = jnp.tile(jnp.cumsum((clean == 0).astype(jnp.int32), axis=1),
                       (1, 2))
        positions = jnp.tile(jnp.arange(length, dtype=jnp.int32), 2)[None]
        with jax.named_scope("embed"):
            h = jnp.take(self.param("embed", _embed_init,
                                    (m.vocab_rows, m.hidden), _f32),
                         ids, axis=0)
        layer = nn.remat(Layer, policy=_KEEP) if m.remat else Layer
        for i in range(m.layers):
            h = layer(m, name=f"layer_{i}")(h, doc, positions)
        with jax.named_scope("head"):
            return _dot(RMSNorm(m.eps, name="final_norm")(h[:, :length]),
                        self.param("head", _init, (m.hidden, m.vocab_rows),
                                   _f32), m.dtype, out=_f32)


# --------------------------------------------------------------- objective
def noise(rng, x0, block: int, t_min: float, mask_id: int):
    """``(xt, masked, t)`` of clean ids ``x0`` ``(S, L)`` under the key of
    the step: the noised ids, which positions were masked, and each
    position's noise level (its block's); the module docstring has the
    recipe."""
    s, length = x0.shape
    t = t_min + (1.0 - t_min) * jax.random.uniform(
        jax.random.fold_in(rng, 0), (s, length // block), _f32)
    t = jnp.repeat(t, block, axis=1)
    masked = jax.random.uniform(jax.random.fold_in(rng, 1), (s, length),
                                _f32) < t
    return jnp.where(masked, mask_id, x0), masked, t


def objective(model: SdarMoe, rng, ids, labels):
    """``Family.objective``: what the model is fed for the batch's clean
    ``ids`` under the step's ``rng``, and ``score(logits) -> (loss,
    metrics)``. The next-id ``labels`` are not read."""
    del labels
    m = model.arch
    x0 = jnp.asarray(ids, jnp.int32)
    with jax.named_scope("noise"):
        xt, masked, t = noise(rng, x0, m.block_length, m.t_min, m.mask_id)
        fed = jnp.concatenate([xt, x0], axis=1)

    def score(logits):
        picked = jnp.take_along_axis(logits, x0[..., None], axis=-1)[..., 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - picked
        hit = masked.astype(_f32)
        right = (jnp.argmax(logits, axis=-1) == x0).astype(_f32)
        return jnp.sum(hit / t * nll) / x0.size, {
            "precision": jnp.sum(hit * right) / jnp.maximum(jnp.sum(hit), 1),
            "diffusion_masked_frac": jnp.mean(hit),
            "diffusion_t_mean": jnp.mean(t)}

    return fed, score


# ------------------------------------------------------------------- FLOPs
def live_entries(length: int, block: int) -> int:
    """The entries of the ``2L x 2L`` scores a head that the three-part
    mask leaves: block causal ``L (L + B) / 2``, offset block causal ``L
    (L - B) / 2``, the noisy diagonal ``L B`` (document masks leave
    fewer)."""
    return length * length + length * block


def train_flops_per_sequence(model: Arch, seq_len: int) -> float:
    """Forward and backward model FLOPs of one sequence of ``seq_len``
    clean ids: 3 x 2 x the multiply-adds of every matrix its ``2 x
    seq_len`` positions are multiplied by (``top_k * count /
    experts_total`` of an expert, the routing being even), of attention's
    scores and values over the live entries, and of the head over the
    noisy positions. Nothing recomputed counts."""
    d, hd = model.hidden, model.heads * model.head_dim
    share = model.top_k * model.experts_held[1] / model.experts_total
    position = (2 * d * hd + 2 * d * model.kv_heads * model.head_dim
                + d * model.experts_total
                + 3 * d * model.expert_width * share)
    macs = (2 * seq_len * model.layers * position
            + model.layers * 2 * hd * live_entries(seq_len,
                                                   model.block_length)
            + seq_len * d * model.vocab_rows)
    return 6.0 * macs


def attention_paths(model: Arch, seq_len: int, backend: str,
                    devices: int) -> List[Dict[str, object]]:
    """For each layer, the ``path`` its attention takes over the ``2 x
    seq_len`` positions on ``devices`` of ``backend``, how its ``inputs``
    are prepared on that path (``transformer.INPUTS``) and, in tiles of
    queries by keys, ``key_blocks_visited`` of ``key_blocks_total``: the
    kernel's from its own mask table, which is over the clean keys alone,
    with the ``diagonal_rows`` it leaves to the product of a block by a
    block beside it (``ops/attention.py``); the scan's every block against
    all the keys, and no such rows."""
    fed = 2 * seq_len
    mask = BlockDiffusion(seq_len, model.block_length)
    path = attention_path(backend, devices, model.head_dim, seq_len)
    if path == "kernel":
        visited, total = key_blocks(fed, mask, model.heads // model.kv_heads)
        beside = diagonal_rows(mask)
    else:
        visited = total = (fed // min(model.attn_block, fed)) ** 2
        beside = 0
    return [dict(layer=i, kind="block_diffusion", path=path,
                 inputs=INPUTS[path],
                 key_blocks_visited=visited, key_blocks_total=total,
                 diagonal_rows=beside)
            for i in range(model.layers)]


# ------------------------------------------------------------------ family
# What models/__init__.py registers as the family ``sdar_moe``, with
# ``COUNTERS`` (the routing's) and ``objective`` above.
def build(cfg) -> SdarMoe:
    a = cfg.sdar_moe
    return SdarMoe(Arch(
        layers=a.layers, hidden=a.hidden, heads=a.heads,
        kv_heads=a.kv_heads, head_dim=a.head_dim,
        expert_width=a.expert_width, experts_total=a.experts_total,
        experts_held=(a.experts_first, a.experts_held), top_k=a.top_k,
        vocab_rows=cfg.data.num_classes, rope_theta=a.rope_theta,
        eps=a.rms_eps, block_length=a.block_length, t_min=a.t_min,
        remat=cfg.model.remat, dtype=jnp.dtype(cfg.model.compute_dtype)))


def spell(cfg):
    """Depth, the experts held of the router's width, the block of the
    diffusion and the sequence length each change the traced program."""
    a = cfg.sdar_moe
    return (f"tokens{cfg.data.seq_len}",
            f"sdar{a.layers}l_e{a.experts_held}of{a.experts_total}"
            f"_blk{a.block_length}")


def train_flops_per_example(cfg, xla_counted: bool = True) -> float:
    """Counted from the shapes: XLA's count of the lowered step would
    hold what attention recomputes backward."""
    return train_flops_per_sequence(build(cfg).arch, cfg.data.seq_len)


def refuses(cfg, data_axis: int):
    """What of ``cfg`` this family does not train with, beside what
    neither token family does."""
    whole = cfg.data.seq_len % cfg.sdar_moe.block_length == 0
    return transformer.refuses(cfg, data_axis) + ([] if whole else [
        f"data.seq_len={cfg.data.seq_len} (whole blocks of "
        f"sdar_moe.block_length={cfg.sdar_moe.block_length})"])


def startup_events(model: SdarMoe, cfg):
    """Static, so said once: the path each layer's attention takes here
    and the key blocks the three-part mask leaves, and the expert layers'
    paths (docs/OBSERVABILITY.md)."""
    backend, devices = jax.default_backend(), jax.device_count()
    return {"attention_path": {"layers": attention_paths(
        model.arch, cfg.data.seq_len, backend, devices)},
            "expert_path": transformer.expert_paths(backend, devices)}
