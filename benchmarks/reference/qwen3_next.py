"""The plain reference of the model family ``qwen3_next``: a decoder whose
layers mix tokens by Gated DeltaNet or by gated full attention, each
followed by routed experts beside a gated shared one (Qwen3-Next, Qwen;
Hugging Face ``transformers`` ``modeling_qwen3_next.py``; Yang et al. 2024,
arXiv:2412.06464), as one chip of an expert-parallel group holds it, and
its training step, in float32 ``jax.numpy``. It imports nothing of the
program; the numerics of a product (``product``: float32 operands
multiplied as the bf16 products of their parts), AdamW's leaf and the
learning rate are those that ``benchmarks/reference/afmoe.py`` already has.

Written out here: the forward pass, the next-token loss, the gradients
(block by block: each block's forward is followed by its ``jax.vjp`` on
the way back, so that one block's intermediates are alive at a time), the
global-norm clip and AdamW with decoupled decay on every leaf of two or
more axes.

The equations (``model`` is the configuration's ``model`` group; ``norm``
is the zero-centred RMSNorm ``x / rms(x) * (1 + w)`` but where said; no
bias anywhere):

- ``h = E[ids]``; logits ``= norm(h_L) W_head``.
- layer ``i``: ``h += Mix(input_norm(h))``; ``h +=
  MoE(post_attention_norm(h))``; ``Mix`` Gated DeltaNet on a ``linear``
  layer, attention on a ``full`` one.
- Gated DeltaNet: ``[q, k, v, z] = x W_qkvz``, ``[b, a] = x W_ba`` (the
  column groups in this order); ``[q, k, v] = silu(conv([q, k, v]))``, the
  convolution causal and depthwise over ``conv_taps`` positions, the last
  tap on the current one, written out tap by tap over an explicit table of
  which earlier position each position may read; ``q``, ``k`` L2-normed
  per head (``+ 1e-6`` under the root), ``q / sqrt(dk)``; ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; per value head
  ``h`` (reading key head ``h // (Hv / Hk)``), token by token from ``S =
  0``: ``S = 0`` where a document begins; ``S = exp(g_t) S``; ``S += beta_t
  k_t (v_t - S^T k_t)^T``; ``o_t = S^T q_t``, each an elementwise product
  and a sum in float32; ``y = rms(o) * w * silu(z)`` (a plain weight);
  ``Mix = y W_out``. The recurrence goes through blocks of ``BLOCK``
  positions under ``jax.checkpoint``, the state kept at the blocks'
  boundaries only: its whole history would be 2 x 4,096 positions x 32
  heads x 64 KiB, 17 GB, in the benchmark's cell.
- attention: ``[q_h, gate_h] = x Wq`` per head; ``k, v = x Wk, x Wv``;
  ``q = norm(q)``, ``k = norm(k)`` per head; rotate-half on the first
  ``rotary_dim`` columns of a head by the position in the sequence, the
  frequencies over those columns, the rest passed; softmax of ``q k^T /
  sqrt(head_dim)`` over the keys ``j <= i`` of the same document, a block
  of queries at a time; ``Mix = ((softmax V) * sigmoid(gate)) Wo``.
- expert layer: ``s = softmax(x Wr)``; chosen = top-k of ``s``; ``w =
  s[chosen] / sum s[chosen]``; ``MoE(x) = sigmoid(x W_sg) Shared(x) + sum
  over the chosen experts THAT ARE HELD HERE of w_e Expert_e(x)``,
  ``Expert(x) = (silu(x W1) * (x W3)) W2``. Here every held expert is
  applied to every token, one expert at a time, and weighted by ``w_e`` or
  0: no dispatch to go wrong.

Departures from the published model: (1) the share of the experts and of
the vocabulary: what the absent experts would add is left out and the
partial result goes on; ids, logits and the loss are over the slice held
here; (2) **packing**: the published forward pass knows no packing; here a
document begins at every id 0, and attention's mask, the convolution's
taps and the recurrence's state each stop at a document's start, so that a
document gets what it would get alone; (3) the multi-token-prediction
module is left out (the configuration carries no key for it, and the
``transformers`` forward pass has none); (4) no auxiliary loss for the
router; (5) the published ``W_qkvz`` and ``W_ba`` interleave their column
groups per key head: here each group is contiguous, a permutation of the
columns that random weights do not see.

``quantize`` (``"fp8"``, ``"bf16"``) rounds both operands of every matrix
product, attention's included and the router's excepted, to that type, and
with them what the program hands on from a product in the stated type
(``W_qkvz``'s result): the stand-ins of a lower precision that the
family's control reads. The recurrence's elementwise products stay in
float32.

Leaves are named ``embed``, ``head``, ``final_norm/scale`` and, in
``layer_<i>/``: ``input_norm/scale``, ``linear_attn/{in_proj_qkvz,
in_proj_ba,conv,A_log,dt_bias,norm,out_proj}`` or ``attn/{wq,wk,wv,wo}``
with ``attn/{q_norm,k_norm}/scale``, ``post_attention_norm/scale``,
``moe/{router,gate,up,down,shared_gate}`` and ``moe/shared/{gate,up,
down}``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.afmoe import (HIGHEST, adamw_leaf, learning_rate,
                                        mm, product, rms, rounded, sub,
                                        swiglu)

QUERY_BLOCK = 1024    # queries whose scores are alive at a time
BLOCK = 64            # positions of the recurrence between kept states


def documents(ids):
    """A document begins at each id 0."""
    return jnp.cumsum((ids == 0).astype(jnp.int32), axis=1)


def reach(doc, back: int):
    """``(B, S)`` 0/1: whether position ``t`` may read position ``t -
    back``: it exists and lies in ``t``'s document."""
    t = jnp.arange(doc.shape[1])
    earlier = jnp.take(doc, jnp.maximum(t - back, 0), axis=1)
    return ((t >= back)[None] & (earlier == doc)).astype(jnp.float32)


def norm(x, w, eps: float):
    """The zero-centred RMSNorm."""
    return rms(x, 1.0 + w, eps)


def conv(u, taps, doc):
    """The causal depthwise convolution within documents: ``u`` ``(B, S,
    C)``, ``taps`` ``(C, K)``."""
    k, s = taps.shape[1], u.shape[1]
    out = jnp.zeros_like(u)
    for j in range(k):
        moved = jnp.concatenate([jnp.zeros_like(u[:, :j]), u[:, :s - j]],
                                axis=1)
        out = out + taps[:, k - 1 - j] * moved * reach(doc, j)[..., None]
    return out


def recurrence(q, k, v, beta, g, begins):
    """The delta rule token by token (the module docstring): ``q``, ``k``
    ``(B, S, Hk, dk)``, ``v`` ``(B, S, Hv, dv)``, ``beta``, ``g`` ``(B, S,
    Hv)``, ``begins`` ``(B, S)`` where a document begins. Returns ``(B, S,
    Hv, dv)``."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))

    def step(state, x):
        qt, kt, vt, bt, gt, new = x
        state = jnp.where(new[:, None, None, None], 0.0, state) \
            * jnp.exp(gt)[..., None, None]
        read = jnp.sum(kt[..., :, None] * state, axis=-2)
        state = state + bt[..., None, None] * kt[..., :, None] \
            * (vt - read)[..., None, :]
        return state, jnp.sum(qt[..., :, None] * state, axis=-2)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    length = min(BLOCK, s)
    xs = [jnp.moveaxis(x, 1, 0).reshape(s // length, length, *x.shape[:1],
                                        *x.shape[2:])
          for x in (q, k, v, beta, g, begins)]
    _, out = jax.lax.scan(block, jnp.zeros((b, hv, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(out.reshape(s, b, hv, dv), 0, 1)


def linear_block(p: Dict, h, ids, model: Dict, quantize: str):
    """``h + GatedDeltaNet(input_norm(h))``."""
    b, s, _ = h.shape
    hk, hv = model["key_heads"], model["value_heads"]
    dk, dv = model["key_dim"], model["value_dim"]
    eps = model["rms_norm_eps"]
    a = sub(p, "linear_attn/")
    mixed = 2 * hk * dk + hv * dv
    qkvz, ba = mm(norm(h, p["input_norm/scale"], eps),
                  [a["in_proj_qkvz"], a["in_proj_ba"]], quantize)
    qkvz = rounded(qkvz, quantize)
    qkv = jax.nn.silu(conv(qkvz[..., :mixed], a["conv"], documents(ids)))
    q, k, v = jnp.split(qkv, [hk * dk, 2 * hk * dk], axis=-1)

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = l2(q.reshape(b, s, hk, dk)) / math.sqrt(dk)
    k = l2(k.reshape(b, s, hk, dk))
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(a["A_log"]) * jax.nn.softplus(ba[..., hv:] + a["dt_bias"])
    o = recurrence(q, k, v.reshape(b, s, hv, dv), beta, g, ids == 0)
    y = rms(o, a["norm"], eps) * jax.nn.silu(
        qkvz[..., mixed:].reshape(b, s, hv, dv))
    return h + mm(y.reshape(b, s, hv * dv), [a["out_proj"]], quantize)[0]


def rotate(x, theta: float, dims: int):
    """``x``: (B, S, H, D); rotate-half over the first ``dims`` columns by
    the position in the sequence, the rest passed."""
    inv = theta ** (-jnp.arange(0, dims, 2, dtype=jnp.float32) / dims)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.tile(jnp.cos(ang), 2)[None, :, None, :]
    sin = jnp.tile(jnp.sin(ang), 2)[None, :, None, :]
    r = x[..., :dims]
    half = jnp.concatenate([-r[..., dims // 2:], r[..., : dims // 2]], -1)
    return jnp.concatenate([r * cos + half * sin, x[..., dims:]], -1)


def attention_block(p: Dict, h, ids, model: Dict, quantize: str):
    """``h + Attn(input_norm(h))``."""
    b, s, _ = h.shape
    heads, kv, hd = model["heads"], model["kv_heads"], model["head_dim"]
    eps, dims = model["rms_norm_eps"], model["rotary_dim"]
    a = sub(p, "attn/")
    doc = documents(ids)
    qg, k, v = mm(norm(h, p["input_norm/scale"], eps),
                  [a["wq"], a["wk"], a["wv"]], quantize)
    qg = qg.reshape(b, s, heads, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:].reshape(b, s, heads * hd)
    q = rotate(norm(q, a["q_norm/scale"], eps), model["rope_theta"], dims)
    k = rotate(norm(k.reshape(b, s, kv, hd), a["k_norm/scale"], eps),
               model["rope_theta"], dims)
    bq = min(QUERY_BLOCK, s)
    pos = jnp.arange(s)

    def head(qkv):
        """One key/value head and the query heads it serves."""
        qh, kh, vh = qkv

        @jax.checkpoint
        def rows(x):
            qb, pos_b, doc_b = x
            sc = product("bqgd,bkd->bgqk", qb, kh, quantize) / math.sqrt(hd)
            see = (pos[None, :] <= pos_b[:, None])[None] \
                & (doc_b[:, :, None] == doc[:, None, :])
            pr = jax.nn.softmax(jnp.where(see[:, None], sc, -jnp.inf), -1)
            return product("bgqk,bkd->bqgd", pr, vh, quantize)

        out = jax.lax.map(rows, (
            jnp.moveaxis(qh.reshape(b, s // bq, bq, *qh.shape[2:]), 1, 0),
            pos.reshape(s // bq, bq),
            jnp.moveaxis(doc.reshape(b, s // bq, bq), 1, 0)))
        return jnp.moveaxis(out, 0, 1).reshape(qh.shape)

    out = jax.lax.map(head, (
        jnp.moveaxis(q.reshape(b, s, kv, heads // kv, hd), 2, 0),
        jnp.moveaxis(k, 2, 0),
        jnp.moveaxis(v.reshape(b, s, kv, hd), 2, 0)))
    out = jnp.moveaxis(out, 0, 2).reshape(b, s, heads * hd)
    return h + mm(out * jax.nn.sigmoid(gate), [a["wo"]], quantize)[0]


def experts(p: Dict, x, model: Dict, quantize: str):
    """The partial result of the experts held here and the gated shared
    one."""
    first, count = model["experts_first"], model["experts_held"]
    total, k = model["experts_total"], model["top_k"]
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    s = jax.nn.softmax(product("nd,de->ne", x, p["router"], terms=HIGHEST),
                       axis=-1)
    picked, chosen = jax.lax.top_k(s, k)
    w = picked / jnp.sum(picked, -1, keepdims=True)
    # (N, total): a token's weight for each expert, 0 where not chosen
    dense = jnp.sum(jax.nn.one_hot(chosen, total) * w[..., None], axis=1)
    gate = jax.nn.sigmoid(mm(x, [p["shared_gate"]], quantize)[0])
    out = gate * swiglu(p["shared/gate"], p["shared/up"], p["shared/down"],
                        x, quantize)

    def one(acc, e):
        """Held expert ``e`` on every token, weighted."""
        w1, w3, w2, weight = e
        return acc + weight[:, None] * swiglu(w1, w3, w2, x, quantize), None

    out, _ = jax.lax.scan(jax.checkpoint(one), out, (
        p["gate"], p["up"], p["down"], dense[:, first:first + count].T))
    return out.reshape(shape)


def moe_block(p: Dict, h, model: Dict, quantize: str):
    """``h + MoE(post_attention_norm(h))``."""
    return h + experts(sub(p, "moe/"), norm(
        h, p["post_attention_norm/scale"], model["rms_norm_eps"]), model,
        quantize)


def head_loss(scale, head, h, labels, eps: float, quantize: str):
    logits, = mm(norm(h, scale, eps), [head], quantize)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)


def of_layer(tree: Dict, i: int) -> Dict:
    return sub(tree, f"layer_{i}/")


def halves(p: Dict) -> Tuple[Dict, Dict]:
    """A layer's leaves as its mixer block's and its expert block's."""
    first = ("input_norm/", "linear_attn/", "attn/")
    return ({k: v for k, v in p.items() if k.startswith(first)},
            {k: v for k, v in p.items() if not k.startswith(first)})


MIXERS = {"linear": linear_block, "full": attention_block}


def forward_loss(params: Dict, ids, labels, model: Dict,
                 quantize: str = "none"):
    """The whole forward pass and loss in one piece (tests; the steps
    below go block by block)."""
    h = params["embed"][ids]
    for i, kind in enumerate(model["layers"]):
        mixer, ffn = halves(of_layer(params, i))
        h = MIXERS[kind](mixer, h, ids, model, quantize)
        h = moe_block(ffn, h, model, quantize)
    return head_loss(params["final_norm/scale"], params["head"], h, labels,
                     model["rms_norm_eps"], quantize)


# ------------------------------------------------------------------ a step
class Programs:
    """The jitted pieces of a step: each kind of mixer block, the expert
    block, the head with the loss; each block forward, and backward as its
    ``jax.vjp`` on the way back (its forward computed again there)."""

    def __init__(self, model: Dict, quantize: str):
        self.model = model

        def pair(f):
            """``f(p, *rest, h) -> h'`` jitted, and its pull-back ``(p,
            *rest, h, dh) -> (dp, dh)``."""
            def back(p, *rest_h_dh):
                *rest, h, dh = rest_h_dh
                return jax.vjp(lambda p_, h_: f(p_, *rest, h_), p, h)[1](dh)

            return jax.jit(f), jax.jit(back)

        self.mixer = {kind: pair(
            lambda p, ids, h, block=block: block(p, h, ids, model, quantize))
            for kind, block in MIXERS.items()}
        self.ffn = pair(lambda p, h: moe_block(p, h, model, quantize))
        self.head = jax.jit(jax.value_and_grad(
            partial(head_loss, eps=model["rms_norm_eps"], quantize=quantize),
            argnums=(0, 1, 2)))
        self.embed_back = jax.jit(
            lambda table, ids, dh: jnp.zeros_like(table).at[ids].add(dh))
        self.square = jax.jit(lambda g: jnp.sum(jnp.square(g)))

    def gradients(self, params: Dict, ids, labels) -> Tuple[float, Dict]:
        kinds = self.model["layers"]
        h = params["embed"][ids]
        inputs = []      # each block's input, in order
        for i, kind in enumerate(kinds):
            mixer, ffn = halves(of_layer(params, i))
            inputs.append(h)
            h = self.mixer[kind][0](mixer, ids, h)
            inputs.append(h)
            h = self.ffn[0](ffn, h)
        loss, (d_scale, d_head, dh) = self.head(
            params["final_norm/scale"], params["head"], h, labels)
        grads = {"final_norm/scale": d_scale, "head": d_head}
        for i in reversed(range(len(kinds))):
            mixer, ffn = halves(of_layer(params, i))
            d_ffn, dh = self.ffn[1](ffn, inputs.pop(), dh)
            d_mixer, dh = self.mixer[kinds[i]][1](mixer, ids, inputs.pop(),
                                                  dh)
            grads.update({f"layer_{i}/{k}": v
                          for k, v in {**d_mixer, **d_ffn}.items()})
        grads["embed"] = self.embed_back(params["embed"], ids, dh)
        return float(loss), grads

    def norm(self, grads: Dict) -> float:
        return math.sqrt(sum(float(self.square(g)) for g in grads.values()))


def follow(params: Dict, inputs: np.ndarray, labels: np.ndarray, model: Dict,
           job: Dict, quantize: str = "none", start_step: int = 0):
    """Train from ``params`` with zero moments over the steps' ``inputs``
    and ``labels`` (``(steps, B, S)`` ids). Returns ``(params, mu, nu,
    losses, gnorms)``."""
    programs = Programs(model, quantize)
    mu = {k: jnp.zeros_like(v) for k, v in params.items()}
    nu = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses: List[float] = []
    gnorms: List[float] = []
    for i in range(len(inputs)):
        loss, grads = programs.gradients(params, jnp.asarray(inputs[i]),
                                         jnp.asarray(labels[i]))
        gnorm = programs.norm(grads)
        clip = min(1.0, job["clip_norm"] / gnorm) if job["clip_norm"] \
            else 1.0
        t = start_step + i + 1
        for k in params:
            params[k], mu[k], nu[k] = adamw_leaf(
                params[k], grads.pop(k), mu[k], nu[k], clip,
                learning_rate(job, start_step + i),
                job["b1"], job["b2"], job["eps"],
                job["weight_decay"] if params[k].ndim >= 2 else 0.0, t)
        losses.append(loss)
        gnorms.append(gnorm)
    return params, mu, nu, losses, gnorms
