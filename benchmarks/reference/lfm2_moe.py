"""The plain reference of the model family ``lfm2_moe``: a decoder whose
layers mix tokens by a gated short convolution or by full attention, with
a dense MLP or routed experts behind either and the embedding as its
output head (LFM2, LiquidAI; Hugging Face ``transformers``
``modeling_lfm2_moe.py``), as one chip of an expert-parallel group holds
it, and its training step, in float32 ``jax.numpy``. It imports nothing of
the program; the numerics of a product (``product``: float32 operands
multiplied as the bf16 products of their parts), AdamW's leaf, the
learning rate and the bias rule are those that
``benchmarks/reference/afmoe.py`` already has.

Written out here: the forward pass, the next-token loss, the gradients
(block by block: each block's forward is followed by its ``jax.vjp`` on
the way back, so that one block's intermediates are alive at a time and
one small program is compiled for each kind of block), the global-norm
clip, AdamW with decoupled decay on every leaf of two or more axes (the
tied embedding and the filters' ``(d, K)`` leaf among them), and the
routers' ``expert_bias`` update.

The equations (``model`` is the configuration's ``model`` group; every
norm is RMSNorm with a weight; no bias anywhere):

- ``h = E[ids]``; logits ``= norm(h_L) E^T``: the head is the embedding,
  and ``E``'s gradient is the sum of the head's and the lookup's.
- layer of kind ``<f>_<mix>``: ``h += Mix(operator_norm(h))``; ``h +=
  F(ffn_norm(h))``; ``Mix`` the short convolution (``conv``) or attention
  (``full``); ``F`` a SwiGLU MLP (``dense``) or the expert layer (``moe``).
- short convolution, ``K = conv_taps``: ``[B, C, X] = x W_in``; ``u = B *
  X``; ``c_t = sum_{j=0}^{K-1} w[:, K-1-j] * u_{t-j}`` where ``u_{t-j}``
  is 0 if ``t - j < 0`` or position ``t - j`` lies in another document
  than ``t``; ``Mix = (C * c) W_out``. The sum is written out tap by tap
  over an explicit table of which earlier position each position may
  read (``reach``), built from the documents' definition.
- attention: ``q, k, v = x Wq, x Wk, x Wv``; per head ``q = norm(q)``, ``k
  = norm(k)``; rotary embedding (rotate-half over the whole head) on every
  attention layer; softmax of ``q k^T / sqrt(head_dim)`` over the keys ``j
  <= i`` of the same document, dense, a block of queries at a time, one
  key/value head at a time; ``Mix = (softmax V) Wo``.
- expert layer: ``s = sigmoid(x Wr)``; chosen = top-k of ``s + b``; ``w =
  s[chosen] / (sum s[chosen] + 1e-6) * route_scale``; ``F(x) = sum over
  the chosen experts THAT ARE HELD HERE of w_e Expert_e(x)``, ``Expert(x)
  = (silu(x W1) * (x W3)) W2``. Here every held expert is applied to every
  token and weighted by ``w_e`` or 0: no dispatch to go wrong. After the
  step ``b += c - mean(c)``, ``c = coeff * sign(mean(n) - n)``, ``n`` the
  step's assignments per expert.

Departures from the published forward pass: (1) the share of the experts
and of the vocabulary (what the absent experts would add is left out and
the partial result goes on; ids, logits and the loss are over the slice of
the tied table held here); (2) **the document cut in the convolution**:
the published operator knows no packing and would let a document's first
positions read the previous document's last ``K - 1``; here a document
gets what it would get alone, as attention's mask gives it; (3) the bias
rule in training is assumed (the published inference code only reads
``b``): the auxiliary-loss-free balancing of Wang et al. 2024
(arXiv:2408.15664) as the repo's other sigmoid router has it.

``quantize`` (``"fp8"``, ``"bf16"``) rounds both operands of every matrix
product, attention's included and the router's excepted, to that type, and
with them what the convolution's elementwise part takes from its product
(the program hands it on in the stated type): the stand-ins of a lower
precision that the family's control reads.

Leaves are named ``embed``, ``embedding_norm/scale`` and, in
``layer_<i>/``: ``operator_norm/scale``, ``conv/{in_proj,conv,out_proj}``
or ``attn/{wq,wk,wv,wo}`` with ``attn/{q_norm,k_norm}/scale``,
``ffn_norm/scale``, ``mlp/{gate,up,down}`` or ``moe/{router,gate,up,
down}``; the biases ``layer_<i>/moe/expert_bias``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.afmoe import (HIGHEST, adamw_leaf, bias_update,
                                        learning_rate, mm, product, rms,
                                        rounded, sub, swiglu)

QUERY_BLOCK = 1024    # queries whose scores are alive at a time


def documents(ids):
    """A document begins at each id 0."""
    return jnp.cumsum((ids == 0).astype(jnp.int32), axis=1)


def reach(doc, back: int):
    """``(B, S)`` 0/1: whether position ``t`` may read position ``t -
    back``: it exists and lies in ``t``'s document."""
    s = doc.shape[1]
    t = jnp.arange(s)
    earlier = jnp.take(doc, jnp.maximum(t - back, 0), axis=1)
    return ((t >= back)[None] & (earlier == doc)).astype(jnp.float32)


def conv_block(p: Dict, h, doc, model: Dict, quantize: str):
    """``h + ShortConv(operator_norm(h))``."""
    taps = model["conv_taps"]
    c = sub(p, "conv/")
    bcx, = mm(rms(h, p["operator_norm/scale"], model["rms_norm_eps"]),
              [c["in_proj"]], quantize)
    gate_b, gate_c, x = jnp.split(rounded(bcx, quantize), 3, axis=-1)
    u = gate_b * x
    s = u.shape[1]
    mixed = jnp.zeros_like(u)
    for j in range(taps):
        # u_{t-j}: the sequence moved right by j, nothing before its start
        moved = jnp.concatenate([jnp.zeros_like(u[:, :j]), u[:, :s - j]],
                                axis=1)
        mixed = mixed + c["conv"][:, taps - 1 - j] * moved \
            * reach(doc, j)[..., None]
    return h + mm(gate_c * mixed, [c["out_proj"]], quantize)[0]


def rotate(x, theta: float):
    """``x``: (B, S, H, D); rotate-half over the whole head by the
    position in the sequence."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.tile(jnp.cos(ang), 2)[None, :, None, :]
    sin = jnp.tile(jnp.sin(ang), 2)[None, :, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + half * sin


def attention_block(p: Dict, h, doc, model: Dict, quantize: str):
    """``h + Attn(operator_norm(h))``."""
    b, s, _ = h.shape
    heads, kv, hd = model["heads"], model["kv_heads"], model["head_dim"]
    eps = model["rms_norm_eps"]
    a = sub(p, "attn/")
    q, k, v = mm(rms(h, p["operator_norm/scale"], eps),
                 [a["wq"], a["wk"], a["wv"]], quantize)
    q = rotate(rms(q.reshape(b, s, heads, hd), a["q_norm/scale"], eps),
               model["rope_theta"])
    k = rotate(rms(k.reshape(b, s, kv, hd), a["k_norm/scale"], eps),
               model["rope_theta"])
    bq = min(QUERY_BLOCK, s)
    blocks = s // bq
    pos = jnp.arange(s)

    def head(qkv):
        """One key/value head and the query heads it serves: ``qg`` (B, S,
        G, D), ``kg`` and ``vg`` (B, S, D); a block of queries at a time
        against every key under the dense mask."""
        qg, kg, vg = qkv

        @jax.checkpoint
        def rows(x):
            qb, pos_b, doc_b = x       # (B, bq, G, D), (bq,), (B, bq)
            sc = product("bqgd,bkd->bgqk", qb, kg, quantize) / math.sqrt(hd)
            see = (pos[None, :] <= pos_b[:, None])[None] \
                & (doc_b[:, :, None] == doc[:, None, :])
            pr = jax.nn.softmax(jnp.where(see[:, None], sc, -jnp.inf), -1)
            return product("bgqk,bkd->bqgd", pr, vg, quantize)

        out = jax.lax.map(rows, (
            jnp.moveaxis(qg.reshape(b, blocks, bq, *qg.shape[2:]), 1, 0),
            pos.reshape(blocks, bq),
            jnp.moveaxis(doc.reshape(b, blocks, bq), 1, 0)))
        return jnp.moveaxis(out, 0, 1).reshape(qg.shape)

    out = jax.lax.map(head, (
        jnp.moveaxis(q.reshape(b, s, kv, heads // kv, hd), 2, 0),
        jnp.moveaxis(k, 2, 0),
        jnp.moveaxis(v.reshape(b, s, kv, hd), 2, 0)))     # (KV, B, S, G, D)
    out = jnp.moveaxis(out, 0, 2).reshape(b, s, heads * hd)
    return h + mm(out, [a["wo"]], quantize)[0]


def experts(p: Dict, bias, x, model: Dict, quantize: str):
    """``(F(x), n)``: the partial result of the experts held here, and the
    assignments per expert over all of them."""
    first, count = model["experts_first"], model["experts_held"]
    total, k = model["experts_total"], model["top_k"]
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    s = jax.nn.sigmoid(product("nd,de->ne", x, p["router"], terms=HIGHEST))
    _, chosen = jax.lax.top_k(s + bias, k)
    picked = jnp.take_along_axis(s, chosen, -1)
    w = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-6) \
        * model["route_scale"]
    # (N, total): a token's weight for each expert, 0 where not chosen
    dense = jnp.sum(jax.nn.one_hot(chosen, total) * w[..., None], axis=1)
    # every held expert on every token, then weighted
    both = product("nd,edf->enf", x,
                   jnp.concatenate([p["gate"], p["up"]], axis=2), quantize)
    g, u = jnp.split(both, 2, axis=2)
    each = product("enf,efd->end", jax.nn.silu(g) * u, p["down"], quantize)
    out = jnp.sum(each * dense[:, first:first + count].T[:, :, None], axis=0)
    n = jnp.sum(jax.nn.one_hot(chosen, total), axis=(0, 1))
    return out.reshape(shape), n


def ffn_block(p: Dict, bias, h, dense: bool, model: Dict, quantize: str):
    """``(h + F(ffn_norm(h)), n)``; ``n`` is None for a dense layer."""
    x = rms(h, p["ffn_norm/scale"], model["rms_norm_eps"])
    if dense:
        return h + swiglu(p["mlp/gate"], p["mlp/up"], p["mlp/down"], x,
                          quantize), None
    f, n = experts(sub(p, "moe/"), bias, x, model, quantize)
    return h + f, n


def head_loss(scale, table, h, labels, eps: float, quantize: str):
    """The next id's cross-entropy under the tied head ``E^T``."""
    logits = product("bsd,vd->bsv", rms(h, scale, eps), table, quantize)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)


def of_layer(tree: Dict, i: int) -> Dict:
    return sub(tree, f"layer_{i}/")


def halves(p: Dict) -> Tuple[Dict, Dict]:
    """A layer's leaves as its mixer block's and its ``F`` block's."""
    first = ("operator_norm/", "conv/", "attn/")
    return ({k: v for k, v in p.items() if k.startswith(first)},
            {k: v for k, v in p.items() if not k.startswith(first)})


def forward_loss(params: Dict, biases: Dict, ids, labels, model: Dict,
                 quantize: str = "none"):
    """The whole forward pass and loss in one piece (tests; the steps
    below go block by block)."""
    doc = documents(ids)
    h = params["embed"][ids]
    for i, kind in enumerate(model["layers"]):
        mixer, ffn = halves(of_layer(params, i))
        block = conv_block if kind.endswith("_conv") else attention_block
        h = block(mixer, h, doc, model, quantize)
        h, _ = ffn_block(ffn, biases.get(f"layer_{i}/moe/expert_bias"), h,
                         kind.startswith("dense"), model, quantize)
    return head_loss(params["embedding_norm/scale"], params["embed"], h,
                     labels, model["rms_norm_eps"], quantize)


# ------------------------------------------------------------------ a step
class Programs:
    """The jitted pieces of a step: the conv block, the attention block,
    the dense and the expert block, the head with the loss; each block
    forward, and backward as its ``jax.vjp`` on the way back (its forward
    computed again there)."""

    def __init__(self, model: Dict, quantize: str):
        self.model = model

        def pair(f):
            """``f(p, *rest, h) -> (h', n)`` jitted, and its pull-back
            ``(p, *rest, h, dh) -> (dp, dh)``."""
            def back(p, *rest_h_dh):
                *rest, h, dh = rest_h_dh
                return jax.vjp(lambda p_, h_: f(p_, *rest, h_)[0], p, h)[1](
                    dh)

            return jax.jit(f), jax.jit(back)

        self.mixer = {conv: pair(
            lambda p, doc, h, block=block: (block(p, h, doc, model,
                                                  quantize), None))
            for conv, block in ((True, conv_block), (False,
                                                     attention_block))}
        self.ffn = {dense: pair(
            lambda p, bias, h, dense=dense: ffn_block(
                p, bias, h, dense, model, quantize)) for dense in (True,
                                                                   False)}
        self.head = jax.jit(jax.value_and_grad(
            partial(head_loss, eps=model["rms_norm_eps"], quantize=quantize),
            argnums=(0, 1, 2)))
        # the tied table's other use: the lookup
        self.embed_back = jax.jit(
            lambda d_table, ids, dh: d_table.at[ids].add(dh))
        self.square = jax.jit(lambda g: jnp.sum(jnp.square(g)))

    def gradients(self, params: Dict, biases: Dict, ids, labels
                  ) -> Tuple[float, Dict, Dict]:
        """``(loss, gradients, n)``: ``n`` the assignments per expert of
        each expert layer."""
        kinds = self.model["layers"]
        doc = documents(ids)
        h = params["embed"][ids]
        inputs, counts = [], {}      # each block's input, in order
        for i, kind in enumerate(kinds):
            mixer, ffn = halves(of_layer(params, i))
            key = f"layer_{i}/moe/expert_bias"
            inputs.append(h)
            h, _ = self.mixer[kind.endswith("_conv")][0](mixer, doc, h)
            inputs.append(h)
            h, n = self.ffn[kind.startswith("dense")][0](
                ffn, biases.get(key), h)
            if n is not None:
                counts[key] = n
        loss, (d_scale, d_table, dh) = self.head(
            params["embedding_norm/scale"], params["embed"], h, labels)
        grads = {"embedding_norm/scale": d_scale}
        for i in reversed(range(len(kinds))):
            mixer, ffn = halves(of_layer(params, i))
            d_ffn, dh = self.ffn[kinds[i].startswith("dense")][1](
                ffn, biases.get(f"layer_{i}/moe/expert_bias"),
                inputs.pop(), dh)
            d_mixer, dh = self.mixer[kinds[i].endswith("_conv")][1](
                mixer, doc, inputs.pop(), dh)
            grads.update({f"layer_{i}/{k}": v
                          for k, v in {**d_mixer, **d_ffn}.items()})
        grads["embed"] = self.embed_back(d_table, ids, dh)
        return float(loss), grads, counts

    def norm(self, grads: Dict) -> float:
        return math.sqrt(sum(float(self.square(g)) for g in grads.values()))


def follow(params: Dict, biases: Dict, inputs: np.ndarray,
           labels: np.ndarray, model: Dict, job: Dict,
           quantize: str = "none", start_step: int = 0):
    """Train from ``params`` and ``biases`` with zero moments over the
    steps' ``inputs`` and ``labels`` (``(steps, B, S)`` ids). Returns
    ``(params, biases, mu, nu, losses, gnorms)``."""
    programs = Programs(model, quantize)
    mu = {k: jnp.zeros_like(v) for k, v in params.items()}
    nu = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses: List[float] = []
    gnorms: List[float] = []
    for i in range(len(inputs)):
        loss, grads, counts = programs.gradients(
            params, biases, jnp.asarray(inputs[i]), jnp.asarray(labels[i]))
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
        biases = {k: bias_update(b, counts[k], model["balance_coeff"])
                  for k, b in biases.items()}
        losses.append(loss)
        gnorms.append(gnorm)
    return params, biases, mu, nu, losses, gnorms
