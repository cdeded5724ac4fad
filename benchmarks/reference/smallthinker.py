"""The plain reference of the model family ``smallthinker``: a decoder that
routes each token before attention, attends over a window with rotary or
over the whole sequence with no position, and sums ReGLU experts
(SmallThinker, PowerInfer, arXiv:2507.20984), as one chip of an
expert-parallel group holds it, and its training step, in float32
``jax.numpy``. It imports nothing of the program; the numerics of a
product (``product``: float32 operands multiplied as the bf16 products of
their parts), AdamW's leaf and the learning rate are those that
``benchmarks/reference/afmoe.py`` already has.

Written out here: the forward pass, the next-token loss, the gradients
(layer by layer: each layer's forward is followed by its ``jax.vjp`` on the
way back, so that one layer's intermediates are alive at a time; a layer
is one piece because its routing, read from the layer's input, weighs its
experts after attention), the global-norm clip and AdamW with decoupled
decay on every leaf of two or more axes.

The equations (``model`` is the configuration's ``model`` group; ``rms``
is RMSNorm with a weight from 1; no bias anywhere):

- ``h = E[ids]``; logits ``= rms(h_L) W_head``.
- layer ``i``: ``x = rms(h)`` (``input_norm``); ``s = softmax(x W_r)``
  over all ``experts_total``, chosen = top-k of ``s``, ``w = s[chosen] /
  sum s[chosen]``; ``h = h + Attn(x)``; ``h = h + sum over the chosen
  experts THAT ARE HELD HERE of w_e (relu(y Wg_e) * (y Wu_e)) Wd_e`` with
  ``y = rms(h)`` (``post_attention_norm``). Here every held expert is
  applied to every token, one expert at a time, and weighted by ``w_e`` or
  0: no dispatch to go wrong.
- ``Attn``: ``q, k, v = x Wq, x Wk, x Wv``, no q/k norm; on a ``window``
  layer rotate-half over the whole head by the position in the sequence
  (``rope_theta``) and the keys ``0 <= i - j < window`` of the query's
  document; on a ``global`` layer no position and every ``j <= i`` of it;
  softmax of ``q k^T / sqrt(head_dim)``, then ``W_o``. A block of queries
  at a time, one key/value head at a time: a window layer's block takes
  the ``window`` positions before it and its own, a global layer's every
  position, under the whole mask.

Departures from the published model: (1) the share of the experts and of
the vocabulary: what the absent experts would add is left out and the
partial result goes on; ids, logits and the loss are over the slice held
here; (2) **packing**: the published forward pass knows no packing; here a
document begins at every id 0, and attention's mask stops at a document's
start; (3) the secondary experts the model's family describes are not
built: the configuration declares primary experts only; (4) no auxiliary
loss for the router.

``quantize`` (``"fp8"``, ``"bf16"``) rounds both operands of every matrix
product, attention's included and the router's excepted, to that type: the
stand-ins of a lower precision that the family's control reads.

Leaves are named ``embed``, ``head``, ``final_norm/scale`` and, in
``layer_<i>/``: ``input_norm/scale``, ``router``, ``attn/{wq,wk,wv,wo}``,
``post_attention_norm/scale`` and ``moe/{gate,up,down}``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.afmoe import (HIGHEST, adamw_leaf, learning_rate,
                                        mm, product, rms, sub)

QUERY_BLOCK = 1024    # queries whose scores are alive at a time


def documents(ids):
    """A document begins at each id 0."""
    return jnp.cumsum((ids == 0).astype(jnp.int32), axis=1)


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


def attention(q, k, v, doc, window: int, quantize: str):
    """Softmax attention within documents, causal, and within ``window``
    keys where it is not 0: ``q`` ``(B, S, H, D)``, ``k`` and ``v`` ``(B,
    S, KV, D)``. Returns ``(B, S, H, D)``."""
    b, s, heads, hd = q.shape
    kv = k.shape[2]
    bq = min(QUERY_BLOCK, s)
    # the keys a block of queries can see start ``reach`` before it
    reach = min(window, s) if window else 0
    span = reach + bq if window else s
    front = ((0, 0), (reach, 0))
    docp = jnp.pad(doc, front, constant_values=-1)
    pos = jnp.arange(s)

    def head(qkv):
        """One key/value head and the query heads it serves."""
        qh, kh, vh = qkv
        kh, vh = (jnp.pad(x, front + ((0, 0),)) for x in (kh, vh))

        @jax.checkpoint
        def rows(x):
            qb, q0 = x
            first = q0 if window else 0          # of the padded keys
            kb, vb, doc_k = (jax.lax.dynamic_slice_in_dim(a, first, span, 1)
                             for a in (kh, vh, docp))
            qi = q0 + jnp.arange(bq)[:, None]
            kj = first - reach + jnp.arange(span)[None, :]
            see = (kj <= qi) & ((qi - kj < window) if window else True)
            doc_q = jax.lax.dynamic_slice_in_dim(doc, q0, bq, 1)
            see = see[None] & (doc_q[:, :, None] == doc_k[:, None, :])
            sc = product("bqgd,bkd->bgqk", qb, kb, quantize) / math.sqrt(hd)
            pr = jax.nn.softmax(jnp.where(see[:, None], sc, -jnp.inf), -1)
            return product("bgqk,bkd->bqgd", pr, vb, quantize)

        out = jax.lax.map(rows, (
            jnp.moveaxis(qh.reshape(b, s // bq, bq, *qh.shape[2:]), 1, 0),
            pos[::bq]))
        return jnp.moveaxis(out, 0, 1).reshape(qh.shape)

    out = jax.lax.map(head, (
        jnp.moveaxis(q.reshape(b, s, kv, heads // kv, hd), 2, 0),
        jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(out, 0, 2).reshape(b, s, heads, hd)


def route(x, router, model: Dict):
    """``(N, total)``: each token's weight for each expert, 0 where not
    chosen: softmax over all, top-k, renormalised."""
    total, k = model["experts_total"], model["top_k"]
    s = jax.nn.softmax(product("nd,de->ne", x, router, terms=HIGHEST),
                       axis=-1)
    picked, chosen = jax.lax.top_k(s, k)
    w = picked / jnp.sum(picked, -1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(chosen, total) * w[..., None], axis=1)


def experts(p: Dict, y, dense, model: Dict, quantize: str):
    """The partial result of the held ReGLU experts on ``y`` ``(N, d)``,
    weighted by ``dense``'s columns of the experts held here."""
    first, count = model["experts_first"], model["experts_held"]

    def one(acc, e):
        """Held expert ``e`` on every token, weighted."""
        wg, wu, wd, weight = e
        g, u = mm(y, [wg, wu], quantize)
        return acc + weight[:, None] * mm(jax.nn.relu(g) * u, [wd],
                                          quantize)[0], None

    out, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(y), (
        p["gate"], p["up"], p["down"], dense[:, first:first + count].T))
    return out


def layer(p: Dict, h, ids, window: int, model: Dict, quantize: str):
    """One layer: ``h'``. ``window`` is 0 on a global layer."""
    b, s, d = h.shape
    heads, kv, hd = model["heads"], model["kv_heads"], model["head_dim"]
    eps = model["rms_norm_eps"]
    x = rms(h, p["input_norm/scale"], eps)
    dense = route(x.reshape(-1, d), p["router"], model)
    a = sub(p, "attn/")
    q, k, v = mm(x, [a["wq"], a["wk"], a["wv"]], quantize)
    q, k = q.reshape(b, s, heads, hd), k.reshape(b, s, kv, hd)
    if window:
        q, k = (rotate(t, model["rope_theta"]) for t in (q, k))
    out = attention(q, k, v.reshape(b, s, kv, hd), documents(ids), window,
                    quantize)
    h = h + mm(out.reshape(b, s, heads * hd), [a["wo"]], quantize)[0]
    y = rms(h, p["post_attention_norm/scale"], eps).reshape(-1, d)
    return h + experts(sub(p, "moe/"), y, dense, model,
                       quantize).reshape(b, s, d)


def window_of(kind: str, model: Dict) -> int:
    return model["window"] if kind == "window" else 0


def head_loss(scale, head, h, labels, eps: float, quantize: str):
    logits, = mm(rms(h, scale, eps), [head], quantize)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)


def of_layer(tree: Dict, i: int) -> Dict:
    return sub(tree, f"layer_{i}/")


def forward_loss(params: Dict, ids, labels, model: Dict,
                 quantize: str = "none"):
    """The whole forward pass and loss in one piece (tests; the steps
    below go layer by layer)."""
    h = params["embed"][ids]
    for i, kind in enumerate(model["layers"]):
        h = layer(of_layer(params, i), h, ids, window_of(kind, model),
                  model, quantize)
    return head_loss(params["final_norm/scale"], params["head"], h, labels,
                     model["rms_norm_eps"], quantize)


# ------------------------------------------------------------------ a step
class Programs:
    """The jitted pieces of a step: a layer of each kind, the head with
    the loss; each layer forward, and backward as its ``jax.vjp`` on the
    way back (its forward computed again there)."""

    def __init__(self, model: Dict, quantize: str):
        self.model = model

        def pair(window):
            def f(p, ids, h):
                return layer(p, h, ids, window, model, quantize)

            def back(p, ids, h, dh):
                return jax.vjp(lambda p_, h_: f(p_, ids, h_), p, h)[1](dh)

            return jax.jit(f), jax.jit(back)

        self.layer = {kind: pair(window_of(kind, model))
                      for kind in set(model["layers"])}
        self.head = jax.jit(jax.value_and_grad(
            partial(head_loss, eps=model["rms_norm_eps"], quantize=quantize),
            argnums=(0, 1, 2)))
        self.embed_back = jax.jit(
            lambda table, ids, dh: jnp.zeros_like(table).at[ids].add(dh))
        self.square = jax.jit(lambda g: jnp.sum(jnp.square(g)))

    def gradients(self, params: Dict, ids, labels) -> Tuple[float, Dict]:
        kinds = self.model["layers"]
        h = params["embed"][ids]
        inputs = []      # each layer's input, in order
        for i, kind in enumerate(kinds):
            inputs.append(h)
            h = self.layer[kind][0](of_layer(params, i), ids, h)
        loss, (d_scale, d_head, dh) = self.head(
            params["final_norm/scale"], params["head"], h, labels)
        grads = {"final_norm/scale": d_scale, "head": d_head}
        for i in reversed(range(len(kinds))):
            d_layer, dh = self.layer[kinds[i]][1](of_layer(params, i), ids,
                                                  inputs.pop(), dh)
            grads.update({f"layer_{i}/{k}": v for k, v in d_layer.items()})
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
