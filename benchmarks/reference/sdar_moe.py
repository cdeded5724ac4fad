"""The plain reference of the model family ``sdar_moe``: a sparse-expert
decoder (a Qwen3-MoE backbone) trained by diffusion over blocks (SDAR,
arXiv:2510.06303; the objective and the attention mask are those of block
diffusion, Arriola et al. 2025, arXiv:2503.09573, ``block_diff_mask``), as
one chip of an expert-parallel group holds it, and its training step, in
float32 ``jax.numpy``. It imports nothing of the program; the numerics of a
product (``product``: float32 operands multiplied as the bf16 products of
their parts), AdamW's leaf and the learning rate are those that
``benchmarks/reference/afmoe.py`` already has.

Written out here: the noise, the dense mask, the forward pass, the loss on
the masked positions, the gradients (block by block: each block's forward
is followed by its ``jax.vjp`` on the way back, so that one block's
intermediates are alive at a time and one small program is compiled for
each kind of block), the global-norm clip and AdamW with decoupled decay
on matrices.

The equations (``model`` is the configuration's ``model`` group; every
norm is RMSNorm with a weight; no bias anywhere):

- the step's input: clean ids ``x0`` ``(S, L)``; blocks of ``B =
  block_length`` positions from the sequence's start; with ``r =
  fold_in(split(PRNGKey(train_seed))[1], step)``: ``t = t_min + (1 -
  t_min) * uniform(fold_in(r, 0), (S, L // B))``, ``masked =
  uniform(fold_in(r, 1), (S, L)) < repeat(t, B)``, ``xt = where(masked,
  mask_id, x0)``. The model is fed ``[xt ; x0]`` with position ids ``[0 ..
  L-1 ; 0 .. L-1]``.
- ``h = E[ids]``; logits ``= norm(h_final) W_head`` at the ``L`` noisy
  positions.
- ``h += Attn(input_norm(h))``; ``h += MoE(post_attention_norm(h))``.
- ``Attn``: ``q, k, v = x Wq, x Wk, x Wv``; per head ``q = norm(q)``, ``k
  = norm(k)``; rotary embedding (rotate-half over the whole head) by the
  position ids; softmax of ``q k^T / sqrt(head_dim)`` over the keys ``r``
  that query ``p`` sees: with ``n(p) = p < L`` and ``blk(p) = (p mod L) //
  B``, the same document and one of (block diagonal) ``n(p) == n(r)`` and
  ``blk(p) == blk(r)``; (offset block causal) ``n(p)``, not ``n(r)``,
  ``blk(r) < blk(p)``; (block causal) neither noisy, ``blk(r) <=
  blk(p)``. The mask is built densely from that definition (``mask``) and
  applied a block of queries at a time, one key/value head at a time.
- ``MoE``: ``s = softmax(x Wr)``; chosen = top-k of ``s``; ``w = s[chosen]
  / sum s[chosen]``; ``sum over the chosen experts THAT ARE HELD HERE of
  w_e Expert_e(x)``, ``Expert(x) = (silu(x Wg) * (x Wu)) Wd``. Here every
  held expert is applied to every position and weighted by ``w_e`` or 0:
  no dispatch to go wrong.
- loss ``= (1 / (S L)) sum over masked i of (1 / t_blk(i)) * (-log
  softmax(logits[i])[x0[i]])``.

Departures from the published description: the share of the experts and of
the vocabulary (what the absent experts would add is left out and the
partial result goes on; ids, logits and the loss are over the slice, whose
last row is the mask id); documents in the mask (a document begins at each
clean id 0, for both copies; a block that two documents share is cut by
it).

``quantize`` (``"fp8"``, ``"bf16"``) rounds both operands of every matrix
product, attention's included and the router's excepted, to that type: the
stand-ins of a lower precision that the family's control reads.

Leaves are named ``embed``, ``head``, ``final_norm/scale`` and, in
``layer_<i>/``: ``input_norm/scale``, ``attn/{wq,wk,wv,wo}``,
``attn/{q_norm,k_norm}/scale``, ``post_attention_norm/scale``,
``moe/{router,gate,up,down}``.
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


def noise(train_seed: int, step: int, x0, model: Dict):
    """``(xt, masked, t)`` of the step ``step`` of a run whose
    ``train.seed`` is ``train_seed``: the recipe of the module docstring."""
    block = model["block_length"]
    r = jax.random.fold_in(
        jax.random.split(jax.random.PRNGKey(train_seed))[1], step)
    s, length = x0.shape
    t = model["t_min"] + (1.0 - model["t_min"]) * jax.random.uniform(
        jax.random.fold_in(r, 0), (s, length // block), jnp.float32)
    t = jnp.repeat(t, block, axis=1)
    masked = jax.random.uniform(jax.random.fold_in(r, 1), (s, length),
                                jnp.float32) < t
    return jnp.where(masked, model["mask_id"], x0), masked, t


def mask(length: int, block: int) -> np.ndarray:
    """The ``2L x 2L`` mask from its definition: ``[p, r]`` is whether
    query ``p`` sees key ``r``, documents aside."""
    p = np.arange(2 * length)
    noisy, blk = p < length, (p % length) // block
    n_p, n_r = noisy[:, None], noisy[None, :]
    b_p, b_r = blk[:, None], blk[None, :]
    return ((n_p == n_r) & (b_p == b_r)
            | n_p & ~n_r & (b_r < b_p)
            | ~n_p & ~n_r & (b_r <= b_p))


def rotate(x, theta: float, positions):
    """``x``: (S, P, H, D); rotate-half over the whole head by the
    position ids ``positions`` (P,)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    cos = jnp.tile(jnp.cos(ang), 2)[None, :, None, :]
    sin = jnp.tile(jnp.sin(ang), 2)[None, :, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + half * sin


def attention_block(p: Dict, h, doc, ok, model: Dict, quantize: str):
    """``h + Attn(input_norm(h))`` over the ``P = 2L`` positions: ``doc``
    ``(S, P)`` the documents of both copies, ``ok`` the dense ``(P, P)``
    mask."""
    s, n, _ = h.shape
    heads, kv, hd = model["heads"], model["kv_heads"], model["head_dim"]
    eps = model["rms_norm_eps"]
    a = sub(p, "attn/")
    q, k, v = mm(rms(h, p["input_norm/scale"], eps),
                 [a["wq"], a["wk"], a["wv"]], quantize)
    positions = jnp.tile(jnp.arange(n // 2), 2)
    q = rotate(rms(q.reshape(s, n, heads, hd), a["q_norm/scale"], eps),
               model["rope_theta"], positions)
    k = rotate(rms(k.reshape(s, n, kv, hd), a["k_norm/scale"], eps),
               model["rope_theta"], positions)
    bq = min(QUERY_BLOCK, n)
    blocks = n // bq

    def head(qkv):
        """One key/value head and the query heads it serves: ``qg`` (S, P,
        G, D), ``kg`` and ``vg`` (S, P, D); a block of queries at a time
        under its rows of the mask."""
        qg, kg, vg = qkv

        @jax.checkpoint
        def rows(x):
            qb, ok_b, doc_b = x        # (S, bq, G, D), (bq, P), (S, bq)
            sc = product("sqgd,skd->sgqk", qb, kg, quantize) / math.sqrt(hd)
            see = ok_b[None] & (doc_b[:, :, None] == doc[:, None, :])
            pr = jax.nn.softmax(jnp.where(see[:, None], sc, -jnp.inf), -1)
            return product("sgqk,skd->sqgd", pr, vg, quantize)

        out = jax.lax.map(rows, (
            jnp.moveaxis(qg.reshape(s, blocks, bq, *qg.shape[2:]), 1, 0),
            ok.reshape(blocks, bq, n),
            jnp.moveaxis(doc.reshape(s, blocks, bq), 1, 0)))
        return jnp.moveaxis(out, 0, 1).reshape(qg.shape)

    out = jax.lax.map(head, (
        jnp.moveaxis(q.reshape(s, n, kv, heads // kv, hd), 2, 0),
        jnp.moveaxis(k, 2, 0),
        jnp.moveaxis(v.reshape(s, n, kv, hd), 2, 0)))     # (KV, S, P, G, D)
    out = jnp.moveaxis(out, 0, 2).reshape(s, n, heads * hd)
    return h + mm(out, [a["wo"]], quantize)[0]


def experts(p: Dict, x, model: Dict, quantize: str):
    """The partial result of the experts held here: every one of them on
    every position, weighted by the router's weight for it or 0."""
    first, count = model["experts_first"], model["experts_held"]
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    s = jax.nn.softmax(product("nd,de->ne", x, p["router"], terms=HIGHEST),
                       axis=-1)
    picked, chosen = jax.lax.top_k(s, model["top_k"])
    w = picked / jnp.sum(picked, -1, keepdims=True)
    # (N, total): a position's weight for each expert, 0 where not chosen
    dense = jnp.sum(jax.nn.one_hot(chosen, model["experts_total"])
                    * w[..., None], axis=1)
    both = product("nd,edf->enf", x,
                   jnp.concatenate([p["gate"], p["up"]], axis=2), quantize)
    g, u = jnp.split(both, 2, axis=2)
    each = product("enf,efd->end", jax.nn.silu(g) * u, p["down"], quantize)
    return jnp.sum(each * dense[:, first:first + count].T[:, :, None],
                   axis=0).reshape(shape)


def moe_block(p: Dict, h, model: Dict, quantize: str):
    """``h + MoE(post_attention_norm(h))``."""
    x = rms(h, p["post_attention_norm/scale"], model["rms_norm_eps"])
    return h + experts(sub(p, "moe/"), x, model, quantize)


def head_loss(scale, head, h, x0, masked, t, eps: float, quantize: str):
    """The loss from the hidden states of the noisy copy ``h`` ``(S, L,
    d)``."""
    logits, = mm(rms(h, scale, eps), [head], quantize)
    picked = jnp.take_along_axis(logits, x0[..., None], -1)[..., 0]
    nll = jax.nn.logsumexp(logits, -1) - picked
    return jnp.sum(masked.astype(jnp.float32) / t * nll) / x0.size


def documents(x0):
    """The documents of both copies, from the clean ids."""
    return jnp.tile(jnp.cumsum((x0 == 0).astype(jnp.int32), axis=1), (1, 2))


def of_layer(tree: Dict, i: int) -> Dict:
    return sub(tree, f"layer_{i}/")


def halves(p: Dict) -> Tuple[Dict, Dict]:
    """A layer's leaves as its attention block's and its expert block's."""
    first = ("input_norm/", "attn/")
    return ({k: v for k, v in p.items() if k.startswith(first)},
            {k: v for k, v in p.items() if not k.startswith(first)})


def forward_loss(params: Dict, xt, x0, masked, t, model: Dict,
                 quantize: str = "none"):
    """The whole forward pass and loss in one piece (tests; the steps
    below go block by block)."""
    length = x0.shape[1]
    doc = documents(x0)
    ok = jnp.asarray(mask(length, model["block_length"]))
    h = params["embed"][jnp.concatenate([xt, x0], axis=1)]
    for i in range(model["layers"]):
        attn, moe = halves(of_layer(params, i))
        h = attention_block(attn, h, doc, ok, model, quantize)
        h = moe_block(moe, h, model, quantize)
    return head_loss(params["final_norm/scale"], params["head"],
                     h[:, :length], x0, masked, t, model["rms_norm_eps"],
                     quantize)


# ------------------------------------------------------------------ a step
class Programs:
    """The jitted pieces of a step: the attention block and the expert
    block (one program each for all layers), the head with the loss; each
    block forward, and backward as its ``jax.vjp`` on the way back (its
    forward computed again there)."""

    def __init__(self, model: Dict, quantize: str):
        self.model = model

        def pair(f):
            """``f(p, *rest, h) -> h'`` jitted, and its pull-back ``(p,
            *rest, h, dh) -> (dp, dh)``."""
            def back(p, *rest_h_dh):
                *rest, h, dh = rest_h_dh
                return jax.vjp(lambda p_, h_: f(p_, *rest, h_), p, h)[1](dh)

            return jax.jit(f), jax.jit(back)

        self.attention = pair(lambda p, doc, ok, h: attention_block(
            p, h, doc, ok, model, quantize))
        self.moe = pair(lambda p, h: moe_block(p, h, model, quantize))
        self.head = jax.jit(jax.value_and_grad(
            partial(head_loss, eps=model["rms_norm_eps"], quantize=quantize),
            argnums=(0, 1, 2)))
        self.embed_back = jax.jit(
            lambda table, ids, dh: jnp.zeros_like(table).at[ids].add(dh))
        self.square = jax.jit(lambda g: jnp.sum(jnp.square(g)))

    def gradients(self, params: Dict, xt, x0, masked, t
                  ) -> Tuple[float, Dict]:
        model = self.model
        length = x0.shape[1]
        doc = documents(x0)
        ok = jnp.asarray(mask(length, model["block_length"]))
        ids = jnp.concatenate([xt, x0], axis=1)
        h = params["embed"][ids]
        inputs = []                    # each block's input, in order
        for i in range(model["layers"]):
            attn, moe = halves(of_layer(params, i))
            inputs.append(h)
            h = self.attention[0](attn, doc, ok, h)
            inputs.append(h)
            h = self.moe[0](moe, h)
        loss, (d_scale, d_head, dh) = self.head(
            params["final_norm/scale"], params["head"], h[:, :length], x0,
            masked, t)
        # the clean copy's final states reach no logit
        dh = jnp.concatenate([dh, jnp.zeros_like(dh)], axis=1)
        grads = {"final_norm/scale": d_scale, "head": d_head}
        for i in reversed(range(model["layers"])):
            attn, moe = halves(of_layer(params, i))
            d_moe, dh = self.moe[1](moe, inputs.pop(), dh)
            d_attn, dh = self.attention[1](attn, doc, ok, inputs.pop(), dh)
            grads.update({f"layer_{i}/{k}": v
                          for k, v in {**d_attn, **d_moe}.items()})
        grads["embed"] = self.embed_back(params["embed"], ids, dh)
        return float(loss), grads

    def norm(self, grads: Dict) -> float:
        return math.sqrt(sum(float(self.square(g)) for g in grads.values()))


def follow(params: Dict, inputs: np.ndarray, model: Dict, job: Dict,
           train_seed: int, quantize: str = "none", start_step: int = 0):
    """Train from ``params`` with zero moments over the steps' clean ids
    ``inputs`` (``(steps, S, L)``), each step noised by the recipe at its
    own step number. Returns ``(params, mu, nu, losses, gnorms,
    masked)``, the last the share of ids each step masked."""
    programs = Programs(model, quantize)
    mu = {k: jnp.zeros_like(v) for k, v in params.items()}
    nu = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses: List[float] = []
    gnorms: List[float] = []
    shares: List[float] = []
    for i in range(len(inputs)):
        x0 = jnp.asarray(inputs[i], jnp.int32)
        xt, masked, t = noise(train_seed, start_step + i, x0, model)
        loss, grads = programs.gradients(params, xt, x0, masked, t)
        gnorm = programs.norm(grads)
        clip = min(1.0, job["clip_norm"] / gnorm) if job["clip_norm"] \
            else 1.0
        step = start_step + i + 1
        for k in params:
            params[k], mu[k], nu[k] = adamw_leaf(
                params[k], grads.pop(k), mu[k], nu[k], clip,
                learning_rate(job, start_step + i),
                job["b1"], job["b2"], job["eps"],
                job["weight_decay"] if params[k].ndim >= 2 else 0.0, step)
        losses.append(loss)
        gnorms.append(gnorm)
        shares.append(float(jnp.mean(masked)))
    return params, mu, nu, losses, gnorms, shares
