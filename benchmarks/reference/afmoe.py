"""The plain reference of the model family ``afmoe``: a sparse-expert
transformer with windowed and full attention mixed, as one chip of an
expert-parallel group holds it, and its training step, in float32
``jax.numpy``. It imports nothing of the program. Every matrix product is
``product``: float32 operands multiplied as the bf16 products of their
parts (see ``TERMS`` and ``_written_out`` for which, and why they are
written out); the caller's ``jax.default_matmul_precision("highest")``
covers whatever else multiplies.

Written out here: the forward pass, the next-token loss, the gradients
(layer by layer: each layer's forward is followed by its ``jax.vjp`` on the
way back, so that one layer's intermediates are alive at a time and one
small program is compiled for each kind of layer, not one for the whole
step), the global-norm clip, AdamW with decoupled decay on matrices, and
the routers' ``expert_bias`` update.

The equations, from the published ``afmoe`` model code (``model`` is the
configuration's ``model`` group; every norm is RMSNorm with a weight):

- ``h = E[ids] * sqrt(d)``; logits ``= norm(h_L) W_head``.
- ``h += post_attn_norm(Attn(input_norm(h)))``;
  ``h += post_mlp_norm(F(pre_mlp_norm(h)))``; ``F`` is a SwiGLU MLP in a
  dense layer and the expert layer otherwise.
- ``Attn``: ``q, k, v, g = x Wq, x Wk, x Wv, x Wg``; per head ``q =
  norm(q)``, ``k = norm(k)``; rotary embedding (rotate-half over the whole
  head) on sliding layers only; softmax of ``q k^T / sqrt(head_dim)`` over
  keys ``j <= i`` in the same document and, sliding, ``i - j < window``;
  ``(softmax V) * sigmoid(g)`` times ``Wo``. A document begins at each id
  0.
- expert layer: ``s = sigmoid(x Wr)``; chosen = top-k of ``s + b``; ``w =
  s[chosen] / (sum s[chosen] + 1e-20) * route_scale``; ``F(x) = Shared(x)
  + sum over the chosen experts THAT ARE HELD HERE of w_e Expert_e(x)``.
  Here every held expert is applied to every token and weighted by ``w_e``
  or 0: no dispatch to go wrong. Attention is computed one key/value head
  at a time over the whole sequence under the whole mask. After the step ``b += c - mean(c)``, ``c
  = coeff * sign(mean(n) - n)``, ``n`` the step's assignments per expert.

``quantize`` (``"fp8"``, ``"bf16"``) rounds both operands of every matrix
product, attention's included and the router's excepted, to that type: the
stand-ins of a lower precision that the family's control reads.

Leaves are named ``embed``, ``head``, ``final_norm/scale`` and, in
``layer_<i>/``: ``input_norm/scale``, ``attn/{wq,wk,wv,wg,wo}``,
``attn/{q_norm,k_norm}/scale``, ``post_attn_norm/scale``,
``pre_mlp_norm/scale``, ``mlp/{gate,up,down}`` or ``moe/{router,gate,up,
down}`` with ``moe/shared/{gate,up,down}``, ``post_mlp_norm/scale``; the
biases ``layer_<i>/moe/expert_bias``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_ROUND = {"none": None, "bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}
_BF16 = jnp.bfloat16
# Which products of the operands' bf16 parts make a float32 product,
# smallest first: (part of a, part of b). HIGHEST, three parts an operand
# (all 24 bits of its mantissa), is what ``Precision.HIGHEST`` computes on a
# TPU; HIGH, two parts (16 bits), is ``Precision.HIGH``: a relative error of
# 2^-16 a product, 128 x finer than the bf16 the configuration states, at
# half the products to compile and to run. A run on the chip takes HIGH
# for everything but the routers' products, because its time limit does not
# hold HIGHEST (PERF.md section 5); a router's scores decide which experts
# are chosen, and a near-tie turns on the last bits. The CPU tests set
# ``TERMS = HIGHEST``.
HIGHEST = ((2, 0), (0, 2), (1, 1), (1, 0), (0, 1), (0, 0))
HIGH = ((1, 0), (0, 1), (0, 0))
TERMS = HIGH


def rounded(x, quantize: str):
    dtype = _ROUND[quantize]
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def _written_out(spec: str, a, b, terms):
    """A float32 product as the bf16 products of the operands' parts,
    summed in float32. Each is asked for at the default precision, which
    on a TPU is one bf16 pass with float32 accumulation, exact for these
    operands, and on a CPU plain float32. Written out because the TPU's
    compiler takes 8 s for every product asked for at ``highest`` (a
    reference of a hundred of them compiled for 237 s; my chip run, PR 30)
    and a tenth of a second for each of these."""
    def parts(x):
        """``x`` as numbers that add up to it, each exact in bf16 (8
        bits of the mantissa apiece), kept as float32."""
        out = []
        for _ in range(1 + max(max(t) for t in terms)):
            out.append(x.astype(_BF16).astype(jnp.float32))
            x = x - out[-1]
        return out

    a, b = parts(a), parts(b)
    return sum(jnp.einsum(spec, a[i], b[j],
                          precision=jax.lax.Precision.DEFAULT)
               for i, j in terms)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def exact(spec: str, terms, a, b):
    """``einsum(spec, a, b)`` in float32, forward and backward."""
    return _written_out(spec, a, b, terms)


def _exact_fwd(spec, terms, a, b):
    return _written_out(spec, a, b, terms), (a, b)


def _exact_bwd(spec, terms, kept, g):
    a, b = kept
    (x, y), z = spec.split("->")[0].split(","), spec.split("->")[1]
    return (_written_out(f"{z},{y}->{x}", g, b, terms),
            _written_out(f"{x},{z}->{y}", a, g, terms))


exact.defvjp(_exact_fwd, _exact_bwd)


def product(spec: str, a, b, quantize: str = "none", terms=None):
    """The reference's every matrix product. A stand-in of a lower
    precision multiplies the rounded operands once (they are exact in
    bf16)."""
    if quantize == "none":
        return exact(spec, terms or TERMS, a, b)
    return jnp.einsum(spec, rounded(a, quantize), rounded(b, quantize),
                      precision=jax.lax.Precision.DEFAULT)


def mm(x, ws, quantize: str):
    """``x @ w`` over the last axis of ``x`` for each ``w`` of ``ws``, as
    one product with the matrices side by side."""
    lead = "bs"[:x.ndim - 1]
    out = product(f"{lead}k,kn->{lead}n", x, jnp.concatenate(ws, axis=1),
                  quantize)
    return jnp.split(out, np.cumsum([w.shape[1] for w in ws])[:-1], axis=-1)


def rms(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotate(x, theta: float, on):
    """``x``: (B, S, H, D); rotate-half over the whole head. ``on`` is 1
    on a sliding layer and 0 on a full one, which carries no position (an
    angle of 0 turns nothing)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = on * jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.tile(jnp.cos(ang), 2)[None, :, None, :]
    sin = jnp.tile(jnp.sin(ang), 2)[None, :, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + half * sin


def sub(p: Dict, pre: str) -> Dict:
    return {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}


def attention_block(p: Dict, h, doc, window, model: Dict, quantize: str):
    """``h + post_attn_norm(Attn(input_norm(h)))``. ``window`` is the
    layer's window, or 0 for a full layer: a number of the run, not of the
    program, so that one compiled program serves both kinds."""
    b, s, _ = h.shape
    heads, kv, hd = model["heads"], model["kv_heads"], model["head_dim"]
    eps = model["rms_norm_eps"]
    x = rms(h, p["input_norm/scale"], eps)
    a = sub(p, "attn/")
    q, k, v, gate = mm(x, [a["wq"], a["wk"], a["wv"], a["wg"]], quantize)
    q = rms(q.reshape(b, s, heads, hd), a["q_norm/scale"], eps)
    k = rms(k.reshape(b, s, kv, hd), a["k_norm/scale"], eps)
    sliding = (window > 0).astype(jnp.float32)
    q = rotate(q, model["rope_theta"], sliding)
    k = rotate(k, model["rope_theta"], sliding)
    pos = jnp.arange(s)
    ok = (pos[None, :] <= pos[:, None]) & (
        (window == 0) | (pos[:, None] - pos[None, :] < window))
    ok = ok[None] & (doc[:, :, None] == doc[:, None, :])      # (B, S, S)

    @jax.checkpoint
    def group(qkv):
        """One key/value head and the query heads it serves, over the
        whole sequence under the whole mask: ``qg`` (B, S, G, D), ``kg``
        and ``vg`` (B, S, D)."""
        qg, kg, vg = qkv
        sc = product("bqgd,bkd->bgqk", qg, kg, quantize) / math.sqrt(hd)
        pr = jax.nn.softmax(jnp.where(ok[:, None], sc, -jnp.inf), axis=-1)
        return product("bgqk,bkd->bqgd", pr, vg, quantize)

    out = jax.lax.map(group, (
        jnp.moveaxis(q.reshape(b, s, kv, heads // kv, hd), 2, 0),
        jnp.moveaxis(k, 2, 0),
        jnp.moveaxis(v.reshape(b, s, kv, hd), 2, 0)))     # (KV, B, S, G, D)
    out = jnp.moveaxis(out, 0, 2).reshape(b, s, heads * hd)
    out, = mm(out * jax.nn.sigmoid(gate), [a["wo"]], quantize)
    return h + rms(out, p["post_attn_norm/scale"], eps)


def swiglu(gate, up, down, x, quantize: str):
    g, u = mm(x, [gate, up], quantize)
    return mm(jax.nn.silu(g) * u, [down], quantize)[0]


def experts(p: Dict, bias, x, model: Dict, quantize: str):
    """``(F(x), n)``: the partial result of the experts held here plus the
    shared one, and the assignments per expert over all of them."""
    first, count = model["experts_first"], model["experts_held"]
    total, k = model["experts_total"], model["top_k"]
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    s = jax.nn.sigmoid(product("nd,de->ne", x, p["router"], terms=HIGHEST))
    _, chosen = jax.lax.top_k(s + bias, k)
    picked = jnp.take_along_axis(s, chosen, -1)
    w = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) \
        * model["route_scale"]
    # (N, total): a token's weight for each expert, 0 where not chosen
    dense = jnp.sum(jax.nn.one_hot(chosen, total) * w[..., None], axis=1)
    out = swiglu(p["shared/gate"], p["shared/up"], p["shared/down"], x,
                 quantize) if model["shared"] else jnp.zeros_like(x)
    # every held expert on every token, then weighted
    both = product("nd,edf->enf", x,
                   jnp.concatenate([p["gate"], p["up"]], axis=2), quantize)
    g, u = jnp.split(both, 2, axis=2)
    each = product("enf,efd->end", jax.nn.silu(g) * u, p["down"], quantize)
    out = out + jnp.sum(
        each * dense[:, first:first + count].T[:, :, None], axis=0)
    n = jnp.sum(jax.nn.one_hot(chosen, total), axis=(0, 1))
    return out.reshape(shape), n


def mlp_block(p: Dict, bias, h, dense: bool, model: Dict, quantize: str):
    """``(h + post_mlp_norm(F(pre_mlp_norm(h))), n)``; ``n`` is None for
    a dense layer."""
    eps = model["rms_norm_eps"]
    x = rms(h, p["pre_mlp_norm/scale"], eps)
    if dense:
        f, n = swiglu(p["mlp/gate"], p["mlp/up"], p["mlp/down"], x,
                      quantize), None
    else:
        f, n = experts(sub(p, "moe/"), bias, x, model, quantize)
    return h + rms(f, p["post_mlp_norm/scale"], eps), n


def window_of(kind: str, model: Dict):
    return jnp.int32(model["window"] if kind.endswith("_sliding") else 0)


def layer(p: Dict, bias, h, doc, kind: str, model: Dict, quantize: str):
    """One layer: ``(h', n)``."""
    h = attention_block(p, h, doc, window_of(kind, model), model, quantize)
    return mlp_block(p, bias, h, kind.startswith("dense"), model, quantize)


def head_loss(scale, head, h, labels, eps: float, quantize: str):
    logits, = mm(rms(h, scale, eps), [head], quantize)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)


def embed(table, ids, model: Dict):
    doc = jnp.cumsum((ids == 0).astype(jnp.int32), axis=1)
    return table[ids] * math.sqrt(model["hidden"]), doc


def of_layer(tree: Dict, i: int) -> Dict:
    return sub(tree, f"layer_{i}/")


def halves(p: Dict) -> Tuple[Dict, Dict]:
    """A layer's leaves as its attention block's and its MLP block's."""
    first = ("input_norm/", "attn/", "post_attn_norm/")
    return ({k: v for k, v in p.items() if k.startswith(first)},
            {k: v for k, v in p.items() if not k.startswith(first)})


def forward_loss(params: Dict, biases: Dict, ids, labels, model: Dict,
                 quantize: str = "none"):
    """The whole forward pass and loss in one piece (tests; the steps
    below go block by block)."""
    h, doc = embed(params["embed"], ids, model)
    for i, kind in enumerate(model["layers"]):
        h, _ = layer(of_layer(params, i), biases.get(
            f"layer_{i}/moe/expert_bias"), h, doc, kind, model, quantize)
    return head_loss(params["final_norm/scale"], params["head"], h, labels,
                     model["rms_norm_eps"], quantize)


# ------------------------------------------------------------------ a step
class Programs:
    """The jitted pieces of a step: the attention block (one program for
    sliding and full layers alike), the dense and the expert block, the
    head with the loss, the embedding; each block forward, and backward as
    its ``jax.vjp`` on the way back (its forward computed again there)."""

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

        self.attention = pair(
            lambda p, doc, window, h: (attention_block(
                p, h, doc, window, model, quantize), None))
        self.mlp = {dense: pair(
            lambda p, bias, h, dense=dense: mlp_block(
                p, bias, h, dense, model, quantize)) for dense in (True,
                                                                   False)}
        self.embed = jax.jit(partial(embed, model=model))
        self.head = jax.jit(jax.value_and_grad(
            partial(head_loss, eps=model["rms_norm_eps"], quantize=quantize),
            argnums=(0, 1, 2)))
        self.embed_back = jax.jit(
            lambda table, ids, dh: jnp.zeros_like(table).at[ids].add(
                dh * math.sqrt(model["hidden"])))
        self.square = jax.jit(lambda g: jnp.sum(jnp.square(g)))

    def gradients(self, params: Dict, biases: Dict, ids, labels
                  ) -> Tuple[float, Dict, Dict]:
        """``(loss, gradients, n)``: ``n`` the assignments per expert of
        each expert layer."""
        model = self.model
        kinds = model["layers"]
        h, doc = self.embed(params["embed"], ids)
        inputs, counts = [], {}      # each block's input, in order
        for i, kind in enumerate(kinds):
            attn, mlp = halves(of_layer(params, i))
            key = f"layer_{i}/moe/expert_bias"
            inputs.append(h)
            h, _ = self.attention[0](attn, doc, window_of(kind, model), h)
            inputs.append(h)
            h, n = self.mlp[kind.startswith("dense")][0](
                mlp, biases.get(key), h)
            if n is not None:
                counts[key] = n
        loss, (d_scale, d_head, dh) = self.head(
            params["final_norm/scale"], params["head"], h, labels)
        grads = {"final_norm/scale": d_scale, "head": d_head}
        for i in reversed(range(len(kinds))):
            attn, mlp = halves(of_layer(params, i))
            d_mlp, dh = self.mlp[kinds[i].startswith("dense")][1](
                mlp, biases.get(f"layer_{i}/moe/expert_bias"),
                inputs.pop(), dh)
            d_attn, dh = self.attention[1](
                attn, doc, window_of(kinds[i], model), inputs.pop(), dh)
            grads.update({f"layer_{i}/{k}": v
                          for k, v in {**d_attn, **d_mlp}.items()})
        grads["embed"] = self.embed_back(params["embed"], ids, dh)
        return float(loss), grads, counts

    def norm(self, grads: Dict) -> float:
        return math.sqrt(sum(float(self.square(g)) for g in grads.values()))


@jax.jit
def adamw_leaf(p, g, mu, nu, clip, lr, b1, b2, eps, decay, t):
    """One leaf of AdamW as optax chains it behind the clip: the gradient
    scaled by ``clip``; bias-corrected moments; the decoupled decay added
    to the update (``decay`` is 0 for a leaf that is no matrix)."""
    g = g * clip
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * g * g
    update = (mu / (1 - b1 ** t)) / (jnp.sqrt(nu / (1 - b2 ** t)) + eps)
    return p - lr * (update + decay * p), mu, nu


def learning_rate(job: Dict, count: int) -> float:
    """The rate of the update after ``count`` earlier ones: ``job["lr"]``
    is a constant, or a linear warm-up from 0 and then a cosine to 0 over
    the run's steps."""
    lr = job["lr"]
    if not isinstance(lr, dict):
        return float(lr)
    if count < lr["warmup"]:
        return lr["base"] * count / max(lr["warmup"], 1)
    progress = min(1.0, (count - lr["warmup"])
                   / max(lr["total"] - lr["warmup"], 1))
    return lr["base"] * 0.5 * (1 + math.cos(math.pi * progress))


def bias_update(bias, n, coeff: float):
    c = coeff * jnp.sign(jnp.mean(n) - n)
    return bias + c - jnp.mean(c)


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
