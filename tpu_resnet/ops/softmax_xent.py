"""Fused softmax cross-entropy as a Pallas TPU kernel (forward + custom VJP).

Replaces the ``softmax → log → one_hot multiply → reduce`` chain
(reference resnet_model.py:76-80 via tf.losses.softmax_cross_entropy) with
one VMEM-resident pass per batch tile:

- forward: per-example ``logsumexp(logits) - logits[label]`` without
  materializing the [B, C] one-hot or probability tensors in HBM,
- backward: ``(softmax(logits) - onehot) * g`` recomputed in-kernel from the
  saved logits (no probs residual).

Integer labels ride along as a [B, 1] int32 VMEM block and the one-hot is
formed on the fly with ``broadcasted_iota`` — the TPU-native counterpart of
the reference's ``sparse_to_dense`` one-hot (cifar_input.py:104-108).

The public entry ``softmax_xent_mean`` pads C up to a lane multiple (128)
with -1e30 and B up to the batch tile, masking padded rows, so callers can
use any (B, C). ``interpret=True`` (auto on non-TPU backends) runs the same
kernel under the Pallas interpreter for CPU tests.

Block-spec retune (MFU campaign; BENCH_r04 measured this kernel at
0.901x of XLA at b128x1000 — a live regression): the forward previously
wrote the per-example loss broadcast across the FULL padded class dim
([B, C] fp32 to HBM — 512 KB of redundant writes per b128x1024 tile)
and the backward materialized the upstream cotangent broadcast to
[B, C] as a kernel INPUT. Both now move one 128-lane tile instead
([B, 128]), cutting that traffic C/128-fold at ImageNet head shapes,
and the batch tile is shape-aware (``default_batch_tile``). The kernel
still must EARN the hot path per shape: ``ensure_xent_probe`` runs the
compile-time A/B (tpu_resnet/ops/autotune.py) and the train step's
default ``optim.use_pallas_xent="auto"`` dispatches to whichever arm
measured faster — an unprofitable shape auto-falls back to XLA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128
_NEG = -1e30


def is_tpu_backend() -> bool:
    """True when the default backend is ``tpu``. Decides, everywhere in
    ops/, between compiling a kernel with Mosaic and running it under the
    Pallas interpreter — so it must never guess: a backend that fails to
    initialize raises here instead of quietly selecting the interpreter."""
    return jax.default_backend() == "tpu"


def _block_spec(shape):
    return pl.BlockSpec(shape, lambda i: (i, 0), memory_space=pltpu.VMEM)


def _fwd_kernel(logits_ref, labels_ref, loss_ref):
    x = logits_ref[:].astype(jnp.float32)          # [TB, C]
    lab = labels_ref[:]                            # [TB, 1] int32
    m = jnp.max(x, axis=1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(x - m), axis=1, keepdims=True)) + m
    classes = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    label_logit = jnp.sum(jnp.where(classes == lab, x, 0.0), axis=1,
                          keepdims=True)
    # Per-example loss broadcast across ONE 128-lane tile (not the full
    # padded class dim — the b128x1000 retune); caller slices [:, 0].
    loss_ref[:] = jnp.broadcast_to(lse - label_logit,
                                   (x.shape[0], _LANE))


def _bwd_kernel(logits_ref, labels_ref, g_ref, dx_ref):
    x = logits_ref[:].astype(jnp.float32)
    lab = labels_ref[:]
    g = g_ref[:][:, :1]                            # [TB, 1]
    m = jnp.max(x, axis=1, keepdims=True)
    ex = jnp.exp(x - m)
    probs = ex / jnp.sum(ex, axis=1, keepdims=True)
    classes = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    onehot = (classes == lab).astype(jnp.float32)
    dx_ref[:] = ((probs - onehot) * g).astype(dx_ref.dtype)


def _pallas_per_example(logits, labels, batch_tile, interpret):
    b, c = logits.shape
    grid = (b // batch_tile,)
    out = pl.pallas_call(
        _fwd_kernel,
        grid=grid,
        in_specs=[_block_spec((batch_tile, c)),
                  _block_spec((batch_tile, 1))],
        out_specs=_block_spec((batch_tile, _LANE)),
        out_shape=jax.ShapeDtypeStruct((b, _LANE), jnp.float32),
        interpret=interpret,
        name="softmax_xent_fwd",  # the kernel's name in a capture
    )(logits, labels)
    return out[:, 0]


def _pallas_bwd(logits, labels, g, batch_tile, interpret):
    b, c = logits.shape
    grid = (b // batch_tile,)
    # Upstream cotangent as ONE lane tile, not a materialized [B, C]
    # broadcast input (the other half of the b128x1000 retune).
    g2d = jnp.broadcast_to(g[:, None], (b, _LANE)).astype(jnp.float32)
    return pl.pallas_call(
        _bwd_kernel,
        grid=grid,
        in_specs=[_block_spec((batch_tile, c)),
                  _block_spec((batch_tile, 1)),
                  _block_spec((batch_tile, _LANE))],
        out_specs=_block_spec((batch_tile, c)),
        out_shape=jax.ShapeDtypeStruct((b, c), logits.dtype),
        interpret=interpret,
        name="softmax_xent_bwd",
    )(logits, labels, g2d)


_TILE_BUDGET = 4 * 2 ** 20


def default_batch_tile(b: int, c_padded: int,
                       budget: int = _TILE_BUDGET) -> int:
    """Shape-aware batch tile: the kernels hold ~2 fp32 copies of the
    [bt, C] logits block live in VMEM; keep that inside the plan budget
    while preferring a single grid step when the whole batch fits (it
    does at every ResNet head shape — b128x1024 is 1 MB)."""
    per_row = 2 * c_padded * 4
    return max(8, min(b, budget // max(per_row, 1)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _xent_padded(logits, labels, batch_tile, interpret):
    return _pallas_per_example(logits, labels, batch_tile, interpret)


def _xent_padded_fwd(logits, labels, batch_tile, interpret):
    loss = _pallas_per_example(logits, labels, batch_tile, interpret)
    return loss, (logits, labels)


def _xent_padded_bwd(batch_tile, interpret, residuals, g):
    logits, labels = residuals
    dx = _pallas_bwd(logits, labels, g, batch_tile, interpret)
    return dx, None


_xent_padded.defvjp(_xent_padded_fwd, _xent_padded_bwd)


def softmax_xent_per_example(logits: jnp.ndarray, labels: jnp.ndarray,
                             batch_tile: int = 128,
                             interpret: bool | None = None) -> jnp.ndarray:
    """Per-example softmax cross-entropy, differentiable w.r.t. logits.

    logits [B, C] (any float dtype), labels [B] int. Internally pads C to a
    multiple of 128 (with -1e30) and B to ``batch_tile`` (masked out).
    """
    if interpret is None:
        interpret = not is_tpu_backend()
    b, c = logits.shape
    c_pad = (-c) % _LANE
    b_tile = min(batch_tile, max(8, b),
                 default_batch_tile(b, c + c_pad))
    b_pad = (-b) % b_tile
    x = logits.astype(jnp.float32)
    if c_pad:
        x = jnp.pad(x, ((0, 0), (0, c_pad)), constant_values=_NEG)
    if b_pad:
        x = jnp.pad(x, ((0, b_pad), (0, 0)))
    lab = jnp.pad(labels.astype(jnp.int32), (0, b_pad)).reshape(-1, 1)
    loss = _xent_padded(x, lab, b_tile, interpret)
    return loss[:b]


def softmax_xent_mean(logits: jnp.ndarray, labels: jnp.ndarray,
                      interpret: bool | None = None) -> jnp.ndarray:
    """Mean loss over the batch — drop-in for the optax/one-hot chain in the
    train step (tpu_resnet/train/step.py softmax_xent)."""
    return jnp.mean(softmax_xent_per_example(logits, labels,
                                             interpret=interpret))


def softmax_xent_reference(logits: jnp.ndarray,
                           labels: jnp.ndarray) -> jnp.ndarray:
    """The XLA arm of the A/B: mean xent via the plain logsumexp/one-hot
    chain — the same math optax's softmax_cross_entropy lowers to (the
    train step's default path)."""
    x = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(x, axis=-1)
    label_logit = jnp.take_along_axis(
        x, labels.astype(jnp.int32)[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - label_logit)


OP_XENT = "xent"


def ensure_xent_probe(batch: int, classes: int, dtype=jnp.float32,
                      iters: int = 100, interpret: bool | None = None):
    """Compile-time A/B of the Pallas xent vs XLA at one (B, C) head
    shape — grad through the mean loss, the training hot path. Cached
    per shape (tpu_resnet/ops/autotune.py); the first call pays two
    small compiles, charged to the caller's setup/compile window.
    Returns the Decision."""
    from tpu_resnet.ops import autotune

    key = autotune.shape_key(batch, classes)
    existing = autotune.decision(OP_XENT, key)
    if existing is not None:
        return existing
    logits = jax.random.normal(jax.random.PRNGKey(classes),
                               (batch, classes), dtype)
    labels = jax.random.randint(jax.random.PRNGKey(1), (batch,), 0,
                                classes)
    return autotune.probe(
        OP_XENT, key,
        lambda x, lab: jax.grad(
            lambda a: softmax_xent_mean(a, lab, interpret=interpret)
        )(x),
        lambda x, lab: jax.grad(
            lambda a: softmax_xent_reference(a, lab))(x),
        (logits, labels), iters=iters)


def make_pallas_xent(mesh=None):
    """Mean-xent callable with the mesh dispatch encapsulated here, so the
    train step's opt-in costs one trace-time branch.

    Three reachable configurations (VERDICT round-1 item 6): single-device
    jit and explicit shard_map bodies call the kernel directly (it sees the
    full/local batch) — pass ``mesh=None``.  Under a multi-device
    auto-sharded jit, pass the mesh: the per-example kernel is itself
    shard_mapped over the batch ('data') axis — embarrassingly parallel, no
    collectives — and the mean taken outside.
    """
    if mesh is None or mesh.size <= 1:
        return softmax_xent_mean

    from jax.sharding import PartitionSpec as P

    def mesh_xent(logits, labels, _mesh=mesh):
        # check_vma off: pallas_call's out_shape carries no vma annotation;
        # the body is per-example (no collectives), so the output's
        # data-axis variance is by construction.
        per_ex = jax.shard_map(
            softmax_xent_per_example, mesh=_mesh,
            in_specs=(P("data"), P("data")), out_specs=P("data"),
            check_vma=False,
        )(logits, labels)
        return jnp.mean(per_ex)

    return mesh_xent
