"""Functional train state — replaces the reference's graph collections,
global_step variable and session hooks (reference resnet_model.py:45-67,
resnet_cifar_train.py:275-311) with one immutable pytree."""

from __future__ import annotations

from typing import Any

import flax.struct
import jax
import jax.numpy as jnp
import optax

from tpu_resnet.models import family_of


@flax.struct.dataclass
class TrainState:
    step: jnp.ndarray          # int32 scalar — the reference's global_step
    params: Any
    batch_stats: Any           # state no gradient reaches (fp32): BN
                               # moving mean/var, a router's expert_bias
    opt_state: Any

    @classmethod
    def create(cls, params, batch_stats, tx: optax.GradientTransformation):
        return cls(step=jnp.zeros((), jnp.int32), params=params,
                   batch_stats=batch_stats, opt_state=tx.init(params))


def build_optimizer(optim_cfg, schedule) -> optax.GradientTransformation:
    """sgd / momentum(0.9) per reference resnet_model.py:96-99.

    Weight decay is *not* here — the reference adds L2 to the loss over all
    trainable variables (resnet_model.py:85-86), which interacts with
    momentum differently than decoupled decay; the train step reproduces
    that. The LR schedule is folded into the transformation as a pure
    function of the optimizer step.
    """
    if optim_cfg.optimizer == "sgd":
        return optax.sgd(schedule)
    if optim_cfg.optimizer == "momentum":
        return optax.sgd(schedule, momentum=optim_cfg.momentum)
    if optim_cfg.optimizer == "adamw":
        # Decoupled decay on matrices only (norm weights and biases are
        # left alone); the global-norm clip comes first, as a trainer of
        # token models applies it.
        adamw = optax.adamw(
            schedule, b1=optim_cfg.adam_b1, b2=optim_cfg.adam_b2,
            eps=optim_cfg.adam_eps, weight_decay=optim_cfg.weight_decay,
            mask=lambda params: jax.tree_util.tree_map(
                lambda p: p.ndim >= 2, params))
        if optim_cfg.grad_clip_norm > 0:
            return optax.chain(
                optax.clip_by_global_norm(optim_cfg.grad_clip_norm), adamw)
        return adamw
    raise ValueError(f"unknown optimizer {optim_cfg.optimizer!r}")


def init_state(model, optim_cfg, schedule, rng: jax.Array,
               sample_batch: jnp.ndarray) -> TrainState:
    init = model.init
    if family_of(model).inputs == "tokens":
        # a token model: its forward pass as one program, not one small
        # program an operation of every layer
        from tpu_resnet.programs.registry import init_program

        init = init_program(model)
    variables = init(rng, sample_batch, train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    tx = build_optimizer(optim_cfg, schedule)
    return TrainState.create(params, batch_stats, tx)


def init_partitioned_state(model, optim_cfg, schedule, rng: jax.Array,
                           sample_batch: jnp.ndarray,
                           partitioner) -> TrainState:
    """Init + validate + place: the partitioner
    (``parallel.StatePartitioner``) owns where every leaf of the fresh
    state lives on the mesh — replicated mode reproduces the historical
    ``device_put(state, replicated(mesh))`` exactly; zero1 lands the
    optimizer slots directly in their shards. ``validate`` runs the full
    rule set against the real state tree FIRST, so an unshardable
    (model × mesh × partition) combination dies with per-leaf messages
    before any device transfer or compile is paid.

    Init runs on this process's first local device (``jax.devices()[0]``
    may be a non-addressable remote device on non-primary hosts)."""
    with jax.default_device(jax.local_devices()[0]):
        state = init_state(model, optim_cfg, schedule, rng, sample_batch)
    partitioner.validate(state)
    return partitioner.shard_state(state)


def param_count(params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))
