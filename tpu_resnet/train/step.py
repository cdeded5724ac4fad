"""Compiled train/eval steps over a device mesh.

This module is where the reference's entire distribution machinery —
``SyncReplicasOptimizer`` gradient accumulation over gRPC parameter servers
(reference resnet_model.py:102-113) and Horovod's NCCL allreduce
(resnet_model.py:115-117) — collapses into *one* jitted SPMD function: the
batch is sharded over the mesh's ``data`` axis, parameters are replicated,
and XLA inserts the ICI all-reduces that the sharding math requires. The
same compiled function is the single-device program when the mesh has one
device (reference serial branch, resnet_cifar_train.py:313-326).

Step semantics (reference file:line):
- loss = softmax cross-entropy on one-hot labels (resnet_model.py:76-80)
  + weight_decay * Σ l2_loss(w) over trainable variables
  (resnet_model.py:85-86; tf.nn.l2_loss = sum(w²)/2).
- BN statistics update inside the step — the analog of running update_ops as
  control deps of minimize (resnet_model.py:120-122). Under global-batch jit
  semantics BN moments are computed over the *global* batch (synced BN);
  the reference's per-replica BN is the shard_map variant.
- LR is a pure function of step (schedule.py) evaluated inside the step;
  exposed in metrics like the reference's learning_rate summary
  (resnet_model.py:92-93).
- Train-precision metric from argmax(logits) == label
  (resnet_cifar_train.py:271-273).
- Augmentation runs on-device at the top of the step with a per-step RNG
  derived from fold_in(base, step) — deterministic on resume.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from flax.traverse_util import flatten_dict
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_resnet import models
from tpu_resnet.train.state import TrainState, build_optimizer


def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray,
                 num_classes: int, label_smoothing: float = 0.0) -> jnp.ndarray:
    """Mean softmax cross-entropy on integer labels (one-hot inside, per
    reference resnet_model.py:76-80 / cifar_input.py:104-108)."""
    onehot = jax.nn.one_hot(labels, num_classes, dtype=logits.dtype)
    if label_smoothing:
        onehot = (onehot * (1 - label_smoothing)
                  + label_smoothing / num_classes)
    return optax.softmax_cross_entropy(logits, onehot).mean()


def token_xent(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean next-token cross-entropy: ``logits`` ``(B, S, V)`` float32,
    ``labels`` ``(B, S)`` ids. The label's logit is picked out by
    ``take_along_axis``; no one-hot of the vocabulary is built."""
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def l2_weight_penalty(params, include_bn: bool) -> jnp.ndarray:
    """weight_decay · Σ sum(w²)/2 over trainable vars
    (reference resnet_model.py:85-86). ``include_bn=False`` drops the 1-D
    scale/bias leaves (the modern variant)."""
    total = jnp.float32(0.0)
    for leaf in jax.tree_util.tree_leaves(params):
        if not include_bn and leaf.ndim <= 1:
            continue
        total += jnp.sum(jnp.square(leaf.astype(jnp.float32))) / 2
    return total


def check_step_config(cfg, data_axis: int) -> None:
    """Config-space legality gate for a compiled step, shared by the
    train loop and the static config-matrix verifier
    (tpu_resnet/analysis/configmatrix.py) so both enforce the SAME rules:
    a combination the verifier certifies is exactly one the loop accepts.

    The fused Pallas kernels take batch moments over the batch the kernel
    sees; their supported multi-chip dispatch is shard_map-explicit (each
    replica's Pallas call gets its concrete local shard — per-replica BN,
    the reference's semantics, resnet_model.py:120-122). Global-batch
    sync-BN under auto-sharded jit is not implemented for the fused
    custom call: fail loudly rather than ship unclear moment semantics
    (VERDICT r4 item 5)."""
    from tpu_resnet.parallel.partition import check_partition_mode

    per_replica_bn = (not cfg.model.sync_bn) and data_axis > 1
    partition = check_partition_mode(cfg.mesh.partition)
    fam = models.family(cfg)
    kind = models.data_kind(cfg)
    if kind != fam.inputs:
        raise ValueError(
            f"model {cfg.model.name!r} takes {fam.inputs} and dataset "
            f"{cfg.data.dataset!r} holds {kind}: "
            + "; ".join(f"a dataset of {k} feeds model {models.feeds(k)}"
                        for k in (kind, fam.inputs)))
    if cfg.optim.grad_clip_norm and cfg.optim.optimizer != "adamw":
        raise ValueError("optim.grad_clip_norm is applied before adamw "
                         "only; sgd and momentum take none")
    bad = []
    if fam.inputs == "tokens":
        refused = [
            ("model.sync_bn=false on a multi-chip data axis (the "
             "shard_map step would route each shard's tokens apart)",
             per_replica_bn),
            ("optim.label_smoothing", cfg.optim.label_smoothing != 0.0),
            ("optim.use_pallas_xent=on (the kernel one-hots class "
             "labels; the token loss never consults it or its probe)",
             str(cfg.optim.use_pallas_xent).lower() in ("on", "true", "1",
                                                        "yes")),
            ("an optimizer other than adamw (the L2 term of sgd and "
             "momentum is not part of the token loss)",
             cfg.optim.optimizer != "adamw"),
        ]
        bad = [what for what, is_set in refused if is_set]
    bad += fam.refuses(cfg, data_axis)
    if bad:
        raise ValueError(f"model {cfg.model.name!r} does not train with: "
                         + "; ".join(bad))
    if partition == "zero1" and per_replica_bn:
        raise ValueError(
            "mesh.partition=zero1 on a multi-chip data axis requires "
            "model.sync_bn=true: per-replica BN runs the step inside "
            "shard_map, where the zero1 sharding annotations "
            "(with_sharding_constraint over the mesh) cannot be applied "
            "— the auto-sharded jit path is the supported dispatch for "
            "cross-replica optimizer sharding (docs/PARALLELISM.md)")
    if cfg.model.fused_blocks and data_axis > 1 and not per_replica_bn:
        raise ValueError(
            "model.fused_blocks on a multi-chip data axis requires "
            "model.sync_bn=false (per-replica BN via shard_map — the "
            "reference's BN semantics); global-batch sync-BN is not "
            "implemented for the fused kernels")
    if (cfg.model.fused_epilogue != "off"
            and data_axis > 1 and not per_replica_bn):
        raise ValueError(
            "model.fused_epilogue on a multi-chip data axis requires "
            "model.sync_bn=false (per-replica BN via shard_map): the "
            "epilogue pallas_call cannot be auto-partitioned by the "
            "sharded jit — same dispatch rule as fused_blocks")


def make_train_step(model, optim_cfg, schedule, num_classes: int,
                    augment_fn: Optional[Callable] = None,
                    base_rng: Optional[jax.Array] = None,
                    mesh: Optional[Mesh] = None,
                    grad_axis: Optional[str] = None,
                    xent_probe_batch: int = 128,
                    partitioner=None):
    """Returns ``train_step(state, images, labels) -> (state, metrics)``.

    What a batch is, is the model's family's to say
    (``models.family_of(model).inputs``). Of ``tokens``: the inputs are
    ``(B, S)`` ids and the labels the next ids. The loss is
    ``token_xent`` on the batch as fed unless the family has an
    ``objective`` of its own, which is then asked, with the step's key,
    what the model is fed and how its logits score (``Family.objective``);
    no augmentation, no L2 term and no xent probe are part of that path,
    and the metrics also carry the ``tokens`` of the step (the ids of the
    batch). Whatever the kind, the metrics carry the family's
    ``counters``, each meaned over the layers that count it.

    ``images`` may be raw uint8 (augment_fn applied on device) or
    pre-processed floats (augment_fn=None).

    ``grad_axis`` selects the per-replica-BN SPMD style: when set, the step
    is meant to run inside ``shard_map`` over that mesh axis — BN moments
    come from the *local* batch shard (the reference's per-worker BN
    update_ops, resnet_model.py:120-122), and gradients / metrics / stored
    BN stats are explicitly ``pmean``-ed across the axis. When None (the
    default), the step runs under auto-sharded ``jit`` and BN moments are
    global-batch (synced BN); XLA inserts the gradient all-reduces.

    ``partitioner`` (parallel.StatePartitioner) owns the weight-update
    sharding: zero1 pins the optimizer step to the slot shards
    (parallel/zero.py); None or replicated traces the identical plain
    optax chain this function always inlined.
    """
    from tpu_resnet.parallel import zero

    fam = models.family_of(model)
    tokens = fam.inputs == "tokens"
    tx = build_optimizer(optim_cfg, schedule)
    apply_update = zero.make_update_fn(tx, partitioner)
    if base_rng is None:
        base_rng = jax.random.PRNGKey(0)

    # Fused Pallas xent dispatch (config.py use_pallas_xent, docs/PERF.md):
    # "auto" (default) runs the compile-time per-shape A/B once at
    # step-build time (host code, charged to the compile window) and
    # takes the measured winner — the BENCH_r04 0.901x regression class
    # auto-falls back to XLA; "on"/"off" force an arm. CPU and
    # label_smoothing always take the optax chain (program unchanged —
    # the config-matrix goldens are defined over that trace). Mesh
    # dispatch lives in ops.make_pallas_xent.
    from tpu_resnet.ops import (ensure_xent_probe, is_tpu_backend,
                                make_pallas_xent)
    mode = str(optim_cfg.use_pallas_xent).lower()
    mode = {"true": "on", "1": "on", "yes": "on",
            "false": "off", "0": "off", "no": "off"}.get(mode, mode)
    if mode not in ("on", "off", "auto"):
        # Same fail-loud guard as model.fused_epilogue: a typo must not
        # silently mean "off" while the operator believes the A/B runs.
        raise ValueError(f"optim.use_pallas_xent must be auto|on|off, "
                         f"got {optim_cfg.use_pallas_xent!r}")
    use_pallas = (mode in ("on", "auto") and not tokens
                  and optim_cfg.label_smoothing == 0.0
                  and is_tpu_backend())
    if use_pallas and mode == "auto":
        use_pallas = ensure_xent_probe(xent_probe_batch,
                                       num_classes).use_pallas
    if use_pallas:
        _pallas_xent = make_pallas_xent(mesh if grad_axis is None else None)

    collections = ["batch_stats"] + (["counters"] if fam.counters else [])

    def train_step(state: TrainState, images, labels):
        rng = jax.random.fold_in(base_rng, state.step)
        if grad_axis is not None:
            # Distinct augmentation stream per shard — without this every
            # replica would replay the same crops/flips on its slot-j
            # example.
            rng = jax.random.fold_in(rng, jax.lax.axis_index(grad_axis))
        # The named scopes split a profiler capture by what the step is
        # doing (tools/profiling.py::reduce_capture); Flax's module scopes
        # nest under ``forward``, and JAX marks the backward pass
        # ``transpose(jvp(...))`` on the same path. They change HLO
        # metadata only: the printed jaxpr is the same.
        if augment_fn is not None:
            with jax.named_scope("augment"):
                images = augment_fn(rng, images)

        def loss_fn(params):
            fed, score = images, None
            with jax.named_scope("forward"):
                if fam.objective is not None:
                    fed, score = fam.objective(model, rng, images, labels)
                logits, new_model_state = model.apply(
                    {"params": params, "batch_stats": state.batch_stats},
                    fed, train=True, mutable=collections)
            with jax.named_scope("loss"):
                if score is not None:
                    loss, scored = score(logits)
                    return loss, (scored, new_model_state)
                if tokens:
                    return token_xent(logits, labels), (logits,
                                                        new_model_state)
                if use_pallas:
                    xent = _pallas_xent(logits.astype(jnp.float32), labels)
                else:
                    xent = softmax_xent(logits.astype(jnp.float32), labels,
                                        num_classes,
                                        optim_cfg.label_smoothing)
                penalty = optim_cfg.weight_decay * l2_weight_penalty(
                    params, optim_cfg.weight_decay_on_bn)
                return xent + penalty, (logits, new_model_state)

        # ``out``: the logits or, where the family's objective scored
        # them, its metrics (``precision`` among them)
        (loss, (out, new_model_state)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        new_batch_stats = new_model_state["batch_stats"]
        scored = {} if fam.objective is None else dict(out)
        with jax.named_scope("metrics"):
            precision = scored.pop("precision") if scored else jnp.mean(
                (jnp.argmax(out, axis=-1) == labels).astype(jnp.float32))
        if grad_axis is not None:
            # Explicit ICI all-reduces (the shard_map analog of what XLA
            # emits on the jit path): average grads; average the EMA stats
            # so the stored state is one consistent replicated tree.
            with jax.named_scope("grad_exchange"):
                grads = jax.lax.pmean(grads, grad_axis)
                new_batch_stats = jax.lax.pmean(new_batch_stats, grad_axis)
                loss = jax.lax.pmean(loss, grad_axis)
                precision = jax.lax.pmean(precision, grad_axis)
        with jax.named_scope("optimizer"):
            new_params, new_opt_state = apply_update(grads, state.opt_state,
                                                     state.params)
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_batch_stats,
            opt_state=new_opt_state,
        )
        with jax.named_scope("metrics"):
            metrics = {
                "loss": loss,
                "precision": precision,
                "learning_rate": schedule(state.step),
                "grad_norm": optax.global_norm(grads),
            }
            counted = flatten_dict(new_model_state.get("counters", {}))
            for name in fam.counters:
                layers = [v for k, v in counted.items() if k[-1] == name]
                if layers:  # a model of dense layers routes nothing
                    metrics[name] = jnp.mean(jnp.stack(layers))
            if tokens:
                metrics["tokens"] = jnp.float32(labels.size)
            metrics.update(scored)
        return new_state, metrics

    return train_step


def make_eval_step(model, num_classes: int,
                   preprocess_fn: Optional[Callable] = None):
    """``eval_step(state, images, labels) -> (correct_count, loss_sum,
    valid_count)``; labels < 0 are padding (pipeline.eval_batches)."""

    def eval_step(state: TrainState, images, labels):
        if preprocess_fn is not None:
            images = preprocess_fn(images)
        logits = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            images, train=False)
        valid = labels >= 0
        safe_labels = jnp.maximum(labels, 0)
        onehot = jax.nn.one_hot(safe_labels, num_classes,
                                dtype=logits.dtype)
        per_ex = optax.softmax_cross_entropy(logits, onehot)
        correct = (jnp.argmax(logits, axis=-1) == safe_labels) & valid
        return (jnp.sum(correct.astype(jnp.int32)),
                jnp.sum(per_ex * valid.astype(per_ex.dtype)),
                jnp.sum(valid.astype(jnp.int32)))

    return eval_step


def per_replica_shard_map(fn, mesh: Mesh, in_specs):
    """Wrap a step/chunk built with ``grad_axis='data'`` in shard_map.
    Outputs (state, metrics) are replicated by construction — every shard
    applies the same pmean-ed grads/stats — hence ``out_specs=P()`` with
    VMA checking off (the explicit pmeans are the replication proof)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=(P(), P()), check_vma=False)


def shard_step(step_fn, mesh: Mesh, donate_state: bool = True,
               per_replica_bn: bool = False, state_sharding=None):
    """Compile a step for the mesh: batch split over 'data', state laid
    out per the partitioner. XLA emits the gradient/BN all-reduces over
    ICI — the entire replacement for ps push/pull + Horovod fusion
    threads.

    ``state_sharding`` is the TrainState-shaped sharding tree from
    ``StatePartitioner.state_shardings`` (None = fully replicated,
    today's default — every caller without an opinion keeps the exact
    historical program). zero1 callers pass their sharded tree so the
    optimizer-slot arguments compile to per-shard buffers.

    ``per_replica_bn=True`` compiles the ``shard_map`` variant: the step
    body (built with ``grad_axis='data'``) sees only its local batch shard,
    so BN moments are per-replica like the reference's, and the body's
    explicit ``pmean``s carry the cross-replica reductions."""
    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P("data"))
    if per_replica_bn:
        step_fn = per_replica_shard_map(
            step_fn, mesh, in_specs=(P(), P("data"), P("data")))
    state_sh = state_sharding if state_sharding is not None else repl
    # out_shardings pins the state to the layout it went in with (see
    # device_data.staged_chunk_jit): the next dispatch takes it back.
    return jax.jit(
        step_fn,
        in_shardings=(state_sh, data, data),
        out_shardings=(state_sh, repl),
        donate_argnums=(0,) if donate_state else (),
    )
