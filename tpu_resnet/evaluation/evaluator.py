"""Continuous checkpoint-polling evaluator — the reference's eval sidecar
(reference resnet_cifar_eval.py:85-143, resnet_imagenet_eval.py:169-230)
rebuilt: poll the train dir for a new checkpoint, restore, run the eval
split, write ``Precision`` / ``Best_Precision`` against the restored step,
sleep ``eval_interval_secs`` (60 s), repeat; ``eval_once`` evaluates the
latest checkpoint and exits (resnet_cifar_eval.py:140-143).

Deviations from the reference, on purpose:
- the full test split is evaluated (the reference samples 50×100 = 5000 of
  CIFAR's 10000 test images, resnet_cifar_eval.py:114-117);
- ``best_precision`` is persisted to ``best_precision.json`` so evaluator
  restarts don't reset the best curve (the reference loses it,
  README.md:33).
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_resnet import parallel
from tpu_resnet.config import RunConfig
from tpu_resnet.data import augment as aug_lib
from tpu_resnet.models import build_model, require_image_model
from tpu_resnet.train import schedule as sched_lib
from tpu_resnet.train.checkpoint import (CheckpointManager, latest_step_in,
                                         partitioned_template,
                                         restore_with_retry)
from tpu_resnet.train.metrics_io import MetricsWriter
from tpu_resnet.train.state import init_state
from tpu_resnet.train.step import make_eval_step

log = logging.getLogger("tpu_resnet")


def _mesh_eval_batch(cfg: RunConfig, mesh) -> int:
    """Round the configured eval batch (reference default 100,
    resnet_cifar_eval.py) up to a multiple of lcm(data axis, process
    count); padded slots are masked out, so the rounding never changes
    results."""
    import math

    n_data = mesh.shape["data"]
    unit = n_data * jax.process_count() // math.gcd(n_data,
                                                    jax.process_count())
    bs = cfg.train.eval_batch_size
    return ((bs + unit - 1) // unit) * unit


def run_eval_pass(cfg: RunConfig, state, mesh, eval_step_fn
                  ) -> Tuple[float, float, int]:
    """One full pass over the eval split → (precision, mean_loss, count).

    Multi-host capable (the reference's eval sidecar is single-node,
    resnet_imagenet_eval.py:83-165): each process streams its own stripe
    of the split as *local* batches, the global batch is assembled with
    ``make_array_from_process_local_data``, and the jitted eval step's
    globally-reduced ``valid`` count doubles as the lockstep termination
    signal — stripes may differ in length, so an exhausted process keeps
    feeding all-padding batches, and every process stops after the first
    round whose global valid count is zero. No cross-host side channel is
    needed; the mesh collective IS the coordination.
    """
    import tpu_resnet.data as data_lib
    from tpu_resnet.data import pipeline

    sharding = parallel.batch_sharding(mesh)
    global_batch = _mesh_eval_batch(cfg, mesh)
    pc = jax.process_count()
    local_batch = global_batch // pc
    size = cfg.data.resolved_image_size
    pad_img = np.zeros((local_batch, size, size, 3), np.uint8)
    pad_lab = np.full((local_batch,), -1, np.int32)

    it = iter(data_lib.eval_split_batches(cfg.data, local_batch))
    correct = loss_sum = count = 0
    try:
        while True:
            nxt = next(it, None)
            img, lab = nxt if nxt is not None else (pad_img, pad_lab)
            gi, gl = pipeline.to_global_arrays((img, lab), sharding)
            c, ls, n = eval_step_fn(state, gi, gl)
            n = int(n)  # global valid count — identical on every process
            if n == 0:
                break
            correct += int(c)
            loss_sum += float(ls)
            count += n
    finally:
        # data.engine=process hands back a HostDataEngine: release its
        # workers and unlink the shared-memory ring even when the pass
        # dies mid-split (it also auto-closes at clean exhaustion).
        close = getattr(it, "close", None)
        if close is not None:
            close()
    return correct / max(count, 1), loss_sum / max(count, 1), count


def build_eval_step(cfg: RunConfig, mesh, state_sharding=None,
                    registry=None, state_template=None):
    """``state_sharding`` (a TrainState-shaped sharding tree, e.g. from
    the partitioned restore template) lets the eval step accept the
    run's partition layout directly — a zero1 state's sharded optimizer
    slots ride through untouched (eval reads only params/batch_stats,
    which every partition mode keeps replicated). None = the historical
    fully-replicated signature.

    ``registry`` (programs.ProgramRegistry) routes the program through
    the persistent AOT executable cache when enabled — a restarted eval
    sidecar re-reaches its compiled pass without re-paying XLA.
    ``state_template`` (the abstract restore template) supplies the
    state avals the cache path lowers over; both default to the
    historical plain-jit behavior."""
    require_image_model(cfg, "evaluation")
    model = build_model(cfg)
    _, eval_pre = aug_lib.get_augment_fns(cfg.data.dataset)
    step = make_eval_step(model, cfg.data.num_classes, eval_pre)
    jitted = jax.jit(step, in_shardings=(
        state_sharding if state_sharding is not None
        else parallel.replicated(mesh), parallel.batch_sharding(mesh),
        parallel.batch_sharding(mesh)))
    if registry is not None and registry.cache_enabled \
            and state_template is not None:
        gb = _mesh_eval_batch(cfg, mesh)
        size = cfg.data.resolved_image_size
        bsh = parallel.batch_sharding(mesh)
        jitted, _ = registry.wrap(
            registry.key("eval", batch=gb), jitted,
            (state_template,
             jax.ShapeDtypeStruct((gb, size, size, 3), "uint8",
                                  sharding=bsh),
             jax.ShapeDtypeStruct((gb,), "int32", sharding=bsh)))
    return model, jitted


def _template_state(cfg: RunConfig, model, mesh):
    """CONCRETE replicated state (multihost smoke workers run an eval
    pass on it directly); the evaluator's restore path uses the
    allocation-free abstract ``checkpoint.partitioned_template``."""
    schedule = sched_lib.build_schedule(cfg.optim, cfg.train)
    size = cfg.data.resolved_image_size
    state = init_state(model, cfg.optim, schedule, jax.random.PRNGKey(0),
                       jnp.zeros((1, size, size, 3)))
    return jax.device_put(state, parallel.replicated(mesh))


# Back-compat alias: the restore-retry logic moved to
# train/checkpoint.py so the serve hot-reload path shares it verbatim.
_restore_with_retry = restore_with_retry


def evaluate(cfg: RunConfig, mesh=None, stop_event=None) -> Optional[float]:
    """Continuous (or once) evaluation; returns last precision.

    ``stop_event`` (a ``threading.Event``) ends the polling loop early —
    used by train_and_eval to stop the in-process sidecar when training
    finishes (the reference runs the sidecar as a separate container/node,
    start-resnet-imagenet-main.sh tail, and kills it with stop.sh)."""
    if mesh is None:
        mesh = parallel.create_mesh(cfg.mesh)
    # Abstract restore template in the run's partition layout
    # (checkpoint.partitioned_template): no device allocation for the
    # template, and a zero1 checkpoint restores straight into its
    # optimizer-slot shards. The eval step accepts that same layout.
    template = partitioned_template(cfg, mesh)
    from tpu_resnet import programs
    model, eval_step_fn = build_eval_step(
        cfg, mesh,
        state_sharding=jax.tree_util.tree_map(lambda s: s.sharding,
                                              template),
        registry=programs.ProgramRegistry(cfg, mesh, context="eval"),
        state_template=template)

    eval_dir = os.path.join(cfg.train.train_dir, "eval")
    metrics = MetricsWriter(eval_dir, enabled=parallel.is_primary())
    # Eval-pass spans on the sidecar's own timeline file (the trainer owns
    # <train_dir>/events.jsonl; the evaluator may be a separate process).
    # The train run's run_id is stamped on every span so trace-export can
    # correlate the sidecar lane with the trainer it is polling; a
    # sidecar started before the trainer re-reads it on first restore.
    from tpu_resnet import obs
    run_id = obs.read_run_id(cfg.train.train_dir)
    spans = obs.SpanTracer(eval_dir, enabled=parallel.is_primary(),
                           run_id=run_id)
    if run_id:
        log.info("eval sidecar polling %s (train run_id=%s)",
                 cfg.train.train_dir, run_id)
    best_file = os.path.join(eval_dir, "best_precision.json")
    best = 0.0
    if os.path.exists(best_file):  # survive evaluator restarts (README.md:33)
        with open(best_file) as f:
            best = json.load(f)["best_precision"]

    ckpt = CheckpointManager(cfg.train.train_dir,
                             keep=cfg.train.keep_checkpoints)
    def _wait() -> bool:
        """Sleep one poll interval; True = keep going, False = stop."""
        if stop_event is not None:
            return not stop_event.wait(cfg.train.eval_interval_secs)
        time.sleep(cfg.train.eval_interval_secs)
        return True

    last_seen = None
    precision = None
    try:
        while True:
            step = latest_step_in(cfg.train.train_dir)
            if step is None:
                # Checkpoint not there yet — keep polling like the reference
                # (resnet_cifar_eval.py:100-109).
                log.info("no checkpoint yet in %s; sleeping",
                         cfg.train.train_dir)
                if cfg.train.eval_once:
                    return None
                if not _wait():
                    break
                continue
            if step != last_seen:
                if spans.run_id is None:
                    # Trainer started after us: pick up its run_id now so
                    # the remaining spans correlate.
                    spans.run_id = run_id = obs.read_run_id(
                        cfg.train.train_dir)
                    if run_id:
                        log.info("eval sidecar now polling train "
                                 "run_id=%s", run_id)
                state = restore_with_retry(
                    ckpt, template, step,
                    retries=cfg.resilience.eval_restore_retries,
                    backoff_sec=cfg.resilience.eval_restore_backoff_sec)
                if state is None:
                    # Skip-and-log, never crash the sidecar: mark the step
                    # seen so the poll doesn't spin on it; the next
                    # committed checkpoint evaluates normally.
                    log.error("skipping eval of checkpoint step %d — "
                              "restore failed repeatedly", step)
                    spans.event("eval_restore_failed", step=step)
                    last_seen = step
                    if cfg.train.eval_once:
                        break
                    if not _wait():
                        break
                    continue
                t0 = time.perf_counter()
                with spans.span("eval_pass", step=step) as span_attrs:
                    precision, loss, count = run_eval_pass(cfg, state, mesh,
                                                           eval_step_fn)
                    span_attrs.update(precision=round(precision, 6),
                                      examples=count)
                dt = time.perf_counter() - t0
                best = max(best, precision)
                if parallel.is_primary():
                    os.makedirs(eval_dir, exist_ok=True)
                    with open(best_file, "w") as f:
                        json.dump({"best_precision": best, "step": step}, f)
                metrics.write(step, {"Precision": precision,
                                     "Best_Precision": best,
                                     "eval_loss": loss})
                log.info("eval @ step %d: precision %.4f best %.4f "
                         "loss %.4f (%.1fs, %d examples)", step, precision,
                         best, loss, dt, count)
                last_seen = step
            if cfg.train.eval_once:
                break
            if not _wait():
                break
    finally:
        # Early returns (eval_once with no checkpoint yet) and torn-
        # checkpoint exceptions must still release the sidecar's jsonl
        # handles — both closers are idempotent.
        spans.close()
        metrics.close()
    return precision


def _last_eval(train_dir: str) -> Tuple[Optional[int], Optional[float]]:
    """(step, precision) of the newest eval record in <train_dir>/eval."""
    path = os.path.join(train_dir, "eval", "metrics.jsonl")
    step = precision = None
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn tail write from a live sidecar
                if "Precision" in rec:
                    step, precision = rec.get("step"), rec["Precision"]
    return step, precision


def train_and_eval(cfg: RunConfig, mesh=None) -> Optional[float]:
    """Train with an in-process eval sidecar — the reference's
    ``--mode=train_and_eval`` (resnet_cifar_main.py main dispatch; its
    ImageNet variant is broken, resnet_imagenet_main.py:528-529 calls
    train with an undefined ``server`` — SURVEY.md §2.1). Here both share
    one process and mesh: the sidecar thread polls/evaluates between
    training dispatches, and a final eval-once covers the last checkpoint
    when the sidecar didn't. Returns the final precision.

    Single-process only: with multiple processes, each host's sidecar
    would enqueue collectives interleaved differently with the training
    stream and deadlock the mesh — multi-host runs launch the evaluator
    as its own process/job like the reference's tf-eval container
    (start-resnet-imagenet-main.sh tail, run_dist_train_eval_daint.sh).
    """
    import copy
    import threading

    from tpu_resnet import parallel as par
    from tpu_resnet.train.loop import train as train_fn

    if jax.process_count() != 1:
        raise ValueError(
            "train_and_eval is single-process; in multi-host runs start "
            "`tpu_resnet eval` as a separate process/job instead")
    if mesh is None:
        mesh = par.create_mesh(cfg.mesh)

    eval_cfg = copy.deepcopy(cfg)
    eval_cfg.train.eval_once = False
    stop = threading.Event()
    sidecar = threading.Thread(
        target=evaluate, args=(eval_cfg,),
        kwargs=dict(mesh=mesh, stop_event=stop), daemon=True)
    sidecar.start()
    try:
        train_fn(cfg, mesh=mesh)
    finally:
        stop.set()
    sidecar.join(timeout=600)
    if sidecar.is_alive():
        log.warning("eval sidecar still mid-pass after 600s; skipping the "
                    "final eval to avoid concurrent device work")
        return _last_eval(cfg.train.train_dir)[1]

    seen_step, seen_precision = _last_eval(cfg.train.train_dir)
    if seen_step is not None and seen_step == latest_step_in(
            cfg.train.train_dir):
        return seen_precision  # sidecar already covered the last checkpoint

    final_cfg = copy.deepcopy(cfg)
    final_cfg.train.eval_once = True
    return evaluate(final_cfg, mesh=mesh)
