"""The training loop — the replacement for every reference trainer's
``while not mon_sess.should_stop(): mon_sess.run(train_op)``
(reference resnet_cifar_train.py:343-344) plus its hook stack:

- logging every ``log_every`` steps (LoggingTensorHook,
  resnet_cifar_train.py:282-287),
- metrics/summaries every ``summary_every`` steps (SummarySaverHook, :275-280),
- checkpoint every ``checkpoint_every`` steps (save_checkpoint_steps=1000,
  :335) with automatic resume from the latest checkpoint on restart
  (MonitoredTrainingSession contract, resnet_imagenet_train.py:267-270),
- stop at ``train_steps`` (StopAtStepHook, :289).

One function serves every execution mode of the reference (single, PS-sync,
async-PS, Horovod — SURVEY.md §2.3): the mesh decides the distribution.
"""

from __future__ import annotations

import logging
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from tpu_resnet import obs, parallel, programs, resilience
from tpu_resnet.config import RunConfig
from tpu_resnet.data import augment as aug_lib
from tpu_resnet.data import device_data
from tpu_resnet.data import pipeline
from tpu_resnet.models import build_model, family, sample_input
from tpu_resnet.tools import profiling
from tpu_resnet.train import schedule as sched_lib
from tpu_resnet.train.checkpoint import CheckpointManager
from tpu_resnet.train.metrics_io import MetricsWriter, ThroughputMeter
from tpu_resnet.train.state import init_partitioned_state, param_count
from tpu_resnet.train.step import (check_step_config, make_train_step,
                                   shard_step)

log = logging.getLogger("tpu_resnet")


def build_train_iterator(cfg: RunConfig, mesh, start_step: int = 0,
                         injector=None, stop_event=None):
    """Host pipeline: per-process shard → background batcher → device
    prefetch queue. With ``transfer_stage`` > 1 the iterator yields whole
    ``(stage, B, ...)`` superbatches (one transfer each) plus their length;
    the loop fuses those steps into single dispatches.

    Returns ``(device_iter, stage, host_iter)``; the ``host_iter`` handle
    (HostDataEngine for ImageNet, BackgroundIterator otherwise) lets the
    NaN-rollback path release the producers before rebuilding the stream
    past the bad window, and joins the shutdown closer chain (engine
    close unlinks its shared-memory ring). ``injector``
    (resilience.FaultInjector) wraps the host batch stream with its
    planned data faults; a default (inactive) plan returns the stream
    object untouched."""
    import tpu_resnet.data as data_lib
    from tpu_resnet.data.engine import HostDataEngine

    local_bs = parallel.local_batch_size(cfg.train.global_batch_size, mesh)
    stage = max(1, cfg.data.transfer_stage)
    # hold = stage + 1: the staged superbatch assembly looks back at most
    # `stage - 1` engine views while collecting one transfer's batches.
    batches = data_lib.train_batches(cfg.data, local_bs, seed=cfg.train.seed,
                                     start_step=start_step, hold=stage + 1,
                                     external_stop=stop_event)
    if isinstance(batches, HostDataEngine):
        # The engine is its own background prefetcher (ring slots ahead of
        # the consumer) — wrapping it in BackgroundIterator would both
        # stack a redundant thread AND buffer more ring views than the
        # hold window allows. The fault injector's wrapper holds nothing.
        host_iter = batches
        stream = (injector.wrap_host_batches(batches, start_step=start_step)
                  if injector is not None else batches)
    else:
        if injector is not None:
            batches = injector.wrap_host_batches(batches,
                                                 start_step=start_step)
        host_iter = pipeline.BackgroundIterator(
            batches, capacity=stage * cfg.data.prefetch + 2,
            external_stop=stop_event)
        stream = host_iter
    if stage > 1:
        if cfg.data.h2d_double_buffer:
            # Double-buffered H2D (pipeline.DoubleBufferedH2D): a producer
            # thread assembles + lands the next superbatch transfer while
            # this thread dispatches the current one; explicit two-slot
            # device buffer, h2d_* gauges, trace transfer lane. Contents
            # are identical to the generator form (loss bit-equality
            # pinned by tests/test_data.py).
            return pipeline.DoubleBufferedH2D(
                stream, parallel.staged_batch_sharding(mesh),
                stage=stage, depth=cfg.data.prefetch,
                external_stop=stop_event), stage, host_iter
        return pipeline.staged_superbatch_prefetch(
            stream, parallel.staged_batch_sharding(mesh),
            stage=stage, depth=cfg.data.prefetch), stage, host_iter
    if isinstance(host_iter, HostDataEngine):
        # Unstaged path: device_prefetch hands each batch straight to an
        # ASYNC host→device transfer and keeps `depth` in flight — a ring
        # view could be recycled (hold counts draws, not transfer
        # completions) while PJRT is still reading it. Copy out of the
        # ring here; the staged path needs no copy because np.stack
        # materializes the superbatch synchronously.
        stream = ((img.copy(), lab.copy()) for img, lab in stream)
    return pipeline.device_prefetch(stream, parallel.batch_sharding(mesh),
                                    depth=cfg.data.prefetch), 1, host_iter


def _chunk_len(step: int, total: int, train_cfg, steps_per_epoch: int,
               extra_boundaries: tuple = ()) -> int:
    """Steps to run in the next fused dispatch: at most ``steps_per_call``,
    clipped so the chunk ends exactly on the next log/summary/checkpoint/
    epoch/stop boundary — every interval fires at precisely the same steps
    a one-dispatch-per-step loop would fire them. ``extra_boundaries`` are
    absolute steps (e.g. a profiler trace window) chunks must not straddle."""
    k = max(1, train_cfg.steps_per_call)
    for interval in (train_cfg.log_every, train_cfg.summary_every,
                     train_cfg.image_summary_every,
                     train_cfg.checkpoint_every, steps_per_epoch):
        if interval > 0:
            k = min(k, interval - step % interval)
    for b in extra_boundaries:
        if b > step:
            k = min(k, b - step)
    return min(k, total - step)


def _local_image_slice(batch, n: int = 4) -> np.ndarray:
    """First ``n`` images of a batch as host numpy, multi-host safe: a
    batch-sharded global array spans non-addressable devices, so slice
    this process's own shard instead of the global array (device_get of a
    global slice raises on non-primary-addressable data). Accepts the
    resident path's host array, a [B,...] device batch, or a staged
    [stage,B,...] superbatch."""
    if isinstance(batch, np.ndarray):
        arr = batch
    elif getattr(batch, "is_fully_addressable", True):
        arr = np.asarray(jax.device_get(batch))
    else:
        arr = np.asarray(jax.device_get(batch.addressable_shards[0].data))
    if arr.ndim == 5:  # staged superbatch: first stage row
        arr = arr[0]
    return arr[:n]


def train(cfg: RunConfig, mesh=None, metrics: Optional[MetricsWriter] = None,
          max_steps: Optional[int] = None):
    """Run training to ``cfg.train.train_steps``; returns the final state."""
    # The loop's span recorder (obs/breakdown.py), from the first line:
    # ``process.before_train`` ends here and ``train.startup`` runs from
    # here to the end of the first dispatch; both, their phases and every
    # compile beneath them are written to events.jsonl when start-up ends
    # (the tracer does not exist yet).
    breakdown = obs.StepBreakdown()
    breakdown.begin("train.startup", keep=True)  # first_dispatch_done ends it
    elastic_ctx = None
    if mesh is None:
        # Elastic resume (resilience/elastic.py): derive the mesh from
        # the devices that actually exist — an explicit mesh.data that no
        # longer fits downsizes instead of dying, and a topology that
        # differs from <train_dir>/topology.json becomes a recorded
        # topology_change (span + manifest entry + gauge) below. A
        # caller-supplied mesh opts out: the caller owns its topology.
        elastic_ctx = resilience.elastic.resolve(cfg)
        mesh = elastic_ctx.mesh
    parallel.check_divisible(cfg.train.global_batch_size, mesh)

    model = build_model(cfg)
    schedule = sched_lib.build_schedule(cfg.optim, cfg.train)
    fam = family(cfg)
    tokens = fam.inputs == "tokens"
    augment_fn = (None if tokens
                  else aug_lib.get_augment_fns(cfg.data.dataset)[0])

    rng = jax.random.PRNGKey(cfg.train.seed)
    init_rng, step_rng = jax.random.split(rng)
    sample = sample_input(cfg)
    # The partitioner (parallel/partition.py) owns every TrainState
    # sharding decision: cfg.mesh.partition=replicated reproduces the
    # historical full-copy device_put; zero1 validates the rule set
    # against the real state tree (must-raise on unshardable leaves,
    # BEFORE any compile is paid) and lands the optimizer slots in their
    # data-axis shards.
    partitioner = parallel.make_partitioner(cfg.mesh, mesh)
    with breakdown.phase("train.init_state", keep=True):
        state = init_partitioned_state(model, cfg.optim, schedule,
                                       init_rng, sample, partitioner)
    n_params = param_count(state.params)

    # Observability (tpu_resnet/obs): event spans + run manifest + the
    # per-host telemetry server. Spans/manifest are primary-only like
    # every other writer; the HTTP server runs on EVERY host so a pod can
    # be scraped for stragglers. The run_id (minted once per train_dir,
    # reused across resumes) correlates this run's artifacts with the
    # eval sidecar, serve and loadgen on one trace-export timeline.
    run_id = (obs.ensure_run_id(cfg.train.train_dir)
              if parallel.is_primary()
              else obs.read_run_id(cfg.train.train_dir))
    spans = obs.SpanTracer(cfg.train.train_dir,
                           enabled=parallel.is_primary(), run_id=run_id)
    obs.write_manifest(
        cfg.train.train_dir, cfg, mesh, run_id=run_id,
        extra=({"topology_change": elastic_ctx.attrs()}
               if elastic_ctx is not None and elastic_ctx.changed
               else None))
    for event, fields in fam.startup_events(model, cfg).items():
        spans.event(event, **fields)  # what the family says once
    from tpu_resnet.obs.server import CORE_HISTOGRAMS
    telemetry = obs.TelemetryRegistry(
        stale_after_sec=cfg.train.telemetry_stale_sec,
        histograms=CORE_HISTOGRAMS)
    telemetry.heartbeat(0)  # alive from startup; re-fired with the real
    server = obs.TelemetryServer.maybe_start(  # step once state is known
        cfg.train.telemetry_port, telemetry, train_dir=cfg.train.train_dir)

    # Everything from here (resilience install, restore, step compile,
    # iterator construction) runs INSIDE the try: a setup failure — a
    # bad restore, a config ValueError, an iterator error — must still
    # run the closer chain, or the process-global signal handlers, the
    # watchdog thread, the telemetry server and the spans file leak
    # into the (in-process) caller.
    rcfg = cfg.resilience
    shutdown = watchdog = ckpt = tracer = host_iter = data_iter = None
    m = None
    run_wall0 = None
    step = last_ckpt_step = 0
    total = None
    # Memory-forensics state must exist before the try: the OOM closer
    # reads it even when setup fails ahead of the first dispatch.
    mem_ledger = obs.memory.MemoryLedger()
    mem_key = None
    mem_ring = obs.memory.MemorySampleRing()
    try:
        # Fault-tolerance layer (tpu_resnet/resilience): preemption-graceful
        # shutdown, NaN rollback, hang watchdog — and, drills only, the
        # deterministic fault injector (inactive plan = zero overhead).
        injector = resilience.FaultInjector(
            resilience.FaultPlan.from_config(rcfg),
            train_dir=cfg.train.train_dir)
        if injector.plan.preempt_burst > 0:
            # Cumulative across supervised restarts (state file in the
            # train_dir) — a resumed child reports the burst so far.
            telemetry.set("fault_preempt_burst",
                          float(injector.burst_fired))
        shutdown = resilience.ShutdownCoordinator(
            enabled=rcfg.graceful_shutdown).install()
        sentinel = resilience.NaNSentinel(rcfg.nan_max_retries,
                                          enabled=rcfg.nan_guard)
        watchdog = resilience.HangWatchdog.maybe_start(
            rcfg.watchdog_stall_sec, cfg.train.train_dir,
            telemetry=telemetry, spans=spans,
            on_stall=lambda: breakdown.flush(spans, ring=True))

        injector.maybe_corrupt_checkpoint(cfg.train.train_dir)
        ckpt = CheckpointManager(
            cfg.train.train_dir, keep=cfg.train.keep_checkpoints,
            spans=spans,
            topology=(elastic_ctx.current if elastic_ctx is not None
                      else resilience.elastic.topology_record(
                          mesh, partitioner.mode,
                          cfg.train.global_batch_size)))
        # topology.json must name the topology that wrote the NEWEST
        # checkpoints, so it is written on this run's FIRST successful
        # save (all three save sites call this), never at startup — a
        # reshaped resume that dies before saving leaves the record on
        # the old topology, keeping the next resume's reshape detection
        # and restore-error hints truthful. The wait() pins that to the
        # save's COMMIT, not its async enqueue (a SIGKILL between
        # enqueue and commit must not leave a record without its
        # checkpoint); once per run, so the sync cost never recurs.
        topology_recorded = False

        def record_topology():
            nonlocal topology_recorded
            if not topology_recorded:
                topology_recorded = True
                ckpt.wait()
                resilience.elastic.write_topology(
                    cfg.train.train_dir, mesh, partitioner.mode,
                    cfg.train.global_batch_size)

        latest = ckpt.latest_step()
        if latest is not None:
            # restore() falls back through all_steps() past corrupt/torn
            # checkpoints to the newest restorable one; as the directory's
            # owner, the trainer also discards the steps that failed (the run
            # will re-reach those step numbers and must be able to save them).
            # The template is the CURRENT topology's partitioned state, so a
            # checkpoint written on a different mesh/partition restores
            # through an explicit cross-topology reshard (orbax stores
            # global logical arrays) — value-identical, never corrupted.
            with breakdown.phase("train.restore", keep=True) as ph:
                # The restore takes the fresh state's layout, not its
                # buffers: they are freed first, so that two states never
                # stand on the device together (a token model's is 6 GB).
                template = partitioner.abstract_state(state)
                for leaf in jax.tree_util.tree_leaves(state):
                    leaf.delete()
                state = ckpt.restore(template, discard_failed=True,
                                     parent=ph.id)
            log.info("resumed from step %d in %s",
                     int(jax.device_get(state.step)), cfg.train.train_dir)
        if elastic_ctx is not None and elastic_ctx.changed:
            # The reshape as a first-class event: a span on the run
            # timeline (trace-export renders capacity waves), a gauge,
            # and — written above — a manifest entry.
            spans.event("topology_change",
                        step=int(jax.device_get(state.step)),
                        **elastic_ctx.attrs())
            telemetry.set("topology_changes", 1.0)

        if metrics is None:
            metrics = MetricsWriter(cfg.train.train_dir,
                                    enabled=parallel.is_primary())

        # Per-replica BN (reference semantics, model.sync_bn=False) runs the
        # step inside shard_map with explicit pmeans; the default is global-
        # batch BN under auto-sharded jit.
        per_replica_bn = (not cfg.model.sync_bn) and mesh.shape["data"] > 1
        # Shared with the static config-matrix verifier (analysis/) so a
        # combination it certifies is exactly one this loop accepts.
        check_step_config(cfg, mesh.shape["data"])
        # Compile-time A/B probes (ops/autotune.py): fused_epilogue="auto"
        # times the epilogue kernels at this model's stage shapes and
        # enables Pallas only where it measured a win; the xent "auto"
        # probe runs inside make_train_step. Host code before the first
        # dispatch — it rides in the compile window, never a throughput
        # interval. A kernel that fails to compile raises out of here:
        # only a measured loss sends a site to XLA.
        from tpu_resnet import ops
        if cfg.model.fused_epilogue == "auto" and ops.is_tpu_backend():
            kernel_batch = (cfg.train.global_batch_size
                            // mesh.shape["data"] if per_replica_bn
                            else cfg.train.global_batch_size)
            with breakdown.phase("train.autotune_probe", keep=True,
                                 op="epilogue"):
                ops.probe_model_epilogues(cfg, kernel_batch)
        # The xent kernel always sees the PER-DEVICE batch (shard_mapped
        # over 'data' under auto-jit, the local shard under per-replica
        # BN, the full batch only on one device) — probe at that shape,
        # not the global one (b1024-probe/b128-execute would decide at
        # the wrong point of the speedup curve).
        xent_probe = breakdown.begin("train.autotune_probe", keep=True,
                                     op="xent")
        base_step = make_train_step(model, cfg.optim, schedule,
                                    cfg.data.num_classes, augment_fn,
                                    base_rng=step_rng, mesh=mesh,
                                    grad_axis="data" if per_replica_bn else None,
                                    xent_probe_batch=max(
                                        1, cfg.train.global_batch_size
                                        // mesh.shape["data"]),
                                    partitioner=partitioner)
        breakdown.end(xent_probe, decisions=ops.autotune.decisions())
        # zero1 compiles with the partitioner's state layout so the
        # optimizer-slot arguments are per-shard buffers; replicated
        # passes None and keeps the exact historical program.
        state_sharding = (partitioner.state_shardings(state)
                          if partitioner.is_sharded else None)
        # Program registry (tpu_resnet/programs): every program this
        # loop dispatches is constructed through it — identity
        # pass-through (the exact historical jit objects) unless the
        # persistent AOT executable cache is enabled
        # (programs.cache/cache_dir or TPU_RESNET_PROGRAM_CACHE_DIR —
        # the elastic-resume cold-start lever), in which case each
        # program is AOT-compiled over its real avals and round-tripped
        # through <cache_dir>, so a resumed process re-reaches its
        # topology's programs without re-paying XLA.
        prog_reg = programs.ProgramRegistry(cfg, mesh, telemetry=telemetry,
                                            spans=spans, context="train")
        state_avals = programs.state_avals(state)
        if parallel.is_primary() and ops.autotune.decisions():
            # The run's dispatch choices as a reviewable artifact.
            ops.autotune.dump(cfg.train.train_dir)

        step = int(jax.device_get(state.step))
        total = max_steps if max_steps is not None else cfg.train.train_steps

        # Input edge: device-resident (whole split in HBM, batches cut
        # on-device, multi-step dispatch) when it applies, else the streaming
        # host pipeline.
        resident = device_data.should_use(cfg.data)
        host_iter = None
        if resident:
            import tpu_resnet.data as data_lib

            with breakdown.phase("train.load_split", keep=True):
                images_np, labels_np = data_lib.load_split(cfg.data,
                                                           train=True)
            with breakdown.phase("train.dataset_to_device", keep=True):
                ds = device_data.DeviceDataset(mesh, images_np, labels_np,
                                               cfg.train.global_batch_size,
                                               seed=cfg.train.seed)
            run_chunk = device_data.compile_resident_steps(
                base_step, ds, mesh, max(1, cfg.train.steps_per_call),
                per_replica_bn=per_replica_bn,
                state_sharding=state_sharding,
                program_hook=(programs.staged_chunk_hook(
                                  prog_reg, state_avals,
                                  ds.steps_per_epoch)
                              if prog_reg.cache_enabled else None))
            data_iter = None
        else:
            with breakdown.phase("train.build_iterator", keep=True):
                data_iter, stage, host_iter = build_train_iterator(
                    cfg, mesh, start_step=step, injector=injector,
                    stop_event=shutdown.event)
            if stage > 1:
                run_staged = device_data.compile_staged_stream_steps(
                    base_step, mesh, per_replica_bn=per_replica_bn,
                    state_sharding=state_sharding,
                    program_hook=(programs.staged_chunk_hook(
                                      prog_reg, state_avals, stage)
                                  if prog_reg.cache_enabled else None))
            else:
                train_step = shard_step(base_step, mesh,
                                        per_replica_bn=per_replica_bn,
                                        state_sharding=state_sharding)
                if prog_reg.cache_enabled:
                    train_step = programs.wrap_train_step(
                        prog_reg, train_step, state_avals)

        meter = ThroughputMeter(cfg.train.global_batch_size,
                                num_chips=mesh.size)
        log.info("training %s/%s to step %d | params %.2fM | mesh %s | "
                 "partition %s | global batch %d | input %s",
                 cfg.model.name, cfg.data.dataset,
                 total, n_params / 1e6, dict(mesh.shape),
                 partitioner.describe(), cfg.train.global_batch_size,
                 "device-resident" if resident else "streaming")

        profiling.maybe_start_server(cfg.train.profiler_port)
        tracer = profiling.StepTracer(cfg.train.train_dir,
                                      cfg.train.profile_steps, spans=spans,
                                      phases=breakdown.spans)

        # From here to the first chunk drained is ``train.first_dispatch``:
        # the recorder's interval clock restarts so that compile_seconds
        # counts from this line, and every backend compile of the first
        # dispatch hangs beneath its ``compile`` span. The breakdown then
        # samples at the existing log boundaries only (chunks already end
        # exactly there), so it never changes fusion behavior.
        first_phase = breakdown.begin("train.first_dispatch", step,
                                      keep=True)
        breakdown.compile_parent = first_compile_id = obs.next_span_id()
        breakdown.reset_interval(step)
        telemetry.heartbeat(step)
        run_wall0 = time.time()
        start_step = step
        last_ckpt_step = step  # resumed or fresh: the last synced point
        last_log_step = step   # for the per-interval step-time histogram
        first_dispatch = True
        # MFU accounting (obs/mfu.py): per-step FLOPs measured once at
        # first dispatch; converted to model_flops_per_sec / mfu at every
        # log boundary (pure host arithmetic — no device syncs).
        step_flops = None
        device_kind = mesh.devices.flat[0].device_kind
        # Memory ledger (obs/memory.py): the step's HBM budget measured
        # once at first dispatch; live hbm_* gauges sampled at log
        # boundaries; mem_ledger/mem_key/mem_ring (initialized above the
        # try) feed the OOM report in the closer chain.

        meter.rate(step)
        last_summary = step
        last_sync = step  # last step the host fully drained the device at
        m = None  # metrics of the newest dispatched chunk
        stage_buf = None  # current streaming superbatch: (gi, gl, k, offset)
        # Raw input images for the image-summary channel (reference
        # cifar_input.py:118): the resident split's head, or the newest
        # streamed batch; augmented at write time so the summary shows what
        # the model actually saw.
        last_inputs = images_np[:4] if resident and not tokens else None
        while step < total:
            injector.maybe_sigterm(step)
            injector.maybe_oom(step)  # OOM-forensics drill (doctor)
            if shutdown.requested:
                break  # stop at the chunk boundary; final save below
            tracer.before(step)
            if resident:
                k = _chunk_len(step, total, cfg.train, ds.steps_per_epoch,
                               tracer.boundaries())
                with breakdown.dispatch(step, k):
                    if ds.epoch_of(step) != ds.epoch:
                        with breakdown.phase("train.epoch_shuffle", step):
                            ds.ensure_epoch(ds.epoch_of(step))
                    state, m = run_chunk(state, step, k)
                step += k
            elif stage > 1:
                if stage_buf is None:
                    with breakdown.data_wait(step):
                        try:
                            gi, gl, k = next(data_iter)
                        except StopIteration:
                            if shutdown.requested:
                                break  # preempted mid-data-wait: save below
                            raise
                    stage_buf = (gi, gl, k, 0)
                gi, gl, k, off = stage_buf
                # Fuse up to the stage end, clipped to the next log/summary/
                # checkpoint/trace boundary so every hook fires at the exact
                # steps a one-dispatch-per-step loop would fire it.
                c = min(k - off,
                        _chunk_len(step, total, cfg.train, 0,
                                   tracer.boundaries()))
                with breakdown.dispatch(step, c):
                    state, m = run_staged(state, gi, gl, off, c)
                step += c
                off += c
                last_inputs = gi  # reference only; sliced at summary time
                stage_buf = None if off >= k else (gi, gl, k, off)
            else:
                with breakdown.data_wait(step):
                    try:
                        images, labels = next(data_iter)
                    except StopIteration:
                        if shutdown.requested:
                            break  # preempted mid-data-wait: save below
                        raise
                with breakdown.dispatch(step, 1):
                    state, m = train_step(state, images, labels)
                step += 1
                last_inputs = images
            if watchdog is not None:
                watchdog.progress(step)
            if tracer.after(step, sync=m):
                # Closing a trace window drains the device mid-interval:
                # the backlog the next boundary sample sees only covers
                # steps dispatched since here.
                last_sync = step

            if first_dispatch:
                # The first dispatch pays jit tracing + XLA compile: report
                # it as compile_seconds and re-prime the throughput meter so
                # the first logged images/sec excludes compile time.
                first_dispatch = False
                compile_s = breakdown.first_dispatch_done(m)
                now = time.time()
                spans.record("compile", now - compile_s, now,
                             seconds=round(compile_s, 3), step=start_step,
                             id=first_compile_id, parent=first_phase.id)
                # Start-up has ended: its phases and the compiles beneath
                # them, held back until now, follow the span they name.
                breakdown.flush(spans)
                telemetry.set("compile_seconds", compile_s)
                # What the memory and comms ledgers account: the program
                # this run's input edge dispatches in steady state — on
                # the staged streaming path a chunk of steps_per_call
                # steps clipped to the superbatch.
                staged_run = not resident and stage > 1
                staged_chunk_steps = (
                    min(stage, max(1, cfg.train.steps_per_call))
                    if staged_run else 1)
                if cfg.train.mfu_accounting:
                    # One abstract trace + HLO cost pass (no second XLA
                    # compile); charged to the compile window, not to any
                    # throughput interval — breakdown/meter re-prime below.
                    t_acct = time.time()
                    try:
                        entry = obs.mfu.account_train_step(
                            cfg, mesh, state, base_step,
                            per_replica_bn=per_replica_bn,
                            train_dir=(cfg.train.train_dir
                                       if parallel.is_primary() else None))
                        step_flops = entry.get("flops_per_step")
                        spans.record("mfu_account", t_acct, time.time(),
                                     flops_per_step=step_flops,
                                     source=entry.get("flops_source"))
                    except Exception as e:  # noqa: BLE001 - accounting
                        log.warning(            # must never kill training
                            "mfu accounting failed (%s: %s) — mfu gauges "
                            "stay 0", type(e).__name__, e)
                    breakdown.reset_interval(step)
                if cfg.train.memory_ledger:
                    # HBM budget of the compiled step (obs/memory.py).
                    # memory_analysis needs a COMPILED program and the
                    # AOT path shares no cache with the jit dispatch:
                    # this is ONE extra XLA compile, charged to the
                    # compile window (meter re-primed below, never a
                    # throughput interval). Degrades to absent.
                    t_mem = time.time()
                    try:
                        # Measure the program THIS run's input edge
                        # dispatches: the fused staged-chunk jit on the
                        # streaming stage>1 path, else the plain sharded
                        # step (the resident path's epoch-buffer chunk
                        # is approximated by its single-step twin —
                        # labeled so on the entry).
                        entry = obs.memory.account_train_step(
                            cfg, mesh, state, base_step,
                            per_replica_bn=per_replica_bn,
                            partitioner=partitioner,
                            stage_rows=stage if staged_run else 1,
                            chunk_steps=staged_chunk_steps,
                            variant=("single-step (resident epoch-buffer "
                                     "program approximated)" if resident
                                     else "single-step"),
                            ledger=mem_ledger,
                            train_dir=(cfg.train.train_dir
                                       if parallel.is_primary() else None))
                        mem_key = entry.get("program_key")
                        spans.record(
                            "memory_account", t_mem, time.time(),
                            program_key=mem_key,
                            temp_bytes=entry.get("temp_bytes"),
                            alias_bytes=entry.get("alias_bytes"),
                            peak_bytes=entry.get("peak_bytes"))
                    except Exception as e:  # noqa: BLE001 - accounting
                        log.warning(            # must never kill training
                            "memory ledger failed (%s: %s) — memory.json "
                            "absent for this run", type(e).__name__, e)
                    breakdown.reset_interval(step)
                if cfg.train.comms_ledger:
                    # Collective summary of the compiled step
                    # (obs/comms.py): op multiset + analytic bytes-on-
                    # wire per mesh axis from the post-partitioner HLO,
                    # plus predicted time-on-wire / comms-fraction from
                    # the per-chip ICI table (feeding step_flops from
                    # the mfu block above when it ran). Same contract
                    # as the memory ledger: ONE extra XLA compile,
                    # charged to the compile window, degrades to
                    # absent.
                    t_comm = time.time()
                    try:
                        entry = obs.comms.account_train_step(
                            cfg, mesh, state, base_step,
                            per_replica_bn=per_replica_bn,
                            partitioner=partitioner,
                            stage_rows=stage if staged_run else 1,
                            chunk_steps=staged_chunk_steps,
                            variant=("single-step (resident epoch-buffer "
                                     "program approximated)" if resident
                                     else "single-step"),
                            flops_per_step=step_flops,
                            train_dir=(cfg.train.train_dir
                                       if parallel.is_primary() else None))
                        frac = entry.get("predicted_comms_fraction")
                        if frac is not None:
                            telemetry.set("predicted_comms_fraction",
                                          float(frac))
                        spans.record(
                            "comms_account", t_comm, time.time(),
                            program_key=entry.get("program_key"),
                            collective_count=entry.get("collective_count"),
                            wire_bytes_per_device=entry.get(
                                "wire_bytes_per_device"),
                            predicted_comms_fraction=frac)
                    except Exception as e:  # noqa: BLE001 - accounting
                        log.warning(            # must never kill training
                            "comms ledger failed (%s: %s) — comms.json "
                            "absent for this run", type(e).__name__, e)
                    breakdown.reset_interval(step)
                meter.rate(step)
                last_sync = step
                last_log_step = step

            if step % cfg.train.log_every == 0 or step == total:
                breakdown.sample_device(m, step - last_sync, step)
                with breakdown.phase("train.log_fetch", step):
                    m = {k: float(v) for k, v in jax.device_get(m).items()}
                last_sync = step
                if sentinel.check(step, m["loss"]):
                    # Divergence rollback: restore the last checkpoint and
                    # (streaming path) advance the data stream past the bad
                    # window so the replayed steps see fresh batches. The
                    # check reuses this boundary's host-synced metrics —
                    # zero extra device syncs, fusion/chunking unchanged.
                    bad_step = step
                    with breakdown.phase("train.checkpoint", step):
                        ckpt.wait()
                        if ckpt.latest_step() is None:
                            raise sentinel.no_checkpoint(step, m["loss"])
                        state = ckpt.restore(state, discard_failed=True)
                    step = int(jax.device_get(state.step))
                    spans.event("nan_rollback", from_step=bad_step,
                                to_step=step, loss=str(m["loss"]),
                                retry=sentinel.rollbacks)
                    telemetry.set("fault_nan_rollbacks", sentinel.rollbacks)
                    if not resident:
                        # The stream is deterministic in (seed, step):
                        # restart it at bad_step so steps (to_step,
                        # bad_step] consume the batches *after* the bad
                        # window instead of replaying it.
                        if hasattr(data_iter, "close"):
                            data_iter.close()  # release the H2D producer
                        host_iter.close()
                        data_iter, stage, host_iter = build_train_iterator(
                            cfg, mesh, start_step=bad_step,
                            injector=injector, stop_event=shutdown.event)
                        stage_buf = None
                    m = None
                    breakdown.reset_interval(step)
                    meter.rate(step)  # re-prime the throughput baseline
                    last_sync = step
                    last_ckpt_step = step
                    last_log_step = step
                    telemetry.heartbeat(step)
                    continue
                log_write = breakdown.begin("train.log_write", step)
                rate = meter.rate(step)
                if rate:
                    m.update(rate)
                    # Step-time histogram: the interval's mean step time,
                    # weighted by its step count — the p50/p95/p99 the
                    # plot panel and /metrics expose.
                    telemetry.observe(
                        "train_step_ms", 1e3 / rate["steps_per_sec"],
                        n=max(1, step - last_log_step))
                    for q in (0.50, 0.95, 0.99):
                        m[f"train_step_ms_p{int(q * 100)}"] = round(
                            telemetry.hist_percentile("train_step_ms", q),
                            3)
                    if step_flops:
                        # Model FLOPs utilization (obs/mfu.py): achieved
                        # model FLOP/s vs the mesh's aggregate peak.
                        mfs = step_flops * rate["steps_per_sec"]
                        m["model_flops_per_sec"] = mfs
                        u = obs.mfu.mfu(mfs, device_kind, mesh.size)
                        if u is not None:
                            m["mfu"] = round(u, 4)
                last_log_step = step
                m.update(breakdown.interval())
                # Live device-memory gauges (obs/memory.py): pure host
                # introspection at this already-synced boundary — zero
                # extra device syncs; {} on backends without stats.
                hbm = obs.memory.sample_device_memory()
                if hbm:
                    m.update(hbm)
                    mem_ring.add(step, hbm)
                if host_iter is not None and hasattr(host_iter, "stats"):
                    # Engine cause-signal for data_wait: ring occupancy
                    # (0 while the step waits = producer-bound) and the
                    # interval decode rate.
                    m.update(host_iter.stats())
                if data_iter is not None and hasattr(data_iter, "stats"):
                    # Double-buffered H2D: interval transfer rate +
                    # overlap fraction, plus the finished transfers as
                    # spans for the trace-export transfer lane.
                    m.update(data_iter.stats())
                    for t0, t1, nbytes, c in data_iter.drain_transfers():
                        spans.record("h2d_transfer", t0, t1,
                                     bytes=nbytes, steps=c)
                # A compile since the last boundary (a new chunk
                # length, a rebuilt stream) is one line that names
                # its step; no-op while none is pending.
                breakdown.flush(spans)
                telemetry.update(m)
                telemetry.set("checkpoint_lag_steps", step - last_ckpt_step)
                telemetry.heartbeat(step)
                log.info("step %d | loss %.4f | precision %.4f | lr %.4g%s"
                         " | wait %d%%",
                         step, m["loss"], m["precision"], m["learning_rate"],
                         f" | {m['steps_per_sec']:.2f} st/s "
                         f"({m['images_per_sec']:.0f} img/s)" if rate else "",
                         round(m["data_wait_frac"] * 100))
                # Summaries reuse the logged measurement, tagged with the
                # step it was measured at (never a stale value under a
                # different step).
                if (step - last_summary >= cfg.train.summary_every
                        or step == total):
                    metrics.write(step, m)
                    last_summary = step
                breakdown.end(log_write)
            if (cfg.train.image_summary_every > 0 and metrics.enabled
                    and last_inputs is not None
                    and step % cfg.train.image_summary_every == 0):
                raw = _local_image_slice(last_inputs)
                aug = augment_fn(jax.random.PRNGKey(step), jnp.asarray(raw))
                metrics.write_images(step, jax.device_get(aug))
            if step % cfg.train.checkpoint_every == 0 or step == total:
                # A checkpoint boundary that is NOT a log boundary hasn't
                # had its loss checked (possible when checkpoint_every is
                # not a multiple of log_every): never persist NaN state —
                # it would become the rollback target. The scalar read
                # piggybacks on the save's own full-state sync, so this
                # adds no standalone device sync.
                if (sentinel.enabled and m is not None
                        and step % cfg.train.log_every != 0
                        and not math.isfinite(
                            float(jax.device_get(m["loss"])))):
                    log.warning("skipping checkpoint save at step %d: "
                                "non-finite loss — rollback engages at "
                                "the next log boundary", step)
                    spans.event("checkpoint_save_skipped_nonfinite",
                                step=step)
                else:
                    with breakdown.phase("train.checkpoint", step):
                        if ckpt.save(step, state):
                            last_ckpt_step = step
                            telemetry.set("checkpoint_lag_steps", 0)
                            record_topology()
        if shutdown.requested and step < total:
            # Preemption honored at the chunk boundary: force a final save
            # so the resume loses zero steps, then mark the event. The
            # Preempted raise (the supervisor's distinct exit code) happens
            # after the closer chain below has shut telemetry down cleanly.
            log.warning("preemption stop at step %d — saving a final "
                        "checkpoint before exit", step)
            spans.event("preempt_stop", step=step, signum=shutdown.signum)
            telemetry.set("fault_preemptions", 1.0)
            if injector.plan.preempt_burst > 0:
                telemetry.set("fault_preempt_burst",
                              float(injector.burst_fired))
            if step > last_ckpt_step:
                with breakdown.phase("train.checkpoint", step):
                    if ckpt.save(step, state, force=True):
                        last_ckpt_step = step
                        record_topology()
    finally:
        # One shutdown path for clean exits AND exceptions. Each closer
        # runs even if an earlier one raises (a failed ckpt.wait must not
        # leave the run span unwritten or the telemetry server answering
        # /healthz for a dead loop); a closer error surfaces on a clean
        # exit but never masks an in-flight loop exception.
        import sys

        closer_errs = []

        def _close(fn):
            try:
                fn()
            except Exception as e:  # noqa: BLE001 - shutdown must finish
                closer_errs.append(e)
                log.warning("shutdown closer %s failed: %s",
                            getattr(fn, "__name__", fn), e)

        exc_type, exc_val = sys.exc_info()[:2]
        if exc_val is not None and obs.memory.is_oom_error(exc_val):
            # OOM forensics FIRST (cheap, pure host writes): the ledger,
            # the recent hbm samples, a live-array census and the
            # offending program key land in <train_dir>/oom_report.json
            # before anything else touches the dying process — a pod OOM
            # becomes a diagnosable artifact, not a dead log line. The
            # original exception still propagates.
            _close(lambda: obs.memory.write_oom_report(
                cfg.train.train_dir, exc_val, context="train", step=step,
                program_key=mem_key, ledger=mem_ledger,
                samples=mem_ring.snapshot(), run_id=run_id))
            _close(lambda: spans.event("oom", step=step,
                                       program_key=mem_key))
        if (rcfg.emergency_save and exc_type is not None
                and ckpt is not None
                and not issubclass(exc_type, (resilience.DivergenceError,
                                              KeyboardInterrupt))
                and step > last_ckpt_step):
            # In-flight exception with unsaved progress: one guarded
            # best-effort save, so the crash loses at most the current
            # interval. Excluded: DivergenceError (the live state is NaN —
            # persisting it would poison the resume) and an operator's
            # escalated abort (they asked for NOW, not a slow save).
            def _emergency_save():
                if ckpt.save(step, state, force=True):
                    spans.event("emergency_save", step=step)
                    record_topology()
                    log.warning("emergency checkpoint saved at step %d "
                                "after in-flight %s", step,
                                exc_type.__name__)

            _close(_emergency_save)
        if tracer is not None:
            _close(lambda: tracer.close(sync=m))
        if ckpt is not None:
            _close(ckpt.wait)
        if run_wall0 is not None:  # the loop actually started
            _close(lambda: spans.record(
                "run", run_wall0, time.time(), start_step=start_step,
                stop_step=step, train_steps=total))
        # The recorder's ring (the last iterations' phases) and whatever
        # start-up span or compile is still pending: the one place the
        # per-iteration spans reach the file.
        _close(breakdown.close)
        _close(lambda: breakdown.flush(spans, ring=True))
        _close(spans.close)
        if server is not None:
            _close(server.close)
        if metrics is not None:
            _close(metrics.close)
        if data_iter is not None and hasattr(data_iter, "close"):
            _close(data_iter.close)  # H2D producer thread + device slots
        if host_iter is not None:
            _close(host_iter.close)
        if watchdog is not None:
            _close(watchdog.close)
        if shutdown is not None:
            _close(shutdown.uninstall)
        if closer_errs and sys.exc_info()[0] is None:
            raise closer_errs[0]
    if shutdown is not None and shutdown.requested \
            and total is not None and step < total:
        raise resilience.Preempted(step, state=state, signum=shutdown.signum)
    return state


# The last line of the package's import as train() sees it: the span
# ``process.import`` ends here, and the compile listeners start.
obs.breakdown.package_imported()
