"""Device-resident dataset — the TPU-native answer to the reference's
16-thread host queue pipeline (reference cifar_input.py:81-103).

CIFAR-scale datasets (150 MB) are small next to TPU HBM, so instead of
streaming every batch over PCIe/host-link each step, the whole training
split is uploaded **once** and batches are cut on-device:

  flat uint8 dataset (replicated)
    ── once per epoch ──► jitted permutation → epoch buffer
                          shape (steps_per_epoch, batch, H, W, C),
                          batch axis sharded over the mesh 'data' axis
    ── every dispatch ──► ``dynamic_slice`` of the chunk's contiguous
                          ``(k, batch, ...)`` block + ``lax.scan`` over it

This removes all per-step host→device traffic (the reference moves every
batch through queue runners and feed dicts, resnet_cifar_train.py:204-247)
and keeps the input edge on the device timeline. Epoch shuffling is a pure
function of (seed, epoch) — same determinism contract as the host
``ShardedBatcher`` — computed by the TPU itself.

Fusing ``k`` steps per dispatch amortizes host→device command latency,
which dominates when the chip is fast and the per-step FLOPs are small
(exactly the CIFAR regime). The chunk program is shared with the
streaming path (``compile_staged_stream_steps``) — see
``compile_resident_steps`` for why the slice offset must not depend on
the scan carry.

Multi-host runs keep the streaming pipeline (each process owns a disjoint
record stripe that never leaves its host); this path is gated to
single-process meshes by ``should_use`` below.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def should_use(data_cfg) -> bool:
    """True when the resident path applies: policy 'on'/'auto', an
    in-memory dataset, a single-process run, and a split small enough for
    double-buffered residency (flat + epoch buffer). Policy 'on' raises
    when the path is impossible rather than silently streaming."""
    policy = getattr(data_cfg, "device_resident", "auto")
    if data_cfg.dataset == "tokens":
        if policy == "off" or jax.process_count() != 1:
            raise ValueError(
                "dataset 'tokens' trains from a split resident on the "
                "device in a single process only: the host data engine "
                "does not carry token data (data.device_resident=off and "
                "multi-host runs are refused)")
        return True
    if policy == "off":
        return False
    forced = policy == "on"
    if jax.process_count() != 1:
        if forced:
            raise ValueError("data.device_resident=on requires a "
                             "single-process run; multi-host uses the "
                             "streaming pipeline")
        return False
    if data_cfg.dataset not in ("cifar10", "cifar100", "synthetic"):
        if forced:
            raise ValueError(
                f"data.device_resident=on is unsupported for dataset "
                f"{data_cfg.dataset!r} (streams from TFRecord shards)")
        return False
    size = data_cfg.resolved_image_size
    nbytes = 2 * data_cfg.train_examples * size * size * 3  # flat + epoch buf
    return forced or nbytes <= data_cfg.resident_max_bytes


class DeviceDataset:
    """Training split resident in HBM with on-device epoch shuffling.
    ``labels`` has one entry an example, ``(N,)``, or one a position,
    ``(N, S)`` beside ``(N, S)`` token inputs."""

    def __init__(self, mesh: Mesh, images: np.ndarray, labels: np.ndarray,
                 batch: int, seed: int = 0):
        n = len(images)
        if n < batch:  # tile tiny (smoke/synthetic) datasets up to one batch
            reps = -(-batch // n)
            images = np.concatenate([images] * reps)
            labels = np.concatenate([labels] * reps)
            n = len(images)
        self.n = n
        self.batch = batch
        self.steps_per_epoch = n // batch
        self.seed = seed
        self.epoch = None  # the epoch the buffer holds

        repl = NamedSharding(mesh, P())
        # Epoch buffer: (steps_per_epoch, batch, ...) with the *batch* axis
        # sharded over 'data' — each step's slice lands pre-sharded.
        self._buf_sharding = NamedSharding(mesh, P(None, "data"))
        self._flat_images = jax.device_put(images, repl)
        self._flat_labels = jax.device_put(labels.astype(np.int32), repl)

        spe, b = self.steps_per_epoch, batch

        def shuffle(flat_i, flat_l, epoch):
            with jax.named_scope("epoch_shuffle"):
                rng = jax.random.fold_in(jax.random.PRNGKey(seed), epoch)
                order = jax.random.permutation(rng, n)[: spe * b]
                ib = jnp.take(flat_i, order, axis=0).reshape(
                    (spe, b) + flat_i.shape[1:])
                lb = jnp.take(flat_l, order, axis=0).reshape(
                    (spe, b) + flat_l.shape[1:])
            return ib, lb

        self._shuffle = jax.jit(
            shuffle,
            in_shardings=(repl, repl, None),
            out_shardings=(self._buf_sharding, self._buf_sharding),
            static_argnums=(),
        )
        self.images = None
        self.labels = None

    def epoch_of(self, step: int) -> int:
        return step // self.steps_per_epoch

    def ensure_epoch(self, epoch: int) -> None:
        """(Re)build the shuffled epoch buffer if ``epoch`` changed — one
        on-device permutation per epoch (~ms), zero host traffic."""
        if epoch != self.epoch:
            self.images, self.labels = self._shuffle(
                self._flat_images, self._flat_labels, epoch)
            self.epoch = epoch


def make_chunk_fn(base_step: Callable, c: int):
    """The fused ``c``-step chunk program over a staged ``(stage, B, ...)``
    superbatch — ``chunk(state, gi, gl, off)`` scans steps ``off ..
    off + c``. Module-level (not a closure of the compile cache) so the
    config-matrix verifier can trace and golden-pin exactly the program
    the staged/double-buffered H2D path dispatches
    (tpu_resnet/analysis/configmatrix.py ``staged-chunk`` entries)."""

    def chunk(state, gi, gl, off):
        with jax.named_scope("batch_cut"):
            imgs = jax.lax.dynamic_slice_in_dim(gi, off, c, axis=0)
            labs = jax.lax.dynamic_slice_in_dim(gl, off, c, axis=0)
        if c == 1:
            return base_step(state, imgs[0], labs[0])

        def body(s, xs):
            s2, _ = base_step(s, xs[0], xs[1])
            return s2, None

        state, _ = jax.lax.scan(
            body, state, (imgs[:-1], labs[:-1]))
        return base_step(state, imgs[-1], labs[-1])

    return chunk


def staged_chunk_jit(base_step: Callable, mesh: Mesh, c: int,
                     per_replica_bn: bool = False,
                     donate_state: bool = True,
                     state_sharding=None):
    """THE jitted fused ``c``-step chunk program over a staged
    superbatch — the one constructor behind ``compile_staged_stream_steps``
    (the loop's streaming/double-buffered dispatch), the memory ledger's
    staged probe (obs/memory.py) and the golden memory-budget engine
    (analysis/memorybudget.py), so the check engines and the runtime can
    never compile different programs for the same key
    (tpu_resnet/programs/registry.py owns the key spelling)."""
    repl = NamedSharding(mesh, P())
    staged = NamedSharding(mesh, P(None, "data"))
    chunk = make_chunk_fn(base_step, c)
    if per_replica_bn:
        from tpu_resnet.train.step import per_replica_shard_map

        chunk = per_replica_shard_map(
            chunk, mesh,
            in_specs=(P(), P(None, "data"), P(None, "data"), P()))
    state_sh = state_sharding if state_sharding is not None else repl
    # The state comes back in the layout it went in with (metrics
    # replicated): left to the partitioner, a four-chip TPU compile
    # returned a 16-element zero1 leaf sharded over 'data', and the next
    # dispatch refused it against in_shardings.
    return jax.jit(
        chunk,
        in_shardings=(state_sh, staged, staged, None),
        out_shardings=(state_sh, repl),
        donate_argnums=(0,) if donate_state else (),
    )


def compile_staged_stream_steps(base_step: Callable, mesh: Mesh,
                                per_replica_bn: bool = False,
                                donate_state: bool = True,
                                state_sharding=None,
                                program_hook=None):
    """Fused multi-step dispatch for the *streaming* input path — the
    counterpart of ``compile_resident_steps`` for data that arrives as
    staged ``(stage, B, ...)`` superbatches
    (pipeline.staged_superbatch_prefetch). ``donate_state=False`` is the
    sweep harness's donation knob (tools/sweep.py) — production callers
    keep the default in-place update.

    Returns ``run(state, gi, gl, off, c) -> (state, metrics)`` executing
    steps ``off .. off+c`` of the superbatch in ONE dispatch (a
    ``lax.scan`` over the stage rows): per-dispatch host↔device command
    latency — which dominates on a remote-attached chip when per-step
    compute is small — is amortized ``c``-fold. ``off`` is a traced
    scalar (no recompile per position); distinct ``c`` values compile
    once each (the loop only uses the handful its log/checkpoint
    boundaries require). Metrics are the last step's, like the
    reference's LoggingTensorHook (resnet_cifar_train.py:282-287).

    ``state_sharding`` is the TrainState-shaped sharding tree from
    ``parallel.StatePartitioner.state_shardings`` (None = fully
    replicated, the historical layout) — the zero1 loop passes its
    sharded tree so the chunk program's optimizer-slot arguments compile
    to per-shard buffers.

    ``program_hook(c, jitted) -> callable`` lets the program registry
    (tpu_resnet/programs/registry.py) intercept each per-``c`` jit for
    its persistent AOT executable cache; None (the default) keeps the
    exact historical jit objects."""
    cache = {}

    def compiled(c: int):
        if c not in cache:
            jitted = staged_chunk_jit(base_step, mesh, c,
                                      per_replica_bn=per_replica_bn,
                                      donate_state=donate_state,
                                      state_sharding=state_sharding)
            cache[c] = (program_hook(c, jitted)
                        if program_hook is not None else jitted)
        return cache[c]

    def run(state, gi, gl, off: int, c: int):
        return compiled(c)(state, gi, gl, jnp.int32(off))

    return run


def compile_resident_steps(base_step: Callable, ds: DeviceDataset,
                           mesh: Mesh, steps_per_call: int,
                           per_replica_bn: bool = False,
                           state_sharding=None,
                           program_hook=None):
    """Returns ``run(state, step, k) -> (state, metrics)`` executing ``k``
    steps (k ≤ steps_per_call) in one dispatch against the resident
    dataset.

    The chunk is the same program as the streaming path's
    (``compile_staged_stream_steps``): a contiguous ``(k, batch, ...)``
    block is ``dynamic_slice``d out of the epoch buffer at the *traced*
    host-step offset, then a ``lax.scan`` consumes its rows. An earlier
    design instead indexed the epoch buffer per step with
    ``state.step % steps_per_epoch`` *inside* the scan — on a real TPU
    that measured ~2.8x slower per step (4.9 ms vs 1.7, v5e, ResNet-50
    CIFAR b128): the slice index hangs off the scan carry, so each HBM
    read serializes behind the previous step's full update instead of
    being prefetched ahead of the loop. Slicing at a scan-independent
    offset restores the pipelining and unifies the two input edges.

    Chunks never cross an epoch boundary (the loop's ``_chunk_len`` and
    the bench's plans both guarantee it), so one contiguous slice always
    covers the chunk. ``per_replica_bn`` compiles the shard_map variant;
    the epoch buffer's batch axis is sharded over 'data', so each replica
    slices its own local rows."""
    run_staged = compile_staged_stream_steps(base_step, mesh,
                                             per_replica_bn=per_replica_bn,
                                             state_sharding=state_sharding,
                                             program_hook=program_hook)

    def run(state, step: int, k: int):
        """``step`` is the host-tracked step counter (avoids a device sync);
        it must equal ``state.step`` (the resume path restores both)."""
        if k > steps_per_call:
            raise ValueError(f"chunk of {k} steps exceeds steps_per_call="
                             f"{steps_per_call}; the host step counter "
                             f"would desync from state.step")
        off = step % ds.steps_per_epoch
        if off + k > ds.steps_per_epoch:
            raise ValueError(f"chunk [{step}, {step + k}) crosses the "
                             f"epoch boundary (steps_per_epoch="
                             f"{ds.steps_per_epoch})")
        ds.ensure_epoch(ds.epoch_of(step))
        return run_staged(state, ds.images, ds.labels, off, k)

    return run
