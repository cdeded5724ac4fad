"""Orbax checkpointing — replaces MonitoredTrainingSession's Saver
(reference resnet_cifar_train.py:330-342, ``save_checkpoint_steps=1000``)
and the implicit resume-on-restart contract
(resnet_imagenet_train.py:267-270).

Only process 0 drives saves (the reference's chief / Horovod rank-0 rule,
resnet_cifar_main.py:328) — orbax handles the multi-host coordination for
sharded arrays itself. Consumers: the train loop (periodic save + resume),
the polling evaluator (latest_step watching — the analog of
``tf.train.get_checkpoint_state`` polling, resnet_cifar_eval.py:102), the
export path and the inspector tool.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import orbax.checkpoint as ocp


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 5, spans=None,
                 topology: Optional[dict] = None):
        """``spans`` (an ``obs.SpanTracer``) records checkpoint_save /
        checkpoint_restore spans on the run's events.jsonl timeline.
        ``topology`` (a ``resilience.elastic`` topology record) names
        the mesh/partition THIS consumer restores into — joined with the
        directory's recorded save topology in restore errors, so a
        template/shard mismatch reads as "saved on mesh8 zero1, you
        asked for mesh4 replicated", not a raw pytree diff."""
        self.directory = os.path.abspath(directory)
        self._spans = spans
        self._topology = topology
        os.makedirs(self.directory, exist_ok=True)
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=keep,
                create=True,
                enable_async_checkpointing=True,
            ),
        )

    def save(self, step: int, state, force: bool = False) -> bool:
        import time

        t0 = time.time()
        saved = self._mgr.save(step, args=ocp.args.StandardSave(state),
                               force=force)
        if saved and self._spans is not None:
            # Async checkpointing: the span covers the blocking enqueue
            # (serialization handoff), not the background write.
            self._spans.record("checkpoint_save", t0, time.time(),
                               step=int(step), **{"async": True})
        return saved

    def restore(self, state_template, step: Optional[int] = None,
                fallback: Optional[bool] = None,
                discard_failed: bool = False,
                parent: Optional[int] = None):
        """Restore into the structure/shardings of ``state_template``.

        ``fallback`` (default: on exactly when ``step`` is None) is the
        corrupt-checkpoint recovery path: if the newest checkpoint fails to
        restore — torn write from a preempted host, bad storage — fall back
        through ``all_steps()`` to the newest *restorable* one (we keep
        ``keep``, default 5) instead of raising. An explicitly requested
        step (evaluator, export) fails loudly by default: silently serving
        an older step than asked for would corrupt eval curves.

        ``discard_failed`` additionally deletes/quarantines the steps that
        failed to restore once a fallback succeeds. Only the *trainer's*
        resume path sets it (the process that owns the directory and will
        re-reach those step numbers, colliding on save): a read-only
        consumer (export, a notebook) must never destroy a checkpoint that
        merely failed transiently for *it*.

        ``parent`` is the ``id`` of the caller's span (the loop's
        ``train.restore``) that the restore spans name as theirs."""
        import logging
        import time

        if fallback is None:
            fallback = step is None
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.directory}")
        candidates = [step]
        if fallback:
            candidates += sorted((s for s in self.all_steps() if s < step),
                                 reverse=True)
        abstract = jax.tree_util.tree_map(ocp.utils.to_shape_dtype_struct,
                                          state_template)
        log = logging.getLogger("tpu_resnet")
        last_err = None
        failed = []
        for i, cand in enumerate(candidates):
            t0 = time.time()
            try:
                restored = self._mgr.restore(
                    cand, args=ocp.args.StandardRestore(abstract))
            except Exception as e:  # noqa: BLE001 - any restore failure
                last_err = e
                failed.append(cand)
                log.warning("checkpoint step %d failed to restore (%s: %s)%s",
                            cand, type(e).__name__, e,
                            " — falling back to the previous step"
                            if i + 1 < len(candidates) else "")
                if self._spans is not None:
                    self._spans.record(
                        "checkpoint_restore_failed", t0, time.time(),
                        step=int(cand), parent=parent,
                        error=f"{type(e).__name__}: {e}"[:200])
                continue
            attrs = {"step": int(cand), "parent": parent}
            if cand != candidates[0]:
                attrs["fallback_from_step"] = int(candidates[0])
            if self._spans is not None:
                self._spans.record("checkpoint_restore", t0, time.time(),
                                   **attrs)
            if discard_failed:
                # Trainer resume: the unrestorable newer steps must go —
                # latest_step()/pollers would keep finding them, and the
                # resumed run will re-reach those step numbers and collide
                # with the corrupt directories on save.
                self._discard(failed, log)
            return restored
        raise RuntimeError(
            f"no restorable checkpoint in {self.directory}: all of "
            f"{candidates} failed; newest error: "
            f"{type(last_err).__name__}: {last_err}"
            f"{self._topology_hint()}") from last_err

    def _topology_hint(self) -> str:
        """Topology context for a failed restore: the directory's
        recorded save topology vs what this consumer asked for. A shard/
        template mismatch after a capacity change surfaces as an opaque
        pytree/sharding error without this — naming both topologies
        turns it into an actionable line (docs/RESILIENCE.md)."""
        from tpu_resnet.resilience import elastic

        saved = elastic.read_topology(self.directory)
        if saved is None and self._topology is None:
            return ""
        hint = (f"\ncheckpoint topology: {elastic.describe(saved)}"
                f"\nrequested topology:  {elastic.describe(self._topology)}")
        if saved and self._topology and any(
                saved.get(k) != self._topology.get(k)
                for k in ("mesh_shape", "partition", "global_batch")):
            hint += ("\nthe topologies differ — an elastic resume "
                     "reshards through the partitioner template "
                     "(resilience/elastic.py), but global array shapes "
                     "and the global batch must stay compatible")
        return hint

    def _discard(self, steps, log) -> None:
        """Remove checkpoints that failed to restore (delete via orbax so
        its step cache stays coherent; quarantine-rename as a fallback)."""
        for bad in steps:
            try:
                self._mgr.delete(bad)
                log.warning("removed unrestorable checkpoint step %d", bad)
            except Exception:  # noqa: BLE001 - best-effort quarantine
                src = os.path.join(self.directory, str(bad))
                try:
                    os.rename(src, src + ".corrupt")
                    log.warning("quarantined unrestorable checkpoint step "
                                "%d as %s.corrupt", bad, src)
                except OSError as e:
                    log.warning("could not remove corrupt checkpoint step "
                                "%d: %s", bad, e)

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def all_steps(self):
        return self._mgr.all_steps()

    def wait(self):
        self._mgr.wait_until_finished()

    def close(self):
        self._mgr.wait_until_finished()
        self._mgr.close()


def partitioned_template(cfg, mesh, model=None):
    """Abstract TrainState restore template laid out by the run's
    partitioner — the ONE way every read-only consumer (eval sidecar,
    serve hot-reload, export) describes what restore should produce.

    Built with ``jax.eval_shape`` + sharded ShapeDtypeStructs, so no
    device buffer is ever allocated for the template itself, and orbax
    restores each leaf STRAIGHT into the layout ``cfg.mesh.partition``
    declares: a zero1 checkpoint restores into its optimizer-slot
    shards without materializing a replicated copy on any device.

    Cross-TOPOLOGY restores are an EXPLICIT reshard, never a silent
    corruption: orbax checkpoints store global logical arrays (layout-
    free), so restoring a zero1-saved checkpoint into a replicated
    template (or vice versa), or a mesh8-saved checkpoint into a mesh4
    template (or vice versa — ``mesh`` here is simply the mesh the
    CURRENT process built over the devices it actually has,
    resilience/elastic.py), reassembles the same global values in the
    template's layout — pinned by tests/test_partition.py and the
    tests/test_elastic.py cross-mesh matrix. A partition mode the
    partitioner cannot satisfy on this mesh raises its per-leaf
    ``validate`` error here, before any restore I/O."""
    import jax

    from tpu_resnet import parallel
    from tpu_resnet.models import build_model, sample_input
    from tpu_resnet.train import schedule as sched_lib
    from tpu_resnet.train.state import init_state

    if model is None:
        model = build_model(cfg)
    schedule = sched_lib.build_schedule(cfg.optim, cfg.train)
    abstract = jax.eval_shape(
        lambda: init_state(model, cfg.optim, schedule,
                           jax.random.PRNGKey(0), sample_input(cfg)))
    partitioner = parallel.make_partitioner(cfg.mesh, mesh)
    return partitioner.abstract_state(abstract)


def latest_step_in(directory: str) -> Optional[int]:
    """Cheap latest-checkpoint probe for pollers (the eval sidecar's analog
    of ``tf.train.get_checkpoint_state``, resnet_cifar_eval.py:102)."""
    directory = os.path.abspath(directory)
    if not os.path.isdir(directory):
        return None
    steps = ocp.utils.checkpoint_steps(directory)
    return max(steps) if steps else None


def restore_with_retry(ckpt, template, step: int, retries: int = 3,
                       backoff_sec: float = 0.5, sleep=None):
    """Restore ``step`` with bounded exponential-backoff retries.

    The trainer's saves are async: a poller (eval sidecar, serve
    hot-reload) can see a step whose directory is still mid-commit, and a
    single transient restore failure used to kill the whole polling loop.
    Returns the restored state, or None after ``retries`` failures — the
    caller skips-and-logs the step instead of crashing; the next committed
    checkpoint restores fine. Shared by ``evaluation/evaluator.py`` and
    ``serve/backend.py`` (extracted so the backoff/skip-and-log logic
    can't drift between the two pollers)."""
    import logging
    import time

    if sleep is None:
        sleep = time.sleep
    log = logging.getLogger("tpu_resnet")
    for attempt in range(max(1, retries)):
        try:
            return ckpt.restore(template, step=step)
        except Exception as e:  # noqa: BLE001 - any restore failure
            wait = backoff_sec * (2 ** attempt)
            log.warning("restore of checkpoint step %d failed "
                        "(attempt %d/%d, %s: %s)%s", step, attempt + 1,
                        max(1, retries), type(e).__name__, e,
                        f"; retrying in {wait:.1f}s"
                        if attempt + 1 < max(1, retries) else "")
            if attempt + 1 < max(1, retries):
                sleep(wait)
    return None


class CheckpointPoller:
    """Newest-step watcher over a train dir — the shared poll half of the
    eval sidecar and the serve hot-reload loop. ``poll()`` returns a step
    exactly once: a step is reported only while it is the newest AND has
    not been marked seen (``mark_seen`` — callers mark both successful
    restores and skipped-after-retries steps so the poll never spins on a
    checkpoint that will not restore)."""

    def __init__(self, directory: str):
        self.directory = os.path.abspath(directory)
        self.last_seen: Optional[int] = None

    def poll(self) -> Optional[int]:
        step = latest_step_in(self.directory)
        if step is not None and step != self.last_seen:
            return step
        return None

    def mark_seen(self, step: int) -> None:
        self.last_seen = int(step)
