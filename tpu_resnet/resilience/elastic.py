"""Elastic capacity — resume on whatever devices exist.

Production TPU fleets are preemptible and capacity is diurnal: the
machine a run resumes on is routinely NOT the machine it checkpointed
on. Before this module every resume assumed the exact mesh shape and
``mesh.partition`` mode that wrote the checkpoint — a run that lost half
its chips was dead, not degraded (the same rigidity as the reference's
``ps_hosts``/``worker_hosts`` launchers, which could only ever restart
the cluster they were scripted for).

This module makes topology a RUNTIME variable, composing two contracts
the repo already proved separately:

- PR 2's preempt/resume contract: SIGTERM → final checkpoint → exit 42 →
  supervisor restarts → resume at the exact stop step;
- PR 9's cross-partition restore: orbax checkpoints store **global
  logical arrays** (layout-free), and every restore goes through the
  partitioner's abstract template — so restoring into a DIFFERENT layout
  is an explicit, value-identical reshard, never a corruption.

The composition: on restart, :func:`resolve` inspects the devices that
actually exist, re-derives the mesh (``parallel.fit_mesh`` — an explicit
``mesh.data`` that no longer fits shrinks to what does; ``-1`` follows
the hardware in both directions) and hands the loop a mesh whose
partitioner template the checkpoint restores straight into — 8→4→2
chips, replicated↔zero1, any direction. The global batch is the
INVARIANT: per-device batch rescales with the data axis, the host-side
work-order slicing (a pure function of ``(seed, step)`` and the
per-process batch) is untouched, so the deterministic batch stream
continues bit-compatibly across the reshape (ROADMAP's contract; the
``doctor --reshape-drill`` gate).

Every run records the topology it trained on in
``<train_dir>/topology.json`` (:func:`write_topology`); a resume whose
topology differs emits a ``topology_change`` span on the run timeline
and a manifest entry, so trace-export and perfwatch can see capacity
waves instead of inferring them from throughput cliffs.

Colocation (the other half of riding capacity waves): a serve replica
joining a trainer's host asks :func:`colocation_admission` first — the
verdict is arbitrated by the PR 8 live HBM gauges
(``device.memory_stats()``), falling back to the per-chip capacity
table, so admission is a measured decision, not hope. Each tenant then
drains per its established contract (trainer: exit 42; serve: drain,
exit 0).

Import stays jax-free at module level (jax only inside functions): the
supervisor-side and doctor-side consumers read topology records on
hosts whose accelerator stack may be the thing that is broken.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
from typing import Optional

log = logging.getLogger("tpu_resnet")

TOPOLOGY_FILE = "topology.json"

# topology dict keys (one flat schema, stdlib-readable):
#   devices        int   total devices the mesh used
#   mesh_shape     dict  {"data": N, "model": M}
#   partition      str   mesh.partition mode the run trained with
#   global_batch   int   train.global_batch_size (the elastic invariant)
#   device_kind    str   e.g. "TPU v5e" / "cpu"


def topology_record(mesh, partition: str, global_batch: int) -> dict:
    """The one constructor of the topology-record schema — shared by
    :func:`write_topology`, :func:`resolve` and the loop's caller-mesh
    fallback, so the records the reshape diff and the restore-error
    hints compare can never drift field-by-field."""
    devices = list(mesh.devices.flat)
    return {
        "devices": len(devices),
        "mesh_shape": dict(mesh.shape),
        "partition": str(partition),
        "global_batch": int(global_batch),
        "device_kind": devices[0].device_kind if devices else "",
    }


def write_topology(train_dir: str, mesh, partition: str,
                   global_batch: int) -> Optional[str]:
    """Record the topology that is writing this directory's checkpoints
    (primary-only, atomic — the same writer discipline as manifest.json).

    The loop calls this on the FIRST SUCCESSFUL SAVE of a (re)start, not
    at startup: the file must name the topology that wrote the NEWEST
    checkpoints — a resume that reshapes but dies before its first save
    leaves the record pointing at the old topology, so the next resume
    still detects the reshape and restore errors still blame the right
    saver."""
    from tpu_resnet import parallel

    if not parallel.is_primary():
        return None
    record = topology_record(mesh, partition, global_batch)
    os.makedirs(train_dir, exist_ok=True)
    path = os.path.join(train_dir, TOPOLOGY_FILE)
    tmp = path + f".tmp{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(record, f, indent=1)
        os.replace(tmp, path)
    except OSError as e:  # recording is best-effort; training must not die
        log.warning("could not write %s: %s", path, e)
        return None
    return path


def read_topology(train_dir: str) -> Optional[dict]:
    """The topology record of the run that last trained in
    ``train_dir``; None for a fresh directory (or a pre-elastic one)."""
    try:
        with open(os.path.join(train_dir, TOPOLOGY_FILE)) as f:
            rec = json.load(f)
    except (OSError, ValueError):
        return None
    return rec if isinstance(rec, dict) and "mesh_shape" in rec else None


def describe(topology: Optional[dict]) -> str:
    """One-line human form of a topology record ('unknown' when None) —
    shared by the reshape log lines and the restore error hints."""
    if not topology:
        return "unknown (no topology record)"
    return (f"mesh {topology.get('mesh_shape')} "
            f"partition={topology.get('partition')} "
            f"({topology.get('devices')} device(s), "
            f"global batch {topology.get('global_batch')})")


@dataclasses.dataclass
class ElasticResume:
    """The resolved topology decision for one (re)start."""

    mesh: object                    # the concrete Mesh to train on
    current: dict                   # topology record this run will write
    prior: Optional[dict] = None    # record of the run that checkpointed
    downsized: bool = False         # requested mesh.data didn't fit
    requested_data: int = -1        # cfg.mesh.data as configured
    stream_compatible: bool = True  # global batch unchanged vs prior

    @property
    def changed(self) -> bool:
        """True when this run's topology differs from the recorded one —
        the condition for a ``topology_change`` span/manifest entry."""
        if self.prior is None:
            return False
        return any(
            self.prior.get(k) != self.current.get(k)
            for k in ("mesh_shape", "partition", "global_batch"))

    def attrs(self) -> dict:
        """Span/manifest attributes describing the reshape."""
        out = {
            "from_mesh": (self.prior or {}).get("mesh_shape"),
            "to_mesh": self.current["mesh_shape"],
            "from_partition": (self.prior or {}).get("partition"),
            "to_partition": self.current["partition"],
            "from_devices": (self.prior or {}).get("devices"),
            "to_devices": self.current["devices"],
            "global_batch": self.current["global_batch"],
            "stream_compatible": self.stream_compatible,
        }
        if self.downsized:
            out["downsized_from_requested_data"] = self.requested_data
        return out


def resolve(cfg, devices=None, train_dir: Optional[str] = None
            ) -> ElasticResume:
    """Derive the mesh for THIS restart from the devices that actually
    exist, and detect whether that is a reshape of the recorded run.

    - ``mesh.data=-1`` follows the hardware in both directions (today's
      behavior, now recorded as an explicit decision);
    - an explicit ``mesh.data`` that no longer fits is DOWNSIZED to the
      largest data axis the devices support (a warning, a
      ``topology_change`` record — not a dead run);
    - the global batch must divide the new data axis: the global batch
      is the determinism invariant (the host batch stream is a pure
      function of (seed, step) and the per-process batch), so it never
      rescales implicitly — a non-divisible combination raises with
      both topologies named;
    - a CHANGED ``train.global_batch_size`` vs the record is allowed but
      loudly marked ``stream_compatible=False`` — the resumed stream is
      a different stream, and every downstream consumer of the span
      should know.
    """
    import jax

    from tpu_resnet import parallel

    devices = list(devices if devices is not None else jax.devices())
    train_dir = train_dir or cfg.train.train_dir
    requested_data = getattr(cfg.mesh, "data", -1)
    data, model, downsized = parallel.fit_mesh(cfg.mesh, len(devices))
    mesh_cfg = dataclasses.replace(cfg.mesh, data=data, model=model)
    mesh = parallel.create_mesh(mesh_cfg, devices=devices[:data * model])
    prior = read_topology(train_dir)

    if cfg.train.global_batch_size % data:
        raise ValueError(
            f"elastic resume: global batch {cfg.train.global_batch_size} "
            f"does not divide the {data}-way data axis of the mesh this "
            f"host supports ({len(devices)} device(s)); checkpoint "
            f"topology: {describe(prior)}. The global batch is the "
            f"deterministic-stream invariant and never rescales "
            f"implicitly — pick a device count whose data axis divides "
            f"it, or change train.global_batch_size knowingly.")

    current = topology_record(mesh,
                              getattr(cfg.mesh, "partition", "replicated"),
                              cfg.train.global_batch_size)
    resume = ElasticResume(
        mesh=mesh, current=current, prior=prior, downsized=downsized,
        requested_data=requested_data,
        stream_compatible=(prior is None or prior.get("global_batch")
                           == current["global_batch"]))
    if downsized:
        log.warning(
            "elastic resume: mesh.data=%d does not fit on %d device(s) — "
            "downsizing to a %dx%d mesh (checkpoint topology: %s)",
            requested_data, len(devices), data, model, describe(prior))
    if resume.changed:
        log.warning(
            "topology change on resume: %s -> %s — restoring through the "
            "partitioner template (explicit cross-topology reshard)%s",
            describe(prior), describe(current),
            "" if resume.stream_compatible else
            "; GLOBAL BATCH CHANGED: the deterministic (seed, step) batch "
            "stream does NOT continue bit-compatibly")
    return resume


# ------------------------------------------------------ colocation admission
def colocation_admission(required_bytes: int, devices=None,
                         reserve_frac: float = 0.05) -> dict:
    """May a new workload (a serve replica, a second trainer) join this
    host's devices? Arbitrated by the live PR 8 HBM gauges.

    Returns ``{"admit": bool, "reason": str, "required_bytes": int,
    "headroom_bytes": int|None, "in_use_bytes": int, "limit_bytes":
    int|None}``. Decision order:

    1. live ``device.memory_stats()`` (``obs.memory.sample_device_memory``)
       — in-use and limit come from the device itself;
    2. no stats (CPU rehearsal, older plugins): the per-chip capacity
       table supplies the limit and in-use is taken as 0;
    3. no limit from anywhere: admit with an explicit "not arbitrated"
       reason — an un-gauged host must not hard-deny capacity it cannot
       measure, but the verdict says so.

    ``reserve_frac`` holds back a slice of the limit for allocator slack
    and the incumbent's transient peaks (fragmentation, checkpoint
    restore double-residency)."""
    import jax

    from tpu_resnet.obs import memory as memory_obs

    if devices is None:
        devices = jax.local_devices()
    sample = memory_obs.sample_device_memory(devices)
    in_use = int(sample.get("hbm_bytes_in_use", 0))
    limit = sample.get("hbm_bytes_limit")
    if limit is None and devices:
        limit = memory_obs.hbm_bytes_per_chip(
            getattr(devices[0], "device_kind", ""))
    verdict = {"required_bytes": int(required_bytes),
               "in_use_bytes": in_use,
               "limit_bytes": int(limit) if limit else None,
               "headroom_bytes": None}
    if not limit:
        verdict.update(admit=True,
                       reason="no device memory limit known — admission "
                              "not arbitrated on this backend")
        return verdict
    headroom = int(limit * (1.0 - reserve_frac)) - in_use
    verdict["headroom_bytes"] = headroom
    if required_bytes <= headroom:
        verdict.update(admit=True,
                       reason=f"fits: {int(required_bytes):,} B required "
                              f"<= {headroom:,} B headroom")
    else:
        verdict.update(admit=False,
                       reason=f"denied: {int(required_bytes):,} B required "
                              f"> {headroom:,} B headroom "
                              f"({in_use:,} B in use of {int(limit):,} B, "
                              f"{reserve_frac:.0%} reserved)")
    return verdict
