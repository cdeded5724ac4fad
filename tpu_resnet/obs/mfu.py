"""MFU accounting — first-class FLOPs/utilization bookkeeping.

The MLPerf TPU-pod scaling report (arXiv:1909.09756) and the pjit TPUv4
training report (arXiv:2204.06514) both drive optimization campaigns off
hardware-utilization accounting, not throughput alone: a steps/sec win
that came from doing less math is not a win. Before this module the
repo's FLOPs math lived ad hoc in two places (bench.py's imagenet entry
and tools/mfu_probe.py) and a *running job* never knew its own MFU. Now:

``PEAK_FLOPS_BY_KIND``  per-device-kind peak dense bf16 FLOP/s (public
                        chip specs), the one table bench/probe/loop share.
``program_flops``       FLOPs of a compiled/lowered XLA program from its
                        cost analysis (handles the list/dict API forms).
``FlopsRegistry``       per-compiled-program FLOPs registry, keyed like
                        the golden-jaxpr entries of the config-matrix
                        verifier (``train|cifar10_rn50_bf16|mesh1x1|b128``)
                        so a FLOPs number is attributable to exactly one
                        certified program shape. Persisted to
                        ``<train_dir>/flops.json`` for tools.
``mfu``                 model FLOPs utilization: achieved model FLOP/s
                        over the mesh's aggregate peak.

Cost analysis runs on the *lowered* (pre-optimization) module via
``jit_fn.lower(...)`` — no second XLA compile, and the pre-fusion count
is the model-FLOPs definition MFU wants (XLA-added recompute, e.g.
remat, is utilization it would be cheating to claim). The lint suite
enforces that these host-side introspection calls never appear in jit
scope (docs/CHECKS.md, rule jit-host-sync): accounting happens once at
compile time, gauges are pure host arithmetic at log boundaries.

Module import stays jax-free (jax appears only inside functions) so
stdlib-only consumers (bench.py's parent process, perfwatch) can use the
peak table and registry file reader without a backend.
"""

from __future__ import annotations

import json
import logging
import os
from typing import Dict, Optional

log = logging.getLogger("tpu_resnet")

REGISTRY_FILE = "flops.json"

# Peak dense bf16 FLOP/s per chip by device_kind substring (public
# specs). Order matters: more specific names first. The single source the
# bench harness, tools/mfu_probe.py and the live mfu gauge all read.
PEAK_FLOPS_BY_KIND = (
    ("v5p", 459e12),
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5litepod", 197e12),
    ("v6 lite", 918e12), ("v6e", 918e12),
    ("v4", 275e12),
)


def peak_flops_per_chip(device_kind: str) -> Optional[float]:
    """Peak dense FLOP/s for one chip of ``device_kind``; None when the
    kind is not in the table (CPU, new silicon) — an unknown chip gets no
    assumed peak."""
    kind = (device_kind or "").lower()
    for sub, peak in PEAK_FLOPS_BY_KIND:
        if sub in kind:
            return peak
    return None


def program_flops(cost) -> Optional[float]:
    """FLOPs from an XLA cost analysis — ``lowered.cost_analysis()`` or
    ``compiled.cost_analysis()``. None when the backend doesn't report
    them."""
    flops = (cost or {}).get("flops")
    return float(flops) if flops and flops > 0 else None


def lowered_flops(jit_fn, *args) -> Optional[float]:
    """FLOPs of ``jit_fn``'s program for ``args`` via AOT lowering (no
    XLA compile — tracing + HLO cost analysis only). ``args`` may mix
    concrete arrays and ``jax.ShapeDtypeStruct`` avals. The count covers
    the module as written (pre-SPMD-partitioning): for an auto-sharded
    jit program that is the GLOBAL per-step FLOPs."""
    try:
        return program_flops(jit_fn.lower(*args).cost_analysis())
    except Exception as e:  # noqa: BLE001 - never sink the caller
        log.warning("lowered cost analysis unavailable (%s: %s)",
                    type(e).__name__, e)
        return None


def analytic_resnet50_flops(batch: int, image: int = 224) -> float:
    """Analytic fallback: ResNet-50 forward ≈ 4.09 G multiply-adds per
    224² image (He et al.), two FLOPs each; training ≈ 3× forward (fwd +
    2×bwd): 24.5 GFLOP an image, 3.6% over the count from shapes that
    leaves out the taps on the padding (benchmarks/lib/flops.py: 23.69).
    Scaled by pixel area for other resolutions. GLOBAL per-step FLOPs for
    ``batch``."""
    return 3 * 2 * 4.09e9 * batch * (image / 224.0) ** 2


def mfu(model_flops_per_sec: Optional[float], device_kind: str,
        n_chips: int) -> Optional[float]:
    """Model FLOPs utilization: achieved model FLOP/s over the aggregate
    peak of ``n_chips`` chips of ``device_kind``. None when either side
    is unknown — an unknown chip reports no number rather than a wrong
    one."""
    peak = peak_flops_per_chip(device_kind)
    if not peak or not model_flops_per_sec or n_chips < 1:
        return None
    return model_flops_per_sec / (peak * n_chips)


def train_program_key(cfg, mesh_shape: Dict[str, int],
                      kind: str = "train") -> str:
    """Registry key for the compiled program of ``cfg`` on a mesh:

        train|cifar10_rn50_bf16|mesh1x1|b128

    Pure delegation to :func:`tpu_resnet.programs.spell` — the ONE
    spelling the FLOPs registry, the memory ledger, the check engines'
    coverage map and the AOT executable cache all share (one key = one
    program; key-parity is pinned by tests/test_programs.py).
    ``data.engine`` is deliberately NOT part of the key: thread and
    process engines feed byte-identical programs (the engine-invariance
    twins the verifier pins), so their FLOPs must be one entry.
    ``mesh.partition`` IS: a zero1 step is a different compiled program
    (per-shard optimizer-slot arguments, reduce-scatter/all-gather
    structure), so its space budget must never be read as the
    replicated twin's.
    """
    from tpu_resnet.programs import spell

    return spell(cfg, mesh_shape, kind=kind)


class FlopsRegistry:
    """Per-compiled-program FLOPs entries, persisted per run.

    One entry per program key: global per-step FLOPs, the source of the
    number (xla_cost_analysis | analytic | none), bytes accessed when
    known. The registry file (``<train_dir>/flops.json``) is what
    trace-export, perfwatch and operators read back."""

    def __init__(self):
        self._entries: Dict[str, dict] = {}

    def register(self, key: str, flops_per_step: Optional[float],
                 source: str = "xla_cost_analysis", **extra) -> dict:
        entry = {"flops_per_step": flops_per_step,
                 "flops_source": source if flops_per_step else "none"}
        entry.update(extra)
        self._entries[key] = entry
        return entry

    def get(self, key: str) -> Optional[dict]:
        return self._entries.get(key)

    def flops(self, key: str) -> Optional[float]:
        entry = self._entries.get(key) or {}
        return entry.get("flops_per_step")

    def to_dict(self) -> dict:
        return {"format": 1, "entries": dict(self._entries)}

    def save(self, train_dir: str) -> Optional[str]:
        """Atomic ``<train_dir>/flops.json`` (tmp + rename, like every
        other run artifact)."""
        try:
            os.makedirs(train_dir, exist_ok=True)
            path = os.path.join(train_dir, REGISTRY_FILE)
            tmp = path + f".tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(self.to_dict(), f, indent=1)
            os.replace(tmp, path)
            return path
        except OSError as e:
            log.warning("could not write %s: %s", REGISTRY_FILE, e)
            return None

    @classmethod
    def load(cls, train_dir: str) -> "FlopsRegistry":
        reg = cls()
        try:
            with open(os.path.join(train_dir, REGISTRY_FILE)) as f:
                payload = json.load(f)
            reg._entries.update(payload.get("entries", {}))
        except (OSError, ValueError):
            pass
        return reg


def account_train_step(cfg, mesh, state, base_step,
                       per_replica_bn: bool = False,
                       registry: Optional[FlopsRegistry] = None,
                       train_dir: Optional[str] = None) -> dict:
    """Measure and register the train step's per-step FLOPs for ``cfg``
    on ``mesh``. Called ONCE per run right after the first dispatch
    (compile already paid; this adds one abstract trace + HLO cost pass,
    never a second XLA compile). Returns the registry entry.

    The probe lowers the plain sharded single step over abstract batch
    avals — the same program every input path (resident chunks, staged
    superbatches, streaming) runs per step, so one entry covers all
    three dispatch shapes."""
    from tpu_resnet import parallel
    from tpu_resnet.models import family
    from tpu_resnet.programs.registry import batch_avals
    from tpu_resnet.train.step import shard_step

    registry = registry or FlopsRegistry()
    key = train_program_key(cfg, dict(mesh.shape))
    gb = cfg.train.global_batch_size
    fam = family(cfg)
    per_example = fam.train_flops_per_example(cfg)
    if per_example is None:
        probe = shard_step(base_step, mesh, donate_state=False,
                           per_replica_bn=per_replica_bn)
        flops = lowered_flops(probe, state, *batch_avals(
            cfg, parallel.batch_sharding(mesh)))
        source = "xla_cost_analysis"
        if flops is None:
            per_example = fam.train_flops_per_example(cfg,
                                                      xla_counted=False)
        elif per_replica_bn:
            # The shard_map body is lowered per-shard: scale the local
            # count back to the global batch so the entry means the same
            # thing on every mesh shape.
            flops *= mesh.shape["data"]
    if per_example is not None:
        flops, source = gb * per_example, "analytic"
    kind = mesh.devices.flat[0].device_kind
    entry = registry.register(
        key, flops, source=source, global_batch=gb,
        device_kind=kind, n_devices=int(mesh.size),
        peak_flops_per_chip=peak_flops_per_chip(kind))
    if train_dir:
        registry.save(train_dir)
    return entry
