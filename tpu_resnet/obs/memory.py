"""Memory observability — the space twin of the MFU/FLOPs accounting.

Every remaining scaling direction is a memory/compute trade the system
could not see: ZeRO-style optimizer-state sharding promises an ~N× cut
per device (arXiv:2004.13336) that nothing could measure, pod meshes
live or die on per-host HBM headroom (arXiv:2204.06514), and the MFU
campaign's next knobs (batch, remat, donation) move temp HBM as much as
they move FLOP/s. This module gives a run the same discipline
``obs/mfu.py`` gave time — measured once, gauged live, pinned golden:

``MemoryLedger``        per-compiled-program HBM budgets extracted from
                        ``compiled.memory_analysis()`` (argument/output/
                        temp/alias/generated-code bytes — donation shows
                        up as aliased bytes), keyed EXACTLY like the
                        FlopsRegistry / golden-jaxpr entries
                        (``train|cifar10_rn50_bf16|mesh8x1|b128``) and
                        persisted to ``<train_dir>/memory.json``.
``sample_device_memory``live per-device HBM gauges via
                        ``device.memory_stats()`` at existing log
                        boundaries — a pure host call, zero device
                        syncs; degrades to absent on backends without
                        stats (CPU), where the pre-declared gauges stay
                        at their explicit zeros.
``write_oom_report``    OOM forensics: on a RESOURCE_EXHAUSTED the loop/
                        serve closer chains persist
                        ``<train_dir>/oom_report.json`` — the last
                        ledger, the recent memory samples, a live-array
                        census (``jax.live_arrays()`` bucketed by
                        shape/dtype/sharding) and the offending program
                        key — so an OOM on a pod is a diagnosable
                        artifact instead of a dead log line.
``HBM_BYTES_BY_KIND``   per-device-kind HBM capacity (public chip
                        specs), the peak-FLOPs table's memory twin, for
                        ``hbm_utilization`` on backends whose
                        ``memory_stats()`` lacks a ``bytes_limit``.

The ledger extraction is the one place this subsystem pays real compile
time: ``memory_analysis()`` only exists on a COMPILED program, and jax's
AOT path shares no cache with the jit-dispatch executable, so
``account_train_step`` costs one extra XLA compile. It runs once per
run, inside the compile window (the loop re-primes its throughput meter
after it), is gated by ``train.memory_ledger`` and degrades to absent —
never a per-step or per-interval cost. The lint suite bans every
introspection call here from jit scope (docs/CHECKS.md, jit-host-sync).

Module import stays jax-free (jax only inside functions) so stdlib-only
consumers (bench.py's parent, tools/perfwatch.py, the doctor checks) can
read ledger files and the capacity table without a backend.
"""
# check: disable-file=jit-host-sync — this module IS the host-side
# memory prober: device.memory_stats()/jax.live_arrays()/
# .memory_analysis() are its whole purpose, called from host code at
# startup, log boundaries and crash handlers only, never from jit scope.

from __future__ import annotations

import collections
import json
import logging
import os
import time
from typing import Dict, List, Optional

log = logging.getLogger("tpu_resnet")

LEDGER_FILE = "memory.json"
OOM_REPORT_FILE = "oom_report.json"

# Per-chip HBM capacity in bytes by device_kind substring (public chip
# specs) — the memory twin of mfu.PEAK_FLOPS_BY_KIND, and the
# ``bytes_limit`` fallback for PJRT plugins whose memory_stats() report
# usage but no capacity. Order matters: more specific names first.
_GIB = 1024 ** 3
HBM_BYTES_BY_KIND = (
    ("v5p", 95 * _GIB),
    ("v5 lite", 16 * _GIB), ("v5e", 16 * _GIB), ("v5litepod", 16 * _GIB),
    ("v6 lite", 32 * _GIB), ("v6e", 32 * _GIB),
    ("v4", 32 * _GIB),
)

# Budget components extracted from CompiledMemoryStats, in report order.
BUDGET_COMPONENTS = ("argument_bytes", "output_bytes", "temp_bytes",
                     "alias_bytes", "generated_code_bytes")


def hbm_bytes_per_chip(device_kind: str) -> Optional[int]:
    """HBM capacity in bytes for one chip of ``device_kind``; None when
    the kind is not in the table (CPU, new silicon)."""
    kind = (device_kind or "").lower()
    for sub, cap in HBM_BYTES_BY_KIND:
        if sub in kind:
            return cap
    return None


def budget_from_compiled(compiled) -> Optional[dict]:
    """HBM budget of a compiled program from its
    ``compiled.memory_analysis()`` (None when the backend doesn't report
    one). Bytes are for one device's compiled module (the per-shard SPMD
    program). ``alias_bytes`` is the donation credit: input buffers the
    outputs alias — a broken donation collapses it to ~0 and every step
    double-buffers the state. ``peak_bytes`` counts each aliased byte
    once (argument + output - alias + temp + generated_code)."""
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # noqa: BLE001 - accounting must never crash
        log.debug("memory analysis unavailable: %s", e)
        return None
    if ma is None:
        return None

    def grab(name: str) -> int:
        try:
            return int(getattr(ma, name, 0) or 0)
        except (TypeError, ValueError):
            return 0

    budget = {
        "argument_bytes": grab("argument_size_in_bytes"),
        "output_bytes": grab("output_size_in_bytes"),
        "temp_bytes": grab("temp_size_in_bytes"),
        "alias_bytes": grab("alias_size_in_bytes"),
        "generated_code_bytes": grab("generated_code_size_in_bytes"),
    }
    budget["peak_bytes"] = (budget["argument_bytes"]
                            + budget["output_bytes"]
                            - budget["alias_bytes"]
                            + budget["temp_bytes"]
                            + budget["generated_code_bytes"])
    return budget


class MemoryLedger:
    """Per-compiled-program HBM budget entries, persisted per run.

    One entry per program key (the FlopsRegistry key spelling, so
    ``memory.json`` and ``flops.json`` describe the same certified
    programs): the budget components plus provenance (device kind,
    device count, per-chip capacity). ``<train_dir>/memory.json`` is
    what trace-export, the doctor mem-probe and operators read back."""

    def __init__(self):
        self._entries: Dict[str, dict] = {}

    def register(self, key: str, budget: Optional[dict], **extra) -> dict:
        entry = dict(budget) if budget else {"budget_source": "none"}
        if budget:
            entry["budget_source"] = "xla_memory_analysis"
        entry.update(extra)
        self._entries[key] = entry
        return entry

    def get(self, key: str) -> Optional[dict]:
        return self._entries.get(key)

    def keys(self) -> List[str]:
        return sorted(self._entries)

    def to_dict(self) -> dict:
        return {"format": 1, "entries": dict(self._entries)}

    def save(self, train_dir: str) -> Optional[str]:
        """Atomic ``<train_dir>/memory.json`` (tmp + rename, like every
        other run artifact)."""
        try:
            os.makedirs(train_dir, exist_ok=True)
            path = os.path.join(train_dir, LEDGER_FILE)
            tmp = path + f".tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(self.to_dict(), f, indent=1)
            os.replace(tmp, path)
            return path
        except OSError as e:
            log.warning("could not write %s: %s", LEDGER_FILE, e)
            return None

    @classmethod
    def load(cls, train_dir: str) -> "MemoryLedger":
        ledger = cls()
        try:
            with open(os.path.join(train_dir, LEDGER_FILE)) as f:
                payload = json.load(f)
            ledger._entries.update(payload.get("entries", {}))
        except (OSError, ValueError):
            pass
        return ledger


def lower_train_step(cfg, mesh, state, base_step,
                     per_replica_bn: bool = False,
                     stage_rows: int = 1, chunk_steps: int = 1,
                     variant: str = "single-step",
                     partitioner=None):
    """Lower the train-step program the run's input edge actually
    dispatches, over abstract avals — the ONE shared builder behind the
    HBM (this module) and comms (``obs/comms.py``) accountants, so both
    ledgers describe the same compiled program: ``stage_rows > 1``
    builds the fused staged-chunk jit (``device_data.staged_chunk_jit``,
    the loop's exact constructor — superbatch arguments and scan temps
    included), else the plain sharded single step with the loop's real
    donation and partitioner shardings. Returns ``(lowered, variant)``
    where ``variant`` labels the program shape on ledger entries."""
    import jax

    from tpu_resnet import parallel
    from tpu_resnet.programs.registry import batch_avals
    from tpu_resnet.train.step import shard_step

    state_sharding = (partitioner.state_shardings(state)
                     if partitioner is not None and partitioner.is_sharded
                     else None)
    if stage_rows > 1:
        # The staged/double-buffered input edge's fused chunk program —
        # built by the ONE canonical constructor the loop itself
        # dispatches (device_data.staged_chunk_jit), so a ledger entry
        # can never describe a different program than the run executes.
        from tpu_resnet.data.device_data import staged_chunk_jit

        jitted = staged_chunk_jit(base_step, mesh, max(1, chunk_steps),
                                  per_replica_bn=per_replica_bn,
                                  state_sharding=state_sharding)
        gi, gl = batch_avals(cfg, rows=stage_rows)
        off = jax.ShapeDtypeStruct((), "int32")
        lowered = jitted.lower(state, gi, gl, off)
        variant = (f"staged-chunk(steps={max(1, chunk_steps)}"
                   f",stage={stage_rows})")
    else:
        images, labels = batch_avals(cfg, parallel.batch_sharding(mesh))
        probe = shard_step(base_step, mesh, per_replica_bn=per_replica_bn,
                           state_sharding=state_sharding)
        lowered = probe.lower(state, images, labels)
    return lowered, variant


def account_train_step(cfg, mesh, state, base_step,
                       per_replica_bn: bool = False,
                       stage_rows: int = 1, chunk_steps: int = 1,
                       variant: str = "single-step",
                       partitioner=None,
                       ledger: Optional[MemoryLedger] = None,
                       train_dir: Optional[str] = None) -> dict:
    """Measure and register the train step's HBM budget for ``cfg`` on
    ``mesh``. Called ONCE per run at first dispatch, inside the compile
    window: unlike the FLOPs probe (lowering only), ``memory_analysis``
    needs a COMPILED program and jax's AOT compile shares no cache with
    the already-paid jit dispatch — this is one extra XLA compile,
    amortized over the run and gated by ``train.memory_ledger``.

    The probe compiles the program the run's input edge actually
    dispatches, with the loop's real donation settings, over abstract
    avals: ``stage_rows > 1`` measures the fused staged-chunk program
    (``compile_staged_stream_steps``'s exact jit — superbatch arguments
    and scan temps included), else the plain sharded single step. The
    ``variant`` label is recorded on the entry so an OOM report says
    which program shape its budget describes (the resident path's
    epoch-buffer program is approximated by its single-step twin, and
    says so).

    ``partitioner`` (parallel.StatePartitioner) supplies the run's state
    layout: the probe compiles with the same in_shardings the loop
    dispatches (zero1 = per-shard optimizer-slot arguments) and the
    entry carries the partitioner's analytic per-component breakdown
    (``params_argument_bytes`` / ``opt_state_argument_bytes`` /
    ``batch_stats_argument_bytes``), so the zero1 optimizer cut is a
    named number next to XLA's aggregate ``argument_bytes``."""
    from tpu_resnet.obs.mfu import train_program_key

    ledger = ledger if ledger is not None else MemoryLedger()
    key = train_program_key(cfg, dict(mesh.shape))
    gb = cfg.train.global_batch_size
    lowered, variant = lower_train_step(
        cfg, mesh, state, base_step, per_replica_bn=per_replica_bn,
        stage_rows=stage_rows, chunk_steps=chunk_steps, variant=variant,
        partitioner=partitioner)
    budget = budget_from_compiled(lowered.compile())
    kind = mesh.devices.flat[0].device_kind
    extra = {}
    if partitioner is not None:
        extra["partition"] = partitioner.describe()
        try:
            extra.update(partitioner.state_argument_bytes(state))
        except Exception as e:  # noqa: BLE001 - accounting must not crash
            log.debug("state argument breakdown unavailable: %s", e)
    entry = ledger.register(
        key, budget, program_key=key, program=variant, global_batch=gb,
        device_kind=kind, n_devices=int(mesh.size),
        hbm_bytes_per_chip=hbm_bytes_per_chip(kind), **extra)
    if train_dir:
        ledger.save(train_dir)
    return entry


# ------------------------------------------------------------- live gauges
def sample_device_memory(devices=None) -> Dict[str, float]:
    """One live HBM sample across this host's devices — the gauge values
    the loop publishes at log boundaries. Pure host-side introspection
    (``device.memory_stats()``), zero device syncs.

    Returns ``{}`` when no device reports stats (CPU backends) — the
    degrade-to-absent contract; the pre-declared gauges then stay at
    their explicit zeros. Otherwise: ``hbm_bytes_in_use`` /
    ``hbm_bytes_peak`` are the MAX across local devices (the binding
    device), ``hbm_bytes_limit`` the MIN reported limit (falling back to
    the :data:`HBM_BYTES_BY_KIND` capacity) and ``hbm_utilization`` =
    in_use / limit."""
    if devices is None:
        import jax

        devices = jax.local_devices()
    in_use = peak = 0
    limit: Optional[int] = None
    kind = ""
    seen = False
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 - plugin-specific failures
            stats = None
        if not stats or stats.get("bytes_in_use") is None:
            continue
        seen = True
        kind = kind or getattr(d, "device_kind", "")
        used = int(stats["bytes_in_use"])
        in_use = max(in_use, used)
        peak = max(peak, int(stats.get("peak_bytes_in_use", used)))
        lim = stats.get("bytes_limit")
        if lim:
            limit = int(lim) if limit is None else min(limit, int(lim))
    if not seen:
        return {}
    out = {"hbm_bytes_in_use": float(in_use),
           "hbm_bytes_peak": float(peak)}
    if limit is None:
        limit = hbm_bytes_per_chip(kind)
    if limit:
        out["hbm_bytes_limit"] = float(limit)
        out["hbm_utilization"] = round(in_use / limit, 4)
    return out


def device_memory_detail(devices=None) -> List[dict]:
    """Per-device ``memory_stats()`` snapshot (id/kind + the raw stats
    dict, or ``stats: null`` where unsupported) — the OOM report's
    device section; the gauges above stay scalar."""
    if devices is None:
        import jax

        devices = jax.local_devices()
    detail = []
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001
            stats = None
        detail.append({"id": int(getattr(d, "id", -1)),
                       "device_kind": str(getattr(d, "device_kind", "?")),
                       "stats": {k: int(v) for k, v in stats.items()
                                 if isinstance(v, (int, float))}
                       if stats else None})
    return detail


class MemorySampleRing:
    """Last-N ring of (wall, step, gauges) memory samples the loop keeps
    so an OOM report can show the minutes BEFORE the kill, not just the
    corpse."""

    def __init__(self, capacity: int = 32):
        self._ring = collections.deque(maxlen=max(1, int(capacity)))

    def add(self, step: int, sample: Dict[str, float]) -> None:
        if sample:
            self._ring.append({"wall": round(time.time(), 3),
                               "step": int(step), **sample})

    def snapshot(self) -> List[dict]:
        return list(self._ring)

    def __len__(self) -> int:
        return len(self._ring)


# ------------------------------------------------------------ OOM forensics
def is_oom_error(exc) -> bool:
    """True for an XLA RESOURCE_EXHAUSTED failure (device out of
    memory). Duck-typed on the class NAME plus the canonical status
    string so this stays importable without jax and also recognizes the
    fault injector's synthetic OOM (a plain RuntimeError carrying the
    same status)."""
    if exc is None or "RESOURCE_EXHAUSTED" not in str(exc):
        return False
    return (type(exc).__name__ == "XlaRuntimeError"
            or isinstance(exc, (RuntimeError, MemoryError)))


def live_array_census(max_buckets: int = 50) -> dict:
    """``jax.live_arrays()`` bucketed by (shape, dtype, sharding):
    count, per-bucket bytes (global logical bytes), sorted largest
    first and capped at ``max_buckets`` buckets (the drop count is
    reported — never a silent truncation). The answer to "WHAT was
    filling HBM" that a bare RESOURCE_EXHAUSTED message never gives."""
    try:
        import jax

        arrays = jax.live_arrays()
    except Exception as e:  # noqa: BLE001 - forensics must never raise
        return {"error": f"{type(e).__name__}: {e}", "buckets": [],
                "total_arrays": 0, "total_bytes": 0}
    buckets: Dict[tuple, dict] = {}
    total_bytes = 0
    for a in arrays:
        try:
            shape = tuple(int(s) for s in a.shape)
            dtype = str(a.dtype)
            sharding = str(getattr(a, "sharding", "?"))[:120]
            nbytes = int(getattr(a, "nbytes", 0))
        except Exception:  # noqa: BLE001 - a deleted/donated buffer
            continue
        key = (shape, dtype, sharding)
        b = buckets.setdefault(key, {"shape": list(shape), "dtype": dtype,
                                     "sharding": sharding, "count": 0,
                                     "bytes": 0})
        b["count"] += 1
        b["bytes"] += nbytes
        total_bytes += nbytes
    ranked = sorted(buckets.values(),
                    key=lambda b: (-b["bytes"], -b["count"],
                                   b["dtype"], b["shape"]))
    return {"buckets": ranked[:max_buckets],
            "dropped_buckets": max(0, len(ranked) - max_buckets),
            "total_arrays": sum(b["count"] for b in ranked),
            "total_bytes": total_bytes}


def write_oom_report(train_dir: str, error, context: str = "train",
                     step: Optional[int] = None,
                     program_key: Optional[str] = None,
                     ledger: Optional[MemoryLedger] = None,
                     samples: Optional[List[dict]] = None,
                     run_id: Optional[str] = None) -> Optional[str]:
    """Persist ``<train_dir>/oom_report.json`` for a RESOURCE_EXHAUSTED
    failure: the error, the offending program key, the last ledger, the
    recent gauge samples, a live-array census and per-device stats.
    Guarded end-to-end (forensics on a dying process must never mask the
    original exception); returns the path or None."""
    try:
        report = {
            "format": 1,
            "written_at": time.time(),
            "context": str(context),
            "step": int(step) if step is not None else None,
            "run_id": run_id,
            "error": {"type": type(error).__name__,
                      "message": str(error)[:4000]},
            "program_key": program_key,
            "ledger": (ledger.to_dict().get("entries", {})
                       if ledger is not None else
                       MemoryLedger.load(train_dir).to_dict()["entries"]),
            "memory_samples": list(samples or []),
            "live_arrays": live_array_census(),
            "devices": device_memory_detail(),
        }
        os.makedirs(train_dir, exist_ok=True)
        path = os.path.join(train_dir, OOM_REPORT_FILE)
        tmp = path + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(report, f, indent=1)
        os.replace(tmp, path)
        log.error("RESOURCE_EXHAUSTED: OOM forensics written to %s "
                  "(program %s, %d live-array buckets)", path,
                  program_key, len(report["live_arrays"]["buckets"]))
        return path
    except Exception as e:  # noqa: BLE001 - never mask the real failure
        log.warning("could not write %s: %s", OOM_REPORT_FILE, e)
        return None


def validate_oom_report(report: dict) -> List[str]:
    """Schema check for an oom_report.json payload, shared by the tests
    and ``doctor --mem-probe``. Returns a list of problems (empty =
    valid)."""
    problems: List[str] = []
    if not isinstance(report, dict):
        return ["report is not a JSON object"]
    for key, types in (("format", int), ("written_at", (int, float)),
                       ("context", str), ("error", dict),
                       ("ledger", dict), ("memory_samples", list),
                       ("live_arrays", dict), ("devices", list)):
        if key not in report:
            problems.append(f"missing required key {key!r}")
        elif not isinstance(report[key], types):
            problems.append(f"{key!r} has wrong type "
                            f"{type(report[key]).__name__}")
    err = report.get("error")
    if isinstance(err, dict):
        if not err.get("type") or not err.get("message"):
            problems.append("error must carry type and message")
        elif "RESOURCE_EXHAUSTED" not in err["message"]:
            problems.append("error.message does not mention "
                            "RESOURCE_EXHAUSTED")
    census = report.get("live_arrays")
    if isinstance(census, dict):
        for key in ("buckets", "total_arrays", "total_bytes"):
            if key not in census:
                problems.append(f"live_arrays missing {key!r}")
        for i, b in enumerate(census.get("buckets", [])):
            if not isinstance(b, dict) or not {"shape", "dtype", "count",
                                               "bytes"} <= set(b):
                problems.append(f"live_arrays.buckets[{i}] malformed")
                break
    for i, s in enumerate(report.get("memory_samples", [])):
        if not isinstance(s, dict) or "wall" not in s or "step" not in s:
            problems.append(f"memory_samples[{i}] malformed")
            break
    return problems
