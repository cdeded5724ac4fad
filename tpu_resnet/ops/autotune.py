"""Compile-time per-shape A/B probe — every Pallas path earns its slot.

The repo's settled lesson (docs/PERF.md "Pallas fused softmax-xent:
honest verdict"): a hand kernel that loses to XLA's own fusion must not
ride in the hot path on vibes. BENCH_r04 measured the Pallas xent at
0.90x-0.99x of XLA — a live regression shipped behind a config flag.
This module makes the decision mechanical and per-shape:

- ``probe(op, key, pallas_fn, xla_fn, args)`` times BOTH lowerings of
  the identical math with the scan-fused timing harness (per-dispatch
  command latency fused away — the bench's ``_measure_pallas_ab``
  discipline, including the accumulator-perturbed input that stops XLA
  from hoisting the loop body) and records a :class:`Decision`.
- A Pallas path stays enabled only when ``speedup >= threshold``
  (default 1.0); otherwise the caller's trace-time dispatch
  (:func:`use_pallas`) falls back to the XLA lowering. The invariant the
  acceptance gate checks: every decision with ``use_pallas=True`` has
  ``speedup >= 1.0`` by construction.
- Decisions are cached per (op, shape-key) for the process and can be
  persisted to ``<train_dir>/autotune.json`` so a run's dispatch choices
  are reviewable artifacts, not folklore.

Probing is HOST code that runs strictly outside any jit trace (it
compiles and executes both candidates); callers run it once at
step-build time — charged to the compile window, never to a throughput
interval. Trace-time dispatch (:func:`use_pallas`) is a pure dict
lookup.

ORDER CONTRACT: probe BEFORE building/compiling any program that calls
a ``*_auto`` dispatch. jax caches traces on (function identity, avals),
so a program traced pre-probe keeps its XLA fallback even after a later
probe flips the decision — correct but permanently unprofiled. The
train loop observes this: probes run before ``make_train_step``.
"""

from __future__ import annotations

# check: disable-file=jit-host-sync — this module IS the host-side
# prober: timing clocks and the device->host fetch barrier are its whole
# job, and nothing here is jit-reachable by contract (probe() compiles
# and runs its candidates; use_pallas() — the only function traced code
# touches — is a pure dict lookup). It lives under ops/ (the lint's jit
# scope) because the decisions belong with the kernels they gate.

import dataclasses
import json
import logging
import os
import threading
from typing import Callable, Dict, Optional, Tuple

log = logging.getLogger("tpu_resnet")

AUTOTUNE_FILE = "autotune.json"

# A kernel must beat XLA to stay enabled; ties go to XLA (no churn for
# nothing — the XLA path needs no Mosaic compile and no fallback risk).
DEFAULT_THRESHOLD = 1.0


@dataclasses.dataclass
class Decision:
    """One probed (op, shape) point: both timings and the verdict."""

    op: str
    key: str
    pallas_us: float
    xla_us: float
    speedup: float          # xla_us / pallas_us; > 1 means Pallas wins
    use_pallas: bool

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


_lock = threading.Lock()
_decisions: Dict[Tuple[str, str], Decision] = {}


def shape_key(*dims) -> str:
    """Canonical shape-key spelling, e.g. ``b128x1000`` — delegated to
    ``tpu_resnet.programs.spell_shape`` so the autotune decision table
    and the program registry can never drift on how a shape is named
    (key-parity pinned by tests/test_programs.py)."""
    from tpu_resnet.programs import spell_shape

    return spell_shape(*dims)


def decision(op: str, key: str) -> Optional[Decision]:
    with _lock:
        return _decisions.get((op, key))


def decisions() -> Dict[str, dict]:
    """Snapshot of every decision, keyed ``op|key`` (persistable form)."""
    with _lock:
        return {f"{op}|{key}": d.to_dict()
                for (op, key), d in sorted(_decisions.items())}


def reset() -> None:
    """Drop all cached decisions (tests; a backend change mid-process)."""
    with _lock:
        _decisions.clear()


def use_pallas(op: str, key: str, default: bool = False) -> bool:
    """Trace-time dispatch: True only when a probe recorded a Pallas win
    for this (op, shape). Unprobed shapes take ``default`` — callers pass
    False so an unprobed path is always the safe XLA lowering."""
    d = decision(op, key)
    return default if d is None else d.use_pallas


def _record(d: Decision) -> Decision:
    with _lock:
        _decisions[(d.op, d.key)] = d
    return d


def _timed_us(fn: Callable, args: tuple, iters: int) -> float:
    """Mean per-iteration wall micros of ``fn(*args)`` with the whole
    loop fused into ONE dispatch (lax.scan) and the result fetched to the
    host, which cannot complete before every iteration it depends on. The
    first array argument is
    perturbed by the running accumulator so XLA can neither hoist the
    loop-invariant body nor overlap iterations."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    def perturbed(acc):
        head = args[0] + (acc * 1e-30).astype(args[0].dtype)
        return (head,) + tuple(args[1:])

    @jax.jit
    def many():
        def body(acc, _):
            out = fn(*perturbed(acc))
            leaves = jax.tree_util.tree_leaves(out)
            total = sum(jnp.sum(leaf).astype(jnp.float32)
                        for leaf in leaves)
            return acc + total, None

        acc, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=iters)
        return acc

    float(np.asarray(jax.device_get(many())))  # compile + warm
    t0 = time.perf_counter()
    float(np.asarray(jax.device_get(many())))
    return (time.perf_counter() - t0) / iters * 1e6


def probe(op: str, key: str, pallas_fn: Callable, xla_fn: Callable,
          args: tuple, iters: int = 50,
          threshold: float = DEFAULT_THRESHOLD,
          force: bool = False) -> Decision:
    """Time both candidates on identical inputs and record the verdict.

    ``pallas_fn``/``xla_fn`` map ``*args`` to any pytree of arrays (time
    a grad if the hot path is a grad — the caller chooses what to
    measure). Re-probing a cached (op, key) is a no-op unless ``force``.
    A Pallas candidate that fails to compile or run RAISES: a kernel
    Mosaic refuses is a defect to see and repair, and "use XLA" is a
    verdict only a measurement may reach."""
    existing = decision(op, key)
    if existing is not None and not force:
        return existing
    xla_us = _timed_us(xla_fn, args, iters)
    pallas_us = _timed_us(pallas_fn, args, iters)
    speedup = xla_us / pallas_us if pallas_us > 0 else 0.0
    d = _record(Decision(op, key, round(pallas_us, 3), round(xla_us, 3),
                         round(speedup, 4), speedup >= threshold))
    log.info("autotune %s[%s]: pallas %.1fus vs xla %.1fus (%.3fx) -> %s",
             op, key, d.pallas_us, d.xla_us, d.speedup,
             "pallas" if d.use_pallas else "xla")
    return d


# ------------------------------------------------------------- persistence
def dump(train_dir: str) -> Optional[str]:
    """Write the decision table to ``<train_dir>/autotune.json`` (atomic;
    best-effort — telemetry must never kill training). Returns the path
    or None."""
    if not train_dir:
        return None
    try:
        os.makedirs(train_dir, exist_ok=True)
        path = os.path.join(train_dir, AUTOTUNE_FILE)
        tmp = path + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"format": 1, "decisions": decisions()}, f, indent=1)
        os.replace(tmp, path)
        return path
    except OSError as e:  # pragma: no cover - fs-specific
        log.warning("could not write %s: %s", AUTOTUNE_FILE, e)
        return None


def load(path: str) -> int:
    """Seed the cache from a dumped decision table (a tuned box's
    artifact reused on an identical box). Returns entries loaded;
    unreadable/malformed files load nothing."""
    try:
        with open(path) as f:
            payload = json.load(f)
        entries = payload.get("decisions", {})
    except (OSError, ValueError):
        return 0
    n = 0
    for joint, rec in entries.items():
        op, _, key = joint.partition("|")
        try:
            _record(Decision(op, key, float(rec["pallas_us"]),
                             float(rec["xla_us"]), float(rec["speedup"]),
                             bool(rec["use_pallas"])))
            n += 1
        except (KeyError, TypeError, ValueError):
            continue
    return n
