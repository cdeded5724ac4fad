"""Event-span tracer — structured lifecycle events of a run.

The reference's run lifecycle (compiles, checkpoint saves, eval passes)
existed only as interleaved log lines across per-task files (SURVEY.md
§5); reconstructing "what happened when" meant grepping timestamps. The
tracer appends one JSON object per span to ``<dir>/events.jsonl``:

    {"span": "checkpoint_save", "start": <wall>, "end": <wall>,
     "duration_sec": 0.041, "mono_ns": <monotonic>, "id": 17,
     "parent": 3, "step": 3000, "async": true}

``start``/``end`` are wall-clock (``time.time()``) so spans from
different hosts/processes can be laid on one timeline. ``mono_ns`` is the
same instant as ``start`` on ``time.monotonic_ns()``, the one clock every
in-process recorder shares (obs/breakdown.py, tools/profiling.py, the
benchmark's capture marks): ``mono_ns - session_zero_mono_ns`` of a
``profiler_trace`` span puts any span on that capture's timeline. ``id``
is unique in the process; ``parent`` names the span that encloses this
one. docs/OBSERVABILITY.md lists the kinds. The writer is append-only,
line-buffered, idempotent on double-``close()`` and a no-op after close —
shutdown races (daemon threads, atexit, sidecars) can never turn
telemetry into a crash.
"""

from __future__ import annotations

import itertools
import json
import logging
import os
import threading
import time
from contextlib import contextmanager
from typing import Optional

log = logging.getLogger("tpu_resnet")

_ids = itertools.count(1)


def next_span_id() -> int:
    """A span ``id`` unique in this process (``itertools.count`` is
    atomic under the interpreter lock). Allocated ahead of ``record`` by
    a caller whose children must name it as ``parent`` before it ends."""
    return next(_ids)


def read_process_start_ns(stat_path: str = "/proc/self/stat"
                          ) -> Optional[int]:
    """The process's start on ``time.monotonic_ns()``, from the kernel's
    ``starttime`` (field 22 of ``stat_path``: ticks of ``SC_CLK_TCK``
    since boot, so 10 ms at 100 Hz) laid on the monotonic clock through
    ``CLOCK_BOOTTIME``. None where there is no such file or clock."""
    try:
        with open(stat_path) as f:
            stat = f.read()
        # the command (field 2) may hold spaces and ")": count from its end
        ticks = int(stat.rsplit(")", 1)[1].split()[19])
        hz = os.sysconf("SC_CLK_TCK")
        boot_ns = time.clock_gettime_ns(time.CLOCK_BOOTTIME)
        mono_ns = time.monotonic_ns()
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return mono_ns - (boot_ns - ticks * 10 ** 9 // hz)


_process_start: Optional[tuple] = None


def process_start() -> tuple:
    """``(ns, source)``: the process's start on ``time.monotonic_ns()``,
    read once, with ``source`` ``"proc_stat"``; where it cannot be read
    (no ``/proc``), the first line of the package's import instead, with
    ``source`` ``"package_import"``."""
    global _process_start
    if _process_start is None:
        from tpu_resnet import IMPORT_NS

        ns = read_process_start_ns()
        _process_start = ((ns, "proc_stat") if ns is not None
                          and ns <= IMPORT_NS
                          else (IMPORT_NS, "package_import"))
    return _process_start


class SpanTracer:
    def __init__(self, directory: str, enabled: bool = True,
                 filename: str = "events.jsonl",
                 run_id: str = None):
        """``run_id`` (obs/manifest.py::ensure_run_id) is stamped on
        every record — the correlation key obs/trace.py uses to lay
        trainer/eval/serve files on one timeline. Mutable: a sidecar
        that starts before the trainer minted the id can set
        ``tracer.run_id`` once discovered."""
        self.enabled = enabled
        self.run_id = run_id
        self._pid = os.getpid()
        # One reading of both clocks: every record is converted through
        # it, so two records of one tracer keep their distance on either.
        self._wall0, self._mono0 = time.time(), time.monotonic_ns()
        self._f = None
        if not enabled:
            return
        os.makedirs(directory, exist_ok=True)
        self._f = open(os.path.join(directory, filename), "a", buffering=1)

    def record(self, kind: str, start: float, end: float, **attrs) -> None:
        """Append one finished span given in wall-clock seconds. Safe
        after ``close()`` (no-op). ``id`` and ``mono_ns`` are filled in
        where the caller passes none; ``parent`` only where it does."""
        if self._f is None:
            return
        rec = {"span": kind, "start": round(start, 6), "end": round(end, 6),
               "duration_sec": round(end - start, 6), "pid": self._pid,
               "mono_ns": self._mono0 + int((start - self._wall0) * 1e9)}
        if self.run_id is not None:
            rec["run_id"] = self.run_id
        rec.update(attrs)
        if rec.get("id") is None:
            rec["id"] = next_span_id()
        if rec.get("parent") is None:
            rec.pop("parent", None)
        try:
            self._f.write(json.dumps(rec) + "\n")
        except ValueError:  # closed underneath us in a shutdown race
            self._f = None

    def record_ns(self, kind: str, start_ns: int, end_ns: int,
                  **attrs) -> None:
        """Append a span taken on ``time.monotonic_ns()`` (the breakdown's
        ring, a compile): ``mono_ns`` is exact, wall times are derived."""
        start = self._wall0 + (start_ns - self._mono0) / 1e9
        self.record(kind, start, start + (end_ns - start_ns) / 1e9,
                    mono_ns=int(start_ns), **attrs)

    def event(self, kind: str, **attrs) -> None:
        """Instantaneous marker (zero-duration span)."""
        now = time.time()
        self.record(kind, now, now, **attrs)

    @contextmanager
    def span(self, kind: str, **attrs):
        """Time a block as a span. Yields the attrs dict so the body can
        attach results (e.g. ``a["precision"] = p``) and read the span's
        ``id`` for its children; an exception is recorded on the span and
        re-raised."""
        t0, t0_ns = time.time(), time.monotonic_ns()
        attrs.setdefault("id", next_span_id())
        try:
            yield attrs
        except BaseException as e:
            attrs.setdefault("error", f"{type(e).__name__}: {e}"[:200])
            raise
        finally:
            self.record(kind, t0, t0 + (time.monotonic_ns() - t0_ns) / 1e9,
                        mono_ns=t0_ns, **attrs)

    def close(self) -> None:
        if self._f is not None:
            f, self._f = self._f, None
            try:
                f.close()
            except OSError:  # pragma: no cover - fs-specific
                pass


class TailSampler:
    """Tail-based retention decision for per-request tracing spans.

    Recording every request as a span would make the event log grow
    linearly with traffic — useless at fleet rates and a disk hazard on
    a long-lived replica. The sampler keeps exactly the traces an
    operator pulls up after an incident:

    * every error / shed / retried / hedged request (always kept),
    * everything slower than a rolling latency quantile ("the slowest
      percentile" — the p99 excursions the fleet plane exists to
      explain),
    * plus a thinning baseline sample of healthy traffic whose period
      doubles as volume accumulates, so steady-state kept-span volume is
      O(log N) in request count — sublinear by construction (asserted in
      tests/test_fleet.py).

    ``observe()`` returns the keep *reason* (stamped on the span as the
    ``sampled`` attr so readers know why a trace exists) or ``None`` to
    drop. Pure in-memory decision under its own lock; callers write the
    span *outside* any lock, keeping the concurrency engine's
    blocking-under-lock rule clean.
    """

    ALWAYS_KEEP = ("error", "shed", "retry", "hedge")

    def __init__(self, quantile: float = 0.95, base_period: int = 50,
                 ring: int = 512, min_samples: int = 100):
        self.quantile = float(quantile)
        self._lock = threading.Lock()
        self._ring = [0.0] * int(ring)
        self._n = 0                     # total observations
        self._kept_baseline = 0         # baseline keeps since last doubling
        self._period = int(base_period)
        self._since_sample = 0          # observations since last baseline keep
        self._threshold = None          # cached rolling quantile
        self._min_samples = int(min_samples)
        self._kept = 0

    def _slow_threshold(self) -> Optional[float]:
        """Rolling nearest-rank quantile over the latency ring, recomputed
        lazily every ~100 observations (sorting 512 floats per request
        would be hot-path noise)."""
        if self._n < self._min_samples:
            return None
        if self._threshold is None or self._n % 100 == 0:
            vals = sorted(self._ring[:min(self._n, len(self._ring))])
            idx = min(len(vals) - 1,
                      max(0, int(self.quantile * len(vals) + 0.5) - 1))
            self._threshold = vals[idx]
        return self._threshold

    def observe(self, latency_ms: float, error: bool = False,
                shed: bool = False, retried: bool = False,
                hedged: bool = False) -> Optional[str]:
        """Record one request; return the keep reason or None (drop)."""
        with self._lock:
            self._ring[self._n % len(self._ring)] = float(latency_ms)
            self._n += 1
            self._since_sample += 1
            reason = None
            if error:
                reason = "error"
            elif shed:
                reason = "shed"
            elif retried:
                reason = "retry"
            elif hedged:
                reason = "hedge"
            else:
                thr = self._slow_threshold()
                if thr is not None and latency_ms > thr:
                    reason = "slow"
                elif self._since_sample >= self._period:
                    reason = "sampled"
                    self._since_sample = 0
                    self._kept_baseline += 1
                    if self._kept_baseline >= 64:
                        self._kept_baseline = 0
                        self._period *= 2
            if reason is not None:
                self._kept += 1
            return reason

    def stats(self) -> dict:
        with self._lock:
            return {"observed": self._n, "kept": self._kept,
                    "period": self._period,
                    "slow_threshold_ms": self._threshold}


def load_jsonl(path: str, require_key: str):
    """Torn-tail-tolerant jsonl reader: one dict per parseable line that
    carries ``require_key``; partial trailing lines (live writer, crash
    mid-write) are skipped, not errors. The single tolerance policy shared
    by the span and metrics readers."""
    out = []
    if not os.path.exists(path):
        return out
    with open(path) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if require_key in rec:
                out.append(rec)
    return out


def load_spans(path: str):
    """``events.jsonl`` → list of span records."""
    return load_jsonl(path, "span")
