"""Hang watchdog — stalls become diagnosable events, not silent hangs.

A wedged collective, a dead data source, or a blocked host thread leaves
the reference trainer sitting at 0% CPU forever; the only signal is an
operator noticing the log went quiet (SURVEY.md §5). The watchdog is a
daemon thread fed a cheap ``progress(step)`` call at every chunk boundary.
When no progress lands for ``stall_sec``:

- all-thread stacks are dumped to ``<train_dir>/stall_stacks_<n>.txt``
  (the "where is it stuck" evidence, captured while it is stuck);
- the telemetry registry is marked unhealthy, so ``/healthz`` answers 503
  with the stall reason even though the heartbeat-staleness threshold
  (``train.telemetry_stale_sec``, typically minutes) has not tripped yet;
- a ``watchdog_stall`` span is recorded and the
  ``fault_watchdog_stalls`` gauge incremented.

If progress then resumes (transient stall — a slow storage blip, a
recovered data source), the unhealthy mark is cleared and a
``watchdog_recovered`` span records the outage length. Timing is armed by
the FIRST ``progress()`` call, so the first-dispatch compile (minutes on
a cold pod) can never false-trigger it.
"""

from __future__ import annotations

import logging
import os
import sys
import threading
import time
import traceback
from typing import Optional

log = logging.getLogger("tpu_resnet")


def dump_all_stacks(path: str, reason: str = "") -> None:
    """Write every live thread's stack to ``path`` (best-effort)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    lines = [f"# all-thread stack dump @ {time.strftime('%F %T')}"]
    if reason:
        lines.append(f"# reason: {reason}")
    for ident, frame in sys._current_frames().items():
        lines.append(f"\n--- thread {names.get(ident, '?')} ({ident}) ---")
        lines.extend(l.rstrip() for l in traceback.format_stack(frame))
    try:
        with open(path, "w") as f:
            f.write("\n".join(lines) + "\n")
    except OSError as e:  # diagnostics must never crash the diagnosed
        log.warning("could not write stack dump %s: %s", path, e)


class HangWatchdog:
    """``maybe_start`` returns None when ``stall_sec <= 0`` (disabled)."""

    def __init__(self, stall_sec: float, train_dir: str, telemetry=None,
                 spans=None, poll_sec: Optional[float] = None,
                 on_stall=None):
        """``on_stall`` is called once per stall, after the stack dump:
        the train loop writes its in-memory phase spans out there
        (obs/breakdown.py), so a hang leaves what the loop last did."""
        self.stall_sec = float(stall_sec)
        self.train_dir = train_dir
        self._telemetry = telemetry
        self._spans = spans
        self._dump_spans = on_stall
        self._poll = poll_sec if poll_sec else min(self.stall_sec / 4, 5.0)
        self._lock = threading.Lock()
        self._last_wall: Optional[float] = None  # armed by first progress()
        self._last_step: Optional[int] = None
        self._stalled_since: Optional[float] = None
        self.stalls = 0
        self.dumps = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run,
                                        name="tpu-resnet-watchdog",
                                        daemon=True)

    @classmethod
    def maybe_start(cls, stall_sec: float, train_dir: str, telemetry=None,
                    spans=None, on_stall=None
                    ) -> Optional["HangWatchdog"]:
        if stall_sec is None or stall_sec <= 0:
            return None
        wd = cls(stall_sec, train_dir, telemetry=telemetry, spans=spans,
                 on_stall=on_stall)
        wd.start()
        return wd

    def start(self) -> "HangWatchdog":
        self._thread.start()
        return self

    def progress(self, step: int) -> None:
        """Mark step progress; called at every chunk boundary (a lock +
        two assignments — nanoseconds against a multi-ms chunk)."""
        with self._lock:
            self._last_wall = time.monotonic()
            self._last_step = int(step)

    def close(self) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=self._poll + 5)

    # ------------------------------------------------------------ internals
    def _run(self) -> None:
        while not self._stop.wait(self._poll):
            with self._lock:
                last_wall, last_step = self._last_wall, self._last_step
            if last_wall is None:  # not armed yet (still compiling)
                continue
            stalled = time.monotonic() - last_wall
            if stalled > self.stall_sec and self._stalled_since is None:
                self._stalled_since = last_wall
                self._on_stall(last_step, stalled)
            elif stalled <= self.stall_sec and self._stalled_since \
                    is not None:
                outage = last_wall - self._stalled_since
                self._stalled_since = None
                self._on_recover(last_step, outage)

    def _on_stall(self, step, stalled_sec: float) -> None:
        n = self.stalls + 1
        path = os.path.join(self.train_dir, f"stall_stacks_{n}.txt")
        reason = (f"no step progress for {stalled_sec:.1f}s "
                  f"(> watchdog deadline {self.stall_sec:.1f}s) at step "
                  f"{step}")
        log.error("watchdog: %s — dumping all-thread stacks to %s and "
                  "flipping /healthz unhealthy", reason, path)
        dump_all_stacks(path, reason=reason)
        self.dumps.append(path)
        if self._telemetry is not None:
            self._telemetry.mark_unhealthy(reason)
            self._telemetry.set("fault_watchdog_stalls", n)
        if self._spans is not None:
            self._spans.event("watchdog_stall", step=step,
                              stalled_sec=round(stalled_sec, 3),
                              stack_dump=path)
        if self._dump_spans is not None:
            try:
                self._dump_spans()
            except Exception as e:  # noqa: BLE001 - the dump is best effort
                log.warning("watchdog: span dump failed: %s", e)
        # Published last: pollers of ``stalls`` see the dump/telemetry/
        # span side effects already landed.
        self.stalls = n

    def _on_recover(self, step, outage_sec: float) -> None:
        log.warning("watchdog: step progress resumed at step %s after a "
                    "%.1fs stall — clearing the unhealthy mark",
                    step, outage_sec)
        if self._telemetry is not None:
            self._telemetry.clear_unhealthy()
        if self._spans is not None:
            self._spans.event("watchdog_recovered", step=step,
                              outage_sec=round(outage_sec, 3))
