"""Profiling — the TPU equivalents of the reference's tracing hooks
(SURVEY.md §5): tfprof param/FLOP analysis (reference resnet_single.py:58-66
→ tools/analysis.py), ``NCCL_DEBUG=INFO`` transport tracing
(start-resnet-cifar-horovod-train.sh:119) and the Slurm profiling one-liner
(mkl-scripts/profile_dist_ps_cori.sh:1) → ``jax.profiler``:

- ``maybe_start_server(port)`` exposes the live profiler service
  (``train.profiler_port``) so TensorBoard / ``xprof`` can attach to a
  running job — the role NCCL debug output played for transport visibility.
- ``StepTracer`` captures a device trace of a step window
  (``train.profile_steps = "100:120"``) into ``<train_dir>/profile`` —
  the per-step timeline the reference could only infer from
  LoggingTensorHook timestamps (resnet_cifar_train.py:282-287) — and
  reduces it once, on ``stop_trace``, to ``profile/scopes.json`` and five
  log lines (``reduce_capture``): the device's busy share of the window,
  self time by the step's named scopes with forward and backward apart,
  and the longest idle gaps named by the loop's ``train.*`` span under
  each.

The capture keeps device events and ``TraceAnnotation``s and leaves the
Python tracer off (``python_tracer_level=0``, ``host_tracer_level=1``):
with jax's defaults 15 s of an ImageNet run were an 826 MB file that took
``stop_trace`` 150 s and halved the decode rate (PERF.md §3). Its clock
counts from the ENTRY into ``start_trace``; the ``profiler_trace`` span
starts there and carries ``session_zero_mono_ns``, that instant on
``time.monotonic_ns()``, so the loop's spans land on the capture by one
subtraction.
"""

from __future__ import annotations

import glob
import json
import logging
import os
import re
import time
from typing import Dict, Iterable, List, Optional, Tuple

import jax

from tpu_resnet.obs.trace import (_load_profiler_json,
                                  find_device_trace_files)

log = logging.getLogger("tpu_resnet")


_server = None


def maybe_start_server(port: int):
    """Start the profiler gRPC server when ``port`` > 0 (idempotent per
    process — jax allows only one); returns the server handle or None."""
    global _server
    if not port:
        return None
    if _server is None:
        _server = jax.profiler.start_server(port)
        log.info("profiler server listening on :%d (attach with TensorBoard "
                 "profile or xprof)", port)
    return _server


def parse_window(spec: str) -> Optional[Tuple[int, int]]:
    """``"start:stop"`` → (start, stop) step window, or None when empty."""
    if not spec:
        return None
    try:
        a, b = spec.split(":")
        start, stop = int(a), int(b)
    except ValueError:
        raise ValueError(
            f"train.profile_steps must be 'start:stop', got {spec!r}")
    if not 0 <= start < stop:
        raise ValueError(f"bad profile window {spec!r}: need 0 <= start < stop")
    return start, stop


class StepTracer:
    """Drives ``jax.profiler`` start/stop at training-step boundaries.

    The training loop calls ``before(step)`` ahead of dispatching the chunk
    that begins at ``step`` and ``after(step)`` once the host step counter
    has advanced past it. ``boundaries()`` feeds the loop's chunk clipper so
    fused multi-step dispatches never straddle the trace window.
    """

    def __init__(self, train_dir: str, spec: str = "", spans=None,
                 phases: Iterable = ()):
        """``spans`` (an ``obs.SpanTracer``) gets a ``profiler_trace`` span
        on the run timeline for every captured window; ``phases`` is the
        loop's span ring (``obs.StepBreakdown.spans``), read on stop to
        name the capture's idle gaps."""
        self.window = parse_window(spec)
        self.dir = os.path.join(train_dir, "profile")
        self._active = False
        self._spans = spans
        self._phases = phases
        self._entry = self._returned = None  # (wall, mono_ns) marks

    def boundaries(self) -> Tuple[int, ...]:
        return self.window or ()

    def before(self, step: int) -> None:
        if (self.window and not self._active and
                self.window[0] <= step < self.window[1]):
            os.makedirs(self.dir, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            self._entry = (time.time(), time.monotonic_ns())
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self._returned = (time.time(), time.monotonic_ns())
            self._active = True
            log.info("profiler: tracing steps %d..%d into %s (start_trace "
                     "took %.3f s)", self.window[0], self.window[1],
                     self.dir, (self._returned[1] - self._entry[1]) / 1e9)

    def _stop(self, sync) -> None:
        if sync is not None:  # drain async dispatches so the device
            jax.block_until_ready(sync)  # work lands inside the trace
        stop_ns = time.monotonic_ns()
        jax.profiler.stop_trace()
        stopped_ns = time.monotonic_ns()
        self._active = False
        zero_ns = self._entry[1]
        attrs = {"start_step": self.window[0], "stop_step": self.window[1],
                 "dir": self.dir, "session_zero_mono_ns": zero_ns,
                 "start_trace_sec": round(
                     (self._returned[1] - zero_ns) / 1e9, 6),
                 "stop_trace_sec": round((stopped_ns - stop_ns) / 1e9, 6)}
        phases = [(name, start - zero_ns, end - zero_ns)
                  for name, start, end, *_ in list(self._phases)]
        # The interval the loop is in has no span yet: it runs from the
        # last one's end, and its self time is the loop's bookkeeping.
        phases.append(("train.interval", max(
            (end for name, _, end in phases if name == "train.interval"),
            default=0), stop_ns - zero_ns))
        try:
            report = reduce_capture(
                os.path.dirname(self.dir), phases=phases,
                window_ns=(self._returned[1] - zero_ns, stop_ns - zero_ns))
            report.update(start_step=self.window[0],
                          stop_step=self.window[1],
                          start_trace_sec=attrs["start_trace_sec"],
                          stop_trace_sec=attrs["stop_trace_sec"])
            path = os.path.join(self.dir, "scopes.json")
            with open(path, "w") as f:
                json.dump(report, f, indent=1)
            for line in summary_lines(report):
                log.info("profiler: %s", line)
            attrs.update(scopes=path, busy_share=report["busy_share"])
        except Exception as e:  # noqa: BLE001 - must never kill training
            # The capture itself is on disk; the report is a convenience,
            # and a trace laid out otherwise than expected is no reason to
            # end the run this thread is driving.
            log.warning("profiler: no scope report (%s: %s)",
                        type(e).__name__, e)
        if self._spans is not None:
            self._spans.record("profiler_trace", self._entry[0],
                               self._entry[0] + (stopped_ns - zero_ns) / 1e9,
                               mono_ns=zero_ns, **attrs)

    def after(self, step: int, sync=None) -> bool:
        """Returns True when this call closed the trace window — it then
        fully drained the device (the caller's device-backlog sampler
        should treat ``step`` as its new sync point)."""
        if self._active and step >= self.window[1]:
            self._stop(sync)
            log.info("profiler: trace written to %s", self.dir)
            return sync is not None
        return False

    def close(self, sync=None) -> None:
        if self._active:  # training ended inside the window
            self._stop(sync)


# ------------------------------------------------- the capture's reduction
# The step's own scopes (train/step.py, data/device_data.py); Flax's module
# scopes nest beneath ``forward``.
STEP_SCOPES = ("augment", "forward", "loss", "grad_exchange", "optimizer",
               "metrics", "batch_cut", "epoch_shuffle")
_WRAPPED = re.compile(r"^(?:\w+\()+([^()]*)\)+$")  # transpose(jvp(forward))
_SUFFIX = re.compile(r"[.\-_]?\d+$")


def scope_of(op_name: str) -> Tuple[str, str, bool]:
    """``(scope, detail, backward)`` of an operation's scope path, as a
    capture gives it under ``tf_op``
    (``jit(chunk)/while/body/closed_call/transpose(jvp(forward))/ResNetV2/
    block_layer2/block1/conv1/conv_general_dilated:``). The scope is the
    first of ``STEP_SCOPES`` on the path (``other`` where there is none),
    the detail the module two below it (the stage, under ``forward``), and
    backward is what JAX marks ``transpose(...)``."""
    parts = (op_name or "").rstrip(":").split("/")
    backward = any(p.startswith("transpose(") for p in parts)
    for i, part in enumerate(parts):
        m = _WRAPPED.match(part)
        name = m.group(1) if m else part
        if name in STEP_SCOPES:
            below = parts[i + 1:i + 3]
            return name, (below[-1] if len(below) == 2 else ""), backward
    return "other", "", backward


def _device_ops(events: List[dict]) -> List[dict]:
    """The operations of the first device: the ``XLA Ops`` thread of the
    lowest ``/device:`` process. A CPU capture has no such process; there
    the XLA worker threads' events that name an ``hlo_op`` stand in (they
    carry no scope path: everything reads ``other``)."""
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    devices = sorted((name, pid) for pid, name in procs.items()
                     if name.startswith("/device:")
                     and "CUSTOM" not in name)
    if devices:
        pid = devices[0][1]
        lanes = {key for key, name in threads.items()
                 if key[0] == pid and name == "XLA Ops"}
        return [e for e in events if e.get("ph") == "X"
                and (e["pid"], e["tid"]) in lanes]
    return [e for e in events if e.get("ph") == "X"
            and "hlo_op" in (e.get("args") or {})]


def reduce_capture(train_dir: str, window_ns: Tuple[int, int],
                   phases: Iterable[Tuple[str, int, int]] = ()) -> Dict:
    """Reduce the newest capture under ``<train_dir>/profile`` to the
    operator's report. It reads the Chrome-trace export ``stop_trace``
    writes beside its ``.xplane.pb`` (the file ``trace-export
    --device-trace`` merges), which, unlike what ``ProfileData`` shows of
    the latter, carries each operation's scope path (``args.tf_op``).
    ``window_ns`` is the traced window on the capture's clock
    (nanoseconds since the entry into ``start_trace``): every operation is
    clipped to it. ``phases`` are the loop's ``(name, start_ns, end_ns)``
    on the same clock. Self time: an operation counts for the time no
    operation nested in it covers (a ``while`` spans its body), so the
    scopes add up to the busy time."""
    files = find_device_trace_files(train_dir)
    if not files:
        raise ValueError(f"no capture under {train_dir}/profile")
    path = files[0]
    events = _load_profiler_json(path).get("traceEvents", [])
    lo, hi = window_ns[0] / 1e3, window_ns[1] / 1e3  # the file counts in us
    ops = []
    for e in _device_ops(events):
        start, end = max(e["ts"], lo), min(e["ts"] + e.get("dur", 0.0), hi)
        if end > start:
            args = e.get("args") or {}
            ops.append((start, end, args.get("tf_op", ""), e["name"]))
    if not ops:
        raise ValueError(f"{path} holds no device operation inside the "
                         f"window {lo:.0f}..{hi:.0f} us")
    ops.sort(key=lambda o: (o[0], -o[1]))
    by_scope: Dict[Tuple[str, str, bool], float] = {}
    by_family: Dict[Tuple[str, str], float] = {}
    open_ops: List[list] = []  # [end, self_us, tf_op, name]

    def close(until: float) -> None:
        while open_ops and open_ops[-1][0] <= until:
            _, self_us, tf_op, name = open_ops.pop()
            scope, detail, backward = scope_of(tf_op)
            key = (scope, detail, backward)
            by_scope[key] = by_scope.get(key, 0.0) + max(self_us, 0.0)
            fam = (scope, _SUFFIX.sub("", name.split(" ")[0].lstrip("%")))
            by_family[fam] = by_family.get(fam, 0.0) + max(self_us, 0.0)

    busy_us, covered, gaps = 0.0, lo, []
    for start, end, tf_op, name in ops:
        close(start)
        if open_ops:  # nested: the parent loses what this one covers
            open_ops[-1][1] -= min(end, open_ops[-1][0]) - start
        open_ops.append([end, end - start, tf_op, name])
        if start > covered:
            gaps.append((start - covered, covered, start))
        if end > covered:
            busy_us += end - max(start, covered)
            covered = end
    close(float("inf"))
    if hi > covered:
        gaps.append((hi - covered, covered, hi))

    scopes: Dict[str, Dict[str, float]] = {}
    for (scope, _, backward), us in by_scope.items():
        row = scopes.setdefault(scope, {"forward_s": 0.0, "backward_s": 0.0})
        row["backward_s" if backward else "forward_s"] += us / 1e6
    details = sorted(((f"{scope}/{detail}" if detail else scope,
                       "backward" if backward else "forward", us / 1e6)
                      for (scope, detail, backward), us in by_scope.items()),
                     key=lambda r: -r[2])[:24]
    families: Dict[str, List] = {}
    for (scope, fam), us in sorted(by_family.items(), key=lambda kv: -kv[1]):
        if len(families.setdefault(scope, [])) < 5:
            families[scope].append([fam, round(us / 1e6, 6)])
    phases = [(n, s / 1e3, e / 1e3) for n, s, e in phases]
    idle = []
    for length, g_lo, g_hi in sorted(gaps, reverse=True)[:3]:
        # The phase under most of the gap; an interval only where none of
        # its phases is (its self time: the loop's bookkeeping).
        under = max(((n != "train.interval", min(e, g_hi) - max(s, g_lo), n)
                     for n, s, e in phases if e > g_lo and s < g_hi),
                    default=(False, 0.0, "idle"))
        idle.append({"seconds": round(length / 1e6, 6),
                     "at_s": round(g_lo / 1e6, 6), "span": under[2]})
    window_s = (hi - lo) / 1e6
    return {
        "capture": os.path.relpath(path, train_dir),
        "capture_bytes": sum(os.path.getsize(p) for p in glob.glob(
            os.path.join(os.path.dirname(path), "*"))),
        "window_s": round(window_s, 6),
        "busy_s": round(busy_us / 1e6, 6),
        "busy_share": round(busy_us / 1e6 / window_s, 6),
        "operations": len(ops),
        "scopes": {k: {d: round(v, 6) for d, v in row.items()}
                   for k, row in sorted(
                       scopes.items(),
                       key=lambda kv: -sum(kv[1].values()))},
        "details": [[n, d, round(v, 6)] for n, d, v in details],
        "families": families,
        "idle_gaps": idle,
    }


def summary_lines(report: Dict) -> List[str]:
    """The report as the five lines ``StepTracer`` logs."""
    def row(direction: str) -> str:
        return ", ".join(f"{scope} {v[direction] * 1e3:.2f} ms"
                         for scope, v in report["scopes"].items()
                         if v[direction] > 0) or "nothing"

    return [
        f"device busy {report['busy_s']:.4f} s of {report['window_s']:.4f}"
        f" s traced ({100 * report['busy_share']:.2f}%), "
        f"{report['operations']} operations",
        f"self time by scope, forward: {row('forward_s')}",
        f"self time by scope, backward: {row('backward_s')}",
        "longest idle gaps: " + (", ".join(
            f"{g['seconds'] * 1e3:.3f} ms under {g['span']}"
            for g in report["idle_gaps"]) or "none"),
        f"capture {report['capture_bytes'] / 1e6:.1f} MB; start_trace "
        f"{report.get('start_trace_sec', 0):.3f} s, stop_trace "
        f"{report.get('stop_trace_sec', 0):.3f} s; full table in "
        f"scopes.json",
    ]
