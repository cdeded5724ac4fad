"""Step-time breakdown — the train loop's span recorder.

The reference could only *infer* step timing from LoggingTensorHook
timestamps (reference resnet_cifar_train.py:282-287); whether a run was
input-bound, dispatch-bound or device-bound was guesswork. The recorder
keeps what the loop thread was doing as spans on ONE clock,
``time.monotonic_ns()`` — the clock ``tools/profiling.StepTracer`` and the
benchmark mark their captures with — so any span can be laid on any
capture by one subtraction.

Every phase of a loop iteration is a span ``(name, start_ns, end_ns, id,
parent, step, steps, attrs)`` appended to a bounded in-memory ring; the
dispatch path never touches a file. The ring is written to
``events.jsonl`` by the loop's closer chain and by the watchdog's hang
dump. The parent of an iteration's phases is the ``train.interval`` span
that runs from one synced log boundary to the next (it is cut where the
boundary's ``block_until_ready`` returns, the one instant the device is
known to be drained):

``train.data_wait``    blocked in ``next(data_iter)`` — the input edge
                       can't keep up.
``train.dispatch``     enqueueing the jitted chunk (host→device command
                       path), with ``train.epoch_shuffle`` beneath it when
                       the resident buffer is rebuilt.
``train.device_wait``  the boundary's ``block_until_ready``: the device-
                       compute backlog — ≈0 when the host is the
                       bottleneck, ≈ device step time × interval steps
                       when the device is. It ends the interval.
``train.log_fetch``    ``device_get`` of the chunk's metrics.
``train.log_write``    from the fetch's end to the end of
                       ``metrics.write``.
``train.checkpoint``   the call into ``ckpt.save`` and the commit wait.

A span's self time is its length less its children's; the interval's self
time is the loop's own bookkeeping. The same context managers enter
``jax.profiler.TraceAnnotation`` (and ``StepTraceAnnotation`` around each
dispatch), a flag test while no profiler session is active, so a capture
shows the phases on its host plane.

Start-up phases and compiles are spans too, but durable ones: they go to a
pending list (``keep=True``) that the loop writes out at its log
boundaries, never to the ring a long run overwrites. A process-wide
``jax.monitoring`` listener turns every backend compile or cache load into
a ``compile`` span that names the step, the program and the phase it fell
in, and every jaxpr trace and lowering to MLIR into a ``trace`` or
``lower`` span the same way; a recompile in the middle of a run is a few
lines of ``events.jsonl``.

What came before ``train()`` is two more durable spans, from the same
clock: ``process.before_train`` (the process's start, read from
``/proc``, to the entry into ``train()``; obs/spans.py::process_start) and
``process.import`` beneath it (the first line of ``tpu_resnet/__init__``
to the end of ``train/loop.py``'s module body, where ``package_imported``
is called and the listeners are registered). A compile made while no
recorder listens (the benchmark's planting of its checkpoint, a compile
between two ``train()`` calls of one process) is a ``compile`` span under
the next ``process.before_train`` and counts in
``before_train_compile_sec``, never in ``compile_load_sec``.

``interval()`` drains the sums of the interval that just closed into the
run's ``metrics.jsonl`` record. The first dispatch — which pays XLA
tracing + compilation — is reported separately as ``compile_seconds`` and
excluded from the first interval so throughput numbers are never polluted
by compile time. Each interval's record also carries ``process_age_sec``,
the seconds from the process's start to the interval's synced end.
"""

from __future__ import annotations

import collections
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

from tpu_resnet.obs.spans import next_span_id, process_start

# name, start_ns, end_ns, id, parent, step, steps, attrs
Span = Tuple[str, int, int, int, Optional[int], Optional[int], int,
             Optional[dict]]

_TIMED_EVENTS = {"/jax/core/compile/backend_compile_duration": "compile",
                 "/jax/core/compile/jaxpr_trace_duration": "trace",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower"}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# The recorder the process-wide jax.monitoring listeners feed. train() runs
# many times in one test process and jax keeps a listener for good, so the
# listeners are registered once and look the recorder up here.
_current: Optional["StepBreakdown"] = None
_listening = False
_imported_ns: Optional[int] = None  # where process.import ends


class _BeforeTrain:
    """The compiles heard while no recorder listens, each a ``compile``
    span under the ``process.before_train`` span (``id``) that the next
    recorder writes; bounded like the ring."""

    def __init__(self):
        self.id = next_span_id()
        self.spans: collections.deque = collections.deque(maxlen=4096)
        self.compile_sec = 0.0
        self.cache_hit = False

    def compile(self, seconds: float, program: Optional[str]) -> None:
        end_ns = time.monotonic_ns()
        self.compile_sec += seconds
        hit, self.cache_hit = self.cache_hit, False
        self.spans.append((
            "compile", end_ns - int(seconds * 1e9), end_ns, next_span_id(),
            self.id, None, 0,
            {"seconds": round(seconds, 4), "program": program,
             "cache_hit": hit, "during": "process.before_train"}))


_before = _BeforeTrain()


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT_EVENT:
        rec = _current
        if rec is not None:
            rec._on_cache_hit()
        else:
            _before.cache_hit = True


def _on_duration(event: str, duration: float, **kw) -> None:
    kind = _TIMED_EVENTS.get(event)
    if kind is None:
        return
    rec = _current
    if rec is not None:
        rec._on_timed(kind, duration, kw.get("fun_name"))
    elif kind == "compile":
        _before.compile(duration, kw.get("fun_name"))


def listen() -> None:
    """Register the process-wide ``jax.monitoring`` listeners (once)."""
    global _listening
    if not _listening:
        import jax

        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


def package_imported() -> None:
    """The end of the package's import (``train/loop.py``'s module body):
    ``process.import`` ends here, and from here on the listeners hear the
    compiles made before ``train()``. The package's ``__init__`` stays
    free of jax (hostenv, chip_smoke's parent), so they cannot start
    earlier."""
    global _imported_ns
    if _imported_ns is None:
        _imported_ns = time.monotonic_ns()
    listen()


class _Open:
    """A phase that has begun: what ``end`` needs to finish its span."""

    __slots__ = ("name", "id", "parent", "step", "steps", "keep", "attrs",
                 "start_ns", "annotation")


class StepBreakdown:
    """Records the loop thread's phases as spans and drains per-interval
    sums; see the module docstring. ``ring`` bounds the spans kept in
    memory."""

    def __init__(self, ring: int = 4096):
        import jax

        global _current, _before
        entry_ns = time.monotonic_ns()
        self._annotate = jax.profiler.TraceAnnotation
        self._annotate_step = jax.profiler.StepTraceAnnotation
        self._block = jax.block_until_ready
        self.spans: collections.deque = collections.deque(maxlen=ring)
        self.compile_seconds: Optional[float] = None
        self.startup_sec: Optional[float] = None
        self.compile_load_sec = 0.0
        self.trace_lower_sec = 0.0
        self.compile_parent: Optional[int] = None
        self._pending: List[Span] = []
        self._stack: List[_Open] = []
        # the outermost trace, lower and compile spans heard so far, as
        # (start_ns, end_ns, span): one that encloses earlier ones (a jit
        # traced inside another's trace) counts only its own time
        self._outer: collections.deque = collections.deque(maxlen=ring)
        self._thread = threading.get_ident()
        self._cache_hit = False
        self._closed: Optional[Dict[str, float]] = None
        self._start_ns, source = process_start()
        self._open_interval(entry_ns, None)
        self._stall_from: Optional[int] = None
        listen()
        # What came before: the process's start to this entry, the
        # package's import beneath it, the compiles heard meanwhile.
        before, _before = _before, _BeforeTrain()
        self.before_train_sec = (entry_ns - self._start_ns) / 1e9
        self.before_train_compile_sec = before.compile_sec
        self.import_sec: Optional[float] = None
        self._pending.append((
            "process.before_train", self._start_ns, entry_ns, before.id,
            None, None, 0, {"process_start": source}))
        if _imported_ns is not None:
            from tpu_resnet import IMPORT_NS

            self.import_sec = (_imported_ns - IMPORT_NS) / 1e9
            self._pending.append((
                "process.import", IMPORT_NS, _imported_ns, next_span_id(),
                before.id, None, 0, None))
        self._pending.extend(before.spans)
        _current = self

    # ------------------------------------------------------------- spans
    def begin(self, name: str, step: Optional[int] = None, steps: int = 0,
              keep: bool = False, **attrs) -> _Open:
        """Open a phase beneath the innermost open one (else the current
        interval). ``keep`` makes the span durable: it goes to the pending
        list instead of the ring."""
        op = _Open()
        op.name, op.id, op.step, op.steps = name, next_span_id(), step, steps
        if self._stack:
            op.parent = self._stack[-1].id
        else:  # a durable span is no part of an interval
            op.parent = None if keep else self._interval_id
        op.keep, op.attrs = keep, attrs or None
        op.annotation = (self._annotate(name) if step is None
                         else self._annotate(name, step=step))
        op.annotation.__enter__()
        self._stack.append(op)
        op.start_ns = time.monotonic_ns()
        return op

    def end(self, op: _Open, **attrs) -> int:
        """Close ``op``, and first anything still open inside it; returns
        the instant on the monotonic clock."""
        end_ns = time.monotonic_ns()
        if attrs:
            op.attrs = {**(op.attrs or {}), **attrs}
        while self._stack:
            top = self._stack.pop()
            top.annotation.__exit__(None, None, None)
            (self._pending if top.keep else self.spans).append((
                top.name, top.start_ns, end_ns, top.id, top.parent,
                top.step, top.steps, top.attrs))
            if top is op:
                break
        return end_ns

    @contextmanager
    def phase(self, name: str, step: Optional[int] = None, steps: int = 0,
              keep: bool = False, **attrs):
        """Time a block as a span; yields the open phase (its ``id`` is
        the ``parent`` of what a caller records beside the recorder)."""
        op = self.begin(name, step, steps, keep, **attrs)
        try:
            yield op
        finally:
            self.end(op)

    @contextmanager
    def data_wait(self, step: Optional[int] = None):
        """Time a blocking ``next(data_iter)``."""
        op = self.begin("train.data_wait", step)
        try:
            yield
        finally:
            self._data_wait_ns += self.end(op) - op.start_ns

    @contextmanager
    def dispatch(self, step: Optional[int] = None, steps: int = 0):
        """Time the (normally async) dispatch of a chunk of ``steps``
        steps that begins at ``step``."""
        step_annotation = self._annotate_step(
            "train_chunk", step_num=0 if step is None else step)
        step_annotation.__enter__()
        op = self.begin("train.dispatch", step, steps)
        try:
            yield
        finally:
            end_ns = self.end(op)
            step_annotation.__exit__(None, None, None)
            self._dispatch_ns += end_ns - op.start_ns
            self._steps_dispatched += steps
            if self._stall_from is not None:
                # The device had nothing queued since the last drain; this
                # return is the first moment it has work again.
                self._stall_ns += end_ns - self._stall_from
                self._stall_from = None

    def sample_device(self, sync, steps: int,
                      step: Optional[int] = None) -> float:
        """Block on the newest chunk's result at a log boundary
        (``train.device_wait``) and close the interval where the wait
        ends. ``steps`` is the number of steps dispatched since the last
        full sync. Returns the wait in seconds."""
        op = self.begin("train.device_wait", step, steps)
        self._block(sync)
        end_ns = self.end(op)
        self._device_wait_ns = waited_ns = end_ns - op.start_ns
        self._closed = self._close_interval(end_ns, step)
        self._stall_from = end_ns
        return waited_ns / 1e9

    def first_dispatch_done(self, sync) -> float:
        """Call right after the first dispatch of the run returns: blocks
        until the chunk is ready and records ``compile_seconds`` — the
        first-dispatch wall time (jit trace + XLA compile + the first
        chunk's device run). Start-up ends here: every phase still open is
        closed, the outermost one's length is ``startup_sec``, and the
        first real interval opens, so the first logged interval excludes
        compile entirely (the throughput meter is re-primed at the same
        point)."""
        op = self.begin("train.device_wait")
        self._block(sync)
        end_ns = self.end(op)
        # Everything since the interval clock was last reset minus time
        # blocked on input: the dispatch call (trace + compile) plus the
        # first chunk's device run.
        self.compile_seconds = (end_ns - self._interval_start
                                - self._data_wait_ns) / 1e9
        self.compile_parent = None
        if self._stack:
            root = self._stack[0]
            self.startup_sec = (self.end(root) - root.start_ns) / 1e9
        self.reset_interval()
        return self.compile_seconds

    # ---------------------------------------------------------- compiles
    def _on_cache_hit(self) -> None:
        if threading.get_ident() == self._thread:
            self._cache_hit = True

    def _on_timed(self, kind: str, seconds: float,
                  program: Optional[str]) -> None:
        """One jaxpr trace (``trace``), lowering to MLIR (``lower``), or
        backend compile or persistent-cache load (``compile``) on the
        loop's thread (other threads' — an eval sidecar's — are theirs).
        A compile adds its seconds to ``compile_load_sec``; a trace or a
        lowering adds to ``trace_lower_sec`` what it did not spend in the
        ones it encloses, so that no second counts twice, and takes the
        place of their spans (jax.numpy's own jits, traced inside a step:
        a thousand lines for a small model's start-up). A compile inside
        a trace keeps its span."""
        if threading.get_ident() != self._thread:
            return
        end_ns = time.monotonic_ns()
        start_ns = end_ns - int(seconds * 1e9)
        inner_ns = 0
        while self._outer and self._outer[-1][0] >= start_ns:
            s, e, inner = self._outer.pop()
            inner_ns += e - s
            if inner[0] != "compile":
                self._unpend(inner)
        top = self._stack[-1] if self._stack else None
        parent = self.compile_parent
        if parent is None:
            parent = top.id if top is not None else self._interval_id
        attrs = {"seconds": round(seconds, 4), "program": program,
                 "during": top.name if top is not None else None}
        if kind == "compile":
            attrs["cache_hit"], self._cache_hit = self._cache_hit, False
            self.compile_load_sec += seconds
        else:
            self.trace_lower_sec += max(end_ns - start_ns - inner_ns, 0) / 1e9
        span = (kind, start_ns, end_ns, next_span_id(), parent,
                top.step if top is not None else None,
                top.steps if top is not None else 0, attrs)
        self._pending.append(span)
        self._outer.append((start_ns, end_ns, span))

    def _unpend(self, span: Span) -> None:
        """Take ``span`` back out of the pending list, where a flush (the
        watchdog's hang dump) has not written it already."""
        for i in range(len(self._pending) - 1, -1, -1):
            if self._pending[i] is span:
                del self._pending[i]
                return

    # ----------------------------------------------------------- writing
    def flush(self, tracer, ring: bool = False) -> None:
        """Write the durable spans (start-up phases, compiles) to
        ``tracer`` (an ``obs.SpanTracer``) and, with ``ring``, the phase
        ring too. Each span is taken out as it is written, so the closer
        chain and the watchdog's hang dump never write one twice."""
        pending, self._pending = self._pending, []
        for span in pending:
            _write(tracer, span)
        while ring and self.spans:
            try:
                span = self.spans.popleft()
            except IndexError:  # the other writer took the last one
                break
            _write(tracer, span)

    def close(self) -> None:
        """End what is still open (a start-up that raised) and stop
        receiving compile events (idempotent)."""
        global _current
        if self._stack:
            self.end(self._stack[0])
        if _current is self:
            _current = None

    # ---------------------------------------------------------- reporting
    def _open_interval(self, now_ns: int, step: Optional[int]) -> None:
        # An interval opened inside a phase (start-up) is no interval of
        # the loop: it gets no span, and its phases hang under that phase.
        self._interval_id = None if self._stack else next_span_id()
        self._interval_start = now_ns
        self._interval_step = step
        self._data_wait_ns = self._dispatch_ns = self._stall_ns = 0
        self._device_wait_ns: Optional[int] = None
        self._steps_dispatched = 0

    def _close_interval(self, end_ns: int,
                        step: Optional[int]) -> Dict[str, float]:
        """The sums of the interval that ends at ``end_ns``, its
        ``train.interval`` span into the ring, and the next one opened."""
        wall_ns = max(end_ns - self._interval_start, 1)
        wait_ns = self._device_wait_ns or 0
        out = {
            "data_wait_sec": round(self._data_wait_ns / 1e9, 6),
            "data_wait_frac": round(
                min(self._data_wait_ns / wall_ns, 1.0), 6),
            "dispatch_sec": round(self._dispatch_ns / 1e9, 6),
            "boundary_stall_sec": round(self._stall_ns / 1e9, 6),
            "loop_host_sec": round(
                (wall_ns - self._data_wait_ns - wait_ns) / 1e9, 6),
        }
        if self._device_wait_ns is not None:
            out["device_sync_sec"] = round(wait_ns / 1e9, 6)
        out["process_age_sec"] = round((end_ns - self._start_ns) / 1e9, 4)
        self._interval_span(end_ns, step)
        self._open_interval(end_ns, step)
        return out

    def _interval_span(self, end_ns: int, step: Optional[int],
                       **attrs) -> None:
        if self._interval_id is not None:
            self.spans.append((
                "train.interval", self._interval_start, end_ns,
                self._interval_id, None, step, self._steps_dispatched,
                dict(attrs, from_step=self._interval_step)))

    def reset_interval(self, step: Optional[int] = None) -> None:
        """Drop what the open interval has summed and start one now (after
        the first dispatch, a ledger's compile, a rollback). The dropped
        stretch keeps its ``train.interval`` span, marked ``reset``, where
        it had one, so its phases keep their parent."""
        now_ns = time.monotonic_ns()
        if self._data_wait_ns or self._dispatch_ns:
            self._interval_span(now_ns, step, reset=True)
        self._open_interval(now_ns, step)
        self._closed = None
        self._stall_from = now_ns

    def interval(self) -> Dict[str, float]:
        """The sums of the interval the last ``sample_device`` closed (or,
        where none was taken, of the one open now, which this closes).

        Always ``data_wait_sec``/``data_wait_frac``/``dispatch_sec``,
        ``boundary_stall_sec`` (from the previous drain's end to the
        return of the first dispatch after it: the idle the loop's own
        sync causes) and ``loop_host_sec`` (wall time less ``data_wait``
        and ``device_wait``: what the loop thread itself held);
        ``device_sync_sec`` when a boundary sample was taken;
        ``process_age_sec`` (the process's start to the interval's end);
        the run constants ``compile_seconds`` (first-dispatch wall time),
        ``startup_sec``, ``compile_load_sec`` (every backend compile or
        cache load so far), ``trace_lower_sec`` (every trace and lowering
        so far), ``before_train_sec``, ``import_sec`` and
        ``before_train_compile_sec`` (the spans ``process.before_train``
        and ``process.import``, the compiles beneath the first) once
        known."""
        out, self._closed = self._closed, None
        if out is None:
            out = self._close_interval(time.monotonic_ns(), None)
        if self.compile_seconds is not None:
            out["compile_seconds"] = round(self.compile_seconds, 4)
        if self.startup_sec is not None:
            out["startup_sec"] = round(self.startup_sec, 4)
        out["compile_load_sec"] = round(self.compile_load_sec, 4)
        out["trace_lower_sec"] = round(self.trace_lower_sec, 4)
        out["before_train_sec"] = round(self.before_train_sec, 4)
        if self.import_sec is not None:
            out["import_sec"] = round(self.import_sec, 4)
        out["before_train_compile_sec"] = round(
            self.before_train_compile_sec, 4)
        return out


def _write(tracer, span: Span) -> None:
    name, start_ns, end_ns, sid, parent, step, steps, attrs = span
    fields = dict(attrs or {}, id=sid)
    if parent is not None:
        fields["parent"] = parent
    if step is not None:
        fields["step"] = step
    if steps:
        fields["steps"] = steps
    tracer.record_ns(name, start_ns, end_ns, **fields)
