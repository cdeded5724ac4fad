"""Unified observability — the subsystem the reference never had.

The reference's visibility into a run was three disconnected channels
(SURVEY.md §5: TensorBoard summaries, a console LoggingTensorHook, and
per-task log files); "is the input pipeline the bottleneck" and "which
pod host is straggling" were answered by grepping logs, if at all. The
MLPerf TPU-pod scaling work (arXiv:1909.09756) and the pjit TPUv4
training report (arXiv:2204.06514) both treat per-step timing
decomposition and pod-level health as *prerequisites* for scaling; this
package provides them as first-class artifacts of every run:

``breakdown``   StepBreakdown — the train loop's span recorder: every
                phase of an iteration (``train.data_wait``,
                ``train.dispatch``, ``train.device_wait``, the log
                boundary, checkpoints), start-up, the process's time
                before ``train()`` and every trace, lowering and compile
                as spans with ``id`` and ``parent`` on the monotonic
                clock, and per-interval sums for ``metrics.jsonl``.
``spans``       SpanTracer — structured event spans (run, compile,
                checkpoint save/restore, eval pass, profiler trace
                window) appended to ``events.jsonl``, wall-clocked and
                with ``mono_ns`` beside.
``manifest``    ``manifest.json`` — resolved config, mesh topology,
                device kinds, process count, package version, git rev —
                written once at startup by the primary process.
``server``      a stdlib-only HTTP telemetry server per host exposing
                ``/healthz`` (liveness + heartbeat age) and ``/metrics``
                (Prometheus text: gauges + fixed-bucket histograms) so
                pods can be scraped and stragglers spotted without
                log-grepping.
``mfu``         first-class FLOPs/MFU accounting: per-device-kind peak
                table, per-compiled-program FLOPs registry (keyed like
                the golden-jaxpr entries), live ``model_flops_per_sec``
                / ``mfu`` gauges.
``memory``      the space twin of ``mfu``: compiled-program HBM ledger
                (``memory.json``, keyed like the FLOPs registry), live
                ``hbm_bytes_*`` gauges from ``device.memory_stats()``,
                OOM forensics (``oom_report.json`` with a live-array
                census) and the per-chip HBM capacity table.
``comms``       the wire twin of ``mfu``/``memory``: compiled-program
                collective summary (op multiset, analytic bytes-on-wire
                per mesh axis from the post-partitioner HLO) persisted
                to ``comms.json`` with the same program keys, predicted
                time-on-wire from the per-chip ICI-bandwidth table and
                a ``predicted_comms_fraction`` gauge.
``trace``       ``tpu_resnet trace-export`` — merge spans, breakdown
                samples, data-engine counters, eval and serve events
                into one Chrome-trace/Perfetto JSON correlated by the
                run's ``run_id``.

Importing this package stays jax-free (jax is imported lazily where a
device sync is needed) so stdlib-only consumers — ``tools/obs_scrape.py``,
the doctor's telemetry check — can use the scrape/parse helpers without
pulling in a backend.
"""

from tpu_resnet.obs import comms, memory, mfu
from tpu_resnet.obs.breakdown import StepBreakdown
from tpu_resnet.obs.manifest import (
    build_manifest,
    ensure_run_id,
    read_run_id,
    write_manifest,
)
from tpu_resnet.obs.server import (
    Histogram,
    TelemetryRegistry,
    TelemetryServer,
    histogram_quantile,
    parse_histograms,
    parse_prometheus,
    read_telemetry_port,
    scrape,
)
from tpu_resnet.obs.spans import SpanTracer, next_span_id

__all__ = [
    "Histogram",
    "StepBreakdown",
    "SpanTracer",
    "TelemetryRegistry",
    "TelemetryServer",
    "build_manifest",
    "comms",
    "ensure_run_id",
    "histogram_quantile",
    "memory",
    "mfu",
    "next_span_id",
    "parse_histograms",
    "parse_prometheus",
    "read_run_id",
    "read_telemetry_port",
    "scrape",
    "write_manifest",
]
