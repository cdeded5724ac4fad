"""Online inference HTTP server — ``python -m tpu_resnet serve``.

The reference's end state was a frozen ``.pb`` fed through a feed-dict
predict *process* (resnet_cifar_predict_from_pd.py:66-105) — batch jobs,
not a service. This module is the serving shape TPU systems treat as a
first-class peer of training: an HTTP front end (the same stdlib
``http.server`` threading pattern as ``obs/server.py``) over the dynamic
micro-batcher (``batcher.py``) and a weight backend (``backend.py``),
with the run-operations contracts this repo already standardized:

- **telemetry**: ``/metrics`` + ``/healthz`` on the same port, reusing
  ``obs.TelemetryRegistry`` with the ``SERVE_GAUGES`` series set;
  ``/healthz`` is the readiness probe — 503 until the model is loaded and
  every bucket shape compiled, 503 again while draining;
- **backpressure**: bounded queue → HTTP 429, draining → 503; latency is
  bounded by admission, not by hope;
- **graceful drain**: SIGTERM via the existing
  ``resilience.ShutdownCoordinator`` (flag-only handler — the PR-4
  signal-safety lint covers this file): stop accepting, flush the queue,
  exit 0.

Wire protocol (``POST /predict``):

- ``application/octet-stream``: raw uint8 pixels, shape in the
  ``X-Shape: N,H,W,C`` header (N may be omitted and inferred from the
  body length) — the fast path ``tools/loadgen.py`` uses;
- ``application/json``: ``{"instances": [[...]]}`` nested uint8 lists,
  one image ``[H,W,C]`` or a batch ``[N,H,W,C]``.

Response: ``{"predictions": [...], "model_step": s, "count": n}``
(plus ``"logits"`` with ``?logits=1``).
"""

from __future__ import annotations

import json
import logging
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional, Tuple

import numpy as np

from tpu_resnet.config import RunConfig
from tpu_resnet.obs import memory as memory_obs
from tpu_resnet.obs.manifest import (device_record, library_versions,
                                     read_run_id)
from tpu_resnet.obs.server import (SERVE_GAUGES, SERVE_HISTOGRAMS,
                                   TelemetryRegistry)
from tpu_resnet.obs.spans import SpanTracer, TailSampler
from tpu_resnet.resilience.faultinject import FaultInjector, FaultPlan
from tpu_resnet.serve.batcher import (LANES, Draining, MicroBatcher,
                                      QueueFull, default_buckets)

log = logging.getLogger("tpu_resnet")

# Upper bound a handler thread waits for its batched result; queued work
# survives a drain, so this only fires if the batcher thread died.
REQUEST_WAIT_SEC = 120.0
SERVE_DISCOVERY = "serve.json"


def parse_predict_body(body: bytes, content_type: str,
                       shape_header: Optional[str],
                       image_shape: Tuple[int, int, int]) -> np.ndarray:
    """Request body → uint8 [N,H,W,C]. Raises ValueError on anything that
    should be an HTTP 400."""
    h, w, c = image_shape
    if content_type.startswith("application/octet-stream"):
        item = h * w * c
        if shape_header:
            try:
                dims = tuple(int(x) for x in shape_header.split(","))
            except ValueError:
                raise ValueError(f"bad X-Shape header {shape_header!r}")
            if len(dims) == 3:
                dims = (len(body) // item,) + dims
            if len(dims) != 4 or dims[1:] != image_shape:
                raise ValueError(f"X-Shape {dims} does not match model "
                                 f"input [N,{h},{w},{c}]")
            n = dims[0]
        else:
            n = len(body) // item
        if n < 1 or len(body) != n * item:
            raise ValueError(f"body of {len(body)} bytes is not a whole "
                             f"number of {h}x{w}x{c} uint8 images")
        return np.frombuffer(body, np.uint8).reshape(n, h, w, c)
    if content_type.startswith("application/json"):
        try:
            payload = json.loads(body.decode())
        except (UnicodeDecodeError, json.JSONDecodeError) as e:
            raise ValueError(f"bad JSON body: {e}")
        if not isinstance(payload, dict) or "instances" not in payload:
            raise ValueError('JSON body must be {"instances": [...]}')
        try:
            arr = np.asarray(payload["instances"], np.uint8)
        except (TypeError, ValueError) as e:
            raise ValueError(f"instances not uint8-coercible: {e}")
        if arr.ndim == 3:
            arr = arr[None]
        if arr.ndim != 4 or arr.shape[1:] != image_shape:
            raise ValueError(f"instances shape {arr.shape} does not match "
                             f"model input [N,{h},{w},{c}]")
        return arr
    raise ValueError(f"unsupported Content-Type {content_type!r} (use "
                     f"application/octet-stream or application/json)")


class PredictServer:
    """Backend + micro-batcher + HTTP front end, drivable in-process
    (tests) or via :func:`serve` (CLI)."""

    def __init__(self, cfg: RunConfig, backend=None,
                 registry: Optional[TelemetryRegistry] = None,
                 spans: Optional[SpanTracer] = None):
        from tpu_resnet.serve.backend import build_backend

        # Time-to-ready clock starts BEFORE the backend build: restore +
        # bucket warmup are the cold-start cost the program cache
        # (tpu_resnet/programs) exists to kill, and the gauge must
        # measure what the cache can actually move (the interpreter/jax
        # import happened before any config was parsed — no process can
        # cache that away).
        self._t_init = time.monotonic()
        self.cfg = cfg
        self.backend = backend if backend is not None \
            else build_backend(cfg)
        raw = cfg.serve.batch_buckets or default_buckets(
            cfg.serve.max_batch)
        self.buckets = self.backend.constrain_buckets(
            tuple(sorted({int(b) for b in raw})))
        self.image_shape = (self.backend.image_size,
                            self.backend.image_size, 3)
        # Staleness = serve.healthz_stale_sec, NOT the trainer's 300 s:
        # the heartbeat is ticked by the batcher thread (per batch and
        # per idle tick), so a wedged inference worker goes dark within
        # seconds — /healthz must report it before a router's half-open
        # probe would flap the hung replica back into rotation.
        self.registry = registry if registry is not None \
            else TelemetryRegistry(
                stale_after_sec=cfg.serve.healthz_stale_sec,
                gauges=SERVE_GAUGES, histograms=SERVE_HISTOGRAMS)
        # Serve-side timeline (serve_events.jsonl) + correlation id: the
        # run_id of the train_dir being served, stamped on spans and
        # echoed in /info so loadgen results join the same timeline.
        self.run_id = read_run_id(cfg.train.train_dir)
        self.spans = spans if spans is not None else SpanTracer(
            cfg.train.train_dir, enabled=False)
        # Tail-based retention for per-request serve_request spans
        # (docs/OBSERVABILITY.md "Fleet"): errors/sheds always kept,
        # the slowest percentile kept, healthy traffic thinned — span
        # volume stays sublinear in request count.
        self.sampler = TailSampler()
        self.registry.mark_unhealthy(
            "loading: compiling bucketed batch shapes")
        self._reload_every = float(cfg.serve.reload_interval_secs)
        self._next_reload = time.monotonic() + self._reload_every
        # Serve-side fault injection (resilience/faultinject.py; off by
        # default and free when off): slow-infer latency, accept-then-
        # hang, and SIGKILL-at-request-K — the chaos levers the fleet
        # drills (doctor --fleet-probe, loadgen scenarios) pull.
        self._injector = FaultInjector(
            FaultPlan.from_config(cfg.resilience), cfg.train.train_dir)
        self.batcher = MicroBatcher(
            self._injector.wrap_serve_infer(self.backend.infer),
            self.image_shape,
            max_batch=max(self.buckets), max_wait_ms=cfg.serve.max_wait_ms,
            buckets=self.buckets, max_queue=cfg.serve.max_queue,
            between_batches=self._between_batches,
            on_stats=self._publish_stats,
            observe=self._observe_sample,
            latency_ring=cfg.serve.latency_ring)
        self._httpd = ThreadingHTTPServer((cfg.serve.host, cfg.serve.port),
                                          self._make_handler())
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self._http_thread = threading.Thread(
            target=self._httpd.serve_forever, name="tpu-resnet-serve-http",
            daemon=True)
        self._closed = False
        self._oom_reported = False
        self._weight_bytes = 0  # published at start(); backend-derived
        # What this replica runs on, as JAX reports it — a latency read
        # off this server is only a device number if /info says so.
        # Resolved once: the router's probe loop reads /info.
        import jax

        self._runs_on = {"devices": device_record(jax.devices()),
                         "versions": library_versions()}

    def note_oom(self, error, phase: str = "infer") -> None:
        """OOM forensics for the serving process (obs/memory.py): the
        first RESOURCE_EXHAUSTED — a bucket warmup that overflows HBM,
        or an inference batch on a memory-starved colocated chip —
        writes <train_dir>/oom_report.json with the live-array census,
        once. Guarded: forensics never takes the server down."""
        if self._oom_reported or not memory_obs.is_oom_error(error):
            return
        self._oom_reported = True
        memory_obs.write_oom_report(
            self.cfg.train.train_dir, error, context=f"serve-{phase}",
            program_key=f"serve|buckets{list(map(int, self.buckets))}"
                        f"|step{int(self.backend.model_step)}",
            run_id=self.run_id)
        self.spans.event("oom", phase=phase)

    # ---------------------------------------------------------- lifecycle
    def start(self) -> "PredictServer":
        """Warm every bucket (compile — or cache-load — ahead of
        traffic) smallest-first, then go ready. The HTTP socket is
        already bound — probes hitting /healthz during warmup see an
        honest 503, not a connection refused — and each bucket gets its
        own ``serve_warmup_bucket`` span with a ``cache_hit`` attr, so
        partial readiness is observable in trace-export and a cache
        regression (hits that became compiles) is visible per bucket."""
        self._http_thread.start()
        bind = getattr(self.backend, "bind_obs", None)
        if bind is not None:
            bind(telemetry=self.registry, spans=self.spans)
        t0 = time.monotonic()
        warm_bucket = getattr(self.backend, "warmup_bucket", None)
        hits = 0
        with self.spans.span("serve_warmup",
                             buckets=list(map(int, self.buckets)),
                             model_step=int(self.backend.model_step)):
            if warm_bucket is None:  # minimal/test backends
                self.backend.warmup(self.buckets)
                self.registry.set("serve_buckets_warm",
                                  float(len(self.buckets)))
            else:
                # Smallest-first: the cheapest program is ready soonest,
                # so a watcher sees warmth accrue instead of a silent
                # all-or-nothing window.
                for n, b in enumerate(sorted(self.buckets), start=1):
                    tb = time.time()
                    info = warm_bucket(int(b)) or {}
                    hits += bool(info.get("cache_hit"))
                    self.spans.record(
                        "serve_warmup_bucket", tb, time.time(),
                        bucket=int(b),
                        cache_hit=bool(info.get("cache_hit")))
                    self.registry.set("serve_buckets_warm", float(n))
        # Weight-argument footprint of the (possibly quantized) bucket
        # programs — the live end of the golden-memory-twin story: a
        # quantized arm's serve_weight_bytes gauge reads ~0.25x its f32
        # twin's, and the A/B scenario feeds it to perfwatch as a
        # lower-is-better series (tools/perfwatch.py `_bytes` rule).
        wb_fn = getattr(self.backend, "weight_argument_bytes", None)
        if wb_fn is not None:
            self._weight_bytes = int(wb_fn())
            self.registry.set("serve_weight_bytes",
                              float(self._weight_bytes))
        stats_fn = getattr(self.backend, "program_cache_stats", None)
        cache_stats = stats_fn() if stats_fn is not None else {}
        ttr = time.monotonic() - self._t_init
        self.registry.set("serve_time_to_ready_seconds", round(ttr, 3))
        self.registry.observe("serve_time_to_ready_s", ttr)
        self.spans.event(
            "serve_ready", seconds=round(ttr, 3),
            buckets=len(self.buckets), cache_hits_total=hits,
            compile_cache_hits=cache_stats.get("compile_cache_hits", 0),
            compile_cache_misses=cache_stats.get("compile_cache_misses",
                                                 0))
        log.info("serve: warmed %d bucket shapes %s in %.1fs "
                 "(time-to-ready %.1fs, %d cache hit(s))",
                 len(self.buckets), list(self.buckets),
                 time.monotonic() - t0, ttr, hits)
        self.batcher.start()
        self.registry.heartbeat(max(0, self.backend.model_step))
        self._publish_stats(self.batcher.stats())
        self.registry.clear_unhealthy()
        return self

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Stop accepting, flush the queue, stop the batcher. The HTTP
        server keeps answering (healthz reports draining) until
        :meth:`close`."""
        self.registry.mark_unhealthy("draining")
        with self.spans.span("serve_drain") as attrs:
            clean = self.batcher.drain(
                self.cfg.serve.drain_timeout_secs if timeout is None
                else timeout)
            attrs["clean"] = clean
        return clean

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._httpd.shutdown()
        self._httpd.server_close()
        close = getattr(self.backend, "close", None)
        if close is not None:
            close()

    # ---------------------------------------------------------- batch hooks
    def _between_batches(self) -> None:
        """Runs on the batcher thread strictly between inferences: the
        liveness heartbeat, and the rate-limited hot-reload poll — so a
        weight swap can never interleave with an in-flight batch."""
        self.registry.heartbeat(max(0, self.backend.model_step))
        if self._reload_every <= 0:
            return
        now = time.monotonic()
        if now < self._next_reload:
            return
        self._next_reload = now + self._reload_every
        t0 = time.time()
        if self.backend.maybe_reload():
            self.registry.set("serve_model_step", self.backend.model_step)
            self.registry.set("serve_reloads_total", self.backend.reloads)
            self.spans.record("serve_reload", t0, time.time(),
                              model_step=int(self.backend.model_step),
                              reloads=int(self.backend.reloads))

    def _observe_sample(self, name: str, value: float) -> None:
        """Batcher distribution samples → Prometheus histograms (the live
        p50/p95/p99 source the SLO-aware bucket retuning will read)."""
        self.registry.observe({
            "latency_ms": "serve_latency_ms",
            "queue_wait_ms": "serve_queue_wait_ms",
            "pad_fraction": "serve_pad_fraction",
        }.get(name, f"serve_{name}"), value)

    def _publish_stats(self, stats: dict) -> None:
        self.registry.update({
            "serve_requests_total": stats["requests"],
            "serve_requests_rejected": stats["rejected"],
            "serve_requests_failed": stats["failed"],
            "serve_images_total": stats["images"],
            "serve_batches_total": stats["batches"],
            "serve_queue_depth": stats["queue_depth"],
            "serve_batch_size_last": stats["batch_size_last"],
            "serve_batch_size_mean": stats["batch_size_mean"],
            "serve_pad_fraction": stats["pad_fraction"],
            "serve_latency_p50_ms": stats["latency_p50_ms"],
            "serve_latency_p95_ms": stats["latency_p95_ms"],
            "serve_latency_p99_ms": stats["latency_p99_ms"],
            "serve_model_step": self.backend.model_step,
            "serve_reloads_total": self.backend.reloads,
        })

    # ---------------------------------------------------------- predict
    def predict(self, images: np.ndarray,
                lane: str = "interactive") -> np.ndarray:
        """Submit ``images`` through the batcher (splitting requests
        larger than the biggest bucket) and block for the logits. The
        chunks are admitted atomically — a request that doesn't fully
        fit is rejected before any of its inference runs. ``lane`` is
        the QoS class: batch-lane work coalesces behind everything
        queued in the interactive lane."""
        return self._predict_pending(images, lane, [])

    def _predict_pending(self, images: np.ndarray, lane: str,
                         pending: list) -> np.ndarray:
        """:meth:`predict` with the submitted :class:`PendingRequest`
        objects appended to ``pending`` — even when a wait raises — so
        the request-tracing path can read the batcher-filled timing
        segments (queue wait, inference, pad) off whatever completed."""
        max_b = self.batcher.max_batch
        pending.extend(self.batcher.submit_many(
            [images[i:i + max_b]
             for i in range(0, images.shape[0], max_b)], lane=lane))
        return np.concatenate([p.wait(REQUEST_WAIT_SEC) for p in pending])

    def retry_after_secs(self) -> int:
        """Honest backpressure hint for 429/503 responses: the seconds a
        full queue needs to drain at the recent per-request service
        rate, floored at 1 — so a retrying client (or the router's
        shed/backoff) waits roughly one queue-drain, not a blind
        constant."""
        stats = self.batcher.stats()
        p50_sec = stats["latency_p50_ms"] / 1e3
        depth = stats["queue_depth"]
        mean_batch = max(1.0, stats["batch_size_mean"])
        return max(1, int(round(depth * p50_sec / mean_batch)))

    def handle_predict(self, body: bytes, content_type: str,
                       shape_header: Optional[str], want_logits: bool,
                       lane: str = "interactive",
                       trace_id: str = "") -> Tuple[int, dict]:
        """(status, response-json) for one predict call — pure enough to
        unit test without sockets. ``lane`` comes from the X-Lane header
        (unknown values fall back to interactive, the strict lane);
        ``trace_id`` from X-Trace-Id (router- or client-minted) — when
        present the call is eligible for a tail-sampled ``serve_request``
        span carrying the replica-side timing segments."""
        if lane not in LANES:
            lane = "interactive"
        self._injector.note_serve_request()
        t0 = time.time()
        pending: list = []
        status, out = self._handle_predict_inner(
            body, content_type, shape_header, want_logits, lane, pending)
        if trace_id:
            self._trace_request(trace_id, lane, status, pending, t0)
        return status, out

    def _handle_predict_inner(self, body, content_type, shape_header,
                              want_logits, lane, pending) -> Tuple[int, dict]:
        try:
            images = parse_predict_body(body, content_type, shape_header,
                                        self.image_shape)
        except ValueError as e:
            return 400, {"error": str(e)}
        try:
            logits = self._predict_pending(images, lane, pending)
        except QueueFull as e:
            return 429, {"error": str(e), "retryable": True,
                         "retry_after_secs": self.retry_after_secs()}
        except Draining as e:
            return 503, {"error": str(e)}
        except TimeoutError as e:
            return 504, {"error": str(e)}
        except ValueError as e:
            return 400, {"error": str(e)}
        except Exception as e:  # noqa: BLE001 - backend failure
            self.note_oom(e)  # RESOURCE_EXHAUSTED gets its forensics
            return 500, {"error": f"{type(e).__name__}: {e}"}
        out = {"predictions": np.argmax(logits, axis=-1).tolist(),
               "model_step": int(self.backend.model_step),
               "count": int(images.shape[0])}
        if want_logits:
            out["logits"] = np.asarray(logits, np.float64).tolist()
        return 200, out

    def _trace_request(self, trace_id: str, lane: str, status: int,
                       pending: list, t0: float) -> None:
        """Tail-sampled ``serve_request`` span: the replica's hop of a
        distributed trace. Segments come off the PendingRequest objects
        the batcher annotated; the sampler decision is pure in-memory
        (no I/O under any lock — the span write happens here, outside)."""
        end = time.time()
        latency_ms = (end - t0) * 1e3
        reason = self.sampler.observe(
            latency_ms, error=(status >= 400 and status != 429),
            shed=(status == 429))
        if reason is None:
            return
        attrs = {"trace_id": trace_id, "lane": lane, "status": int(status),
                 "sampled": reason,
                 "replica": self.cfg.serve.replica_name or "serve",
                 "latency_ms": round(latency_ms, 3),
                 "model_step": int(self.backend.model_step)}
        if pending:
            qw = [p.queue_wait_ms for p in pending
                  if p.queue_wait_ms is not None]
            inf = [p.infer_ms for p in pending if p.infer_ms is not None]
            pads = [p.pad_fraction for p in pending
                    if p.pad_fraction is not None]
            sizes = [p.batch_size for p in pending
                     if p.batch_size is not None]
            attrs["n"] = sum(p.n for p in pending)
            if qw:
                attrs["queue_wait_ms"] = round(max(qw), 3)
            if inf:  # chunks ride separate batches: inference time adds
                attrs["infer_ms"] = round(sum(inf), 3)
            if pads:
                attrs["pad_fraction"] = round(max(pads), 4)
            if sizes:
                attrs["batch_size"] = max(sizes)
        self.spans.record("serve_request", t0, end, **attrs)

    def info(self) -> dict:
        stats = self.batcher.stats()
        return {
            "backend": type(self.backend).__name__,
            "run_id": self.run_id,
            "replica_name": self.cfg.serve.replica_name,
            "model_step": int(self.backend.model_step),
            "reloads": int(self.backend.reloads),
            "image_shape": list(self.image_shape),
            "num_classes": int(self.backend.num_classes),
            "buckets": list(self.buckets),
            **self._runs_on,
            # Arm identity (the router A/B scenario and fleetmon label
            # arms from here — no out-of-band config): numeric compute
            # dtype, quant mode, and the calibration digest the
            # quantized weights were built from.
            "compute_dtype": self.cfg.model.compute_dtype,
            "quantize": getattr(self.backend, "quantize", "off"),
            "calibration_digest": getattr(self.backend,
                                          "calibration_digest", ""),
            "weight_bytes": int(self._weight_bytes),
            "max_wait_ms": self.cfg.serve.max_wait_ms,
            "max_queue": self.cfg.serve.max_queue,
            # Top-level copy: the router's passive queue-pressure signal
            # reads one /info — no second /metrics scrape in the probe
            # loop (the full stats dict stays nested below).
            "queue_depth": stats["queue_depth"],
            "stats": stats,
        }

    # ---------------------------------------------------------- HTTP layer
    def _make_handler(self):
        server = self
        from tpu_resnet.serve.discovery import send_json

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def _send(self, code: int, payload: dict,
                      ctype: str = "application/json",
                      extra_headers: Optional[dict] = None):
                send_json(self, code, payload, ctype, extra_headers)

            def do_GET(self):
                path = self.path.split("?", 1)[0]
                if path == "/metrics":
                    self._send(200, server.registry.render().encode(),
                               "text/plain; version=0.0.4; charset=utf-8")
                elif path == "/healthz":
                    health = server.registry.health()
                    self._send(200 if health["ok"] else 503, health)
                elif path in ("/", "/info"):
                    self._send(200, server.info())
                else:
                    self._send(404, {"error": "not found"})

            def do_POST(self):
                path, _, query = self.path.partition("?")
                if path != "/predict":
                    self._send(404, {"error": "not found"})
                    return
                try:
                    length = int(self.headers.get("Content-Length", 0))
                except ValueError:
                    length = 0
                if length <= 0:
                    self._send(400, {"error": "empty body"})
                    return
                body = self.rfile.read(length)
                if server._injector.should_drop_connection():
                    # One-shot connection-drop fault (faultinject
                    # SERVE_DROP_REQ): slam the socket with no HTTP
                    # response — the abrupt RemoteDisconnected the
                    # router's retry-once failover must absorb.
                    self.close_connection = True
                    try:
                        self.connection.close()
                    except OSError:
                        pass
                    return
                trace_id = (self.headers.get("X-Trace-Id") or "").strip()
                code, payload = server.handle_predict(
                    body, self.headers.get("Content-Type", ""),
                    self.headers.get("X-Shape"),
                    want_logits="logits=1" in query,
                    lane=(self.headers.get("X-Lane")
                          or "interactive").strip().lower(),
                    trace_id=trace_id)
                headers = {}
                if code == 429:
                    # Backpressure responses carry Retry-After so a
                    # client (or the router) backs off for one honest
                    # queue-drain instead of hammering the full queue.
                    headers["Retry-After"] = payload.get(
                        "retry_after_secs", 1)
                if trace_id:
                    # Echo the trace id so every hop of a distributed
                    # trace names itself to its caller.
                    headers["X-Trace-Id"] = trace_id
                self._send(code, payload, extra_headers=headers or None)

            def log_message(self, *args):  # request logs would swamp stderr
                pass

        return Handler


def write_discovery(train_dir: str, port: int,
                    run_id: Optional[str] = None,
                    name: str = "",
                    extra: Optional[dict] = None) -> None:
    """Atomic ``<train_dir>/serve.json`` — the telemetry.json analog for
    the predict server (loadgen/doctor dial the port from here). A
    nonempty ``name`` (serve.replica_name) writes
    ``serve-<name>.json`` instead, so N replicas sharing one train_dir
    each announce themselves and the router (serve/router.py) discovers
    the whole fleet from one directory scan. ``extra`` fields ride along
    in the record — the server announces its arm identity (compute
    dtype / quant mode) here so the router scenario and fleetmon can
    label arms from the discovery scan alone."""
    from tpu_resnet.serve.discovery import write_record

    record = {"run_id": run_id, "name": name or None}
    record.update(extra or {})
    write_record(train_dir,
                 f"serve-{name}.json" if name else SERVE_DISCOVERY,
                 port, extra=record)


def read_serve_port(train_dir: str) -> Optional[int]:
    from tpu_resnet.serve.discovery import read_port

    return read_port(train_dir, SERVE_DISCOVERY)


def serve(cfg: RunConfig) -> int:
    """CLI entry: start, announce, block until SIGTERM/SIGINT, drain,
    exit 0 on a clean drain (the contract ``doctor --serve-probe``
    verifies)."""
    from tpu_resnet.obs.trace import SERVE_EVENTS_FILE
    from tpu_resnet.resilience import ShutdownCoordinator

    coordinator = ShutdownCoordinator(
        enabled=cfg.resilience.graceful_shutdown,
        action_desc="draining the predict server (stop accepting, flush "
                    "the request queue), then exiting 0")
    # Serve-side timeline: warmup/reload/drain spans land beside the
    # trainer's events.jsonl (same train_dir, same run_id) so
    # trace-export renders one correlated session.
    spans = SpanTracer(cfg.train.train_dir, filename=SERVE_EVENTS_FILE,
                       run_id=read_run_id(cfg.train.train_dir))
    if cfg.serve.admission_hbm_bytes > 0:
        # Colocation admission (resilience/elastic.py): a replica
        # joining a trainer's host starts only when the live HBM gauges
        # say its estimated footprint fits the measured headroom.
        # NO_CAPACITY is the scheduler-facing "no capacity here" —
        # distinct from a crash, so a placement loop tries another host
        # instead of backing off on this one.
        from tpu_resnet.resilience import elastic, exitcodes

        verdict = elastic.colocation_admission(cfg.serve.admission_hbm_bytes)
        spans.event("colocation_admission", **verdict)
        if not verdict["admit"]:
            log.error("serve: colocation admission denied — %s",
                      verdict["reason"])
            spans.close()
            return exitcodes.NO_CAPACITY
        log.info("serve: colocation admission ok — %s", verdict["reason"])
    server = PredictServer(cfg, spans=spans)
    clean = True
    with coordinator:
        try:
            server.start()
        except Exception as e:
            # Warmup compiles every bucket shape — the most likely spot
            # for a serving OOM. Write the forensics artifact before the
            # crash surfaces (the loop's closer-chain contract).
            server.note_oom(e, phase="warmup")
            server.close()
            spans.close()
            raise
        write_discovery(cfg.train.train_dir, server.port,
                        run_id=server.run_id,
                        name=cfg.serve.replica_name,
                        extra={
                            "compute_dtype": cfg.model.compute_dtype,
                            "quantize": getattr(server.backend,
                                                "quantize", "off"),
                        })
        log.info("serve: ready on :%d — backend=%s model_step=%d "
                 "buckets=%s max_wait_ms=%s (POST /predict; /metrics; "
                 "/healthz)", server.port, cfg.serve.backend,
                 server.backend.model_step, list(server.buckets),
                 cfg.serve.max_wait_ms)
        try:
            while not coordinator.event.wait(0.5):
                pass
            log.info("serve: shutdown requested (%s) — draining",
                     coordinator.signum)
            clean = server.drain()
        except KeyboardInterrupt:
            # Second signal (or coordinator disabled): abort the drain.
            log.warning("serve: immediate abort requested")
            clean = False
        finally:
            server.close()
            spans.close()
    if clean:
        log.info("serve: drained cleanly, exiting 0")
    return 0 if clean else 1
