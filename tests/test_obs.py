"""Unified observability subsystem (tpu_resnet/obs): step-time breakdown,
event spans, run manifest, and the /metrics + /healthz telemetry server —
the channels the reference never had (SURVEY.md §5)."""

import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from tpu_resnet import obs
from tpu_resnet.obs.server import (
    CORE_HISTOGRAMS,
    Histogram,
    LATENCY_BUCKETS_MS,
    TelemetryRegistry,
    TelemetryServer,
    histogram_quantile,
    parse_histograms,
    parse_prometheus,
    read_telemetry_port,
    scrape,
)
from tpu_resnet.obs.spans import load_spans


# ------------------------------------------------------------- breakdown

class _Slow:
    """A pytree leaf whose ``block_until_ready`` takes a while: stands in
    for a chunk the device has not finished."""

    def __init__(self, seconds):
        self.seconds = seconds

    def block_until_ready(self):
        time.sleep(self.seconds)
        return self


def test_breakdown_interval_decomposition():
    bd = obs.StepBreakdown()
    with bd.data_wait():
        time.sleep(0.03)
    with bd.dispatch(step=0, steps=10):
        time.sleep(0.01)
    waited = bd.sample_device({"loss": _Slow(0.05)}, steps=10, step=10)
    out = bd.interval()
    assert out["data_wait_sec"] >= 0.02
    assert 0.0 < out["data_wait_frac"] <= 1.0
    assert out["dispatch_sec"] >= 0.005
    assert out["device_sync_sec"] == pytest.approx(waited, abs=1e-6)
    assert waited >= 0.05
    assert "device_step_sec_sampled" not in out  # gone: step_device_ms
    assert "compile_seconds" not in out  # never known in this run
    # The new keys: the three parts add up to the interval's wall time,
    # which ends where the device wait ended.
    (iv,) = [s for s in bd.spans if s[0] == "train.interval"]
    wall = (iv[2] - iv[1]) / 1e9
    assert iv[2] == [s for s in bd.spans
                     if s[0] == "train.device_wait"][0][2]
    assert (out["loop_host_sec"] + out["data_wait_sec"]
            + out["device_sync_sec"]) == pytest.approx(wall, abs=5e-6)
    assert out["loop_host_sec"] >= 0.01  # the dispatch is the loop's own
    assert out["boundary_stall_sec"] == 0.0  # no drain came before
    # Counters with no reader are not drained (PERF.md section 3): what
    # the interval dispatched stands on its span.
    assert iv[6] == 10
    assert not {"synced_at_ns", "dispatches", "steps_dispatched",
                "boundaries", "checkpoints", "compiles"} & out.keys()
    # interval() drains: the next interval starts from zero, at the sync
    time.sleep(0.02)
    with bd.dispatch(step=10, steps=10):
        pass
    out2 = bd.interval()
    assert out2["data_wait_sec"] == 0.0
    assert "device_sync_sec" not in out2
    assert out2["dispatch_sec"] >= 0.0
    # ... and the stall the sync caused is reported with the interval in
    # which it ended: from the drain's end to the next dispatch's return.
    assert out2["boundary_stall_sec"] >= 0.02


def test_breakdown_ring_is_bounded_and_spans_nest():
    bd = obs.StepBreakdown(ring=16)
    for step in range(40):
        with bd.data_wait(step):
            pass
        with bd.dispatch(step, 1):
            with bd.phase("train.epoch_shuffle", step):
                pass
    assert len(bd.spans) == 16
    bd.sample_device({"loss": np.zeros(())}, steps=40, step=40)
    spans = list(bd.spans)
    by_id = {s[3]: s for s in spans}
    interval = spans[-1]
    assert interval[0] == "train.interval" and interval[4] is None
    assert interval[5] == 40 and interval[6] == 40  # step, steps
    for name, start, end, sid, parent, step, steps, _ in spans[:-1]:
        assert start <= end
        if name == "train.epoch_shuffle":  # beneath its dispatch
            p = by_id[parent]
            assert p[0] == "train.dispatch" and p[1] <= start and end <= p[2]
        else:  # every phase of the interval names it
            assert parent == interval[3]
            assert interval[1] <= start and end <= interval[2]
    # one clock: the monotonic one
    assert abs(spans[-1][2] - time.monotonic_ns()) < 5e9


def test_breakdown_compile_listener_feeds_the_current_recorder(tmp_path):
    import jax
    import jax.numpy as jnp

    a, b, c = jnp.ones(7), jnp.ones(5), jnp.ones(3)  # compiled up front
    bd = obs.StepBreakdown()
    with bd.dispatch(step=7, steps=3):
        jax.jit(lambda x: x * 3 + 1)(a).block_until_ready()
    assert bd.compile_load_sec > 0
    out = bd.interval()
    assert out["compile_load_sec"] == round(bd.compile_load_sec, 4) > 0
    tracer = obs.SpanTracer(str(tmp_path))
    bd.flush(tracer, ring=True)
    bd.flush(tracer, ring=True)  # everything was taken out: no doubles
    bd.close()
    other = obs.StepBreakdown()  # the listener now feeds this one
    jax.jit(lambda x: x * 5 - 2)(b).block_until_ready()
    other.close()
    heard = other.compile_load_sec
    jax.jit(lambda x: x * 7 - 3)(c).block_until_ready()
    tracer.close()
    assert other.interval()["compile_load_sec"] == round(heard, 4) > 0
    spans = load_spans(str(tmp_path / "events.jsonl"))
    assert len({s["id"] for s in spans}) == len(spans)
    # (what compiled before the recorder, jnp.ones above where this
    # process heard it, stands under process.before_train)
    compiles = [s for s in spans if s["span"] == "compile"
                and s["during"] != "process.before_train"]
    (dispatch,) = [s for s in spans if s["span"] == "train.dispatch"]
    assert compiles and all(
        c["step"] == 7 and c["steps"] == 3 and c["parent"] == dispatch["id"]
        and c["during"] == "train.dispatch" and c["cache_hit"] is False
        and c["seconds"] > 0 and c["program"] for c in compiles)
    assert all("mono_ns" in s for s in spans)
    assert dispatch["mono_ns"] <= compiles[0]["mono_ns"]


def test_breakdown_compile_excludes_data_wait():
    t_outer = time.perf_counter()
    bd = obs.StepBreakdown()
    with bd.data_wait():
        time.sleep(0.03)
    time.sleep(0.02)  # stands in for trace+compile+first chunk
    # numpy pytrees pass block_until_ready untouched — no device needed
    compile_s = bd.first_dispatch_done({"loss": np.zeros(())})
    elapsed = time.perf_counter() - t_outer
    assert compile_s == bd.compile_seconds
    assert 0.015 <= compile_s <= elapsed - 0.025  # data wait excluded
    out = bd.interval()
    assert out["compile_seconds"] == round(compile_s, 4)
    assert out["data_wait_sec"] == 0.0  # interval re-primed at the sync
    # compile_seconds is a run constant: every later interval reports it
    assert bd.interval()["compile_seconds"] == round(compile_s, 4)


# ----------------------------------------------------------------- spans

def test_span_tracer_records_and_loads(tmp_path):
    tr = obs.SpanTracer(str(tmp_path))
    with tr.span("eval_pass", step=5) as attrs:
        attrs["precision"] = 0.5
    first_id = attrs["id"]
    tr.event("marker", step=7)
    tr.record("child", time.time(), time.time(), parent=first_id)
    tr.close()
    tr.close()  # idempotent
    tr.record("after_close", 0.0, 1.0)  # no-op, not a crash
    spans = load_spans(str(tmp_path / "events.jsonl"))
    assert [s["span"] for s in spans] == ["eval_pass", "marker", "child"]
    assert spans[0]["precision"] == 0.5
    # id, parent and the monotonic twin of ``start``
    assert len({s["id"] for s in spans}) == 3
    assert "parent" not in spans[0] and spans[2]["parent"] == spans[0]["id"]
    for s in spans:
        assert abs((s["mono_ns"] - time.monotonic_ns()) / 1e9
                   - (s["start"] - time.time())) < 0.05
    assert spans[0]["end"] >= spans[0]["start"]
    assert spans[0]["duration_sec"] >= 0
    assert spans[1]["duration_sec"] == 0  # instantaneous marker


def test_span_tracer_disabled_writes_nothing(tmp_path):
    tr = obs.SpanTracer(str(tmp_path), enabled=False)
    tr.event("x")
    tr.close()
    assert not (tmp_path / "events.jsonl").exists()


def test_span_records_exception_and_reraises(tmp_path):
    tr = obs.SpanTracer(str(tmp_path))
    with pytest.raises(RuntimeError):
        with tr.span("checkpoint_save", step=3):
            raise RuntimeError("disk full")
    tr.close()
    (span,) = load_spans(str(tmp_path / "events.jsonl"))
    assert span["step"] == 3
    assert "RuntimeError: disk full" in span["error"]


def test_load_spans_tolerates_torn_tail(tmp_path):
    path = tmp_path / "events.jsonl"
    path.write_text('{"span": "run", "start": 0, "end": 1}\n{"span": "to')
    assert [s["span"] for s in load_spans(str(path))] == ["run"]


# -------------------------------------------------------------- manifest

def test_manifest_schema_and_atomic_write(tmp_path):
    import jax

    from tpu_resnet import parallel
    from tpu_resnet.config import load_config

    cfg = load_config("smoke")
    mesh = parallel.create_mesh(cfg.mesh)
    path = obs.write_manifest(str(tmp_path), cfg, mesh)
    assert path == str(tmp_path / "manifest.json")
    assert os.listdir(tmp_path) == ["manifest.json"]  # no tmp leftovers
    with open(path) as f:
        m = json.load(f)
    assert m["schema"] == 2
    assert m["config"]["train"]["train_steps"] == cfg.train.train_steps
    assert m["mesh"]["shape"] and m["mesh"]["axis_names"]
    assert m["devices"]["count"] == mesh.size
    assert m["devices"]["platform"] == jax.devices()[0].platform
    assert m["processes"] == {"count": 1, "index": 0}
    assert m["versions"]["jax"] == jax.__version__
    assert m["versions"]["python"]
    assert m["hostname"] and isinstance(m["argv"], list)


# ---------------------------------------------------------------- server

def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read().decode()


def test_telemetry_server_live_scrape(tmp_path):
    reg = TelemetryRegistry(stale_after_sec=60.0)
    reg.heartbeat(7)
    reg.update({"loss": 1.5, "images_per_sec": 1234.0,
                "data_wait_frac": 0.25})
    srv = TelemetryServer.maybe_start(0, reg, train_dir=str(tmp_path))
    assert srv is not None
    try:
        port = read_telemetry_port(str(tmp_path))
        assert port == srv.port  # discovery file matches the bound port

        status, text = _get(f"http://127.0.0.1:{port}/metrics")
        assert status == 200
        metrics = parse_prometheus(text)
        assert metrics["tpu_resnet_step"] == 7.0
        assert metrics["tpu_resnet_loss"] == 1.5
        assert metrics["tpu_resnet_images_per_sec"] == 1234.0
        assert metrics["tpu_resnet_data_wait_frac"] == 0.25
        # pre-declared core gauges exist before any interval completes
        assert "tpu_resnet_steps_per_sec" in metrics
        assert "tpu_resnet_checkpoint_lag_steps" in metrics
        assert metrics["tpu_resnet_heartbeat_age_seconds"] < 60.0
        assert "# TYPE tpu_resnet_loss gauge" in text

        status, body = _get(f"http://127.0.0.1:{port}/healthz")
        health = json.loads(body)
        assert status == 200 and health["ok"] is True
        assert health["step"] == 7

        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(f"http://127.0.0.1:{port}/nope")
        assert exc.value.code == 404

        # the shared scrape helper (doctor + obs_scrape) sees the same
        report = scrape(f"127.0.0.1:{port}")
        assert report["health_status"] == 200
        assert report["metrics"]["tpu_resnet_step"] == 7.0
    finally:
        srv.close()
        srv.close()  # idempotent


def test_healthz_stale_returns_503():
    reg = TelemetryRegistry(stale_after_sec=0.0)  # everything is stale
    srv = TelemetryServer(reg, 0, host="127.0.0.1")
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(f"http://127.0.0.1:{srv.port}/healthz")
        assert exc.value.code == 503
        assert json.loads(exc.value.read().decode())["ok"] is False
        # scrape() treats 503 as a report, not an error
        report = scrape(f"127.0.0.1:{srv.port}")
        assert report["health_status"] == 503
        assert report["health"]["ok"] is False
    finally:
        srv.close()


def test_stall_visibility_heartbeat_staleness_and_ckpt_lag(tmp_path):
    """A stalled loop is visible from outside: /healthz flips to 503 once
    the heartbeat goes stale, /metrics keeps exposing the frozen step and
    the checkpoint lag, and a resumed heartbeat flips it back."""
    reg = TelemetryRegistry(stale_after_sec=0.25)
    reg.heartbeat(7)
    reg.set("checkpoint_lag_steps", 12)
    srv = TelemetryServer.maybe_start(0, reg, train_dir=str(tmp_path))
    try:
        status, _ = _get(f"http://127.0.0.1:{srv.port}/healthz")
        assert status == 200  # fresh heartbeat
        time.sleep(0.4)  # the simulated loop stops heartbeating
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(f"http://127.0.0.1:{srv.port}/healthz")
        assert exc.value.code == 503
        health = json.loads(exc.value.read().decode())
        assert health["ok"] is False and health["step"] == 7
        assert health["heartbeat_age_sec"] > 0.25
        _, text = _get(f"http://127.0.0.1:{srv.port}/metrics")
        metrics = parse_prometheus(text)
        assert metrics["tpu_resnet_step"] == 7.0  # frozen, not absent
        assert metrics["tpu_resnet_checkpoint_lag_steps"] == 12.0
        assert metrics["tpu_resnet_heartbeat_age_seconds"] > 0.25
        # fault counters are pre-declared (zero), not missing series
        assert metrics["tpu_resnet_fault_watchdog_stalls"] == 0.0
        assert metrics["tpu_resnet_fault_nan_rollbacks"] == 0.0
        reg.heartbeat(8)  # the loop recovers
        status, body = _get(f"http://127.0.0.1:{srv.port}/healthz")
        assert status == 200 and json.loads(body)["step"] == 8
    finally:
        srv.close()


def test_mark_unhealthy_overrides_fresh_heartbeat():
    """The hang watchdog's channel: /healthz must report unhealthy with
    the stall reason even while heartbeats are technically fresh."""
    reg = TelemetryRegistry(stale_after_sec=300.0)
    reg.heartbeat(3)
    reg.mark_unhealthy("no step progress for 9.3s at step 3")
    srv = TelemetryServer(reg, 0, host="127.0.0.1")
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(f"http://127.0.0.1:{srv.port}/healthz")
        assert exc.value.code == 503
        health = json.loads(exc.value.read().decode())
        assert health["ok"] is False
        assert "no step progress" in health["unhealthy_reason"]
        reg.clear_unhealthy()
        status, body = _get(f"http://127.0.0.1:{srv.port}/healthz")
        assert status == 200
        assert "unhealthy_reason" not in json.loads(body)
    finally:
        srv.close()


def test_maybe_start_disabled_and_bind_failure(tmp_path):
    reg = TelemetryRegistry()
    assert TelemetryServer.maybe_start(-1, reg) is None  # -1 = off
    srv = TelemetryServer.maybe_start(0, reg, train_dir=str(tmp_path))
    try:
        # A taken port degrades to "no telemetry", never a crashed trainer.
        assert TelemetryServer.maybe_start(srv.port, reg) is None
    finally:
        srv.close()


def test_parse_prometheus_rejects_malformed():
    with pytest.raises(ValueError):
        parse_prometheus("lonely_sample_without_value")
    out = parse_prometheus("# HELP a b\n# TYPE a gauge\na 1.5\n"
                           'b{host="x"} 2\n')
    assert out == {"a": 1.5, "b": 2.0}


def test_read_telemetry_port_missing(tmp_path):
    assert read_telemetry_port(str(tmp_path)) is None


# ------------------------------------------------- doctor + scrape tool

def test_doctor_telemetry_check(tmp_path):
    from tpu_resnet.tools import doctor

    # no telemetry.json at all
    out = doctor._check_telemetry(str(tmp_path))
    assert out["ok"] is False and "telemetry.json" in out["error"]

    reg = TelemetryRegistry(stale_after_sec=60.0)
    reg.heartbeat(3)
    srv = TelemetryServer.maybe_start(0, reg, train_dir=str(tmp_path))
    try:
        out = doctor._check_telemetry(str(tmp_path))
        assert out["ok"] is True
        assert out["port"] == srv.port and out["step"] == 3
        assert out["heartbeat_age_sec"] < 60.0
    finally:
        srv.close()
    # stale telemetry.json pointing at a dead server: loud, not a hang
    out = doctor._check_telemetry(str(tmp_path), timeout=2.0)
    assert out["ok"] is False and "error" in out


def test_obs_scrape_tool(tmp_path, capsys):
    from tpu_resnet.tools import obs_scrape

    # histograms included so --json must serialize the +Inf bucket edge
    reg = TelemetryRegistry(stale_after_sec=60.0,
                            histograms=CORE_HISTOGRAMS)
    reg.heartbeat(11)
    reg.observe("train_step_ms", 12.5, n=3)
    srv = TelemetryServer.maybe_start(0, reg, train_dir=str(tmp_path))
    try:
        assert obs_scrape.main(["--dir", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "health: ok" in out
        assert "tpu_resnet_step" in out and "11" in out

        assert obs_scrape.main(
            ["--url", f"127.0.0.1:{srv.port}", "--json"]) == 0
        raw = capsys.readouterr().out
        # strict JSON: the +Inf histogram bucket edge must serialize as
        # the string "+Inf", never a bare Infinity literal
        assert "Infinity" not in raw
        report = json.loads(raw)
        assert report["metrics"]["tpu_resnet_step"] == 11.0
    finally:
        srv.close()
    assert obs_scrape.main(["--dir", str(tmp_path / "none")]) == 2
    assert obs_scrape.main(["--dir", str(tmp_path), "--timeout", "2"]) == 1


# ------------------------------------------------------------ histograms

def test_histogram_percentiles_vs_numpy_reference():
    """Bucket/percentile math against a numpy reference: with bucket
    edges placed densely around the data, the interpolated estimate must
    track np.percentile within one bucket width."""
    rng = np.random.RandomState(0)
    values = rng.gamma(shape=2.0, scale=30.0, size=5000)  # latency-ish
    edges = tuple(float(e) for e in np.linspace(1, 500, 100))
    h = Histogram("lat", edges=edges)
    for v in values:
        h.observe(v)
    width = edges[1] - edges[0]
    for q in (0.50, 0.90, 0.95, 0.99):
        ref = float(np.percentile(values, q * 100))
        got = h.percentile(q)
        assert abs(got - ref) <= width + 1e-9, (q, got, ref)


def test_histogram_exposition_round_trip():
    """render() emits valid Prometheus histogram exposition that
    parse_histograms reconstructs exactly (cumulative buckets, sum,
    count) — and histogram_quantile agrees on both sides."""
    h = Histogram("serve_latency_ms", "help text",
                  edges=(1.0, 10.0, 100.0))
    for v in (0.5, 3.0, 3.0, 50.0, 400.0):
        h.observe(v)
    text = "\n".join(h.render()) + "\n"
    assert '# TYPE tpu_resnet_serve_latency_ms histogram' in text
    assert 'tpu_resnet_serve_latency_ms_bucket{le="+Inf"} 5' in text
    parsed = parse_histograms(text)
    snap = parsed["tpu_resnet_serve_latency_ms"]
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(456.5)
    assert snap["buckets"][:3] == [(1.0, 1), (10.0, 3), (100.0, 4)]
    assert snap["buckets"][3][1] == 5  # +Inf cumulative
    for q in (0.1, 0.5, 0.9):
        assert histogram_quantile(snap, q) == pytest.approx(
            h.percentile(q))
    # plain-gauge parser still accepts the same text (histogram series
    # collapse instead of crashing)
    flat = parse_prometheus(text)
    assert flat["tpu_resnet_serve_latency_ms_count"] == 5.0


def test_histogram_weighted_observe_and_edge_cases():
    h = Histogram("x", edges=(10.0, 20.0))
    h.observe(5.0, n=9)   # the train loop's interval form
    h.observe(15.0)
    assert h.snapshot()["count"] == 10
    assert h.percentile(0.5) == pytest.approx(
        np.interp(5, [0, 9], [0, 10]), abs=10.0)
    assert histogram_quantile({"buckets": [], "count": 0}, 0.5) == 0.0
    assert Histogram("y").snapshot()["count"] == 0
    with pytest.raises(ValueError):
        Histogram("bad", edges=(3.0, 2.0))


def test_registry_histograms_predeclared_and_live(tmp_path):
    """Pre-declared histograms render empty buckets before the first
    observation; observe()/hist_percentile() flow through a live scrape
    as real percentile data."""
    reg = TelemetryRegistry(stale_after_sec=60.0,
                            histograms=CORE_HISTOGRAMS)
    srv = TelemetryServer.maybe_start(0, reg, train_dir=str(tmp_path))
    try:
        report = scrape(f"127.0.0.1:{srv.port}")
        hist = report["histograms"]["tpu_resnet_train_step_ms"]
        assert hist["count"] == 0  # pre-declared, empty — not absent
        for ms, n in ((5.0, 18), (7.0, 18), (40.0, 4)):
            reg.observe("train_step_ms", ms, n=n)
        report = scrape(f"127.0.0.1:{srv.port}")
        hist = report["histograms"]["tpu_resnet_train_step_ms"]
        assert hist["count"] == 40
        p50 = histogram_quantile(hist, 0.50)
        p99 = histogram_quantile(hist, 0.99)
        assert 0 < p50 <= 10.0 < p99 <= 50.0
        assert reg.hist_percentile("train_step_ms", 0.5) == pytest.approx(
            p50)
        # undeclared names auto-create with default latency buckets
        reg.observe("adhoc_ms", 3.0)
        assert reg.hist_percentile("adhoc_ms", 0.5) > 0
    finally:
        srv.close()


def test_core_gauges_include_mfu_series(tmp_path):
    reg = TelemetryRegistry()
    srv = TelemetryServer.maybe_start(0, reg, train_dir=str(tmp_path))
    try:
        metrics = scrape(f"127.0.0.1:{srv.port}")["metrics"]
        assert metrics["tpu_resnet_mfu"] == 0.0  # pre-declared
        assert metrics["tpu_resnet_model_flops_per_sec"] == 0.0
    finally:
        srv.close()


# ---------------------------------------------------------------- run_id

def test_run_id_minted_once_and_shared(tmp_path):
    d = str(tmp_path)
    assert obs.read_run_id(d) is None  # read-only consumers: no minting
    rid = obs.ensure_run_id(d)
    assert rid and len(rid) == 12
    assert obs.ensure_run_id(d) == rid      # stable across resumes
    assert obs.read_run_id(d) == rid        # sidecars see the same id
    with open(tmp_path / "run_id.json") as f:
        assert json.load(f)["run_id"] == rid


def test_span_tracer_stamps_run_id_and_pid(tmp_path):
    tr = obs.SpanTracer(str(tmp_path), run_id="abc123")
    tr.event("marker", step=1)
    tr.run_id = "late-id"  # mutable: sidecar discovers the id later
    tr.event("marker2")
    tr.close()
    spans = load_spans(str(tmp_path / "events.jsonl"))
    assert [s["run_id"] for s in spans] == ["abc123", "late-id"]
    assert all(s["pid"] == os.getpid() for s in spans)


def test_manifest_carries_run_id(tmp_path):
    from tpu_resnet import parallel
    from tpu_resnet.config import load_config

    cfg = load_config("smoke")
    mesh = parallel.create_mesh(cfg.mesh)
    rid = obs.ensure_run_id(str(tmp_path))
    obs.write_manifest(str(tmp_path), cfg, mesh, run_id=rid)
    with open(tmp_path / "manifest.json") as f:
        assert json.load(f)["run_id"] == rid
