"""Chrome-trace timeline exporter (tpu_resnet/obs/trace.py): schema
validity, lane/counter construction, run_id correlation, deterministic
re-export — on synthetic artifacts and on a real tiny train run."""

import json
import os
import time

import pytest

from tpu_resnet.obs.spans import load_spans
from tpu_resnet.obs.trace import (
    SERVE_EVENTS_FILE,
    build_trace,
    export_trace,
    main as trace_main,
    validate_trace,
)


def _write_jsonl(path, records):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


@pytest.fixture
def run_dir(tmp_path):
    """A synthetic train_dir with every artifact class the exporter
    merges: train spans, metrics with breakdown + engine counters, eval
    sidecar spans (same run_id), serve spans, manifest + run_id."""
    d = str(tmp_path / "run")
    rid = "deadbeef1234"
    t0 = 1_700_000_000.0
    _write_jsonl(os.path.join(d, "events.jsonl"), [
        {"span": "compile", "start": t0, "end": t0 + 3.5, "pid": 111,
         "run_id": rid, "step": 0},
        {"span": "checkpoint_save", "start": t0 + 10, "end": t0 + 10.4,
         "pid": 111, "run_id": rid, "step": 50, "async": True},
        {"span": "preempt_stop", "start": t0 + 30, "end": t0 + 30,
         "pid": 111, "run_id": rid, "step": 90},
        {"span": "run", "start": t0, "end": t0 + 31, "pid": 111,
         "run_id": rid, "start_step": 0, "stop_step": 90},
    ])
    _write_jsonl(os.path.join(d, "metrics.jsonl"), [
        {"step": 20, "wall": t0 + 8, "loss": 2.1, "steps_per_sec": 4.0,
         "data_wait_sec": 0.2, "data_wait_frac": 0.04,
         "dispatch_sec": 0.5, "mfu": 0.31,
         "model_flops_per_sec": 1.2e12, "data_ring_occupancy": 3.0,
         "data_decode_images_per_sec": 800.0},
        {"step": 40, "wall": t0 + 13, "loss": 1.9, "steps_per_sec": 4.1,
         "data_wait_sec": 0.1, "data_wait_frac": 0.02,
         "dispatch_sec": 0.5, "mfu": 0.32,
         "model_flops_per_sec": 1.25e12, "data_ring_occupancy": 4.0,
         "data_decode_images_per_sec": 810.0},
    ])
    _write_jsonl(os.path.join(d, "eval", "events.jsonl"), [
        {"span": "eval_pass", "start": t0 + 11, "end": t0 + 14,
         "pid": 222, "run_id": rid, "step": 50, "precision": 0.7},
    ])
    _write_jsonl(os.path.join(d, SERVE_EVENTS_FILE), [
        {"span": "serve_warmup", "start": t0 + 20, "end": t0 + 22,
         "pid": 333, "run_id": rid, "model_step": 50},
        {"span": "serve_reload", "start": t0 + 25, "end": t0 + 25.2,
         "pid": 333, "run_id": rid, "model_step": 90},
    ])
    with open(os.path.join(d, "run_id.json"), "w") as f:
        json.dump({"run_id": rid}, f)
    with open(os.path.join(d, "manifest.json"), "w") as f:
        json.dump({"schema": 2, "run_id": rid}, f)
    return d


def test_trace_schema_and_lanes(run_dir):
    trace = build_trace(run_dir)
    assert validate_trace(trace) == []
    meta = trace["metadata"]
    assert meta["run_id"] == "deadbeef1234"
    # every source reported the SAME run_id — the correlated-session claim
    assert meta["source_run_ids"] == {
        "train": ["deadbeef1234"], "eval": ["deadbeef1234"],
        "serve": ["deadbeef1234"]}

    events = trace["traceEvents"]
    pids = {e["pid"] for e in events}
    assert {111, 222, 333} <= pids  # three process lanes
    names = {e["name"] for e in events}
    assert {"run", "compile", "eval_pass", "serve_warmup",
            "serve_reload"} <= names
    # process lanes labeled with the run_id
    proc_names = {e["args"]["name"] for e in events
                  if e["ph"] == "M" and e["name"] == "process_name"}
    assert any("trainer run=deadbeef1234" == n for n in proc_names)
    assert any(n.startswith("eval-sidecar") for n in proc_names)
    assert any(n.startswith("serve") for n in proc_names)

    # counters: breakdown + data-engine ring series, values preserved
    counters = [e for e in events if e["ph"] == "C"]
    by_name = {}
    for c in counters:
        by_name.setdefault(c["name"], []).append(c["args"]["value"])
    assert by_name["mfu"] == [0.31, 0.32]
    assert by_name["data_ring_occupancy"] == [3.0, 4.0]
    assert by_name["steps_per_sec"] == [4.0, 4.1]

    # interval slice carries the breakdown args
    (interval,) = [e for e in events
                   if e["name"].startswith("train_interval")]
    assert interval["ph"] == "X"
    assert interval["dur"] == pytest.approx(5e6)
    assert interval["args"]["data_wait_frac"] == 0.02

    # zero-duration spans render as instants, ts are sorted + non-negative
    (instant,) = [e for e in events if e["name"] == "preempt_stop"]
    assert instant["ph"] == "i"
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts) and ts[0] >= 0


def test_trace_export_deterministic_and_cli(run_dir, tmp_path, capsys):
    out1 = str(tmp_path / "a.json")
    out2 = str(tmp_path / "b.json")
    path1, trace1 = export_trace(run_dir, out=out1)
    assert path1 == out1
    assert validate_trace(trace1) == []
    export_trace(run_dir, out=out2)
    with open(out1, "rb") as a, open(out2, "rb") as b:
        assert a.read() == b.read()  # stable under re-export

    # default output path + CLI wrapper
    assert trace_main(["--dir", run_dir]) == 0
    assert "run_id=deadbeef1234" in capsys.readouterr().out
    with open(os.path.join(run_dir, "trace.json")) as f:
        assert validate_trace(json.load(f)) == []


def test_trace_export_tolerates_partial_dirs(tmp_path):
    # nothing at all → loud error, not an empty trace
    with pytest.raises(FileNotFoundError):
        build_trace(str(tmp_path))
    assert trace_main(["--dir", str(tmp_path)]) == 1
    # metrics-only (no spans, no manifest): still a valid trace
    _write_jsonl(str(tmp_path / "metrics.jsonl"),
                 [{"step": 5, "wall": 100.0, "steps_per_sec": 2.0},
                  {"step": 10, "wall": 105.0, "steps_per_sec": 2.1,
                   "data_wait_sec": 0.1}])
    trace = build_trace(str(tmp_path))
    assert validate_trace(trace) == []
    assert trace["metadata"]["run_id"] is None
    assert any(e["ph"] == "C" for e in trace["traceEvents"])


def test_validate_trace_catches_bad_traces():
    assert validate_trace([]) == ["trace is not a JSON object"]
    assert validate_trace({}) == ["traceEvents missing or not a list"]
    bad = {"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "ts": 5.0, "dur": -1},
        {"name": "b", "ph": "??", "pid": 1, "ts": 1.0},
        {"ph": "C", "pid": 1, "ts": 2.0},
    ]}
    problems = "\n".join(validate_trace(bad))
    assert "dur >= 0" in problems
    assert "unknown phase" in problems
    assert "missing required key 'name'" in problems
    assert "must be sorted" in problems


def test_trace_export_on_real_train_run(tmp_path, monkeypatch):
    """Integration: a real tiny CPU train (telemetry artifacts written by
    the actual loop) exports a schema-valid trace whose run_id matches
    the manifest and whose counters carry the live mfu series."""
    from tpu_resnet.config import load_config
    from tpu_resnet.train import train

    # CPU has no entry in the peak-FLOPs table (an unknown chip reports
    # no mfu); a patched table makes the gauge genuinely nonzero here.
    from tpu_resnet.obs import mfu as mfu_mod
    monkeypatch.setattr(mfu_mod, "PEAK_FLOPS_BY_KIND", (("cpu", 1e12),))
    cfg = load_config("smoke")
    cfg.model.name = "mlp"
    cfg.data.device_resident = "off"
    cfg.data.transfer_stage = 1
    cfg.train.train_dir = str(tmp_path / "run")
    cfg.train.train_steps = 8
    cfg.train.checkpoint_every = 4
    cfg.train.log_every = 2
    cfg.train.summary_every = 2
    cfg.train.image_summary_every = 0
    cfg.train.steps_per_call = 2
    cfg.train.global_batch_size = 16
    train(cfg)

    # ... the eval sidecar evaluates the final checkpoint ...
    import copy

    from tpu_resnet.evaluation import evaluate

    eval_cfg = copy.deepcopy(cfg)
    eval_cfg.train.eval_once = True
    assert evaluate(eval_cfg) is not None

    # ... and a serve session (real checkpoint backend) warms and drains.
    from tpu_resnet.obs import read_run_id
    from tpu_resnet.obs.spans import SpanTracer
    from tpu_resnet.serve.server import PredictServer

    serve_cfg = copy.deepcopy(cfg)
    serve_cfg.serve.port = 0
    serve_cfg.serve.host = "127.0.0.1"
    serve_cfg.serve.max_batch = 2
    serve_cfg.serve.reload_interval_secs = 0
    spans = SpanTracer(cfg.train.train_dir, filename=SERVE_EVENTS_FILE,
                       run_id=read_run_id(cfg.train.train_dir))
    srv = PredictServer(serve_cfg, spans=spans).start()
    srv.drain(10.0)
    srv.close()
    spans.close()

    path, trace = export_trace(cfg.train.train_dir)
    assert validate_trace(trace) == []
    with open(os.path.join(cfg.train.train_dir, "manifest.json")) as f:
        manifest = json.load(f)
    rid = manifest["run_id"]
    assert rid
    assert trace["metadata"]["run_id"] == rid
    # one correlated session: all three lanes report the SAME run_id
    assert trace["metadata"]["source_run_ids"] == {
        "train": [rid], "eval": [rid], "serve": [rid]}
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"run", "compile", "checkpoint_save", "mfu_account",
            "eval_pass", "serve_warmup", "serve_drain"} <= names
    counter_names = {e["name"] for e in trace["traceEvents"]
                     if e["ph"] == "C"}
    assert {"steps_per_sec", "data_wait_frac", "mfu",
            "model_flops_per_sec"} <= counter_names
    # the registry file the accounting wrote is readable and non-empty
    from tpu_resnet.obs.mfu import FlopsRegistry
    reg = FlopsRegistry.load(cfg.train.train_dir)
    (key,) = reg.to_dict()["entries"].keys()
    assert key.startswith("train|synthetic_mlp_f32|mesh")
    assert reg.flops(key) and reg.flops(key) > 0


@pytest.mark.slow  # live train subprocess + mid-run scrape (~40s); the
# exporter/schema/run_id plumbing is covered in the default tier above
def test_doctor_trace_probe_contract():
    """doctor --trace-probe: the live model_flops_per_sec gauge and
    train_step_ms histogram go live mid-run, the SIGTERM preemption contract holds,
    and the exported trace schema-checks with the manifest's run_id."""
    from tpu_resnet.tools.doctor import _check_trace_probe

    out = _check_trace_probe()
    assert out["ok"], out
    assert out["model_flops_per_sec"] > 0
    assert out["step_ms_observations"] > 0
    assert out["trace_events"] > 0
    assert out["run_id"]


def test_h2d_transfer_lane(tmp_path):
    """h2d_transfer spans (the double-buffered staged transfers) render
    on their own named thread of the trainer lane, with the byte counters
    lifted from metrics.jsonl — the overlap-visibility contract of the
    MFU campaign's transfer leg."""
    d = str(tmp_path / "run")
    t0 = 1_700_000_000.0
    _write_jsonl(os.path.join(d, "events.jsonl"), [
        {"span": "run", "start": t0, "end": t0 + 20, "pid": 7,
         "run_id": "r", "start_step": 0, "stop_step": 10},
        {"span": "h2d_transfer", "start": t0 + 1.0, "end": t0 + 1.2,
         "pid": 7, "run_id": "r", "bytes": 147648, "steps": 3},
        {"span": "h2d_transfer", "start": t0 + 2.0, "end": t0 + 2.3,
         "pid": 7, "run_id": "r", "bytes": 147648, "steps": 3},
    ])
    _write_jsonl(os.path.join(d, "metrics.jsonl"), [
        {"step": 6, "wall": t0 + 3, "loss": 2.0, "steps_per_sec": 3.0,
         "data_wait_sec": 0.1, "data_wait_frac": 0.02,
         "dispatch_sec": 0.4, "h2d_bytes_per_sec": 1.1e6,
         "h2d_overlap_frac": 0.8},
    ])
    trace = build_trace(d)
    assert validate_trace(trace) == []
    ev = trace["traceEvents"]
    h2d = [e for e in ev if e["name"] == "h2d_transfer"]
    assert len(h2d) == 2
    assert {e["tid"] for e in h2d} == {4}          # the transfer lane
    assert all(e["args"]["bytes"] == 147648 for e in h2d)
    run = next(e for e in ev if e["name"] == "run")
    assert run["tid"] != h2d[0]["tid"]              # distinct threads
    names = {(e.get("tid"), e["args"]["name"]) for e in ev
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert (4, "h2d-transfer") in names
    counters = {e["name"] for e in ev if e["ph"] == "C"}
    assert {"h2d_bytes_per_sec", "h2d_overlap_frac"} <= counters


def test_request_lanes_and_fleet_lane(run_dir):
    """Tail-sampled route_request/serve_request spans sharing a trace id
    render as per-request lanes (slowest first, drop-counted in the
    metadata, never silently capped), the replica span's queue/infer/
    stall segments are synthesized inside it, and fleetmon's spans get
    their own process lane."""
    rid = "deadbeef1234"
    t0 = 1_700_000_000.0
    _write_jsonl(os.path.join(run_dir, "route_events.jsonl"), [
        {"span": "route_request", "start": t0 + 20, "end": t0 + 20.5,
         "pid": 444, "run_id": rid, "trace_id": "tr-slow",
         "duration_sec": 0.5, "lane": "interactive", "status": 200,
         "sampled": "slow", "replica": "r0", "latency_ms": 500.0,
         "legs": [{"replicas": ["r0"], "status": 200,
                   "answered": "r0", "ms": 499.0}]},
        {"span": "route_request", "start": t0 + 21, "end": t0 + 21.05,
         "pid": 444, "run_id": rid, "trace_id": "tr-fast",
         "duration_sec": 0.05, "lane": "interactive", "status": 200,
         "sampled": "sampled", "replica": "r1", "latency_ms": 50.0},
    ])
    _write_jsonl(os.path.join(run_dir, SERVE_EVENTS_FILE), [
        {"span": "serve_warmup", "start": t0 + 19, "end": t0 + 19.5,
         "pid": 333, "run_id": rid, "model_step": 50},
        {"span": "serve_request", "start": t0 + 20.05,
         "end": t0 + 20.45, "pid": 333, "run_id": rid,
         "trace_id": "tr-slow", "duration_sec": 0.4, "status": 200,
         "sampled": "slow", "replica": "r0", "latency_ms": 400.0,
         "queue_wait_ms": 100.0, "infer_ms": 250.0,
         "pad_fraction": 0.5, "batch_size": 4, "n": 1},
    ])
    _write_jsonl(os.path.join(run_dir, "fleet_events.jsonl"), [
        {"span": "fleet_start", "start": t0 + 18, "end": t0 + 18,
         "pid": 555, "run_id": rid, "slo_ms": 50.0},
        {"span": "fleet_burn_alert", "start": t0 + 22, "end": t0 + 22,
         "pid": 555, "run_id": rid, "burn_rate_fast": 300.0,
         "burn_rate_slow": 120.0, "fleet_p99_ms": 420.0},
    ])
    trace = build_trace(run_dir)
    assert validate_trace(trace) == []
    meta = trace["metadata"]
    assert meta["request_lanes"] == {"traces": 2, "rendered": 2,
                                     "dropped": 0}
    assert meta["source_run_ids"]["route"] == [rid]
    assert meta["source_run_ids"]["fleet"] == [rid]

    events = trace["traceEvents"]
    lanes = {e["args"]["name"]: e for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"
             and e["pid"] == 7000000}
    # slowest trace is lane 1, by max span duration
    assert lanes["req tr-slow"]["tid"] == 1
    assert lanes["req tr-fast"]["tid"] == 2
    proc_names = {e["args"]["name"] for e in events
                  if e["ph"] == "M" and e["name"] == "process_name"}
    assert "requests (tail-sampled)" in proc_names
    assert any(n.startswith("fleetmon") for n in proc_names)

    req = [e for e in events if e.get("cat") == "request"]
    by_name = {e["name"]: e for e in req if e["tid"] == 1}
    assert {"route_request", "serve_request", "queue_wait", "infer",
            "stall"} <= set(by_name)
    # segments partition the replica span: 100ms wait + 250ms infer +
    # 50ms unattributed stall, nested inside it on the same lane
    assert by_name["queue_wait"]["dur"] == pytest.approx(1e5, abs=1.0)
    assert by_name["infer"]["dur"] == pytest.approx(2.5e5, abs=1.0)
    assert by_name["stall"]["dur"] == pytest.approx(5e4, abs=1.0)
    assert by_name["serve_request"]["ts"] >= by_name["route_request"]["ts"]
    assert by_name["route_request"]["args"]["trace_id"] == "tr-slow"
    assert by_name["route_request"]["args"]["legs"][0]["answered"] == "r0"
    # the fleet lane carries the alert instant
    assert any(e["name"] == "fleet_burn_alert" for e in events)
    # deterministic re-export with request lanes present
    assert build_trace(run_dir) == trace


# ------------------------------------------------- the loop's span tree
# One tiny train() (tpu_resnet/obs/breakdown.py is its recorder) read by
# several tests: chunks of 4, 2, 4 and 2 steps against log boundaries at 6
# and 12, so that the 2-step program compiles in the middle of the run.

@pytest.fixture(scope="module")
def span_run(tmp_path_factory):
    from tpu_resnet.config import load_config
    from tpu_resnet.obs.spans import SpanTracer
    from tpu_resnet.train import train

    cfg = load_config("smoke")
    cfg.model.name = "mlp"
    cfg.data.device_resident = "on"
    cfg.train.train_dir = str(tmp_path_factory.mktemp("span_run"))
    cfg.train.train_steps = 12
    cfg.train.checkpoint_every = 12
    cfg.train.log_every = 6
    cfg.train.summary_every = 6
    cfg.train.image_summary_every = 0
    cfg.train.steps_per_call = 4
    cfg.train.global_batch_size = 16
    writes = []  # the monotonic instant of every line events.jsonl got
    record = SpanTracer.record

    def noting(self, kind, *args, **kwargs):
        writes.append((time.monotonic_ns(), kind))
        return record(self, kind, *args, **kwargs)

    SpanTracer.record = noting
    try:
        train(cfg)
    finally:
        SpanTracer.record = record
    spans = load_spans(os.path.join(cfg.train.train_dir, "events.jsonl"))
    with open(os.path.join(cfg.train.train_dir, "metrics.jsonl")) as f:
        records = [json.loads(line) for line in f]
    return {"dir": cfg.train.train_dir, "spans": spans, "records": records,
            "writes": writes}


def _end_ns(span):
    return span["mono_ns"] + round(span["duration_sec"] * 1e9)


def test_startup_is_a_span_tree(span_run):
    spans = span_run["spans"]
    assert all("mono_ns" in s and "id" in s for s in spans)
    assert len({s["id"] for s in spans}) == len(spans)
    (startup,) = [s for s in spans if s["span"] == "train.startup"]
    assert "parent" not in startup
    children = {s["span"]: s for s in spans
                if s.get("parent") == startup["id"]}
    assert {"train.init_state", "train.autotune_probe", "train.load_split",
            "train.dataset_to_device", "train.first_dispatch"} <= \
        set(children)
    for child in children.values():
        assert startup["mono_ns"] <= child["mono_ns"]
        assert _end_ns(child) <= _end_ns(startup) + 1000
    first = children["train.first_dispatch"]
    assert _end_ns(first) == pytest.approx(_end_ns(startup), abs=1000)
    (legacy,) = [s for s in spans if s["span"] == "compile"
                 and "program" not in s]  # the first-dispatch wall time
    assert legacy["parent"] == first["id"] and legacy["step"] == 0
    under = [s for s in spans if s.get("parent") == first["id"]]
    assert {"train.dispatch", "train.device_wait", "compile"} <= \
        {s["span"] for s in under}
    # the run constant on every record is this span's length
    for rec in span_run["records"]:
        assert rec["startup_sec"] == pytest.approx(startup["duration_sec"],
                                                   abs=1e-3)


def test_one_compile_span_per_program_with_its_step(span_run):
    spans = span_run["spans"]
    compiles = [s for s in spans if s["span"] == "compile"
                and "program" in s
                and s["during"] != "process.before_train"]
    (legacy,) = [s for s in spans if s["span"] == "compile"
                 and "program" not in s]
    chunks = [s for s in compiles if s["program"] == "jit(chunk)"]
    # the 4-step program in the first dispatch, beneath its compile span;
    # the 2-step program at step 4, in the middle of training
    assert [(c["step"], c["steps"]) for c in chunks] == [(0, 4), (4, 2)]
    assert chunks[0]["parent"] == legacy["id"]
    assert chunks[1]["during"] == "train.dispatch"
    (dispatch,) = [s for s in spans if s["id"] == chunks[1]["parent"]]
    assert dispatch["span"] == "train.dispatch" and dispatch["step"] == 4
    assert all(c["cache_hit"] is False and c["seconds"] > 0
               for c in compiles)
    # every compile is in the running total the records carry, the one
    # at step 4 from the first on (what the save at step 12 compiles
    # comes after the last record)
    intervals = [s for s in spans if s["span"] == "train.interval"]
    for rec, interval in zip(span_run["records"], intervals):
        before = [c for c in compiles if c["mono_ns"] < _end_ns(interval)]
        assert chunks[1] in before
        assert rec["compile_load_sec"] == pytest.approx(
            sum(c["seconds"] for c in before), abs=1e-3 * len(before))
        assert rec["compile_load_sec"] < rec["startup_sec"] + 60


def test_phase_spans_nest_and_add_up(span_run):
    spans, records = span_run["spans"], span_run["records"]
    intervals = [s for s in spans if s["span"] == "train.interval"]
    assert [s["step"] for s in intervals] == [6, 12]
    assert [s["from_step"] for s in intervals] == [4, 6]
    for interval, rec in zip(intervals, records):
        kids = sorted((s for s in spans
                       if s.get("parent") == interval["id"]),
                      key=lambda s: s["mono_ns"])
        assert {"train.dispatch", "train.device_wait"} <= \
            {s["span"] for s in kids}
        assert all(s["span"].startswith("train.")
                   or s["span"] in ("compile", "trace", "lower")
                   for s in kids)
        phases = [s for s in kids
                  if s["span"] not in ("compile", "trace", "lower")]
        assert phases[-1]["span"] == "train.device_wait"  # it ends it
        edge = interval["mono_ns"]
        for s in phases:  # in order, inside the parent, never overlapping
            assert s["mono_ns"] >= edge - 1000
            edge = _end_ns(s)
        assert edge <= _end_ns(interval) + 1000
        assert rec["step"] == interval["step"]
        # the three parts of the interval add up to it within a millisecond
        assert (rec["loop_host_sec"] + rec["data_wait_sec"]
                + rec["device_sync_sec"]) == pytest.approx(
                    interval["duration_sec"], abs=1e-3)
        assert rec["dispatch_sec"] <= rec["loop_host_sec"]
        assert rec["boundary_stall_sec"] > 0
        # counters nothing reads are not on the record (PERF.md section 3)
        assert not {"synced_at_ns", "dispatches", "steps_dispatched",
                    "boundaries", "checkpoints", "compiles"} & rec.keys()
    assert [s["steps"] for s in intervals] == [2, 6]
    assert [sum(s["span"] == "train.dispatch" and s["parent"] == i["id"]
                for s in spans) for i in intervals] == [1, 2]
    # the boundary's own work belongs to the interval it opens
    second = intervals[1]["id"]
    assert {"train.log_fetch", "train.log_write"} <= {
        s["span"] for s in spans if s.get("parent") == second}
    # a checkpoint is a phase too, the last boundary's after its sync
    assert any(s["span"] == "train.checkpoint" and s["step"] == 12
               for s in spans)


def test_nothing_is_written_between_two_dispatches(span_run):
    spans, writes = span_run["spans"], span_run["writes"]
    dispatches = sorted((s for s in spans if s["span"] == "train.dispatch"),
                        key=lambda s: s["mono_ns"])
    assert [d["step"] for d in dispatches] == [0, 4, 6, 10]
    # steps 6..10 and 10..12 are dispatched back to back: no boundary and
    # no checkpoint lies between, so no line may have been written there
    lo, hi = _end_ns(dispatches[2]), dispatches[3]["mono_ns"]
    assert hi > lo
    assert [kind for t, kind in writes if lo < t < hi] == []
    # the iteration phases reach the file only in the closer chain
    last_dispatch = _end_ns(dispatches[3])
    assert all(t > last_dispatch for t, kind in writes
               if kind in ("train.dispatch", "train.interval",
                           "train.data_wait", "train.device_wait"))


def test_trace_export_draws_the_loop_phases(span_run):
    from tpu_resnet.obs.trace import _TID_PHASES

    _, trace = export_trace(span_run["dir"])
    assert validate_trace(trace) == []
    lane = [e for e in trace["traceEvents"]
            if e.get("tid") == _TID_PHASES and e["ph"] in ("X", "i")]
    assert {"train.startup", "train.interval", "train.dispatch",
            "train.device_wait", "train.log_write"} <= \
        {e["name"] for e in lane}
    assert all(e["name"].startswith("train.") for e in lane)
    assert any(e["ph"] == "M" and e["args"]["name"] == "loop-phases"
               for e in trace["traceEvents"])
