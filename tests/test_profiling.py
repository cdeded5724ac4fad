"""Profiling subsystem (tools/profiling.py) and train_and_eval mode —
the tracing/observability parity items of SURVEY.md §5."""

import glob
import os

import jax
import pytest

from tpu_resnet.config import load_config
from tpu_resnet.evaluation import train_and_eval
from tpu_resnet.parallel import create_mesh
from tpu_resnet.tools import profiling
from tpu_resnet.train.loop import _chunk_len, train


def test_parse_window():
    assert profiling.parse_window("") is None
    assert profiling.parse_window("100:120") == (100, 120)
    with pytest.raises(ValueError):
        profiling.parse_window("120:100")
    with pytest.raises(ValueError):
        profiling.parse_window("abc")


def test_chunk_len_respects_trace_window():
    cfg = load_config("smoke")
    cfg.train.steps_per_call = 10
    cfg.train.log_every = 100
    cfg.train.summary_every = 100
    cfg.train.checkpoint_every = 100
    # 95 → 100 (log boundary), 100 → 103 (window start), 103 → 107
    # (window end): fused chunks never straddle the trace window.
    assert _chunk_len(95, 1000, cfg.train, 10_000, (103, 107)) == 5
    assert _chunk_len(100, 1000, cfg.train, 10_000, (103, 107)) == 3
    assert _chunk_len(103, 1000, cfg.train, 10_000, (103, 107)) == 4


@pytest.mark.slow  # 32s: opt-in profiler window end-to-end; the chunk/
# window clipping invariant stays tier-1 via the pure _chunk_len test
# above. Joined the slow tier to keep the default tier inside the 870s
# verify budget (precedent: the fused A/B smokes).
def test_trace_window_during_training(tmp_path):
    """A traced run writes a profile under <train_dir>/profile and the
    trace covers whole chunks (no straddle)."""
    cfg = load_config("smoke")
    cfg.data.device_resident = "on"
    cfg.train.steps_per_call = 4
    cfg.train.train_steps = 20
    cfg.train.checkpoint_every = 20
    cfg.train.profile_steps = "6:10"
    cfg.train.train_dir = str(tmp_path)
    mesh = create_mesh(cfg.mesh, devices=jax.devices()[:8])
    state = train(cfg, mesh=mesh)
    assert int(jax.device_get(state.step)) == 20
    profile_dir = os.path.join(str(tmp_path), "profile")
    assert os.path.isdir(profile_dir)
    assert glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                     recursive=True)


@pytest.mark.slow  # 22s: in-process train_and_eval e2e whose CLI-level
# sibling (test_cli.py::test_train_and_eval_cli) stays tier-1; joined
# the slow tier to keep the default tier inside the 870s verify budget
# (precedent: PR1-3 budget moves).
def test_train_and_eval(tmp_path):
    """train_and_eval trains to completion and produces the sidecar's
    best-precision artifact for the final checkpoint."""
    cfg = load_config("smoke")
    cfg.train.train_steps = 20
    cfg.train.checkpoint_every = 10
    cfg.train.eval_interval_secs = 1
    cfg.train.train_dir = str(tmp_path)
    mesh = create_mesh(cfg.mesh, devices=jax.devices()[:8])
    precision = train_and_eval(cfg, mesh=mesh)
    assert precision is not None and 0.0 <= precision <= 1.0
    best = os.path.join(str(tmp_path), "eval", "best_precision.json")
    assert os.path.exists(best)


# ------------------------------------- one clock, and the capture's report

def _fake_capture(train_dir, events, stamp="2026_01_01_00_00_00"):
    """A capture as ``stop_trace`` leaves it, as far as the reduction and
    trace-export read it: the Chrome-trace export beside the xplane."""
    import gzip
    import json

    d = os.path.join(train_dir, "profile", "plugins", "profile", stamp)
    os.makedirs(d, exist_ok=True)
    with gzip.open(os.path.join(d, "host.trace.json.gz"), "wt") as f:
        json.dump({"traceEvents": events}, f)


def _op(name, ts, dur, tf_op=None, pid=3, tid=3):
    args = {"long_name": f"%{name} = f32[] fusion()"}
    if tf_op is not None:
        args["tf_op"] = tf_op
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
            "dur": dur, "args": args}


_DEVICE_META = [
    {"ph": "M", "pid": 3, "name": "process_name",
     "args": {"name": "/device:TPU:0"}},
    {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
     "args": {"name": "XLA Ops"}},
    {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name",
     "args": {"name": "XLA Modules"}},
]
_SCAN = "jit(chunk)/while/body/closed_call/"


def test_step_tracer_span_starts_at_the_entry_into_start_trace(
        tmp_path, monkeypatch):
    """The capture's clock counts from the ENTRY into start_trace: the
    profiler_trace span starts there, on both clocks, and says how long
    the call took; the Python tracer is off, annotations are kept."""
    import time

    from tpu_resnet.obs import SpanTracer
    from tpu_resnet.obs.spans import load_spans

    seen = {}

    def slow_start(log_dir, profiler_options=None, **_):
        seen["options"] = profiler_options
        time.sleep(0.25)

    def stop():
        # what the real stop_trace leaves: one operation 0.3 s after the
        # session's zero, inside the traced window
        _fake_capture(str(tmp_path),
                      _DEVICE_META + [_op("fusion.1", 300e3, 50.0,
                                          "jit(chunk)/optimizer/add:")])

    monkeypatch.setattr(jax.profiler, "start_trace", slow_start)
    monkeypatch.setattr(jax.profiler, "stop_trace", stop)
    spans = SpanTracer(str(tmp_path))
    tracer = profiling.StepTracer(str(tmp_path), "2:4", spans=spans)
    wall0, mono0 = time.time(), time.monotonic_ns()
    tracer.before(1)   # outside the window: nothing starts
    assert seen == {}
    tracer.before(2)
    returned = time.monotonic_ns()
    time.sleep(0.1)
    assert not tracer.after(3)
    assert tracer.after(4, sync=None) is False  # closed, nothing to drain
    spans.close()
    (span,) = [s for s in load_spans(str(tmp_path / "events.jsonl"))
               if s["span"] == "profiler_trace"]
    assert seen["options"].python_tracer_level == 0
    assert seen["options"].host_tracer_level == 1
    # at the entry, not 0.25 s later at the return
    assert 0 <= span["start"] - wall0 < 0.1
    assert 0 <= span["mono_ns"] - mono0 < 0.1e9
    assert span["session_zero_mono_ns"] == span["mono_ns"]
    assert 0.25 <= span["start_trace_sec"] <= \
        (returned - span["mono_ns"]) / 1e9
    assert span["stop_trace_sec"] >= 0
    assert span["start_step"] == 2 and span["stop_step"] == 4
    assert os.path.exists(span["scopes"])

    # trace-export lays the device lanes on that entry: the operation at
    # 0.3 s of the capture lands 0.3 s after the span's start, not 0.55 s
    from tpu_resnet.obs.trace import build_trace

    events = build_trace(str(tmp_path), device_trace=True)["traceEvents"]
    (anchor,) = [e for e in events if e["name"] == "profiler_trace"]
    (op,) = [e for e in events if e.get("cat") == "device"
             and e["ph"] == "X"]
    assert op["name"] == "fusion.1"
    assert op["ts"] - anchor["ts"] == pytest.approx(300e3, abs=2.0)


def test_step_tracer_survives_a_capture_it_cannot_read(tmp_path,
                                                       monkeypatch):
    """The report runs on the train loop's thread: a capture laid out
    otherwise than the reduction expects (an operation without ``ts``, a
    thread without a name) costs the report, never the run."""
    from tpu_resnet.obs import SpanTracer
    from tpu_resnet.obs.spans import load_spans

    broken = _op("fusion.1", 300e3, 50.0, "jit(chunk)/optimizer/add:")
    del broken["ts"]
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: _fake_capture(
        str(tmp_path), _DEVICE_META + [
            {"ph": "M", "pid": 3, "tid": 9, "name": "thread_name"}, broken]))
    spans = SpanTracer(str(tmp_path))
    tracer = profiling.StepTracer(str(tmp_path), "2:4", spans=spans)
    tracer.before(2)
    assert tracer.after(4, sync=None) is False  # returned, did not raise
    spans.close()
    (span,) = [s for s in load_spans(str(tmp_path / "events.jsonl"))
               if s["span"] == "profiler_trace"]
    assert "scopes" not in span and span["stop_step"] == 4
    assert not os.path.exists(tmp_path / "profile" / "scopes.json")


@pytest.mark.parametrize("tf_op,want", [
    # as read off a capture of the chip (PR 26): the scan's wrappers, the
    # step's scope, Flax's modules beneath, JAX's mark on the backward pass
    (_SCAN + "transpose(jvp(forward))/ResNetV2/block_layer2/block1/conv1/"
     "conv_general_dilated:", ("forward", "block_layer2", True)),
    ("jit(chunk)/jvp(forward)/ResNetV2/initial_conv/conv/"
     "conv_general_dilated:", ("forward", "initial_conv", False)),
    (_SCAN + "augment/vmap()/gather:", ("augment", "gather", False)),
    ("jit(chunk)/batch_cut/dynamic_slice:", ("batch_cut", "", False)),
    (_SCAN + "transpose(jvp(loss))/reduce_sum:", ("loss", "", True)),
    (_SCAN + "optimizer/add:", ("optimizer", "", False)),
    ("jit(shuffle)/epoch_shuffle/jit(_take)/gather:",
     ("epoch_shuffle", "gather", False)),
    ("jit(chunk)/while/body/closed_call/jvp()/reduce_sum:",
     ("other", "", False)),
    ("", ("other", "", False)),
])
def test_scope_of_an_operations_path(tf_op, want):
    assert profiling.scope_of(tf_op) == want


def test_reduce_capture_self_time_scopes_and_gaps(tmp_path):
    """A hand-made capture: a while covering its body (self time, not a
    sum), forward and backward apart, clipping to the window, and the
    idle gaps named by the loop's phase under each."""
    fwd = _SCAN + "jvp(forward)/ResNetV2/block_layer1/conv:"
    bwd = _SCAN + "transpose(jvp(forward))/ResNetV2/block_layer1/conv:"
    _fake_capture(str(tmp_path), _DEVICE_META + [
        _op("copy.1", 0.0, 100.0),                  # before the window
        _op("while.9", 1000.0, 4000.0),             # covers the next three
        _op("fusion.1", 1000.0, 1000.0, fwd),
        _op("fusion.2", 2000.0, 2000.0, bwd),
        _op("dynamic-update-slice.7", 4000.0, 500.0,
            _SCAN + "augment/vmap()/gather:"),
        _op("fusion.3", 5600.0, 400.0, "jit(chunk)/optimizer/add:"),
        _op("jit_chunk", 1000.0, 5000.0, tid=2),    # a module, not an op
        _op("fusion.4", 9000.0, 2000.0, bwd),       # runs past the window
    ])
    report = profiling.reduce_capture(
        str(tmp_path), window_ns=(500_000, 10_000_000),
        phases=[("train.interval", 0, 20_000_000),
                ("train.log_write", 6_100_000, 8_900_000),
                ("train.dispatch", 8_900_000, 9_000_000),
                ("train.device_wait", 400_000, 950_000)])
    assert report["window_s"] == pytest.approx(9.5e-3)
    # 1000..5000 (the while), 5600..6000, 9000..10000 clipped
    assert report["busy_s"] == pytest.approx(5.4e-3)
    assert report["busy_share"] == pytest.approx(5.4 / 9.5, abs=1e-4)
    scopes = report["scopes"]
    assert scopes["forward"] == {"forward_s": pytest.approx(1e-3),
                                 "backward_s": pytest.approx(3e-3)}
    assert scopes["augment"]["forward_s"] == pytest.approx(0.5e-3)
    assert scopes["optimizer"]["forward_s"] == pytest.approx(0.4e-3)
    # the while's own time is what its body left: 4000 - 3500
    assert scopes["other"]["forward_s"] == pytest.approx(0.5e-3)
    assert sum(sum(v.values()) for v in scopes.values()) == \
        pytest.approx(report["busy_s"])
    assert report["families"]["augment"][0] == \
        ["dynamic-update-slice", pytest.approx(0.5e-3)]
    assert ["forward/block_layer1", "backward",
            pytest.approx(3e-3)] in report["details"]
    gaps = report["idle_gaps"]
    assert [(g["seconds"], g["span"]) for g in gaps] == [
        (pytest.approx(3e-3), "train.log_write"),      # 6000..9000
        (pytest.approx(0.6e-3), "train.interval"),     # 5000..5600: no phase
        (pytest.approx(0.5e-3), "train.device_wait")]  # 500..1000
    lines = profiling.summary_lines(report)
    assert len(lines) == 5
    assert "forward 1.00 ms" in lines[1] and "forward 3.00 ms" in lines[2]
    assert "under train.log_write" in lines[3]
    with pytest.raises(ValueError):
        profiling.reduce_capture(str(tmp_path), window_ns=(20e9, 21e9))


def test_step_tracer_reduces_a_real_capture(tmp_path):
    """A capture made on the CPU in this process, through StepTracer and
    the loop's recorder: scopes.json and the profiler_trace span. (The CPU
    backend's operations carry no scope path: they all read ``other``;
    the chip's do, see test_scope_of_an_operations_path.)"""
    import json

    import jax.numpy as jnp

    from tpu_resnet import obs
    from tpu_resnet.obs.spans import load_spans

    @jax.jit
    def step(x):
        with jax.named_scope("forward"):
            return jnp.tanh(x @ x).sum()

    x = jnp.ones((256, 256))
    step(x).block_until_ready()
    spans = obs.SpanTracer(str(tmp_path))
    breakdown = obs.StepBreakdown()
    tracer = profiling.StepTracer(str(tmp_path), "0:3", spans=spans,
                                  phases=breakdown.spans)
    tracer.before(0)
    for i in range(3):
        with breakdown.dispatch(i, 1):
            y = step(x)
        breakdown.sample_device(y, 1, i + 1)
    assert tracer.after(3, sync=y) is True
    breakdown.close()
    spans.close()
    with open(os.path.join(str(tmp_path), "profile", "scopes.json")) as f:
        report = json.load(f)
    assert report["operations"] > 0
    assert 0 < report["busy_s"] <= report["window_s"]
    assert 0 < report["busy_share"] <= 1
    assert sum(sum(v.values()) for v in report["scopes"].values()) == \
        pytest.approx(report["busy_s"], rel=1e-3)
    assert report["start_step"] == 0 and report["stop_step"] == 3
    assert report["capture_bytes"] > 0 and report["start_trace_sec"] >= 0
    assert all(g["span"].startswith("train.") or g["span"] == "idle"
               for g in report["idle_gaps"])
    (span,) = [s for s in load_spans(str(tmp_path / "events.jsonl"))
               if s["span"] == "profiler_trace"]
    assert span["busy_share"] == report["busy_share"]
    assert span["session_zero_mono_ns"] == span["mono_ns"]


def test_lowered_step_names_the_scopes_of_the_step():
    """The chunk program's operations carry the step's own scopes in
    their ``op_name``: what the capture's reduction splits a step by."""
    import re

    import jax.numpy as jnp

    from tpu_resnet import parallel
    from tpu_resnet.data import augment as aug_lib
    from tpu_resnet.data import device_data
    from tpu_resnet.models import build_model
    from tpu_resnet.train import schedule as sched_lib
    from tpu_resnet.train.state import init_partitioned_state
    from tpu_resnet.train.step import make_train_step

    cfg = load_config("smoke")
    cfg.model.name = "mlp"
    mesh = create_mesh(cfg.mesh, devices=jax.devices()[:1])
    model = build_model(cfg)
    sched = sched_lib.build_schedule(cfg.optim, cfg.train)
    augment_fn, _ = aug_lib.get_augment_fns(cfg.data.dataset)
    size = cfg.data.resolved_image_size
    state = init_partitioned_state(
        model, cfg.optim, sched, jax.random.PRNGKey(0),
        jnp.zeros((1, size, size, 3)),
        parallel.make_partitioner(cfg.mesh, mesh))
    base = make_train_step(model, cfg.optim, sched, cfg.data.num_classes,
                           augment_fn, base_rng=jax.random.PRNGKey(1),
                           mesh=mesh)
    lowered = device_data.staged_chunk_jit(base, mesh, 3).lower(
        state, jnp.zeros((4, 16, size, size, 3), jnp.uint8),
        jnp.zeros((4, 16), jnp.int32), jnp.int32(0))
    names = set(re.findall(r'loc\("([^"]+)"', lowered.as_text(
        debug_info=True)))
    found = {profiling.scope_of(n)[0] for n in names}
    assert {"augment", "forward", "loss", "optimizer", "metrics",
            "batch_cut"} <= found
    assert any(profiling.scope_of(n) == ("forward", "hidden", True)
               for n in names)  # the backward pass, on the same path
    ds = device_data.DeviceDataset(
        mesh, jnp.zeros((32, size, size, 3), jnp.uint8).__array__(),
        jnp.zeros((32,), jnp.int32).__array__(), 16)
    text = ds._shuffle.lower(ds._flat_images, ds._flat_labels,
                             0).as_text(debug_info=True)
    assert "epoch_shuffle" in text
