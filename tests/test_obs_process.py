"""What the loop's recorder sees of the process before ``train()`` and of
the first dispatch's trace and lowering (tpu_resnet/obs/breakdown.py,
tpu_resnet/obs/spans.py::process_start): the process's start read from
``/proc`` and its fallback, the ``process.*`` spans and the compiles made
before ``train()`` beneath them, ``trace``/``lower`` spans counted once,
and the process's age on every interval."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import tpu_resnet
from tpu_resnet import obs
from tpu_resnet.obs import breakdown as bd_lib
from tpu_resnet.obs import spans as spans_lib
from tpu_resnet.obs.spans import load_spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------ the process start

def test_process_start_is_read_from_proc():
    code = ("import json, time; t = time.monotonic_ns(); "
            "from tpu_resnet.obs.spans import process_start; "
            "print(json.dumps([t, *process_start()]))")
    env = dict(os.environ, PYTHONPATH=REPO)
    t0 = time.monotonic_ns()
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=120)
    t_line, start, source = json.loads(out.stdout.strip().splitlines()[-1])
    assert source == "proc_stat"
    # the child started after we called it, and its first line ran after
    # its start; the kernel's tick (10 ms) rounds the start down
    assert t0 - 20e6 <= start <= t_line
    assert t_line - start < 30e9
    # in this process: read once, before the package's first line
    ns, source = spans_lib.process_start()
    assert source == "proc_stat" and ns <= tpu_resnet.IMPORT_NS
    assert abs(spans_lib.read_process_start_ns() - ns) < 5e6


def test_stat_parsing_counts_fields_from_the_commands_end(tmp_path):
    hz = os.sysconf("SC_CLK_TCK")
    boot_ns = time.clock_gettime_ns(time.CLOCK_BOOTTIME)
    ticks = (boot_ns - 3 * 10 ** 9) * hz // 10 ** 9  # 3 s ago
    fields = ["S"] + ["0"] * 18 + [str(ticks), "0", "0"]
    stat = tmp_path / "stat"
    stat.write_text("4242 (a b) (c)) " + " ".join(fields) + "\n")
    got = spans_lib.read_process_start_ns(str(stat))
    want = time.monotonic_ns() - 3e9
    assert abs(got - want) < 2e9 / hz + 5e6


def test_without_proc_the_spans_start_at_the_package_import(
        tmp_path, monkeypatch):
    assert spans_lib.read_process_start_ns(str(tmp_path / "none")) is None
    (tmp_path / "bad").write_text("12 (x) S 1 2\n")
    assert spans_lib.read_process_start_ns(str(tmp_path / "bad")) is None
    monkeypatch.setattr(spans_lib, "read_process_start_ns",
                        lambda *a: None)
    monkeypatch.setattr(spans_lib, "_process_start", None)
    monkeypatch.setattr(bd_lib, "process_start", spans_lib.process_start)
    assert spans_lib.process_start() == (tpu_resnet.IMPORT_NS,
                                         "package_import")
    rec = obs.StepBreakdown()
    try:
        (before,) = [s for s in rec._pending
                     if s[0] == "process.before_train"]
        assert before[1] == tpu_resnet.IMPORT_NS
        assert before[7] == {"process_start": "package_import"}
        out = rec.interval()
        assert out["before_train_sec"] == round(
            (before[2] - before[1]) / 1e9, 4)
        assert out["process_age_sec"] >= out["before_train_sec"]
    finally:
        rec.close()


def test_the_listeners_start_at_the_end_of_the_package_import():
    import tpu_resnet.train.loop  # noqa: F401 - calls package_imported

    assert bd_lib._listening
    assert tpu_resnet.IMPORT_NS < bd_lib._imported_ns < time.monotonic_ns()


# ------------------------------------------------------ trace and lower

def test_trace_and_lower_count_once(tmp_path, monkeypatch):
    """A jit traced inside another's trace is part of it: one span and
    one count; a compile made inside a trace (a jit called on concrete
    arrays while tracing) keeps its span and counts as a compile only."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(bd_lib, "_current", None)
    x = jnp.arange(11.0)
    inner = jax.jit(lambda y: jnp.cos(y) * 2.0)
    eager = jax.jit(lambda y: y - 1.5)
    concrete = np.arange(13.0)

    def outer(y):
        # the eager call runs (and compiles) while outer is being traced
        with jax.ensure_compile_time_eval():
            shift = float(np.asarray(eager(concrete)).sum())
        return inner(y) + jnp.sin(y) + shift

    rec = obs.StepBreakdown()
    with rec.dispatch(step=3, steps=1):
        jax.jit(outer)(x).block_until_ready()
    out = rec.interval()
    tracer = obs.SpanTracer(str(tmp_path))
    rec.flush(tracer, ring=True)
    tracer.close()
    rec.close()
    spans = load_spans(str(tmp_path / "events.jsonl"))
    timed = [s for s in spans if s["span"] in ("trace", "lower")]
    compiles = [s for s in spans if s["span"] == "compile"
                and s["during"] != "process.before_train"]
    assert {s["span"] for s in timed} == {"trace", "lower"}
    assert all(s["program"] and s["during"] == "train.dispatch"
               and s["step"] == 3 for s in timed + compiles)
    # no written trace or lowering lies inside another
    for a in timed:
        for b in timed:
            if a is not b:
                assert not (b["mono_ns"] <= a["mono_ns"]
                            and _end(a) <= _end(b))
    (eager_compile,) = [s for s in compiles
                        if any(t["mono_ns"] <= s["mono_ns"]
                               and _end(s) <= _end(t) for t in timed)]
    outer_trace = [t for t in timed if t["mono_ns"] <= eager_compile[
        "mono_ns"] and _end(eager_compile) <= _end(t)]
    assert [t["span"] for t in outer_trace] == ["trace"]
    assert out["compile_load_sec"] == pytest.approx(
        sum(s["seconds"] for s in compiles), abs=1e-3)
    assert out["trace_lower_sec"] == pytest.approx(
        sum(s["seconds"] for s in timed) - eager_compile["seconds"],
        abs=2e-3)
    assert out["trace_lower_sec"] > 0


def _end(span):
    return span["mono_ns"] + round(span["duration_sec"] * 1e9)


# ------------------------------------------------------ through train()

def _made_before_train(x):
    return x * 2.5 + 0.25


@pytest.fixture(scope="module")
def before_run(tmp_path_factory):
    """A small train() run after a compile made before it, as the
    benchmark's planting of its checkpoint makes one."""
    import jax
    import jax.numpy as jnp

    from tpu_resnet.config import load_config
    from tpu_resnet.train import train

    cfg = load_config("smoke")
    cfg.model.name = "mlp"
    cfg.data.device_resident = "on"
    cfg.train.train_dir = str(tmp_path_factory.mktemp("before_train"))
    cfg.train.train_steps = 12
    cfg.train.checkpoint_every = 12
    cfg.train.log_every = 4
    cfg.train.summary_every = 4
    cfg.train.image_summary_every = 0
    cfg.train.steps_per_call = 4
    cfg.train.global_batch_size = 16
    bd_lib._current = None  # a recorder an earlier test left open
    jax.jit(_made_before_train)(jnp.ones(17)).block_until_ready()
    train(cfg)
    spans = load_spans(os.path.join(cfg.train.train_dir, "events.jsonl"))
    with open(os.path.join(cfg.train.train_dir, "metrics.jsonl")) as f:
        records = [json.loads(line) for line in f]
    return spans, records


def test_a_compile_before_train_is_beneath_process_before_train(
        before_run):
    spans, records = before_run
    (before,) = [s for s in spans if s["span"] == "process.before_train"]
    (made,) = [s for s in spans if s["span"] == "compile"
               and s.get("program") == "jit(_made_before_train)"]
    assert made["parent"] == before["id"]
    assert made["during"] == "process.before_train"
    assert "step" not in made
    assert before["mono_ns"] <= made["mono_ns"]
    assert _end(made) <= _end(before)
    under = [s for s in spans if s["span"] == "compile"
             and s.get("parent") == before["id"]]
    intervals = [s for s in spans if s["span"] == "train.interval"]
    assert len(intervals) == len(records) == 3
    for rec, interval in zip(records, intervals):
        assert rec["before_train_compile_sec"] == pytest.approx(
            sum(s["seconds"] for s in under), abs=1e-3 * len(under))
        assert rec["before_train_compile_sec"] >= made["seconds"] - 1e-4
        # compile_load_sec reads what it read before: the compiles heard
        # since train() began, up to this boundary, and none before it
        mine = [s for s in spans if s["span"] == "compile"
                and "program" in s and s["parent"] != before["id"]
                and s["mono_ns"] < _end(interval)]
        assert rec["compile_load_sec"] == pytest.approx(
            sum(s["seconds"] for s in mine), abs=1e-3 * len(mine))


def test_every_interval_carries_the_processs_age(before_run):
    spans, records = before_run
    by = {}
    for s in spans:
        by.setdefault(s["span"], []).append(s)
    (before,) = by["process.before_train"]
    (imported,) = by["process.import"]
    (startup,) = by["train.startup"]
    start_ns = before["mono_ns"]
    for rec, interval in zip(records, by["train.interval"]):
        assert rec["process_age_sec"] == pytest.approx(
            (_end(interval) - start_ns) / 1e9, abs=1e-3)
        assert rec["before_train_sec"] == pytest.approx(
            before["duration_sec"], abs=1e-3)
        assert rec["import_sec"] == pytest.approx(
            imported["duration_sec"], abs=1e-3)
        assert 0 < rec["import_sec"] <= rec["before_train_sec"]
        assert rec["trace_lower_sec"] > 0
        # before train() + its start-up + what followed = the age
        warmup = (_end(interval) - _end(startup)) / 1e9
        assert rec["before_train_sec"] + rec["startup_sec"] + warmup == \
            pytest.approx(rec["process_age_sec"], abs=0.05)
    trace_lower = [s for s in spans if s["span"] in ("trace", "lower")]
    assert records[0]["trace_lower_sec"] == pytest.approx(
        sum(s["seconds"] for s in trace_lower
            if s["mono_ns"] < _end(by["train.interval"][0])), abs=0.01)
