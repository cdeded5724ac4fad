"""The per-layer metrics that split ``setup_s`` by the program's own spans
(``before_train_sec``, ``import_sec``, ``trace_lower_sec`` and the
opening boundary's ``process_age_sec``; tpu_resnet/obs/breakdown.py):
each reader on a hand-made run, its None where the program hands nothing
over, and all four through the real loop on both input edges.

They have no entry in ``BENCHMARK.json`` nor in the tiny benchmark yet:
``lastline.validate`` refuses a traced run's whole line where a listed
metric is missing, and the parent of the PR that wrote them hands none of
their keys over (as ``test_benchmark_program_spans`` tells of the four
before them). The runs here take a copy of the tiny benchmark with the
four entries added."""

import contextlib
import io
import json
import math
import os
import re
import shutil
import time
from types import SimpleNamespace

import pytest

from benchmarks.lib import harness, lastline
from benchmarks.lib.manifest import Manifest
from tpu_resnet.obs.spans import load_spans, process_start

TINY = os.path.join(os.path.dirname(__file__), "fixtures", "tiny")
START = "process start-up (tpu_resnet/__init__.py, obs/breakdown.py)"
LOOP = "train loop (train/loop.py, data/device_data.py)"
NEW = (
    {"name": "before_train_s", "unit": "s", "better": "lower",
     "source": "program_span", "layer": START, "moves": "setup_s"},
    {"name": "import_s", "unit": "s", "better": "lower",
     "source": "program_span", "layer": START, "moves": "setup_s"},
    {"name": "trace_lower_s", "unit": "s", "better": "lower",
     "source": "program_counter", "layer": LOOP, "moves": "setup_s"},
    {"name": "warmup_s", "unit": "s", "better": "lower",
     "source": "program_span", "layer": LOOP, "moves": "setup_s"},
)


def run_of(records):
    return SimpleNamespace(records=records, window_s=10.0, steps=40,
                           trace=None)


# The window's first two records as the loop hands them over: the first
# interval runs from the opening boundary's sync (age 70.0 s) to its own
# (75.0 s).
NEW_RECORDS = [
    {"_dt": 5.0, "_steps": 20, "data_wait_sec": 0.5, "device_sync_sec": 4.3,
     "loop_host_sec": 0.2, "process_age_sec": 75.0, "startup_sec": 24.5,
     "before_train_sec": 38.25, "import_sec": 19.5,
     "trace_lower_sec": 3.75, "compile_load_sec": 2.5,
     "before_train_compile_sec": 1.25},
    {"_dt": 5.0, "_steps": 20, "data_wait_sec": 0.5, "device_sync_sec": 4.3,
     "loop_host_sec": 0.2, "process_age_sec": 80.0, "startup_sec": 24.5,
     "before_train_sec": 38.25, "import_sec": 19.5,
     "trace_lower_sec": 3.75, "compile_load_sec": 2.5,
     "before_train_compile_sec": 1.25},
]
OLD_KEYS = ("_dt", "_steps", "data_wait_sec", "device_sync_sec",
            "loop_host_sec", "startup_sec", "compile_load_sec")
OLD_RECORDS = [{k: v for k, v in r.items() if k in OLD_KEYS}
               for r in NEW_RECORDS]


@pytest.mark.parametrize("name,want", [
    ("before_train_s", 38.25),
    ("import_s", 19.5),
    ("trace_lower_s", 3.75),
    # opened at 75.0 - (0.2 + 0.5 + 4.3) = 70.0 s: 70.0 - 38.25 - 24.5
    ("warmup_s", 7.25),
])
def test_reader_gives_its_number_and_its_none(name, want):
    read = Manifest().reader(name)
    assert read(run_of(NEW_RECORDS)) == pytest.approx(want)
    assert read(run_of(OLD_RECORDS)) is None  # the parent's records
    assert read(run_of([])) is None
    assert read(run_of([{"loss": 1.0, "_dt": 5.0}] * 2)) is None


@pytest.mark.parametrize("name", [e["name"] for e in NEW])
def test_readers_read_the_windows_first_record(name):
    read = Manifest().reader(name)
    later = [NEW_RECORDS[0], dict(NEW_RECORDS[1], before_train_sec=1.0,
                                  import_sec=0.5, trace_lower_sec=9.0,
                                  process_age_sec=99.0)]
    assert read(run_of(later)) == read(run_of(NEW_RECORDS[:1]))
    # a first record without the key: nothing, whatever a later one holds
    assert read(run_of([OLD_RECORDS[0], NEW_RECORDS[1]])) is None


def test_the_readers_are_found_by_name():
    m = Manifest()
    assert m.problems() == []
    for entry in NEW:
        assert callable(m.reader(entry["name"]))


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One traced run of each tiny cell, through a copy of the tiny
    benchmark that lists the four metrics (no ``workloads`` list), with
    the harness's log and its cell's ``events.jsonl``. ``started`` is the
    process's start on the harness's clock, so that the log's "window
    opened ... s after start" is the process's age as the program's is."""
    root = str(tmp_path_factory.mktemp("tiny_benchmark_setup"))
    shutil.copytree(TINY, root, dirs_exist_ok=True)
    with open(os.path.join(root, "tiny_manifest.json")) as f:
        spec = json.load(f)
    os.remove(os.path.join(root, "tiny_manifest.json"))
    spec["per_layer"] += [dict(e) for e in NEW]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f, indent=1)
    m = Manifest(root=root, bench_dir=root)
    assert m.problems() == []
    out = {}
    for workload, seed in (("tiny_rn8.resident_b16", 2 ** 31 + 38),
                           ("tiny_rn18.stream_b8", 38)):
        start_ns = process_start()[0]
        started = time.perf_counter() - (time.monotonic_ns() - start_ns) / 1e9
        line, log = io.StringIO(), io.StringIO()
        with contextlib.redirect_stderr(log):
            rc = harness.run_cell(workload, seed, 0.3, True,
                                  started=started, manifest=m,
                                  require_tpu=False, out=line)
        assert rc == 0, log.getvalue()[-3000:]
        events = os.path.join(root, harness.CACHE_DIR, workload, "train",
                              "events.jsonl")
        out[workload] = SimpleNamespace(
            line=json.loads(line.getvalue().strip()), log=log.getvalue(),
            spans=load_spans(events), manifest=m,
            warmup=m.traffic_of(workload)["warmup_boundaries"])
    return out


def _end_ns(span):
    return span["mono_ns"] + round(span["duration_sec"] * 1e9)


CELLS = ["tiny_rn8.resident_b16", "tiny_rn18.stream_b8"]


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reads_the_four(runs, workload):
    run = runs[workload]
    lastline.validate({k: v for k, v in run.line.items() if k != "compared"},
                      run.manifest.metrics_for(workload, True), True)
    got = {k: v["value"] for k, v in run.line["metrics"].items()}
    for e in NEW:
        assert math.isfinite(got[e["name"]]), e["name"]
        assert run.line["metrics"][e["name"]]["unit"] == e["unit"]
    assert 0 < got["import_s"] <= got["before_train_s"]
    assert got["warmup_s"] > 0 and got["trace_lower_s"] > 0
    assert got["trace_lower_s"] + got["compile_load_s"] <= \
        got["train_startup_s"] + got["warmup_s"]
    # the three parts are the process's age where the window opened, as
    # the harness logs it (its clock set to the process's start)
    opened = float(re.search(r"window opened at step \d+, ([\d.]+) s after "
                             r"start", run.log).group(1))
    assert got["before_train_s"] + got["train_startup_s"] + \
        got["warmup_s"] == pytest.approx(opened, abs=1.0)
    assert run.line["correct"] is True, run.line["compared"]


@pytest.mark.parametrize("workload", CELLS)
def test_the_parts_are_the_spans(runs, workload):
    run = runs[workload]
    got = {k: v["value"] for k, v in run.line["metrics"].items()}
    by_name = {}
    for s in run.spans:
        by_name.setdefault(s["span"], []).append(s)
    (before,) = by_name["process.before_train"]
    (imported,) = by_name["process.import"]
    (startup,) = by_name["train.startup"]
    assert before["process_start"] == "proc_stat" and "parent" not in before
    assert imported["parent"] == before["id"]
    assert before["mono_ns"] <= imported["mono_ns"]
    assert _end_ns(imported) <= _end_ns(before)
    assert before["duration_sec"] == pytest.approx(got["before_train_s"],
                                                   abs=1e-3)
    assert imported["duration_sec"] == pytest.approx(got["import_s"],
                                                     abs=1e-3)
    # train() begins where the time before it ends
    assert abs(startup["mono_ns"] - _end_ns(before)) < 5e6
    # the warm-up: from the end of start-up to the sync of the boundary
    # that opened the window (the traffic's warmup_boundaries-th)
    intervals = sorted((s for s in by_name["train.interval"]
                        if not s.get("reset")), key=_end_ns)
    opening = intervals[run.warmup - 1]
    warmup = (_end_ns(opening) - _end_ns(startup)) / 1e9
    assert warmup == pytest.approx(got["warmup_s"], abs=0.05)
    assert got["before_train_s"] + got["train_startup_s"] + warmup == \
        pytest.approx((_end_ns(opening) - process_start()[0]) / 1e9,
                      abs=0.05)


@pytest.mark.parametrize("workload", CELLS)
def test_trace_and_lower_spans_name_their_program(runs, workload):
    spans = runs[workload].spans
    ids = {s["id"]: s for s in spans}
    for kind in ("trace", "lower"):
        mine = [s for s in spans if s["span"] == kind]
        assert mine, kind
        for s in mine:
            assert s["program"] and s["seconds"] >= 0
            assert "during" in s and s["parent"]
        # the step program is traced and lowered beneath the first
        # dispatch's span, as its compile is
        assert any(ids.get(s["parent"], {}).get("span") == "compile"
                   and "program" not in ids[s["parent"]] for s in mine)
