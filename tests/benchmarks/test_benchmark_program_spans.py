"""The per-layer metrics that read the loop's own spans and counters
(``startup_sec``, ``compile_load_sec``, ``boundary_stall_sec``,
``loop_host_sec``; tpu_resnet/obs/breakdown.py): each reader on a
hand-made run, its None where the program hands nothing over, and all four
through the real loop on both input edges.

All four have an entry in ``BENCHMARK.json`` and in the tiny benchmark
since the program that hands their keys over became the parent
(``lastline.validate`` refuses a traced run's whole line where a listed
metric is missing, so they could not be listed before)."""

import io
import json
import os
import shutil
import time
from types import SimpleNamespace

import pytest

from benchmarks.lib import harness, lastline
from benchmarks.lib.manifest import Manifest

TINY = os.path.join(os.path.dirname(__file__), "fixtures", "tiny")
LOOP = "train loop (train/loop.py, data/device_data.py)"
NEW = (
    {"name": "train_startup_s", "unit": "s", "better": "lower",
     "source": "program_span", "layer": LOOP, "moves": "setup_s"},
    {"name": "compile_load_s", "unit": "s", "better": "lower",
     "source": "program_counter", "layer": LOOP, "moves": "setup_s"},
    {"name": "boundary_stall_ms", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": LOOP,
     "moves": "train_images_per_s"},
    {"name": "loop_host_pct", "unit": "%", "better": "lower",
     "source": "program_span", "layer": LOOP,
     "moves": "train_images_per_s"},
)


def run_of(records, window_s=10.0):
    return SimpleNamespace(records=records, window_s=window_s, steps=40,
                           trace=None)


# What the loop hands the writer at three boundaries of a window, and
# what a program from before the new keys handed over there. The first
# record's interval began before the window and holds the harness's
# ``start_trace``: the two readers of intervals leave it out.
NEW_RECORDS = [
    {"_dt": 5.0, "_steps": 20, "data_wait_sec": 0.5, "device_sync_sec": 4.3,
     "dispatch_sec": 0.01, "loop_host_sec": 0.25,
     "boundary_stall_sec": 0.053, "startup_sec": 36.5,
     "compile_load_sec": 20.25},
    {"_dt": 5.0, "_steps": 20, "data_wait_sec": 0.5, "device_sync_sec": 4.3,
     "dispatch_sec": 0.01, "loop_host_sec": 0.2,
     "boundary_stall_sec": 0.003, "startup_sec": 36.5,
     "compile_load_sec": 20.25},
    {"_dt": 5.0, "_steps": 20, "data_wait_sec": 0.4, "device_sync_sec": 4.5,
     "dispatch_sec": 0.01, "loop_host_sec": 0.1,
     "boundary_stall_sec": 0.005, "startup_sec": 36.5,
     "compile_load_sec": 20.25},
]
OLD_RECORDS = [{k: v for k, v in r.items()
                if k in ("_dt", "_steps", "data_wait_sec", "device_sync_sec",
                         "dispatch_sec")} for r in NEW_RECORDS]


@pytest.mark.parametrize("name,want", [
    ("train_startup_s", 36.5),
    ("compile_load_s", 20.25),
    # the second and the third boundary's: (3 + 5) / 2
    ("boundary_stall_ms", 4.0),
    # of the same two intervals: (0.2 + 0.1) s of (5.0 + 5.0) s
    ("loop_host_pct", 3.0),
])
def test_reader_gives_its_number_and_its_none(name, want):
    read = Manifest().reader(name)
    assert read(run_of(NEW_RECORDS)) == pytest.approx(want)
    assert read(run_of(OLD_RECORDS)) is None  # one quantity, or nothing
    assert read(run_of([])) is None
    assert read(run_of([{"loss": 1.0, "_dt": 5.0}] * 2)) is None


@pytest.mark.parametrize("name", ["boundary_stall_ms", "loop_host_pct"])
def test_interval_readers_leave_out_what_began_before_the_window(name):
    read = Manifest().reader(name)
    # only the opening boundary's record: nothing lies inside the window
    assert read(run_of(NEW_RECORDS[:1])) is None
    # what the opening record holds (the harness's start_trace) moves
    # nothing
    held = [dict(NEW_RECORDS[0], loop_host_sec=9.0, boundary_stall_sec=9.0)]
    assert read(run_of(held + NEW_RECORDS[1:])) == \
        read(run_of(NEW_RECORDS))


def test_the_readers_have_their_entries():
    m = Manifest()
    assert m.problems() == []
    listed = {e["name"]: e for e in m.spec["per_layer"]}
    with open(os.path.join(TINY, "tiny_manifest.json")) as f:
        tiny = {e["name"]: e for e in json.load(f)["per_layer"]}
    for entry in NEW:
        assert listed[entry["name"]] == entry == tiny[entry["name"]]
        assert callable(m.reader(entry["name"]))
    # the first per-layer metrics that move the set-up time
    assert {n for n, e in listed.items() if e["moves"] == "setup_s"} == \
        {"train_startup_s", "compile_load_s"}


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    """A copy of the tiny benchmark, which lists all four metrics, so that
    the harness runs every reader through the real loop. The interval
    readers need a boundary inside the window beside the one that opens
    it: the tiny traffic files have short log intervals and a traced
    window of several. (A step of the stream cell takes 0.7 s on the CPU,
    of the resident one 0.07 s.)"""
    root = str(tmp_path_factory.mktemp("tiny_benchmark_spans"))
    shutil.copytree(TINY, root, dirs_exist_ok=True)
    os.rename(os.path.join(root, "tiny_manifest.json"),
              os.path.join(root, "BENCHMARK.json"))
    m = Manifest(root=root, bench_dir=root)
    assert m.problems() == []
    return m


@pytest.mark.parametrize("workload,seed", [
    ("tiny_rn8.resident_b16", 2 ** 31 + 26), ("tiny_rn18.stream_b8", 26)])
def test_traced_run_reads_the_loops_spans(manifest, workload, seed):
    out = io.StringIO()
    rc = harness.run_cell(workload, seed, 0.3, True,
                          started=time.perf_counter(), manifest=manifest,
                          require_tpu=False, out=out)
    assert rc == 0
    line = json.loads(out.getvalue().strip())
    lastline.validate({k: v for k, v in line.items() if k != "compared"},
                      manifest.metrics_for(workload, True), True)
    got = line["metrics"]
    assert {e["name"] for e in NEW} <= set(got)
    for e in NEW:
        assert got[e["name"]]["unit"] == e["unit"]
    # start-up holds its compiles; both are seconds of this process
    assert 0 < got["compile_load_s"]["value"] < \
        got["train_startup_s"]["value"] < 600
    assert 0 <= got["boundary_stall_ms"]["value"] < 60e3
    assert 0 < got["loop_host_pct"]["value"] <= 100
    assert line["correct"] is True, line["compared"]
