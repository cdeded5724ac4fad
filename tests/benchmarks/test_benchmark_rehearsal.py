"""The harness end to end on the CPU at a tiny size, through ``train()``:
both input edges, both trace modes; the control and each planted fault
come out as not correct. Every line passes the same
validator the chip runs use (the builder validates before it prints)."""

import io
import json
import os
import shutil
import time

import pytest

from benchmarks.lib import faults, harness, lastline
from benchmarks.lib.manifest import Manifest

TINY = os.path.join(os.path.dirname(__file__), "fixtures", "tiny")
STREAM, RESIDENT = "tiny_rn18.stream_b8", "tiny_rn8.resident_b16"


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_benchmark"))
    shutil.copytree(TINY, root, dirs_exist_ok=True)
    os.rename(os.path.join(root, "tiny_manifest.json"),
              os.path.join(root, "BENCHMARK.json"))
    m = Manifest(root=root, bench_dir=root)
    assert m.problems() == []
    return m


def run(manifest, workload, seed, trace, **kw):
    out = io.StringIO()
    rc = harness.run_cell(workload, seed, 0.3, trace,
                          started=time.perf_counter(), manifest=manifest,
                          require_tpu=False, out=out, **kw)
    assert rc == 0
    lines = out.getvalue().strip().splitlines()
    assert len(lines) == 1
    line = json.loads(lines[0])
    body = {k: v for k, v in line.items() if k != "compared"}
    lastline.validate(body, manifest.metrics_for(workload, trace), trace)
    assert list(line)[-1] == "compared"
    return line


def test_untraced_run_is_correct_and_its_fp8_control_is_not(manifest, capfd):
    line = run(manifest, RESIDENT, 11, False, control="fp8")
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"train_images_per_s", "setup_s"}
    assert line["attempted"] >= 20 and line["failed"] == 0
    for value, limit in line["compared"].values():
        assert limit is None or value <= limit
    assert "CONTROL fp8 correct=False" in capfd.readouterr().err


@pytest.mark.parametrize("workload,seed", [(RESIDENT, 2 ** 31 + 13),
                                           (STREAM, 14)])
def test_traced_run_reports_every_per_layer_metric(manifest, workload,
                                                   seed):
    line = run(manifest, workload, seed, True)
    want = {m["name"] for m in manifest.per_layer(workload)}
    assert set(line["metrics"]) == want
    assert ("data_wait_pct" in want) == (workload == STREAM)
    dev = line["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert dev["count"] == 1
    assert line["breakdown"]["device_ops"]
    assert line["correct"] is True, line["compared"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "loss_altered"])
def test_planted_fault_is_not_correct(manifest, fault):
    line = run(manifest, RESIDENT, 17, False, fault=faults.FAULTS[fault])
    assert line["correct"] is False, (fault, line["compared"])


def test_no_chip_is_an_error_and_prints_nothing(manifest):
    out = io.StringIO()
    with pytest.raises(harness.BenchmarkError):
        harness.run_cell(RESIDENT, 1, 0.3, False,
                         started=time.perf_counter(), manifest=manifest,
                         out=out)
    assert out.getvalue() == ""
