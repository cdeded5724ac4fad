"""The harness end to end on the CPU at a tiny size, through ``train()``:
both input edges, both trace modes, two model families; the control and
each planted fault come out as not correct. Every line passes the same
validator the chip runs use (the builder validates before it prints).

The second family (``fixtures/tiny/families/mlp.py``, the program's
one-hidden-layer classifier) lives wholly under the fixture: it shows that
a family is added by files alone, and ``test_lib_names_no_family`` that
the harness's own code names none."""

import ast
import glob
import io
import json
import os
import shutil
import time

import pytest

from benchmarks.lib import faults, harness, lastline
from benchmarks.lib.manifest import BENCH_DIR, Manifest

TINY = os.path.join(os.path.dirname(__file__), "fixtures", "tiny")
STREAM, RESIDENT = "tiny_rn18.stream_b8", "tiny_rn8.resident_b16"
MLP = "tiny_mlp.resident_b16"


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


@pytest.mark.parametrize("workload,control", [(RESIDENT, "fp8"),
                                              (MLP, "bf16")])
def test_untraced_run_is_correct_and_its_control_is_not(manifest, capfd,
                                                        workload, control):
    line = run(manifest, workload, 11, False, control=control)
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"train_images_per_s", "setup_s"}
    assert line["attempted"] >= 4 and line["failed"] == 0  # an interval
    for value, limit in line["compared"].values():
        assert limit is None or value <= limit
    assert f"CONTROL {control} correct=False" in capfd.readouterr().err


@pytest.mark.parametrize("workload,seed", [(RESIDENT, 2 ** 31 + 13),
                                           (STREAM, 14), (MLP, 2 ** 31 + 15)])
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


@pytest.mark.parametrize("workload", [RESIDENT, MLP])
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "loss_altered"])
def test_planted_fault_is_not_correct(manifest, fault, workload):
    line = run(manifest, workload, 17, False, fault=faults.FAULTS[fault])
    assert line["correct"] is False, (fault, line["compared"])


def test_no_chip_is_an_error_and_prints_nothing(manifest):
    out = io.StringIO()
    with pytest.raises(harness.BenchmarkError):
        harness.run_cell(RESIDENT, 1, 0.3, False,
                         started=time.perf_counter(), manifest=manifest,
                         out=out)
    assert out.getvalue() == ""


# What only a family's module may say: its own name, and the leaves and
# fields of its models and their state.
FAMILY_TERMS = ("resnet_v2", "batch_stats", "final_dense",
                "resolved_image_size", "/mean")
# One file of lib/ is exempt, as long as it is one import and nothing
# else: tests/test_mfu.py, which is not the benchmark's to edit, imports
# the FLOP count from there.
SHIM = os.path.join(BENCH_DIR, "lib", "flops.py")


def code_of(path):
    """The file's syntax tree without its docstrings."""
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        body = getattr(node, "body", None)
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)) and body \
                and isinstance(body[0], ast.Expr) \
                and isinstance(body[0].value, ast.Constant) \
                and isinstance(body[0].value.value, str):
            body.pop(0)
    return tree


def test_lib_names_no_family():
    files = sorted(glob.glob(os.path.join(BENCH_DIR, "lib", "*.py"))) + [
        os.path.join(BENCH_DIR, "run.py")]
    assert len(files) > 8
    for path in files:
        tree = code_of(path)
        if path == SHIM:
            assert [type(n) for n in tree.body] == [ast.ImportFrom], path
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                modules = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                modules = [node.module or ""] + [
                    f"{node.module}.{a.name}" for a in node.names]
            else:
                modules = []
            for m in modules:
                assert ".families" not in m and ".reference" not in m, \
                    (path, node.lineno, m)
            said = (node.value if isinstance(node, ast.Constant)
                    and isinstance(node.value, str) else
                    getattr(node, "id", None) or getattr(node, "attr", None)
                    or getattr(node, "arg", None) or "")
            for term in FAMILY_TERMS:
                assert term not in str(said), (path, node.lineno, term)
    # and the fixture's family is the fixture's alone
    assert not os.path.exists(os.path.join(BENCH_DIR, "families", "mlp.py"))
    assert os.path.exists(os.path.join(TINY, "families", "mlp.py"))
