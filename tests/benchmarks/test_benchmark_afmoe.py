"""The family ``afmoe`` in the harness, on the CPU at a tiny size: its kind
of data set, its configuration's entries, and a fixture cell
(``fixtures/tiny_afmoe``: d 64, 16 experts of which 4 are held, S 32)
through ``run_cell`` plain and traced, with the fp8 control and each
planted fault read as not correct. The family, its reference and the data
kind are the benchmark's own files; only the cell is the fixture's."""

import io
import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmarks.data_kinds import packed_tokens
from benchmarks.families import afmoe as family
from benchmarks.lib import faults, harness, lastline
from benchmarks.lib.manifest import Manifest

TINY = os.path.join(os.path.dirname(__file__), "fixtures", "tiny_afmoe")
CELL = "tiny_afmoe.packed_b8_s32"
PARAMS = {"sequences": 50, "seq_len": 64, "vocab": 300, "median": 20,
          "sigma": 1.2, "min_len": 4, "max_len": 64}


@pytest.fixture(autouse=True)
def exact_products(monkeypatch):
    """The reference in float32 to the last bits (a chip run's carries 16
    bits a product, ``HIGH``, for its time limit's sake): two float32
    implementations then choose the same experts, and the fixture's limits
    can stand a hundredfold under the bf16 stand-in's readings."""
    from benchmarks.reference import afmoe as ref

    monkeypatch.setattr(ref, "TERMS", ref.HIGHEST)


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_afmoe"))
    shutil.copytree(TINY, root, dirs_exist_ok=True)
    os.rename(os.path.join(root, "tiny_manifest.json"),
              os.path.join(root, "BENCHMARK.json"))
    m = Manifest(root=root, bench_dir=root)
    assert m.problems() == []
    return m


def run(manifest, seed, trace, **kw):
    out = io.StringIO()
    rc = harness.run_cell(CELL, seed, 0.3, trace,
                          started=time.perf_counter(), manifest=manifest,
                          require_tpu=False, out=out, **kw)
    assert rc == 0
    lines = out.getvalue().strip().splitlines()
    assert len(lines) == 1
    line = json.loads(lines[0])
    body = {k: v for k, v in line.items() if k != "compared"}
    lastline.validate(body, manifest.metrics_for(CELL, trace), trace)
    return line


def test_the_benchmark_itself_is_sound_with_the_new_entries():
    m = Manifest()
    assert m.problems() == []
    cell = "trinity_mini_ep16.packed_b2_s4096"
    assert m.family_of(cell) is family
    assert m.data_kind_of(cell) is packed_tokens.generate
    names = [p["name"] for p in m.per_layer(cell)]
    assert names[-2:] == ["moe_dropped_pct", "moe_load_max_over_mean"]
    assert len(names) == 10
    other = [p["name"] for p in
             m.per_layer("wrn28_10_cifar100.resident_b1024")]
    assert "moe_dropped_pct" not in other and len(other) == 8
    limits = m.limits_of(cell)
    assert limits["step_count"] == 0 and limits["moments0"] == 0


def test_packed_tokens_same_seed_same_bytes_and_documents_in_range(tmp_path):
    a = packed_tokens.generate(str(tmp_path / "a"), PARAMS, 2 ** 31 + 9)
    b = packed_tokens.generate(str(tmp_path / "b"), PARAMS, 2 ** 31 + 9)
    c = packed_tokens.generate(str(tmp_path / "c"), PARAMS, 10)
    read = lambda d: open(os.path.join(d, "train.tokens"), "rb").read()
    assert read(a) == read(b) != read(c)
    ids = np.frombuffer(read(a), "<i4")
    assert len(ids) == 50 * 64 + 1 and ids[0] == 0
    assert ids.min() == 0 and ids.max() < 300
    starts = np.flatnonzero(ids == 0)
    lengths = np.diff(starts)          # every document but the cut last
    assert lengths.min() >= 4 and lengths.max() <= 64
    assert 10 < np.median(lengths) < 40


def test_readers_find_nothing_where_the_program_reports_no_counter():
    from types import SimpleNamespace

    m = Manifest()
    for name in ("moe_dropped_pct", "moe_load_max_over_mean"):
        read = m.reader(name)
        assert read(SimpleNamespace(records=[{"loss": 1.0}])) is None
        assert read(SimpleNamespace(records=[])) is None
    assert m.reader("moe_dropped_pct")(SimpleNamespace(records=[
        {"moe_dropped_frac": 0.0}, {"moe_dropped_frac": 0.02}])) == 1.0
    assert m.reader("moe_load_max_over_mean")(SimpleNamespace(records=[
        {"moe_load_max_over_mean": 1.0},
        {"moe_load_max_over_mean": 1.5}])) == 1.25


def test_untraced_run_is_correct_and_its_fp8_control_is_not(manifest, capfd):
    line = run(manifest, 2 ** 31 + 21, False, control="fp8")
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"train_images_per_s", "setup_s"}
    assert line["attempted"] >= 4 and line["failed"] == 0
    assert "CONTROL fp8 correct=False" in capfd.readouterr().err


def test_traced_run_reports_every_per_layer_metric(manifest):
    line = run(manifest, 23, True)
    assert set(line["metrics"]) == {m["name"]
                                    for m in manifest.per_layer(CELL)}
    assert line["metrics"]["moe_dropped_pct"]["value"] == 0
    assert line["metrics"]["moe_load_max_over_mean"]["value"] >= 1
    assert line["correct"] is True, line["compared"]


@pytest.mark.parametrize("fault", [
    faults.state_unchanged, family.state_unchanged, family.half_batch,
    faults.loss_altered])
def test_planted_fault_is_not_correct(manifest, fault):
    line = run(manifest, 17, False, fault=fault)
    assert line["correct"] is False, (fault.__name__, line["compared"])
