"""BENCHMARK.json as data: every file it names exists, every name and
unit keeps to the contract's characters, and the peaks table refuses a
device it does not know."""

import json
import os
import re
import shutil

import pytest

from benchmarks.lib import peaks
from benchmarks.lib.manifest import (NAME_RE, REPO_ROOT, UNIT_RE, Manifest,
                                     ManifestError)

ONE_LINE = re.compile(r"^[^\t\n]{1,200}$")


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


def test_manifest_has_no_problems(manifest):
    assert manifest.problems() == []


def test_keys_are_exactly_the_contracts(manifest):
    spec = manifest.spec
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51
    assert len(json.dumps(spec)) < 64 * 1024


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_names_units_and_lines(manifest, group):
    for entry in manifest.spec[group]:
        assert NAME_RE.match(entry["name"]), entry["name"]
        if "unit" in entry:
            assert UNIT_RE.match(entry["unit"]), entry["unit"]
        for key in ("why", "layer", "source"):
            if key in entry and group != "end_to_end" \
                    and not (group == "per_layer" and key == "source"):
                assert ONE_LINE.match(entry[key]), (entry["name"], key)
        for key in ("config", "traffic"):
            if key in entry:
                assert NAME_RE.match(entry[key])


def test_every_cell_finds_its_files(manifest):
    for w in manifest.spec["workloads"]:
        name = w["name"]
        assert name == f"{w['config']}.{w['traffic']}"
        config = manifest.config_of(name)
        assert {"source", "family", "preset", "model", "job"} <= set(config)
        traffic = manifest.traffic_of(name)
        assert {"data", "overrides", "warmup_boundaries",
                "trace_seconds"} <= set(traffic)
        assert manifest.limits_of(name)
        family = manifest.family_of(name)
        for said in ("example", "example_input", "snapshot", "follow",
                     "groups", "readings", "train_flops_per_example"):
            assert callable(getattr(family, said)), said
        assert family.STAND_INS
        assert callable(manifest.data_kind_of(name))
        for metric in manifest.per_layer(name):
            assert callable(manifest.reader(metric["name"]))
        assert {m["name"] for m in manifest.end_to_end(name)} == \
            {"train_images_per_s", "setup_s"}


def test_paths_and_command_stay_inside_the_benchmark(manifest):
    spec = manifest.spec
    assert spec["paths"] == ["benchmarks", "tests/benchmarks"]
    for word in spec["command"]:
        assert not word.startswith("/") and ".." not in word
    for c in spec["configs"]:
        assert c["file"].startswith("benchmarks/")
        assert os.path.exists(os.path.join(REPO_ROOT, c["file"]))
    four = [w for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 4)


def test_unknown_workload_and_metric_are_errors(manifest):
    with pytest.raises(ManifestError):
        manifest.workload("no_such.cell")
    with pytest.raises(ManifestError):
        manifest.reader("no_such_metric")


TINY = os.path.join(os.path.dirname(__file__), "fixtures", "tiny")
CELL = "tiny_rn8.resident_b16"


@pytest.mark.parametrize("file,spoil,lookup,said", [
    ("configs/tiny_rn8.json", lambda c: c.pop("family"),
     "config_of", "names no family"),
    ("configs/tiny_rn8.json", lambda c: c.update(family="no_such_family"),
     "family_of", "family 'no_such_family' has no file"),
    ("traffic/resident_b16.json",
     lambda t: t["data"].update(kind="no_such_kind"),
     "data_kind_of", "data kind 'no_such_kind' has no generator"),
], ids=["no_family_key", "family_file_absent", "data_kind_absent"])
def test_missing_family_or_data_kind_is_an_error_and_a_problem(
        tmp_path, file, spoil, lookup, said):
    """No default family and no default kind of data: a configuration
    without the key, a family without its file and a kind without its
    generator are errors where they are looked up, and ``problems()``
    reports each for its cell."""
    root = str(tmp_path)
    shutil.copytree(TINY, root, dirs_exist_ok=True)
    os.rename(os.path.join(root, "tiny_manifest.json"),
              os.path.join(root, "BENCHMARK.json"))
    sound = Manifest(root=root, bench_dir=root)
    assert sound.problems() == []
    # the fixture's own family, and the benchmark's for the fixture's ResNet
    assert sound.family_of("tiny_mlp.resident_b16").STAND_INS == ("bf16",)
    assert sound.family_of(CELL).STAND_INS == ("fp8", "bf16")
    with open(os.path.join(root, file)) as f:
        data = json.load(f)
    spoil(data)
    with open(os.path.join(root, file), "w") as f:
        json.dump(data, f)
    broken = Manifest(root=root, bench_dir=root)
    with pytest.raises(ManifestError, match=said):
        getattr(broken, lookup)(CELL)
    assert [p for p in broken.problems() if CELL in p and said in p]


def test_peaks_table():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "source" in v5e
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
