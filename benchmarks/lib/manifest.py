"""``BENCHMARK.json`` and the data files it names, found by name.

A cell ``<config>.<traffic>`` reads ``configs/<config>.json`` (through the
configuration's ``file``), ``traffic/<traffic>.json``,
``limits/<cell>.json`` and, for each per-layer metric listed for it,
``layer_metrics/<metric>.py``. The configuration's file names its model
family (``"family"``), found as ``families/<family>.py``: the example
input the weights are drawn on, the snapshot of the state that is
compared, the plain reference and its stand-ins, the numbers read from
the two, and the FLOPs of one example. The traffic file names its kind of
data set (``data.kind``), found as ``data_kinds/<kind>.py`` with a
``generate(out_dir, params, seed)``. A later PR adds a cell, a
configuration, a family, a traffic mix, a kind of data or a metric by
adding such files and the entries that name them; nothing here lists
them.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    """``BENCHMARK.json`` or one of its data files is missing or malformed."""


def _load_json(path: str) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise ManifestError(f"cannot read {path}: {e}") from None


def load_module(path: str):
    """The Python file at ``path`` as a module: a reader, a family, a kind
    of data, a family's reference beside it. A file of the benchmark's own
    package comes under its package name (a pool's workers find a task by
    it); one from elsewhere, such as a test's fixture, by its path."""
    path = os.path.abspath(path)
    if path.startswith(BENCH_DIR + os.sep):
        rel = os.path.relpath(path, REPO_ROOT)
        return importlib.import_module(
            os.path.splitext(rel)[0].replace(os.sep, "."))
    spec = importlib.util.spec_from_file_location(
        "benchmarks_file_" + re.sub(r"\W", "_", path), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Manifest:
    """The benchmark as data: ``root`` holds ``BENCHMARK.json`` (and is
    where a run keeps its cache directory); ``traffic``, ``limits`` and
    ``families`` sit in ``bench_dir``, the metrics' readers in
    ``metrics_dir``. Tests point ``root`` and ``bench_dir`` at a tiny
    benchmark of their own; a family that such a benchmark does not hold
    itself is the benchmark's own."""

    def __init__(self, root: str = REPO_ROOT, bench_dir: str = BENCH_DIR,
                 metrics_dir: str = os.path.join(BENCH_DIR,
                                                 "layer_metrics")):
        self.root = root
        self.bench_dir = bench_dir
        self.metrics_dir = metrics_dir
        self.spec = _load_json(os.path.join(root, "BENCHMARK.json"))
        self.workloads = {w["name"]: w for w in self.spec["workloads"]}
        self.configs = {c["name"]: c for c in self.spec["configs"]}

    # -------------------------------------------------------------- cells
    def workload(self, name: str) -> Dict:
        if name not in self.workloads:
            raise ManifestError(f"unknown workload {name!r}; BENCHMARK.json "
                                f"has {sorted(self.workloads)}")
        return self.workloads[name]

    def config_of(self, workload: str) -> Dict:
        entry = self.configs[self.workload(workload)["config"]]
        config = _load_json(os.path.join(self.root, entry["file"]))
        if "family" not in config:
            raise ManifestError(f"configuration {entry['name']!r} names no "
                                f"family ({entry['file']})")
        return config

    def family_of(self, workload: str):
        """The module of the configuration's model family:
        ``families/<family>.py`` in ``bench_dir`` or, failing that, in the
        benchmark's own directory."""
        name = self.config_of(workload)["family"]
        dirs = [os.path.join(d, "families")
                for d in dict.fromkeys((self.bench_dir, BENCH_DIR))]
        for d in dirs:
            path = os.path.join(d, name + ".py")
            if os.path.exists(path):
                return load_module(path)
        raise ManifestError(f"family {name!r} has no file {name}.py in "
                            f"{dirs}")

    def data_kind_of(self, workload: str):
        """``generate(out_dir, params, seed)`` of the traffic's kind of
        data set: ``data_kinds/<kind>.py``."""
        kind = self.traffic_of(workload)["data"]["kind"]
        path = os.path.join(BENCH_DIR, "data_kinds", kind + ".py")
        if not os.path.exists(path):
            raise ManifestError(f"data kind {kind!r} has no generator at "
                                f"{path}")
        return load_module(path).generate

    def traffic_of(self, workload: str) -> Dict:
        return _load_json(os.path.join(
            self.bench_dir, "traffic",
            self.workload(workload)["traffic"] + ".json"))

    def limits_of(self, workload: str) -> Dict[str, float]:
        return _load_json(os.path.join(self.bench_dir, "limits",
                                       workload + ".json"))["limits"]

    # ------------------------------------------------------------ metrics
    def _applies(self, metric: Dict, workload: str) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    def end_to_end(self, workload: str) -> List[Dict]:
        return [m for m in self.spec["end_to_end"]
                if self._applies(m, workload)]

    def per_layer(self, workload: str) -> List[Dict]:
        moved = {m["name"] for m in self.end_to_end(workload)}
        return [m for m in self.spec["per_layer"]
                if self._applies(m, workload) and m["moves"] in moved]

    def metrics_for(self, workload: str, trace: bool) -> List[Dict]:
        return self.per_layer(workload) if trace else \
            self.end_to_end(workload)

    def reader(self, metric_name: str):
        """The metric's own reader: ``layer_metrics/<name>.py`` with a
        ``read(run)`` that returns the number, or None where it finds
        nothing to read."""
        path = os.path.join(self.metrics_dir, metric_name + ".py")
        if not os.path.exists(path):
            raise ManifestError(f"per-layer metric {metric_name!r} has no "
                                f"reader at {path}")
        return load_module(path).read

    # --------------------------------------------------------- validation
    def problems(self) -> List[str]:
        """Everything wrong with the manifest that can be seen without a
        run: names, units, sources, files. Empty when it is sound."""
        out = []
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            names = [e["name"] for e in self.spec[group]]
            for n in names:
                if not NAME_RE.match(n):
                    out.append(f"{group}: bad name {n!r}")
            if len(set(names)) != len(names):
                out.append(f"{group}: duplicate names")
        for m in self.spec["end_to_end"] + self.spec["per_layer"]:
            if not UNIT_RE.match(m["unit"]):
                out.append(f"metric {m['name']}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                out.append(f"metric {m['name']}: bad better")
            if m["source"] not in SOURCES:
                out.append(f"metric {m['name']}: bad source")
            for w in m.get("workloads", ()):
                if w not in self.workloads:
                    out.append(f"metric {m['name']}: unknown cell {w!r}")
        e2e = {m["name"] for m in self.spec["end_to_end"]}
        if "setup_s" not in e2e:
            out.append("end_to_end lacks setup_s")
        for m in self.spec["per_layer"]:
            if m["moves"] not in e2e:
                out.append(f"metric {m['name']}: moves unknown "
                           f"{m['moves']!r}")
            try:
                self.reader(m["name"])
            except ManifestError as e:
                out.append(str(e))
        for c in self.spec["configs"]:
            if not os.path.exists(os.path.join(self.root, c["file"])):
                out.append(f"config {c['name']}: no file {c['file']}")
        for w in self.spec["workloads"]:
            if w["config"] not in self.configs:
                out.append(f"cell {w['name']}: unknown config")
                continue
            for what in (self.traffic_of, self.limits_of, self.family_of,
                         self.data_kind_of):
                try:
                    what(w["name"])
                except (ManifestError, KeyError) as e:
                    out.append(f"cell {w['name']}: {e}")
            if len(self.end_to_end(w["name"])) < 2:
                out.append(f"cell {w['name']}: under two end-to-end metrics")
            if not self.per_layer(w["name"]):
                out.append(f"cell {w['name']}: no per-layer metric")
        return out
