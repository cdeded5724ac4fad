"""Perf-regression tracker (tools/perfwatch.py): trajectory parsing
(driver rounds, archived chip artifacts), backend cohorting, and noise-band verdicts on seeded regressing/flat/improving
trajectories."""

import importlib.util
import json
import os

import pytest

_spec = importlib.util.spec_from_file_location(
    "perfwatch", os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "perfwatch.py"))
perfwatch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(perfwatch)


def _bench_record(value, backend="tpu", mfu=None, imagenet_sps=None):
    rec = {"metric": perfwatch.HEADLINE_METRIC, "value": value,
           "unit": "steps/sec", "backend": backend,
           "device_kind": "TPU v5 lite", "n_devices": 1}
    if mfu is not None or imagenet_sps is not None:
        rec["imagenet"] = {"value": imagenet_sps, "mfu": mfu}
    return rec


def _seed_root(tmp_path, values, backend="tpu", mfus=None):
    """Write one driver-round file per value (oldest first)."""
    root = str(tmp_path)
    for i, v in enumerate(values, start=1):
        rec = _bench_record(v, backend=backend,
                            mfu=mfus[i - 1] if mfus else None,
                            imagenet_sps=10.0 if mfus else None)
        with open(os.path.join(root, f"BENCH_r{i:02d}.json"), "w") as f:
            json.dump({"n": i, "rc": 0, "parsed": rec, "tail": ""}, f)
    return root


# ----------------------------------------------------------- trajectories

def test_regressing_trajectory_fails(tmp_path):
    root = _seed_root(tmp_path, [200.0, 205.0, 198.0, 150.0])
    verdict = perfwatch.judge(perfwatch.load_samples(root), noise=0.08)
    m = verdict["metrics"]["cifar_steps_per_sec"]
    assert m["verdict"] == "regress"
    assert m["latest"] == 150.0
    assert m["reference"] == 200.0  # median of the priors
    assert verdict["overall"] == "regress"
    assert perfwatch.main(["--root", root]) == 1  # exit-code contract


def test_flat_trajectory_passes_inside_noise_band(tmp_path):
    root = _seed_root(tmp_path, [200.0, 205.0, 198.0, 193.0])
    verdict = perfwatch.judge(perfwatch.load_samples(root), noise=0.08)
    assert verdict["metrics"]["cifar_steps_per_sec"]["verdict"] == "flat"
    assert verdict["overall"] == "flat"
    assert perfwatch.main(["--root", root]) == 0


def test_improving_trajectory_reports_improve(tmp_path):
    root = _seed_root(tmp_path, [200.0, 205.0, 198.0, 240.0],
                      mfus=[0.30, 0.31, 0.30, 0.41])
    verdict = perfwatch.judge(perfwatch.load_samples(root), noise=0.08)
    assert verdict["metrics"]["cifar_steps_per_sec"]["verdict"] == \
        "improve"
    assert verdict["metrics"]["imagenet_mfu"]["verdict"] == "improve"
    assert verdict["overall"] == "improve"
    assert perfwatch.main(["--root", root]) == 0


def test_insufficient_data(tmp_path):
    root = _seed_root(tmp_path, [200.0])
    verdict = perfwatch.judge(perfwatch.load_samples(root))
    assert verdict["metrics"]["cifar_steps_per_sec"]["verdict"] == \
        "insufficient_data"
    assert verdict["overall"] == "insufficient_data"
    assert perfwatch.main(["--root", root]) == 0


# --------------------------------------------------------------- cohorts

def test_cpu_fallback_round_never_judged_against_chip_numbers(tmp_path):
    """Chip rounds, then a round from a CPU. The latest (cpu) sample has no cpu predecessors — the verdict must
    be insufficient_data, NOT a 99.99% regression vs the TPU median."""
    root = _seed_root(tmp_path, [200.0, 205.0, 210.0])
    with open(os.path.join(root, "BENCH_r04.json"), "w") as f:
        json.dump({"n": 4, "rc": 0, "tail": "",
                   "parsed": _bench_record(0.03, backend="cpu")}, f)
    verdict = perfwatch.judge(perfwatch.load_samples(root))
    m = verdict["metrics"]["cifar_steps_per_sec"]
    assert m["backend"] == "cpu"
    assert m["verdict"] == "insufficient_data"


def test_archived_chip_artifact_and_extra_file_ordering(tmp_path):
    """docs/runs chip artifacts sort with their round; --add files are
    judged as the newest run."""
    root = _seed_root(tmp_path, [0.03, 0.02], backend="cpu")
    runs = os.path.join(root, "docs", "runs")
    os.makedirs(runs)
    for rnd, v in ((1, 200.0), (2, 204.0)):
        with open(os.path.join(runs, f"bench_r{rnd}_tpu_v5e.json"),
                  "w") as f:
            json.dump(_bench_record(v), f)
    new = os.path.join(root, "new_run.json")
    with open(new, "w") as f:
        json.dump(_bench_record(150.0), f)
    verdict = perfwatch.judge(perfwatch.load_samples(root,
                                                     extra_files=[new]))
    m = verdict["metrics"]["cifar_steps_per_sec"]
    assert m["backend"] == "tpu"          # cohort of the newest sample
    assert m["latest"] == 150.0
    assert m["reference"] == pytest.approx(202.0)
    assert m["verdict"] == "regress"


def test_verdict_json_output(tmp_path, capsys):
    root = _seed_root(tmp_path, [200.0, 100.0])
    out = str(tmp_path / "v.json")
    rc = perfwatch.main(["--root", root, "--json", out])
    assert rc == 1
    with open(out) as f:
        verdict = json.load(f)
    assert verdict["overall"] == "regress"
    stdout = capsys.readouterr().out
    assert "PERFWATCH_JSON:" in stdout and "regress" in stdout


# ------------------------------------------------------- memory gating

def test_bench_hbm_peak_growth_gates_as_regress(tmp_path):
    """imagenet_hbm_peak_bytes is lower-is-better: a round whose peak
    HBM grows past the band regresses even while throughput improves —
    the knob that "wins" MFU by blowing the memory budget."""
    root = str(tmp_path)
    for i, (sps, mem) in enumerate([(10.0, 10e9), (10.1, 10.2e9),
                                    (11.5, 14e9)], start=1):
        rec = _bench_record(200.0, imagenet_sps=sps, mfu=0.4)
        rec["imagenet"]["hbm_bytes_peak"] = mem
        with open(os.path.join(root, f"BENCH_r{i:02d}.json"), "w") as f:
            json.dump({"n": i, "rc": 0, "parsed": rec, "tail": ""}, f)
    verdict = perfwatch.judge(perfwatch.load_samples(root), noise=0.08)
    m = verdict["metrics"]["imagenet_hbm_peak_bytes"]
    assert m["direction"] == "lower_is_better"
    assert m["verdict"] == "regress"
    assert verdict["metrics"]["imagenet_steps_per_sec"]["verdict"] == \
        "improve"
    assert verdict["overall"] == "regress"
    assert perfwatch.main(["--root", root]) == 1


def test_sweep_hbm_per_point_gating(tmp_path):
    """Every sweep point's hbm_bytes_peak becomes a lower-is-better
    sweep-mem: sample — a memory CUT (the future ZeRO proof) reports
    improve, growth regresses."""
    def traj(path, mem):
        json.dump({"points": [{"id": "p1", "status": "ok",
                               "steps_per_sec": 100.0,
                               "hbm_bytes_peak": mem,
                               "backend": "tpu"}]}, open(path, "w"))

    a, b = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    traj(a, 10e9)
    traj(b, 5e9)  # optimizer-state sharding landed: ~2x cut
    samples = perfwatch.load_sweep_samples([a, b])
    names = sorted({s["metric"] for s in samples})
    assert names == ["sweep-mem:p1", "sweep:p1"]
    verdict = perfwatch.judge(samples, noise=0.08, metric_names=names)
    verdict = perfwatch.apply_sweep_statuses(
        verdict, perfwatch.sweep_point_statuses(b))
    assert verdict["metrics"]["sweep-mem:p1"]["verdict"] == "improve"
    assert verdict["metrics"]["sweep:p1"]["verdict"] == "flat"
    traj(b, 14e9)  # and the blown budget gates
    samples = perfwatch.load_sweep_samples([a, b])
    verdict = perfwatch.judge(samples, noise=0.08,
                              metric_names=names)
    assert verdict["metrics"]["sweep-mem:p1"]["verdict"] == "regress"
    assert verdict["overall"] == "regress"


def test_sweep_comm_per_point_gating(tmp_path):
    """Every sweep point's comms_bytes_per_step becomes a lower-is-
    better sweep-comm: sample — a collective-bytes CUT (a zero1/ZeRO-2
    win) reports improve, growth (a stray gather landing) regresses,
    noise-band wobble stays flat."""
    def traj(path, wire):
        with open(path, "w") as f:
            json.dump({"points": [{"id": "p1", "status": "ok",
                                   "steps_per_sec": 100.0,
                                   "comms_bytes_per_step": wire,
                                   "backend": "tpu"}]}, f)

    a, b = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    traj(a, 4_000_000)
    traj(b, 2_000_000)  # exchange landed: ~2x wire cut
    samples = perfwatch.load_sweep_samples([a, b])
    names = sorted({s["metric"] for s in samples})
    assert "sweep-comm:p1" in names
    verdict = perfwatch.judge(samples, noise=0.08, metric_names=names)
    m = verdict["metrics"]["sweep-comm:p1"]
    assert m["direction"] == "lower_is_better"
    assert m["verdict"] == "improve"

    traj(b, 4_100_000)  # inside the noise band
    samples = perfwatch.load_sweep_samples([a, b])
    verdict = perfwatch.judge(samples, noise=0.08, metric_names=names)
    assert verdict["metrics"]["sweep-comm:p1"]["verdict"] == "flat"

    traj(b, 8_000_000)  # stray gather doubled the wire: gate
    samples = perfwatch.load_sweep_samples([a, b])
    verdict = perfwatch.judge(samples, noise=0.08, metric_names=names)
    assert verdict["metrics"]["sweep-comm:p1"]["verdict"] == "regress"
    assert verdict["overall"] == "regress"


def test_sweep_comm_absent_field_yields_no_series(tmp_path):
    """Old trajectory files (pre-comms bench) must not grow a bogus
    sweep-comm: series."""
    path = str(tmp_path / "a.json")
    with open(path, "w") as f:
        json.dump({"points": [{"id": "p1", "status": "ok",
                               "steps_per_sec": 100.0,
                               "backend": "tpu"}]}, f)
    samples = perfwatch.load_sweep_samples([path])
    assert not any(s["metric"].startswith(perfwatch.SWEEP_COMM_PREFIX)
                   for s in samples)
