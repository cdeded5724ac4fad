#!/usr/bin/env python3
"""Perf-regression tracker — verdicts over the bench RESULT_JSON
trajectory.

Bench artifacts accumulate one per round (``BENCH_r0N.json`` driver
round files at the root, ``docs/runs/bench_r*_tpu_v5e.json`` archived
chip runs). Whether a round's number is a win, noise, or a regression
was judged by eyeball. This tool makes the judgment mechanical and
consumable by ``doctor --perfwatch``:

- parse every artifact (a driver round file's ``parsed`` field, or a
  raw bench line);
- extract the tracked metrics (headline CIFAR steps/sec, ImageNet
  steps/sec and MFU) as (round, backend, value) samples;
- cohort by backend — a number from one backend is never compared
  against another's;
- compare the newest sample of the newest-sampled cohort against the
  median of its predecessors with a configurable noise band.

Verdicts per metric: ``regress`` (below band), ``improve`` (above),
``flat`` (inside), ``insufficient_data`` (< 2 comparable samples).
Exit code: 1 if ANY tracked metric regresses, else 0.

    python tools/perfwatch.py [--root .] [--noise 0.08]
        [--add runs/new_bench.json ...] [--json verdict.json]

Stdlib-only and jax-free: runs anywhere the checkout does.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import sys
from typing import Dict, List, Optional

HEADLINE_METRIC = "cifar10_resnet50_train_steps_per_sec_b128"

# (name, extractor) — every tracked metric is higher-is-better.
def _headline(rec: dict) -> Optional[float]:
    if rec.get("metric") == HEADLINE_METRIC:
        return rec.get("value")
    return None


def _imagenet_sps(rec: dict) -> Optional[float]:
    return (rec.get("imagenet") or {}).get("value")


def _imagenet_mfu(rec: dict) -> Optional[float]:
    return (rec.get("imagenet") or {}).get("mfu")


def _imagenet_hbm_peak(rec: dict) -> Optional[float]:
    return (rec.get("imagenet") or {}).get("hbm_bytes_peak")


METRICS = (
    ("cifar_steps_per_sec", _headline),
    ("imagenet_steps_per_sec", _imagenet_sps),
    ("imagenet_mfu", _imagenet_mfu),
    ("imagenet_hbm_peak_bytes", _imagenet_hbm_peak),
)

# Memory metrics invert the verdict: growth past the band is the
# regression (a knob that "wins" MFU by blowing the HBM budget must not
# pass silently). Bench records carry hbm_bytes_peak next to mfu
# (obs/memory.py device stats), sweep points per knob. Time-to-ready is
# the cold-start twin (doctor --coldstart-probe feeds cold/warm serve
# restart points): a restart getting SLOWER to ready is the regression.
LOWER_IS_BETTER = {"imagenet_hbm_peak_bytes"}
SWEEP_MEM_PREFIX = "sweep-mem:"
SWEEP_TTR_PREFIX = "sweep-ttr:"
SWEEP_LAT_PREFIX = "sweep-lat:"
# Scenario-conductor series (tpu_resnet/scenario): point ids are
# "<scenario>:<metric>", so any declared scenario series regression-
# gates with zero glue. Direction comes from the metric's unit suffix —
# _ms/_bytes/_s name costs (lower is better), everything else a rate.
SWEEP_SCN_PREFIX = "sweep-scn:"
# Bytes-on-wire twin (lower-is-better): bench records carry
# comms_bytes_per_step from the compiled step's collective summary
# (obs/comms.py) — a knob that "wins" throughput by inflating the
# per-step collective traffic gates as regress, the same contract as
# the peak-HBM series.
SWEEP_COMM_PREFIX = "sweep-comm:"


def _lower_is_better(name: str) -> bool:
    if name.startswith(SWEEP_SCN_PREFIX):
        return name.endswith(("_ms", "_bytes", "_s"))
    return (name in LOWER_IS_BETTER
            or name.startswith((SWEEP_MEM_PREFIX, SWEEP_TTR_PREFIX,
                                SWEEP_LAT_PREFIX, SWEEP_COMM_PREFIX)))


def _record_of(payload: dict) -> Optional[dict]:
    """A driver round file ({parsed, ...}) or a raw bench snapshot → the
    bench result record (None for a round that parsed nothing)."""
    if "parsed" in payload:
        return payload["parsed"]
    return payload if isinstance(payload, dict) else None


def load_samples(root: str, extra_files=()) -> List[dict]:
    """Every (round, backend, metric, value) sample from the root's
    ``BENCH_r*.json`` + archived ``docs/runs/bench_r*_tpu_v5e.json`` +
    ``extra_files``. Samples are ordered oldest→newest: archived chip
    artifacts sort by their round number alongside the driver rounds
    (they are the same round's chip truth); extra files come last (they
    are "the new run" perfwatch is asked to judge)."""
    sources = []
    for path in glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if m:
            sources.append((int(m.group(1)), 0, path))
    for path in glob.glob(os.path.join(root, "docs", "runs",
                                       "bench_r*_tpu_v5e.json")):
        m = re.search(r"bench_r(\d+)_tpu_v5e\.json$", path)
        if m:
            # Archived chip artifacts supersede the driver capture of the
            # same round (sort later within the round).
            sources.append((int(m.group(1)), 1, path))
    sources.sort()
    order = [path for _, _, path in sources] + list(extra_files)

    samples = []
    for idx, path in enumerate(order):
        try:
            with open(path) as f:
                payload = json.load(f)
        except (OSError, ValueError) as e:
            samples.append({"source": path, "error": f"{type(e).__name__}: "
                                                     f"{e}"})
            continue
        rec = _record_of(payload)
        if not rec:
            samples.append({"source": path,
                            "error": "no parseable RESULT_JSON"})
            continue
        backend = rec.get("backend") or "unknown"
        for name, extract in METRICS:
            try:
                value = extract(rec)
            except (TypeError, AttributeError):
                value = None
            if isinstance(value, (int, float)) and value > 0:
                samples.append({"source": os.path.basename(path),
                                "order": idx, "metric": name,
                                "backend": backend, "value": float(value),
                                "partial": bool(rec.get("partial"))})
    return samples


def judge(samples: List[dict], noise: float = 0.08,
          metric_names: Optional[List[str]] = None) -> dict:
    """Per-metric verdicts. For each metric the cohort is the backend of
    its NEWEST sample; reference = median of the cohort's earlier
    samples; the verdict compares latest/reference against the ±noise
    band. ``metric_names`` overrides the default tracked set (the sweep
    path passes the per-knob point names discovered in the samples)."""
    verdict: Dict[str, dict] = {}
    errors = [s for s in samples if "error" in s]
    names = (metric_names if metric_names is not None
             else [n for n, _ in METRICS])
    for name in names:
        series = [s for s in samples if s.get("metric") == name]
        if not series:
            verdict[name] = {"verdict": "insufficient_data", "samples": 0}
            continue
        latest = series[-1]
        cohort = [s for s in series if s["backend"] == latest["backend"]]
        prior = [s["value"] for s in cohort[:-1]]
        entry = {"backend": latest["backend"],
                 "latest": latest["value"],
                 "latest_source": latest["source"],
                 "samples": len(cohort)}
        if not prior:
            entry["verdict"] = "insufficient_data"
        else:
            ref = statistics.median(prior)
            ratio = latest["value"] / ref if ref else float("inf")
            entry.update(reference=round(ref, 6), ratio=round(ratio, 4),
                         noise_band=noise)
            lower = _lower_is_better(name)
            if lower:
                entry["direction"] = "lower_is_better"
            if ratio < 1.0 - noise:
                entry["verdict"] = "improve" if lower else "regress"
            elif ratio > 1.0 + noise:
                entry["verdict"] = "regress" if lower else "improve"
            else:
                entry["verdict"] = "flat"
        verdict[name] = entry
    verdicts = {v["verdict"] for v in verdict.values()}
    overall = ("regress" if "regress" in verdicts
               else "improve" if "improve" in verdicts
               else "flat" if "flat" in verdicts
               else "insufficient_data")
    return {"overall": overall, "noise": noise, "metrics": verdict,
            "unparseable_sources": [e["source"] for e in errors]}


def sweep_record_of(payload) -> Optional[dict]:
    """A sweep trajectory (tools/sweep.py ``--json`` artifact, a raw
    RESULT_JSON dict, or a driver-style {parsed} wrapper) → the
    trajectory record, else None."""
    rec = _record_of(payload) if isinstance(payload, dict) else None
    if isinstance(rec, dict) and isinstance(rec.get("points"), list):
        return rec
    return None


def sweep_point_statuses(path: str) -> Dict[str, str]:
    """point id → status for one sweep trajectory file ({} when
    unreadable)."""
    try:
        with open(path) as f:
            rec = sweep_record_of(json.load(f))
    except (OSError, ValueError):
        return {}
    if rec is None:
        return {}
    return {str(p.get("id")): str(p.get("status"))
            for p in rec["points"] if p.get("id")}


def apply_sweep_statuses(verdict: dict, latest_statuses: Dict[str, str]
                         ) -> dict:
    """A point that succeeded in earlier sweep runs but FAILED in the
    newest one is the worst possible regression — value-based judging
    alone would degrade it to insufficient_data (no latest sample).
    skipped_timeout/error gate as ``regress``; ``skipped_budget`` is the
    harness's own scheduling (operator shrank the budget), reported as
    ``not_measured`` without gating."""
    for name, entry in verdict["metrics"].items():
        pid = name.split(":", 1)[1] if ":" in name else name
        status = latest_statuses.get(pid)
        if status in (None, "ok"):
            continue
        entry["latest_status"] = status
        if status == "skipped_budget":
            entry["verdict"] = "not_measured"
        else:
            entry["verdict"] = "regress"
            entry["reason"] = (f"point completed in earlier runs but "
                               f"ended '{status}' in the newest")
    verdicts = {v["verdict"] for v in verdict["metrics"].values()}
    verdict["overall"] = ("regress" if "regress" in verdicts
                          else "improve" if "improve" in verdicts
                          else "flat" if "flat" in verdicts
                          else "insufficient_data")
    return verdict


def load_sweep_samples(paths: List[str]) -> List[dict]:
    """Per-knob samples from an ordered (oldest → newest) list of sweep
    trajectory files: every completed point becomes a
    ``sweep:<point_id>`` metric sample, cohorted by the point's backend
    like the headline metrics — so a CPU-fallback sweep is never judged
    against chip numbers."""
    samples: List[dict] = []
    for idx, path in enumerate(paths):
        try:
            with open(path) as f:
                payload = json.load(f)
        except (OSError, ValueError) as e:
            samples.append({"source": path,
                            "error": f"{type(e).__name__}: {e}"})
            continue
        rec = sweep_record_of(payload)
        if rec is None:
            samples.append({"source": path,
                            "error": "no sweep trajectory (missing "
                                     "'points')"})
            continue
        for point in rec["points"]:
            if point.get("status") != "ok":
                continue
            backend = (point.get("backend") or rec.get("backend")
                       or "unknown")
            value = point.get("steps_per_sec")
            if isinstance(value, (int, float)) and value > 0:
                samples.append({
                    "source": os.path.basename(path), "order": idx,
                    "metric": f"sweep:{point.get('id')}",
                    "backend": backend,
                    "value": float(value), "partial": False})
            # Peak-HBM twin of the throughput sample (lower-is-better):
            # judged with the same cohort/noise machinery, so a knob
            # whose "win" blows the memory budget gates as regress.
            mem = point.get("hbm_bytes_peak")
            if isinstance(mem, (int, float)) and mem > 0:
                samples.append({
                    "source": os.path.basename(path), "order": idx,
                    "metric": f"{SWEEP_MEM_PREFIX}{point.get('id')}",
                    "backend": backend,
                    "value": float(mem), "partial": False})
            # Time-to-ready twin (lower-is-better): the coldstart probe's
            # cold/warm serve restart points — a warm restart drifting
            # back toward cold-start times (an executable-cache
            # regression) gates as regress across probe runs.
            ttr = point.get("time_to_ready_s")
            if isinstance(ttr, (int, float)) and ttr > 0:
                samples.append({
                    "source": os.path.basename(path), "order": idx,
                    "metric": f"{SWEEP_TTR_PREFIX}{point.get('id')}",
                    "backend": backend,
                    "value": float(ttr), "partial": False})
            # Serving-latency twin (lower-is-better): fleetmon's merged
            # fleet p99 and burn-rate series from the doctor probe — a
            # latency regression across probe runs gates exactly like a
            # throughput one.
            lat = point.get("latency_ms")
            if isinstance(lat, (int, float)) and lat > 0:
                samples.append({
                    "source": os.path.basename(path), "order": idx,
                    "metric": f"{SWEEP_LAT_PREFIX}{point.get('id')}",
                    "backend": backend,
                    "value": float(lat), "partial": False})
            # Bytes-on-wire twin (lower-is-better): the compiled step's
            # per-step collective traffic (obs/comms.py summary via
            # bench) — a throughput "win" that inflates wire traffic
            # gates as regress before it ever meets a real pod.
            comm = point.get("comms_bytes_per_step")
            if isinstance(comm, (int, float)) and comm > 0:
                samples.append({
                    "source": os.path.basename(path), "order": idx,
                    "metric": f"{SWEEP_COMM_PREFIX}{point.get('id')}",
                    "backend": backend,
                    "value": float(comm), "partial": False})
            # Scenario-conductor series: the point id already carries
            # "<scenario>:<metric>"; direction is derived from the
            # metric's unit suffix in _lower_is_better.
            scn = point.get("scenario_value")
            if isinstance(scn, (int, float)) and scn > 0:
                samples.append({
                    "source": os.path.basename(path), "order": idx,
                    "metric": f"{SWEEP_SCN_PREFIX}{point.get('id')}",
                    "backend": backend,
                    "value": float(scn), "partial": False})
    return samples


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))),
        help="repo root holding BENCH_r*.json (default: this checkout)")
    ap.add_argument("--noise", type=float, default=0.08,
                    help="relative noise band; a latest/reference ratio "
                         "inside 1±noise is 'flat' (default 0.08 — run-"
                         "to-run swing measured on the rehearsal box)")
    ap.add_argument("--add", action="append", default=[],
                    help="extra result file(s) to judge as the newest "
                         "run (bench emit JSON or driver round file); "
                         "repeatable")
    ap.add_argument("--sweep", action="append", default=[],
                    help="judge per-knob sweep trajectories "
                         "(tools/sweep.py artifacts) instead of the "
                         "bench trajectory; repeatable, ordered oldest "
                         "to newest — each point id is cohorted and "
                         "judged across the runs")
    ap.add_argument("--json", default="",
                    help="also write the verdict JSON to this path")
    args = ap.parse_args(argv)

    if args.sweep:
        samples = load_sweep_samples(args.sweep)
        names = sorted({s["metric"] for s in samples if "metric" in s})
        verdict = judge(samples, noise=args.noise, metric_names=names)
        verdict = apply_sweep_statuses(
            verdict, sweep_point_statuses(args.sweep[-1]))
    else:
        samples = load_samples(args.root, extra_files=args.add)
        verdict = judge(samples, noise=args.noise)

    for name, entry in verdict["metrics"].items():
        line = f"[perfwatch] {name:24s} {entry['verdict']:18s}"
        if "ratio" in entry:
            line += (f" latest={entry['latest']:g} "
                     f"ref={entry['reference']:g} "
                     f"ratio={entry['ratio']:g} "
                     f"({entry['backend']}, n={entry['samples']})")
        elif "latest" in entry:
            line += (f" latest={entry['latest']:g} "
                     f"({entry['backend']}, n={entry['samples']})")
        print(line)
    print(f"[perfwatch] overall: {verdict['overall']} "
          f"(noise band ±{args.noise:.0%})")
    print("PERFWATCH_JSON: " + json.dumps(verdict))
    if args.json:
        tmp = args.json + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(verdict, f, indent=1)
        os.replace(tmp, args.json)
    return 1 if verdict["overall"] == "regress" else 0


if __name__ == "__main__":
    sys.exit(main())
