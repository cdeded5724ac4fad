"""Plot/CSV results-artifact tool (reference results/cifar10.jpeg +
ps1workers1.csv role, SURVEY.md §2.2 results artifacts)."""

import json
import os

from tpu_resnet.tools.plot_metrics import load_series, plot


def _write_jsonl(path, records):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
        f.write('{"torn tail')  # live-writer torn line must be tolerated


def test_plot_and_csv(tmp_path):
    run = tmp_path / "run"
    _write_jsonl(str(run / "metrics.jsonl"), [
        {"step": s, "loss": 2.0 / (1 + s), "precision": min(1.0, s / 100),
         "steps_per_sec": 0.3, "images_per_sec_per_chip": 2.5,
         # step-time breakdown channel (tpu_resnet/obs/breakdown.py)
         "data_wait_frac": 0.1 + s / 1000, "compile_seconds": 3.2,
         "loop_host_sec": 0.05}
        for s in (20, 40, 60, 80, 100)])
    _write_jsonl(str(run / "eval" / "metrics.jsonl"), [
        {"step": 50, "Precision": 0.4, "Best_Precision": 0.4,
         "eval_loss": 1.0},
        {"step": 100, "Precision": 0.9, "Best_Precision": 0.9,
         "eval_loss": 0.5}])

    out = plot(str(run), csv_out=str(run / "series.csv"))
    assert os.path.exists(out) and os.path.getsize(out) > 10_000
    csv_text = (run / "series.csv").read_text()
    assert csv_text.splitlines()[0].startswith("series,step")
    assert any(line.startswith("eval,100") for line in csv_text.splitlines())
    assert len(load_series(str(run / "metrics.jsonl"))) == 5  # torn line ok


def test_plot_without_breakdown_keys(tmp_path):
    """Runs recorded before the obs layer (no data_wait_frac /
    compile_seconds) must still render."""
    run = tmp_path / "run"
    _write_jsonl(str(run / "metrics.jsonl"),
                 [{"step": 1, "loss": 1.0, "precision": 0.1},
                  {"step": 2, "loss": 0.5, "precision": 0.2}])
    out = plot(str(run))
    assert os.path.exists(out) and os.path.getsize(out) > 10_000
