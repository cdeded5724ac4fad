"""CLI argument-wiring tests — every ``python -m tpu_resnet`` subcommand
driven through ``main(argv)`` (tpu_resnet/main.py).

The round-1 ``inspect --peek`` crash showed that library-level tests can
all pass while a CLI path is broken: nothing previously exercised the
argparse wiring, flag plumbing, or the subcommand dispatch itself. The
reference's CLI surface was its nine entry scripts (SURVEY.md §1 L4);
ours is this one command, so this file is the matrix audit.
"""

import json
import os

import pytest

from tpu_resnet.main import main


@pytest.fixture(scope="module")
def run_dir(tmp_path_factory):
    """One short training run through the CLI, shared by the read-only
    subcommand tests below."""
    d = str(tmp_path_factory.mktemp("cli") / "run")
    rc = main(["train", "--preset", "smoke",
               f"train.train_dir={d}",
               "train.train_steps=4", "train.checkpoint_every=2",
               "train.log_every=2", "train.global_batch_size=16"])
    assert rc == 0
    return d


def test_train_cli_writes_checkpoints_and_metrics(run_dir):
    assert os.path.isdir(os.path.join(run_dir, "4"))
    assert os.path.exists(os.path.join(run_dir, "metrics.jsonl"))


def test_eval_once_cli(run_dir, capsys):
    rc = main(["eval", "--once", "--preset", "smoke",
               f"train.train_dir={run_dir}",
               "train.global_batch_size=16", "train.eval_batch_size=16"])
    assert rc == 0
    assert os.path.exists(os.path.join(run_dir, "eval",
                                       "best_precision.json"))


def test_eval_once_on_an_empty_dir_exits_nonzero(tmp_path):
    """`eval --once` that evaluated nothing (no checkpoint to restore) is
    a failure the caller can see, not a quiet exit 0."""
    rc = main(["eval", "--once", "--preset", "smoke",
               f"train.train_dir={tmp_path}"])
    assert rc != 0
    assert not os.path.exists(os.path.join(str(tmp_path), "eval",
                                           "best_precision.json"))


def test_info_cli(capsys):
    assert main(["info", "--preset", "smoke"]) == 0
    out = capsys.readouterr().out
    assert "params" in out.lower()


def test_info_layers_cli(capsys):
    assert main(["info", "--preset", "smoke", "--layers"]) == 0
    out = capsys.readouterr().out
    assert "initial_conv" in out


def test_inspect_cli_with_step_and_peek(run_dir, capsys):
    assert main(["inspect", "--dir", run_dir, "--step", "2"]) == 0
    assert "checkpoint step 2" in capsys.readouterr().out
    # --peek end-to-end through the CLI (the round-1 crash path).
    assert main(["inspect", "--dir", run_dir]) == 0
    listing = capsys.readouterr().out
    name = next(line.split()[0] for line in listing.splitlines()
                if "initial_conv" in line and line.lstrip().startswith("params"))
    assert main(["inspect", "--dir", run_dir, "--peek", name.strip()]) == 0
    assert "mean=" in capsys.readouterr().out


def test_export_and_predict_cli(run_dir, tmp_path, capsys):
    out = str(tmp_path / "frozen")
    rc = main(["export", "--out", out, "--preset", "smoke",
               f"train.train_dir={run_dir}", "--batch-size", "8"])
    assert rc == 0
    assert os.path.exists(os.path.join(out, "inference.stablehlo"))

    pred = str(tmp_path / "pred")
    rc = main(["predict", "--export-dir", out, "--out", pred,
               "--num-examples", "16", "--preset", "smoke"])
    assert rc == 0
    assert os.path.exists(os.path.join(pred, "predictions.json"))


def test_plot_cli_with_csv(run_dir, tmp_path, capsys):
    png = str(tmp_path / "curves.png")
    csv = str(tmp_path / "curves.csv")
    rc = main(["plot", "--dir", run_dir, "--out", png, "--csv", csv])
    assert rc == 0
    assert os.path.exists(png) and os.path.exists(csv)


def test_train_and_eval_cli(tmp_path):
    d = str(tmp_path / "tae")
    rc = main(["train_and_eval", "--preset", "smoke",
               f"train.train_dir={d}",
               "train.train_steps=4", "train.checkpoint_every=2",
               "train.log_every=2", "train.global_batch_size=16",
               "train.eval_batch_size=16"])
    assert rc == 0
    assert os.path.exists(os.path.join(d, "eval", "best_precision.json"))


def test_doctor_cli_dataset_requires_data_dir():
    with pytest.raises(SystemExit):
        main(["doctor", "--dataset", "cifar10"])  # parser.error


def test_fetch_cli_rejects_unknown_dataset():
    with pytest.raises(SystemExit):
        main(["fetch", "mnist", "--out", "/tmp/x"])  # not in choices


def test_bad_override_fails_loudly(run_dir):
    with pytest.raises(Exception):
        main(["train", "--preset", "smoke", "nonexistent.key=1"])


def test_unknown_subcommand_exits():
    with pytest.raises(SystemExit):
        main(["frobnicate"])


def test_load_label_map_reference_format(tmp_path):
    """Golden test of the clsidx_to_labels format (VERDICT r3 missing #3):
    the vendored fixture mirrors /root/reference/data/
    imagenet1000_clsidx_to_labels.txt exactly — python-dict-ish listing,
    braces inline with the first/last entries, comma-laden names — so the
    brace/quote stripping is pinned (the final entry used to keep a
    trailing quote-brace)."""
    from tpu_resnet.config import load_config
    from tpu_resnet.tools.predict import load_label_map

    fixture = os.path.join(os.path.dirname(__file__), "fixtures",
                           "clsidx_to_labels_10.txt")
    cfg = load_config("smoke")
    names = load_label_map(cfg, fixture)
    assert names[0] == "alpha craft, test flyer"
    assert names[2] == "gamma bird, crested pinger, Pingus fictus"
    assert names[9] == "kappa truck, long-haul rig"   # no trailing "'}"
    assert len(names) == cfg.data.num_classes


def test_predict_cli_with_label_file(run_dir, tmp_path):
    """predict --label-file end to end through the CLI: mispredicted
    entries in predictions.json must carry names from the file, not raw
    class indices."""

    out = str(tmp_path / "frozen")
    assert main(["export", "--out", out, "--preset", "smoke",
                 f"train.train_dir={run_dir}", "--batch-size", "8"]) == 0
    fixture = os.path.join(os.path.dirname(__file__), "fixtures",
                           "clsidx_to_labels_10.txt")
    pred = str(tmp_path / "pred")
    assert main(["predict", "--export-dir", out, "--out", pred,
                 "--num-examples", "16", "--preset", "smoke",
                 "--label-file", fixture]) == 0
    results = json.load(open(os.path.join(pred, "predictions.json")))
    allowed = {"alpha craft, test flyer", "beta wagon",
               "gamma bird, crested pinger, Pingus fictus", "delta cat",
               "epsilon deer", "zeta dog", "eta frog", "theta horse",
               "iota ship", "kappa truck, long-haul rig"}
    for m in results["mispredicted"]:
        assert m["label"] in allowed and m["pred"] in allowed
    # A 2-step smoke model on synthetic data essentially guesses — the
    # name-mapping assertion above must actually see entries.
    assert results["mispredicted"], "expected >=1 misprediction at chance"
