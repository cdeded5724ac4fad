"""chip_smoke.py (the on-chip bring-up proof) as far as a CPU can test it:
it refuses to run without a TPU, it never imports jax, and its phase
checks — pure functions of a train_dir — accept what the real CLI leaves
behind and reject the absences they exist to catch."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_refuses_cpu_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr and "platform=cpu" in proc.stderr
    assert '"ok"' not in proc.stdout
    assert not os.path.exists(os.path.join(REPO, "chip_smoke_out")), \
        "nothing may be run or written without a TPU"


def test_alone_in_a_directory_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=120)
    assert proc.returncode != 0 and '"ok"' not in proc.stdout
    assert os.listdir(tmp_path) == ["chip_smoke.py"]


def test_module_stays_off_jax():
    code = ("import sys, chip_smoke; "
            "assert 'jax' not in sys.modules, 'chip_smoke imported jax'")
    subprocess.run([sys.executable, "-c", code], cwd=REPO, check=True,
                   timeout=120)


@pytest.fixture(scope="module")
def smoke_run(tmp_path_factory):
    """train -> eval --once -> a served request -> drain, at the smoke
    preset, through the same entry points chip_smoke.py drives."""
    import copy

    import numpy as np

    from tpu_resnet.config import load_config
    from tpu_resnet.main import main
    from tpu_resnet.obs import read_run_id
    from tpu_resnet.obs.spans import SpanTracer
    from tpu_resnet.obs.trace import SERVE_EVENTS_FILE
    from tpu_resnet.serve.server import PredictServer

    d = str(tmp_path_factory.mktemp("smoke_run"))
    args = ["--preset", "smoke", f"train.train_dir={d}"]
    assert main(["train"] + args + [
        "train.train_steps=8", "train.checkpoint_every=4",
        "train.log_every=2", "train.summary_every=2"]) == 0
    assert main(["eval", "--once"] + args) == 0

    cfg = load_config("smoke", "", [f"train.train_dir={d}"])
    cfg = copy.deepcopy(cfg)
    cfg.serve.port, cfg.serve.host = 0, "127.0.0.1"
    cfg.serve.max_batch, cfg.serve.reload_interval_secs = 2, 0
    spans = SpanTracer(d, filename=SERVE_EVENTS_FILE, run_id=read_run_id(d))
    srv = PredictServer(cfg, spans=spans).start()
    status, payload = srv.handle_predict(
        np.zeros((2, 32, 32, 3), np.uint8).tobytes(),
        "application/octet-stream", "2,32,32,3", True)
    info = srv.info()
    srv.drain(10.0)
    srv.close()
    spans.close()
    return d, status, payload, info


def test_phase_checks_accept_a_real_run(smoke_run):
    d, status, payload, info = smoke_run
    facts = chip_smoke.check_train(d, 8, 4, tpu=False)
    assert facts["devices"]["platform"] == "cpu"
    assert facts["compile_seconds"] > 0
    assert [s for s, _ in facts["loss"]][-1] == 8
    assert chip_smoke.check_eval(d, 8, 256)["examples"] == 256
    sv = chip_smoke.check_serve_events(d, (1, 2))
    assert sorted(sv["bucket_cache_hit"]) == [1, 2]
    # /info names the device and the installation
    assert info["devices"] == {"count": 8, "kinds": ["cpu"],
                               "platform": "cpu"}
    assert set(info["versions"]) == {"jax", "jaxlib", "libtpu"}
    assert status == 200


def test_predict_check_rejects_wrong_answers(monkeypatch):
    monkeypatch.setattr(chip_smoke, "NUM_CLASSES", 4)
    good = {"predictions": [2], "logits": [[0.0, 1.0, 3.0, 2.0]]}
    assert chip_smoke.check_predict(1, 200, good) == good["logits"]
    for status, bad in (
            (503, good),
            (200, dict(good, logits=[[0.0, 1.0, 3.0]])),        # shape
            (200, dict(good, logits=[[0.0, 1.0, float("nan"), 2.0]])),
            (200, dict(good, predictions=[0]))):                # argmax
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.check_predict(1, status, bad)


def test_phase_checks_fail_on_what_they_exist_to_catch(smoke_run, tmp_path):
    d = str(tmp_path / "run")
    shutil.copytree(smoke_run[0], d)
    # a TPU run without an mfu / an autotune table is a failure ...
    with pytest.raises(chip_smoke.SmokeFailure, match="trained on"):
        chip_smoke.check_train(d, 8, 4, tpu=True)
    # ... as is an eval that names another step, or saw another split
    with pytest.raises(chip_smoke.SmokeFailure, match="step"):
        chip_smoke.check_eval(d, 12)
    with pytest.raises(chip_smoke.SmokeFailure, match="examples"):
        chip_smoke.check_eval(d, 8, 100)
    # ... a bucket that never warmed
    with pytest.raises(chip_smoke.SmokeFailure, match="warmed buckets"):
        chip_smoke.check_serve_events(d, (1, 2, 4))
    # ... an absent ledger, a missing checkpoint, a non-finite loss
    os.remove(os.path.join(d, "comms.json"))
    with pytest.raises(chip_smoke.SmokeFailure, match="comms.json"):
        chip_smoke.check_train(d, 8, 4, tpu=False)
    shutil.copy(os.path.join(smoke_run[0], "comms.json"), d)
    shutil.rmtree(os.path.join(d, "4"))
    with pytest.raises(chip_smoke.SmokeFailure, match="checkpoint"):
        chip_smoke.check_train(d, 8, 4, tpu=False)
    os.makedirs(os.path.join(d, "4"))
    with open(os.path.join(d, "metrics.jsonl"), "a") as f:
        f.write(json.dumps({"step": 8, "loss": float("nan")}) + "\n")
    with pytest.raises(chip_smoke.SmokeFailure, match="non-finite"):
        chip_smoke.check_train(d, 8, 4, tpu=False)
