"""tools/pallas_compile_smoke.py as far as a CPU can test it: under the
interpreter at toy shapes every family's cases run and match their
references; a failing case is reported (with its message) without
stopping the others and makes the exit code non-zero; and without a TPU
a non-interpret run refuses instead of pretending to have compiled."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import pallas_compile_smoke as smoke  # noqa: E402


def test_every_family_matches_its_reference_under_the_interpreter(capsys):
    assert smoke.main(["--interpret", "--tiny"]) == 0
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["ok"] and report["interpret"]
    assert report["device"]["platform"] == "cpu"
    assert set(report["families"]) == set(smoke.FAMILIES)
    checks = {fam: set().union(*(c["checks"] for c in
                                 body["cases"].values()))
              for fam, body in report["families"].items()}
    assert checks["xent"] == {"fwd", "bwd"}
    assert checks["epilogue"] == {"sbr_fwd", "sbr_bwd", "add_fwd",
                                  "add_bwd"}
    assert checks["block"] == {"fwd", "bwd", "train_fwd", "train_bwd"}
    for body in report["families"].values():
        for case in body["cases"].values():
            assert max(case["checks"].values()) < smoke.TOL


def test_model_shapes_are_the_ones_the_models_produce():
    """The non-tiny epilogue sweep is derived from the two rn50 presets,
    and contains the stage shapes the issue lists."""
    shapes = smoke._epilogue_shapes(tiny=False)
    for want in ((128, 32, 32, 16), (128, 16, 16, 32), (128, 8, 8, 64),
                 (128, 56, 56, 64), (128, 56, 56, 256),
                 (128, 14, 14, 1024), (128, 7, 7, 2048)):
        assert want in shapes


def test_a_failing_case_is_reported_and_fails_the_run(monkeypatch, capsys,
                                                      tmp_path):
    def cases(interpret, tiny):
        def refused():
            raise RuntimeError("Mosaic failed to compile: not implemented")
        # the refusal of one direction must not hide the next one's verdict
        yield "refused", lambda: {"fwd": refused, "bwd": lambda: 0.0}
        yield "wrong", lambda: {"fwd": lambda: 0.5}
        yield "fine", lambda: {"fwd": lambda: 0.0}

    monkeypatch.setitem(smoke._CASES, "xent", cases)
    out = tmp_path / "sub" / "report.json"
    rc = smoke.main(["--interpret", "--tiny", "--family", "xent",
                     "--out", str(out)])
    assert rc == 1
    report = json.loads(out.read_text())
    cases = report["families"]["xent"]["cases"]
    assert not report["ok"]
    assert "Mosaic failed to compile" in cases["refused"]["errors"]["fwd"]
    assert cases["refused"]["checks"] == {"bwd": 0.0}
    assert not cases["refused"]["ok"]
    assert not cases["wrong"]["ok"] and cases["fine"]["ok"]
    assert "FAIL" in capsys.readouterr().out


def test_without_a_tpu_a_real_compile_is_refused():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "pallas_compile_smoke.py")],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr and "cpu" in proc.stderr
    assert '"ok"' not in proc.stdout
