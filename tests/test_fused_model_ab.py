"""tools/fused_model_ab.py CPU smoke — battery stage 15_fused_model_ab
runs unattended on a live TPU window; a tiny-config run here keeps that
from being its first execution ever (the rule every unattended stage
follows: streaming_gap, mfu cifar10, fused_block_ab)."""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

import pytest

import fused_model_ab  # noqa: E402


@pytest.mark.slow
def test_ab_tiny_config(tmp_path, monkeypatch):
    """Full A/B harness (two model compiles) — battery stage 15 runs it
    unattended; slow-tiered."""
    out = tmp_path / "ab.json"
    monkeypatch.setattr(sys, "argv", [
        "fused_model_ab.py", "--resnet-size", "14", "--batch", "8",
        "--split", "64", "--steps-per-call", "2", "--warmup-chunks", "1",
        "--measure-chunks", "1", "--out", str(out)])
    fused_model_ab.main()
    got = json.load(open(out))
    assert got["steps_per_sec"]["xla"] > 0
    assert got["steps_per_sec"]["fused"] > 0
    assert "fused_speedup" in got
