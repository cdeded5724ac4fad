"""The last-line builder refuses what the contract refuses."""

import copy
import json

import pytest

from benchmarks.lib import lastline
from benchmarks.lib.lastline import LastLineError

E2E = [{"name": "train_images_per_s", "unit": "images/s"},
       {"name": "setup_s", "unit": "s"}]
LAYER = [{"name": "step_mfu_pct", "unit": "%"},
         {"name": "device_idle_pct", "unit": "%"}]
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 5_000_000_000}


def build(trace=False, values=None, device=None, **kw):
    metrics = LAYER if trace else E2E
    if values is None:
        values = ({"step_mfu_pct": 30.5, "device_idle_pct": 12.25} if trace
                  else {"train_images_per_s": 1500.123, "setup_s": 61.5})
    if device is None:
        device = dict(DEVICE)
        if trace:
            device.update(busy_s=7.0, window_s=8.0)
    return lastline.build(correct=True, attempted=40, failed=0,
                          values=values, metrics=metrics, device=device,
                          trace=trace, **kw)


def test_a_sound_line_in_both_trace_modes():
    line = json.loads(build())
    assert list(line)[-1] == "compared"
    assert line["metrics"]["setup_s"] == {"value": 61.5, "unit": "s"}
    traced = json.loads(build(trace=True, breakdown={
        "device_ops": [["fusion", 1.5]], "idle_gaps": [["idle", 0.01]]},
        compared={"loss_rel": [0.001, 0.02]}))
    assert traced["device"]["busy_s"] == 7.0
    assert traced["breakdown"]["device_ops"] == [["fusion", 1.5]]
    assert traced["compared"] == {"loss_rel": [0.001, 0.02]}


@pytest.mark.parametrize("values", [
    {"train_images_per_s": 1500.0},                       # setup_s missing
    {"train_images_per_s": 1500.0, "setup_s": None},      # reader found none
    {"train_images_per_s": float("nan"), "setup_s": 60.0},
    {"train_images_per_s": float("inf"), "setup_s": 60.0},
])
def test_missing_or_unreal_metric_is_refused(values):
    with pytest.raises(LastLineError):
        build(values=values)


@pytest.mark.parametrize("busy,window", [(0.0, 8.0), (-1.0, 8.0),
                                         (8.5, 8.0), (None, 8.0),
                                         (float("nan"), 8.0)])
def test_busy_outside_its_window_is_refused(busy, window):
    device = dict(DEVICE, busy_s=busy, window_s=window)
    with pytest.raises(LastLineError):
        build(trace=True, device=device)


def test_traced_line_needs_busy_and_window():
    with pytest.raises(LastLineError):
        build(trace=True, device=dict(DEVICE))


@pytest.mark.parametrize("key", ["platform", "kind", "count",
                                 "memory_peak_bytes"])
def test_device_facts_are_required(key):
    device = dict(DEVICE)
    del device[key]
    with pytest.raises(LastLineError):
        build(device=device)


def test_zero_memory_peak_is_refused():
    with pytest.raises(LastLineError):
        build(device=dict(DEVICE, memory_peak_bytes=0))


def test_share_of_a_peak_over_105_is_refused():
    with pytest.raises(LastLineError):
        build(trace=True, values={"step_mfu_pct": 106.0,
                                  "device_idle_pct": 1.0})


def test_validate_rejects_a_foreign_metric_and_long_breakdown():
    line = json.loads(build(trace=True))
    del line["compared"]
    extra = copy.deepcopy(line)
    extra["metrics"]["setup_s"] = {"value": 1.0, "unit": "s"}
    with pytest.raises(LastLineError):
        lastline.validate(extra, LAYER, True)
    long = copy.deepcopy(line)
    long["breakdown"] = {"device_ops": [["op", 1.0]] * 11, "idle_gaps": []}
    with pytest.raises(LastLineError):
        lastline.validate(long, LAYER, True)
    failed = copy.deepcopy(line)
    failed["failed"] = 41
    with pytest.raises(LastLineError):
        lastline.validate(failed, LAYER, True)
