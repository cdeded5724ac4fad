"""The comparison's arithmetic (the ResNet family's readings on
benchmarks/lib/check.py's measures) and the per-layer readers on made-up
numbers: what reads nought, what a fault moves, and that a reader with
nothing to read returns nothing."""

import math
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.families import resnet_v2 as family
from benchmarks.lib import check
from benchmarks.lib.manifest import Manifest


def sides(rng):
    """A program and a reference that agree exactly."""
    shapes = {"final_dense/kernel": (8, 4), "final_dense/bias": (4,),
              "a/bn/scale": (8,), "b/conv/kernel": (3, 3, 2, 8)}
    stat_shapes = {"a/bn/mean": (8,), "a/bn/var": (8,),
                   "c/bn/mean": (4,), "c/bn/var": (4,)}
    draw = lambda shapes: {k: rng.standard_normal(s) for k, s in
                           shapes.items()}
    params0, stats0 = draw(shapes), draw(stat_shapes)
    ref = {"params": draw(shapes), "stats": draw(stat_shapes),
           "mom": draw(shapes), "loss": 4.5, "gnorm": 2.0}
    prog = {k: ({n: v.copy() for n, v in ref[k].items()}
                if isinstance(ref[k], dict) else ref[k]) for k in ref}
    prog.update(params0=params0, stats0=stats0, step0=16, step=20, rows=4)
    return prog, ref


def test_equal_sides_read_nought_and_pass_any_limit():
    prog, ref = sides(np.random.default_rng(0))
    read = family.readings(prog, ref)
    assert all(abs(v) < 1e-12 for v in read.values()), read
    ok, compared = check.judge(read, {k: 0.0 for k in read
                                      if k == "step_count"})
    assert ok and compared["step_count"] == [0.0, 0.0]


@pytest.mark.parametrize("fault,number", [
    ("loss", "loss_rel"), ("steps", "step_count"), ("head", "head_cos"),
    ("unchanged", "dparam_med"), ("bn_mean", "bn_mean_cos"),
    ("head_bias", "head_bias_cos")])
def test_each_fault_moves_its_number(fault, number):
    rng = np.random.default_rng(1)
    prog, ref = sides(rng)
    if fault == "loss":
        prog["loss"] *= 1.05
    elif fault == "steps":
        prog["step"] += 1
    elif fault == "head":
        prog["mom"]["final_dense/kernel"] += rng.standard_normal((8, 4))
    elif fault == "unchanged":
        prog["params"] = dict(prog["params0"])
    elif fault == "bn_mean":
        prog["stats"]["a/bn/mean"] += rng.standard_normal(8)
    elif fault == "head_bias":
        prog["mom"]["final_dense/bias"] += rng.standard_normal(4)
    read = family.readings(prog, ref)
    assert read[number] > 0.01, read
    untouched = {"loss": "head_cos", "steps": "loss_rel", "head": "bn_cos",
                 "unchanged": "bn_cos", "bn_mean": "head_cos",
                 "head_bias": "head_cos"}[fault]
    assert abs(read[untouched]) < 1e-12


def test_a_cosine_sees_direction_and_not_length():
    prog, ref = sides(np.random.default_rng(2))
    for k in ("a/bn/mean", "c/bn/mean"):
        prog["stats"][k] = prog["stats0"][k] + 3.0 * (
            ref["stats"][k] - prog["stats0"][k])
    read = family.readings(prog, ref)
    assert abs(read["bn_mean_cos"]) < 1e-12
    assert read["bn_all"] > 0.1


def test_judge_fails_what_is_over_missing_or_not_finite():
    assert check.judge({"a": 0.5}, {"a": 0.4})[0] is False
    assert check.judge({"a": 0.3}, {"a": 0.4, "b": 1.0})[0] is False
    ok, compared = check.judge({"a": math.inf}, {"a": 0.4})
    assert ok is False and compared["a"][0] == 1e30
    ok, compared = check.judge({"a": 0.3, "shown": 9.0}, {"a": 0.4})
    assert ok is True and compared["shown"] == [9.0, None]


def test_leaves_have_to_match():
    prog, ref = sides(np.random.default_rng(3))
    del ref["stats"]["c/bn/var"]
    with pytest.raises(KeyError):
        family.readings(prog, ref)


# ------------------------------------------------------------ the readers
TRACE = {"busy_s": 5.0, "window_s": 10.0}


def a_run(**kw):
    base = dict(window_s=10.0, steps=20, images=20 * 64, global_batch=64,
                chips=1, records=[], trace=TRACE,
                peaks={"bf16_flops_per_s": 100e12}, flops_per_image=1e10)
    base.update(kw)
    return SimpleNamespace(**base)


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


@pytest.mark.parametrize("name,run,want", [
    ("step_mfu_pct", a_run(), 100 * 1e10 * 1280 / 5.0 / 100e12),
    ("step_device_ms", a_run(), 250.0),
    ("device_idle_pct", a_run(), 50.0),
    ("dispatch_ms", a_run(records=[{"dispatch_sec": 0.01},
                                   {"dispatch_sec": 0.03}]), 2.0),
    ("data_wait_pct", a_run(records=[{"data_wait_sec": 2.0},
                                     {"data_wait_sec": 1.0}]), 30.0),
    ("decode_images_per_s", a_run(records=[
        {"data_decode_images_per_sec": 100.0, "_dt": 4.0},
        {"data_decode_images_per_sec": 200.0, "_dt": 6.0}]), 160.0),
])
def test_reader_reads_its_number(manifest, name, run, want):
    assert manifest.reader(name)(run) == pytest.approx(want)


@pytest.mark.parametrize("name,run", [
    ("step_mfu_pct", a_run(trace=None)), ("step_mfu_pct", a_run(images=0)),
    ("step_device_ms", a_run(trace=None)),
    ("device_idle_pct", a_run(trace=None)),
    ("dispatch_ms", a_run()), ("data_wait_pct", a_run()),
    ("decode_images_per_s", a_run())])
def test_reader_with_nothing_to_read_returns_nothing(manifest, name, run):
    assert manifest.reader(name)(run) is None


def test_step_mfu_follows_the_devices_time_and_not_the_windows(manifest):
    read = manifest.reader("step_mfu_pct")
    slow_loop = a_run(window_s=40.0)      # the same device time, more idle
    assert read(slow_loop) == read(a_run())
    assert read(a_run(trace={"busy_s": 2.5, "window_s": 10.0})) == \
        pytest.approx(2 * read(a_run()))


# ------------------------------------------------- the control's rounding
@pytest.mark.parametrize("name,step", [("fp8", 2.0 ** -4),
                                       ("bf16", 2.0 ** -8)])
def test_rounders_round_to_their_precision_and_pass_gradients(name, step):
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import resnet_v2 as ref

    rnd = ref._ROUNDERS[name]
    x = jnp.asarray(np.random.default_rng(4).uniform(1.0, 400.0, 4096),
                    jnp.float32)
    err = np.abs(np.asarray(rnd(x) - x)) / np.asarray(x)
    assert err.max() <= step            # half a unit in the last place
    assert err.max() > step / 8         # and it does round
    grad = jax.grad(lambda v: jnp.sum(rnd(v) * 2.0))(x)
    assert np.all(np.asarray(grad) == 2.0)


def test_no_rounding_is_the_identity():
    from benchmarks.reference import resnet_v2 as ref

    x = np.float32(1.2345678)
    assert ref._ROUNDERS["none"](x) is x
