"""MFU accounting (tpu_resnet/obs/mfu.py): peak table, cost-analysis
extraction, registry keys, engine-twin FLOPs identity, utilization math."""

import json

import pytest

from tpu_resnet.config import load_config
from tpu_resnet.obs import mfu


# ------------------------------------------------------------ peak table

def test_peak_flops_table(monkeypatch):
    assert mfu.peak_flops_per_chip("TPU v5 lite") == 197e12
    assert mfu.peak_flops_per_chip("TPU v5p chip") == 459e12
    assert mfu.peak_flops_per_chip("TPU v4") == 275e12
    assert mfu.peak_flops_per_chip("cpu") is None  # unknown = no claim
    # no environment variable may assume a peak for an unknown chip
    monkeypatch.setenv("BENCH_PEAK_FLOPS", "5e12")
    monkeypatch.setenv("TPU_RESNET_PEAK_FLOPS", "5e12")
    assert mfu.peak_flops_per_chip("cpu") is None

    # bench._peak_flops delegates to the same table
    import bench
    monkeypatch.delenv("BENCH_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("TPU_RESNET_PEAK_FLOPS", raising=False)
    assert bench._peak_flops("TPU v5e") == mfu.peak_flops_per_chip(
        "TPU v5e")


def test_program_flops_api_forms():
    assert mfu.program_flops({"flops": 12.5}) == 12.5
    assert mfu.program_flops({}) is None
    assert mfu.program_flops(None) is None
    assert mfu.program_flops({"flops": 0}) is None


def test_lowered_flops_matches_known_matmul():
    """XLA's cost analysis of a lone matmul is the textbook 2*M*N*K (+
    bias-free): pin the extraction end-to-end through a real lowering."""
    import jax
    import jax.numpy as jnp

    m = n = k = 64
    f = jax.jit(lambda a, b: a @ b)
    a = jnp.zeros((m, k), jnp.float32)
    b = jax.ShapeDtypeStruct((k, n), "float32")  # concrete + aval mix
    flops = mfu.lowered_flops(f, a, b)
    assert flops == pytest.approx(2 * m * n * k, rel=0.01)


def test_mfu_math():
    assert mfu.mfu(98.5e12, "TPU v5e", 1) == pytest.approx(0.5)
    assert mfu.mfu(197e12, "TPU v5e", 2) == pytest.approx(0.5)
    assert mfu.mfu(1e12, "cpu", 8) is None      # unknown chip
    assert mfu.mfu(None, "TPU v5e", 1) is None  # unknown flops
    # a multiply-add is two FLOPs: 3 x 2 x 4.09 G an image
    assert mfu.analytic_resnet50_flops(128) == pytest.approx(
        3 * 2 * 4.09e9 * 128)
    assert mfu.analytic_resnet50_flops(128, image=112) == pytest.approx(
        3 * 2 * 4.09e9 * 128 / 4)


def test_analytic_flops_agree_with_the_count_from_shapes():
    """The loop's fallback against the benchmark's count of the same model
    from its shapes (23.69 GFLOP an image): within a few percent, not the
    factor of two a multiply-add counted as one FLOP made."""
    import json
    import os

    from benchmarks.lib import flops

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "imagenet_rn50.json")) as f:
        arch = json.load(f)["model"]
    counted = flops.train_flops_per_image(arch)
    assert counted == pytest.approx(23.69e9, rel=1e-3)
    assert mfu.analytic_resnet50_flops(1) == pytest.approx(counted, rel=0.05)


# -------------------------------------------------------- registry keys

def test_train_program_key_spelled_like_golden_entries():
    cfg = load_config("cifar10")
    cfg.model.compute_dtype = "bfloat16"
    key = mfu.train_program_key(cfg, {"data": 8, "model": 1})
    assert key == "train|cifar10_rn50_bf16|mesh8x1|b128"
    cfg.model.remat = True
    cfg.model.fused_blocks = True
    assert "_fused_remat" in mfu.train_program_key(cfg, {"data": 1})
    wrn = load_config("wrn28_10_cifar100")  # preset default dtype: bf16
    assert mfu.train_program_key(wrn, {"data": 1, "model": 1}) == \
        "train|cifar100_wrn28_10_bf16|mesh1x1|b128"
    smoke = load_config("smoke")
    smoke.model.name = "mlp"
    assert "synthetic_mlp_f32" in mfu.train_program_key(smoke, {})


def test_key_and_flops_identical_for_engine_twins(tmp_path):
    """data.engine=thread vs process feed byte-identical compiled
    programs (the configmatrix engine-invariance contract): the MFU
    registry must key them identically AND measure identical FLOPs."""
    import jax
    import jax.numpy as jnp

    from tpu_resnet import parallel
    from tpu_resnet.models import build_model
    from tpu_resnet.train import build_schedule, init_state
    from tpu_resnet.train.step import make_train_step

    entries = {}
    for engine in ("thread", "process"):
        cfg = load_config("smoke")
        cfg.data.engine = engine
        cfg.train.global_batch_size = 16
        mesh = parallel.create_mesh(cfg.mesh)
        model = build_model(cfg)
        sched = build_schedule(cfg.optim, cfg.train)
        rng = jax.random.PRNGKey(0)
        state = init_state(model, cfg.optim, sched, rng,
                           jnp.zeros((1, 32, 32, 3)))
        state = jax.device_put(state, parallel.replicated(mesh))
        step = make_train_step(model, cfg.optim, sched,
                               cfg.data.num_classes, None, base_rng=rng,
                               mesh=mesh)
        entry = mfu.account_train_step(
            cfg, mesh, state, step,
            train_dir=str(tmp_path / engine))
        key = mfu.train_program_key(cfg, dict(mesh.shape))
        assert "thread" not in key and "process" not in key
        entries[engine] = (key, entry)

    (k1, e1), (k2, e2) = entries["thread"], entries["process"]
    assert k1 == k2
    assert e1["flops_per_step"] == e2["flops_per_step"] > 0
    assert e1["flops_source"] == "xla_cost_analysis"
    # persisted registry round-trips
    reg = mfu.FlopsRegistry.load(str(tmp_path / "thread"))
    assert reg.flops(k1) == e1["flops_per_step"]


def test_registry_save_load_and_missing(tmp_path):
    reg = mfu.FlopsRegistry()
    reg.register("train|x|mesh1x1|b8", 123.0, global_batch=8)
    path = reg.save(str(tmp_path))
    with open(path) as f:
        payload = json.load(f)
    assert payload["format"] == 1
    assert payload["entries"]["train|x|mesh1x1|b8"]["flops_per_step"] == 123.0
    loaded = mfu.FlopsRegistry.load(str(tmp_path))
    assert loaded.flops("train|x|mesh1x1|b8") == 123.0
    assert loaded.flops("absent") is None
    assert mfu.FlopsRegistry.load(str(tmp_path / "nope")).to_dict()[
        "entries"] == {}
    none_entry = mfu.FlopsRegistry().register("k", None)
    assert none_entry["flops_source"] == "none"
