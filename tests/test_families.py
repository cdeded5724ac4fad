"""The seam of ``tpu_resnet/models``: a model family is one record, and
the step, the loop, the program registry and the FLOP accounting ask it.

The proof is a toy token family that only this file knows: defined and
registered here, trained through ``train()`` on a ``train.tokens`` file
written here. If a file of the package had to be edited for it, the seam
would be in the wrong place."""

import ast
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_resnet import models
from tpu_resnet.config import load_config
from tpu_resnet.data.tokens import write_tokens
from tpu_resnet.models import Family, build_model, sample_input
from tpu_resnet.programs import registry, spell
from tpu_resnet.train import schedule as sched_lib
from tpu_resnet.train.state import init_state
import tpu_resnet.train.step as step_lib
from tpu_resnet.train.step import check_step_config, make_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ONE = {"data": 1, "model": 1}
TOY_FLOPS_PER_TOKEN = 1234.0


class ToyTokens(nn.Module):
    """One embedding, one dense layer, a head."""
    vocab: int
    width: int = 16

    @nn.compact
    def __call__(self, ids, *, train: bool = False):
        h = nn.Embed(self.vocab, self.width)(jnp.asarray(ids, jnp.int32))
        h = nn.relu(nn.Dense(self.width)(h))
        self.sow("counters", "toy_active_frac", jnp.mean(h > 0),
                 reduce_fn=lambda old, new: new, init_fn=lambda: 0.0)
        return nn.Dense(self.vocab)(h).astype(jnp.float32)


def toy_family(**fields) -> Family:
    return Family(
        "toy_tokens", "tokens", ToyTokens,
        build=lambda cfg: ToyTokens(cfg.data.num_classes),
        spell=lambda cfg: (f"tokens{cfg.data.seq_len}", "toy16"), **fields)


@pytest.fixture
def toy():
    """The toy family with every optional field said; gone afterwards."""
    fam = models.register(toy_family(
        train_flops_per_example=lambda cfg, xla_counted=True: (
            TOY_FLOPS_PER_TOKEN * cfg.data.seq_len),
        refuses=lambda cfg, data_axis: (
            ["model.remat (the toy has no layer to recompute)"]
            if cfg.model.remat else []),
        startup_events=lambda model, cfg: {
            "toy_says": {"width": model.width, "seq_len": cfg.data.seq_len}}))
    try:
        yield fam
    finally:
        models._FAMILIES.pop(fam.name)


TOY = ["model.name=toy_tokens", "data.seq_len=16", "data.vocab_size=64",
       "model.compute_dtype=float32", "train.global_batch_size=8",
       "mesh.data=1"]


# ---------------------------------------------- (a) the toy, through train()
def test_toy_token_family_trains_through_train(toy, tmp_path):
    """One log interval of a family no file of the package names: its
    batches are ids, its key is its own spelling, its FLOPs its own
    count, and its start-up event is in the run's events."""
    from tpu_resnet.train.loop import train

    rng = np.random.default_rng(0)
    write_tokens(str(tmp_path / "data"), rng.integers(1, 64, 40 * 16 + 1))
    cfg = load_config("trinity_mini_ep16", overrides=TOY + [
        f"data.data_dir={tmp_path}/data", f"train.train_dir={tmp_path}/run",
        "train.train_steps=5", "train.log_every=5", "train.summary_every=5",
        "train.steps_per_call=5", "train.checkpoint_every=5",
        "optim.schedule=constant", "train.memory_ledger=false",
        "train.comms_ledger=false"])
    state = train(cfg)
    assert int(state.step) == 5 and isinstance(build_model(cfg), ToyTokens)
    with open(tmp_path / "run" / "metrics.jsonl") as f:
        record = [json.loads(line) for line in f][-1]
    assert record["step"] == 5 and record["tokens"] == 8 * 16
    assert np.isfinite(record["loss"]) and "moe_dropped_frac" not in record
    key = spell(cfg, ONE)
    assert key == "train|tokens16_toy16_f32|mesh1x1|b8"
    with open(tmp_path / "run" / "flops.json") as f:
        entry = json.load(f)["entries"][key]
    assert entry["flops_source"] == "analytic"
    assert entry["flops_per_step"] == 8 * 16 * TOY_FLOPS_PER_TOKEN
    with open(tmp_path / "run" / "events.jsonl") as f:
        said = [e for e in map(json.loads, f) if e["span"] == "toy_says"]
    assert len(said) == 1
    assert (said[0]["width"], said[0]["seq_len"]) == (16, 16)


@pytest.mark.parametrize("preset,overrides,model,dataset", [
    ("smoke", ["model.name=toy_tokens"], "toy_tokens", "synthetic"),
    ("trinity_mini_ep16", ["model.name=resnet"], "resnet", "tokens"),
    ("trinity_mini_ep16", ["model.name=mlp"], "mlp", "tokens"),
])
def test_a_family_is_fed_by_its_kind_of_data_only(toy, preset, overrides,
                                                  model, dataset):
    cfg = load_config(preset, overrides=overrides)
    with pytest.raises(ValueError) as err:
        check_step_config(cfg, 1)
    said = str(err.value)
    assert repr(model) in said and repr(dataset) in said
    assert ("feeds model 'afmoe', 'lfm2_moe', 'qwen3_next', 'sdar_moe', "
            "'smallthinker', 'toy_tokens'") in said
    assert "feeds model 'mlp', 'resnet'" in said


def test_a_familys_own_refusals_reach_check_step_config(toy):
    cfg = load_config("trinity_mini_ep16",
                      overrides=TOY + ["model.remat=true"])
    with pytest.raises(ValueError, match="'toy_tokens' does not train "
                                         "with: model.remat"):
        check_step_config(cfg, 1)
    # what follows from inputs == tokens is said for every such family
    cfg = load_config("trinity_mini_ep16",
                      overrides=TOY + ["optim.label_smoothing=0.1"])
    with pytest.raises(ValueError, match="with: optim.label_smoothing"):
        check_step_config(cfg, 1)
    # zero1 is afmoe's refusal, not every token model's
    check_step_config(load_config(
        "trinity_mini_ep16", overrides=TOY + ["mesh.partition=zero1"]), 1)


def test_the_step_means_the_familys_counters_and_asks_no_other():
    """``counters`` names what the step reads from the ``counters``
    collection; a family that names none gets no such collection asked
    for, whatever its module sows."""
    cfg = load_config("trinity_mini_ep16", overrides=TOY)

    def metrics_of(fam):
        models.register(fam)
        try:
            model = build_model(cfg)
            schedule = sched_lib.build_schedule(cfg.optim, cfg.train)
            state = init_state(model, cfg.optim, schedule,
                               jax.random.PRNGKey(0), sample_input(cfg))
            step = jax.jit(make_train_step(model, cfg.optim, schedule,
                                           cfg.data.num_classes))
            ids = jnp.ones((8, 16), jnp.int32)
            return step(state, ids, ids)[1]
        finally:
            models._FAMILIES.pop(fam.name)

    counted = metrics_of(toy_family(counters=("toy_active_frac",)))
    assert 0 <= float(counted["toy_active_frac"]) <= 1
    assert counted["tokens"] == 8 * 16
    assert "toy_active_frac" not in metrics_of(toy_family())


# ------------------------------------------------- (b) frozen program keys
# Copied from the parent's ``spell`` (commit 1af8388): flops.json, the
# goldens and the executable cache are keyed by them.
@pytest.mark.parametrize("preset,overrides,mesh,kind,batch,key", [
    ("cifar10", ["model.compute_dtype=bfloat16"], ONE, "train", None,
     "train|cifar10_rn50_bf16|mesh1x1|b128"),
    ("wrn28_10_cifar100", [], ONE, "train", None,
     "train|cifar100_wrn28_10_bf16|mesh1x1|b128"),
    ("imagenet", [], {"data": 8, "model": 1}, "train", None,
     "train|imagenet_rn50_bf16|mesh8x1|b1024"),
    ("imagenet", ["model.stem_space_to_depth=false"],
     {"data": 8, "model": 1}, "train", None,
     "train|imagenet_rn50_bf16_nos2d|mesh8x1|b1024"),
    ("smoke", ["model.fused_blocks=true", "model.remat=true"], {"data": 1},
     "train", None, "train|synthetic_rn8_f32_fused_remat|mesh1x1|b16"),
    ("smoke", ["model.fused_epilogue=on"], {"data": 1}, "train", None,
     "train|synthetic_rn8_f32_ep|mesh1x1|b16"),
    ("smoke", ["model.sync_bn=false"], {"data": 8, "model": 1}, "train",
     None, "train|synthetic_rn8_f32_pr|mesh8x1|b16"),
    ("smoke", ["mesh.partition=zero1"], {"data": 8, "model": 1}, "train",
     None, "train|synthetic_rn8_f32_zero1|mesh8x1|b16"),
    ("smoke", ["model.name=mlp", "data.synthetic_classes=100"], {"data": 1},
     "train", None, "train|synthetic100_mlp_f32|mesh1x1|b16"),
    ("trinity_mini_ep16", [], ONE, "train", None,
     "train|tokens4096_afmoe5l_e8of128_bf16|mesh1x1|b2"),
    ("cifar10", ["serve.quantize=int8"], ONE, "serve", 4,
     "serve|cifar10_rn50_bf16_q8|mesh1x1|b4"),
    ("smoke", ["model.fused_epilogue=auto"], {"data": 4, "model": 2},
     "chunk", None, "chunk|synthetic_rn8_f32|mesh4x2|b16"),
])
def test_program_keys_are_frozen(preset, overrides, mesh, kind, batch, key):
    cfg = load_config(preset, overrides=overrides)
    assert spell(cfg, mesh, kind=kind, batch=batch) == key


# ------------------------------- (c) what a batch is, one case a kind
KINDS = [("images", "smoke", ["model.name=mlp"]),
         ("tokens", "trinity_mini_ep16", TOY)]


@pytest.mark.parametrize("inputs,preset,overrides", KINDS)
def test_batch_avals_follow_the_familys_inputs(toy, inputs, preset,
                                               overrides):
    cfg = load_config(preset, overrides=overrides)
    assert models.family(cfg).inputs == inputs
    b = cfg.train.global_batch_size
    x, y = registry.batch_avals(cfg)
    sx, sy = registry.batch_avals(cfg, rows=3)
    if inputs == "tokens":
        assert (x.shape, x.dtype, y.shape, y.dtype) == (
            (b, 16), jnp.int32, (b, 16), jnp.int32)
        assert sx.shape == sy.shape == (3, b, 16)
    else:
        assert (x.shape, x.dtype, y.shape, y.dtype) == (
            (b, 32, 32, 3), jnp.uint8, (b,), jnp.int32)
        assert (sx.shape, sy.shape) == ((3, b, 32, 32, 3), (3, b))


@pytest.mark.parametrize("inputs,preset,overrides", KINDS)
def test_sample_input_follows_the_familys_inputs(toy, inputs, preset,
                                                 overrides):
    sample = sample_input(load_config(preset, overrides=overrides))
    if inputs == "tokens":
        assert (sample.shape, sample.dtype) == ((1, 8), jnp.int32)
    else:
        assert (sample.shape, sample.dtype) == ((1, 32, 32, 3), jnp.float32)


@pytest.mark.parametrize("inputs,preset,overrides", KINDS)
def test_init_draws_a_token_model_as_one_program(toy, monkeypatch, inputs,
                                                 preset, overrides):
    """``init_state`` is given a model and a sample, no config: the
    model's class finds its family, and ``inputs`` chooses the path."""
    cfg = load_config(preset, overrides=overrides)
    model = build_model(cfg)
    assert models.family_of(model) is models.family(cfg)
    drawn, one_program = [], registry.init_program
    monkeypatch.setattr(registry, "init_program",
                        lambda m: drawn.append(m) or one_program(m))
    state = init_state(model, cfg.optim,
                       sched_lib.build_schedule(cfg.optim, cfg.train),
                       jax.random.PRNGKey(0), sample_input(cfg))
    assert drawn == ([model] if inputs == "tokens" else [])
    assert int(state.step) == 0 and jax.tree_util.tree_leaves(state.params)


# -------------------------------------------------- (d) model FLOPs a family
def test_token_preset_counts_its_flops_from_its_shapes():
    from tpu_resnet.models.afmoe import train_flops_per_sequence

    cfg = load_config("trinity_mini_ep16")
    fam = models.family(cfg)
    assert fam.train_flops_per_example(cfg) == train_flops_per_sequence(
        build_model(cfg).arch, cfg.data.seq_len) > 1e12
    assert fam.train_flops_per_example(cfg, xla_counted=False) == \
        fam.train_flops_per_example(cfg)


@pytest.mark.parametrize("preset,overrides,analytic", [
    ("imagenet", [], True),
    ("imagenet", ["data.image_size=128"], True),
    ("imagenet", ["model.resnet_size=18"], False),   # not ResNet-50's count
    ("cifar10", [], False),
    ("smoke", ["model.name=mlp"], False),
])
def test_image_families_take_xlas_count_first(preset, overrides, analytic):
    from tpu_resnet.obs.mfu import analytic_resnet50_flops

    cfg = load_config(preset, overrides=overrides)
    fam = models.family(cfg)
    assert fam.train_flops_per_example(cfg) is None
    without = fam.train_flops_per_example(cfg, xla_counted=False)
    if analytic:
        assert without == pytest.approx(analytic_resnet50_flops(
            1, cfg.data.resolved_image_size), rel=1e-12)
    else:
        assert without is None


def test_accounting_falls_back_to_the_familys_count(monkeypatch):
    """ImageNet rn50 where XLA gives no count: the registry entry is the
    analytic one; with XLA's count it is XLA's."""
    from tpu_resnet import parallel
    from tpu_resnet.obs import mfu

    cfg = load_config("imagenet", overrides=[
        "train.global_batch_size=8", "data.image_size=32", "mesh.data=1"])
    mesh = parallel.create_mesh(cfg.mesh, devices=jax.devices()[:1])
    counts = iter([None, 7.0e9])
    monkeypatch.setattr(mfu, "lowered_flops", lambda *a, **k: next(counts))
    monkeypatch.setattr(step_lib, "shard_step", lambda *a, **k: None)
    entry = mfu.account_train_step(cfg, mesh, None, None)
    assert entry["flops_source"] == "analytic"
    assert entry["flops_per_step"] == pytest.approx(
        mfu.analytic_resnet50_flops(8, 32), rel=1e-12)
    entry = mfu.account_train_step(cfg, mesh, None, None)
    assert (entry["flops_source"], entry["flops_per_step"]) == (
        "xla_cost_analysis", 7.0e9)


# ------------------------------------------------------- the record itself
def test_register_refuses_an_unknown_kind_of_input():
    with pytest.raises(ValueError, match="inputs must be one of"):
        models.register(Family("audio_net", "waveforms", ToyTokens,
                               build=None, spell=None))
    assert "audio_net" not in models._FAMILIES


def test_unknown_model_and_unknown_module_are_said():
    cfg = load_config("smoke", overrides=["model.name=resnext"])
    with pytest.raises(ValueError, match="unknown model 'resnext'.*'afmoe', "
                                         "'lfm2_moe', 'mlp', 'qwen3_next', "
                                         "'resnet'"):
        build_model(cfg)
    with pytest.raises(ValueError, match="no registered model family "
                                         "builds a ToyTokens"):
        models.family_of(ToyTokens(8))


@pytest.mark.parametrize("what", ["evaluation", "serving", "export"])
def test_who_can_evaluate_serve_export_is_keyed_by_inputs(toy, what):
    models.require_image_model(load_config("smoke"), what)
    for cfg in (load_config("trinity_mini_ep16", overrides=TOY),
                load_config("smoke", overrides=["model.name=toy_tokens"]),
                load_config("trinity_mini_ep16",
                            overrides=["model.name=mlp"])):
        with pytest.raises(NotImplementedError, match=what):
            models.require_image_model(cfg, what)


# ------------------------------------- (e) nothing else knows a family
# ops/epilogue.py is the ResNet family's own kernel module: its probe reads
# that family's stage table (ISSUE 32 leaves the epilogues as they are).
FAMILYS_OWN = {"tpu_resnet/ops/epilogue.py"}


def test_no_file_outside_models_names_a_family():
    """No import of a family's module and no comparison with a family's
    name outside ``models/`` and ``config.py``: what a family is, is asked
    of its record."""
    names = set(models._FAMILIES)
    modules = {f"tpu_resnet.models.{n}" for n in (
        "afmoe", "mlp", "resnet", "sdar_moe", "transformer")}
    found = []
    for dirpath, _, files in os.walk(os.path.join(REPO, "tpu_resnet")):
        for fn in files:
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, REPO).replace(os.sep, "/")
            if (not fn.endswith(".py") or rel == "tpu_resnet/config.py"
                    or rel.startswith("tpu_resnet/models/")
                    or rel in FAMILYS_OWN):
                continue
            with open(path) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Import):
                    hit = any(a.name in modules for a in node.names)
                elif isinstance(node, ast.ImportFrom):
                    hit = node.module in modules or (
                        node.module == "tpu_resnet.models" and any(
                            f"tpu_resnet.models.{a.name}" in modules
                            for a in node.names))
                elif isinstance(node, ast.Compare):
                    hit = any(isinstance(c, ast.Constant) and c.value in names
                              for c in ast.walk(node))
                else:
                    continue
                if hit:
                    found.append(f"{rel}:{node.lineno}")
    assert found == []
