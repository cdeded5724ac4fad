"""The block-diffusion family (models/sdar_moe.py) and the objective seam
of the trainer, on the CPU at a tiny size (d 64, heads 4/2 of 16, 16
experts top-4 of which 4 are held, vocabulary 128, 2 layers, sequences of
32 clean ids in blocks of 4, fed as 64 positions), in float32 against the
benchmark's plain reference (benchmarks/reference/sdar_moe.py)."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import afmoe as numerics
from benchmarks.reference import sdar_moe as ref
from tpu_resnet import models
from tpu_resnet.config import load_config
from tpu_resnet.data.tokens import write_tokens
from tpu_resnet.models import (build_model, sample_input, sdar_moe,
                               transformer)
from tpu_resnet.models.sdar_moe import Arch, SdarMoe
from tpu_resnet.programs import spell
from tpu_resnet.train import schedule as sched_lib
from tpu_resnet.train.state import init_state
from tpu_resnet.train.step import check_step_config, make_train_step

TINY = ["sdar_moe.layers=2", "sdar_moe.hidden=64", "sdar_moe.heads=4",
        "sdar_moe.kv_heads=2", "sdar_moe.head_dim=16",
        "sdar_moe.expert_width=32", "sdar_moe.experts_total=16",
        "sdar_moe.experts_first=4", "sdar_moe.experts_held=4",
        "sdar_moe.top_k=4", "data.seq_len=32", "data.vocab_size=128",
        "model.compute_dtype=float32", "train.global_batch_size=4",
        "mesh.data=1"]
ARCH = Arch(layers=2, hidden=64, heads=4, kv_heads=2, head_dim=16,
            expert_width=32, experts_total=16, experts_held=(4, 4),
            top_k=4, vocab_rows=128, attn_block=16, dtype=jnp.float32)
MODEL = dict(layers=2, hidden=64, heads=4, kv_heads=2, head_dim=16,
             expert_width=32, experts_total=16, experts_first=4,
             experts_held=4, top_k=4, vocab_rows=128, mask_id=127,
             seq_len=32, block_length=4, t_min=0.001, rope_theta=1e6,
             rms_norm_eps=1e-6)
SEED = 0                       # the run's train.seed


@pytest.fixture(autouse=True)
def exact_products(monkeypatch):
    """Float32 to the last bits on both sides (a chip run's reference
    carries 16 bits a product, which its time limit forces)."""
    monkeypatch.setattr(numerics, "TERMS", numerics.HIGHEST)
    with jax.default_matmul_precision("highest"):
        yield


def clean_ids(seed=0, batch=3, length=32):
    """Packed ids: documents begin inside blocks and at their edges."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, 127, (batch, length))
    ids[:, [0, 6, 16, 27]] = 0
    return jnp.asarray(ids, jnp.int32)


def weights(arch=ARCH, seed=1):
    return SdarMoe(arch).init(jax.random.PRNGKey(seed),
                              jnp.zeros((1, 8), jnp.int32))["params"]


def as_reference(tree):
    return {"/".join(p.key for p in path): leaf for path, leaf in
            jax.tree_util.tree_leaves_with_path(tree)}


def worst(a, b):
    return max(float(jnp.max(jnp.abs(a[k] - b[k]))
                     / (jnp.max(jnp.abs(b[k])) + 1e-30)) for k in b)


def step_key(step):
    """The step's key as the loop makes it (``train/loop.py``: the second
    half of ``split(PRNGKey(train.seed))``, folded with the step)."""
    return jax.random.fold_in(
        jax.random.split(jax.random.PRNGKey(SEED))[1], step)


# ------------------------------------------------- (1) against the reference
@pytest.mark.parametrize("step", [0, 7])
def test_masked_positions_are_the_references_from_the_recipe_alone(step):
    x0 = clean_ids()
    xt, masked, t = sdar_moe.noise(step_key(step), x0, 4, 0.001, 127)
    want_xt, want_masked, want_t = ref.noise(SEED, step, x0, MODEL)
    np.testing.assert_array_equal(np.asarray(masked), np.asarray(want_masked))
    np.testing.assert_array_equal(np.asarray(xt), np.asarray(want_xt))
    np.testing.assert_array_equal(np.asarray(t), np.asarray(want_t))
    # one level a block, in (t_min, 1]; masked ids read the mask id
    blocks = np.asarray(t).reshape(3, 8, 4)
    assert (blocks == blocks[:, :, :1]).all() and 0.001 <= blocks.min()
    assert ((np.asarray(xt) == 127) == np.asarray(masked)).all()
    assert 0.2 < float(jnp.mean(masked)) < 0.8


def test_loss_and_every_gradient_match_the_reference():
    params, x0 = weights(), clean_ids()
    model = SdarMoe(ARCH)

    def loss(p):
        fed, score = sdar_moe.objective(model, step_key(3), x0, None)
        logits, _ = model.apply({"params": p}, fed, train=True,
                                mutable=["counters"])
        return score(logits)[0]

    got, grads = jax.jit(jax.value_and_grad(loss))(params)
    xt, masked, t = ref.noise(SEED, 3, x0, MODEL)
    flat = as_reference(params)
    want = jax.jit(lambda p: ref.forward_loss(p, xt, x0, masked, t,
                                              MODEL))(flat)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    block_by_block, want_grads = ref.Programs(MODEL, "none").gradients(
        flat, xt, x0, masked, t)
    assert abs(block_by_block - float(want)) < 1e-5 * float(want)
    grads = as_reference(grads)
    assert set(grads) == set(want_grads)
    assert worst(grads, want_grads) < 2e-5
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in want_grads.values())


def test_a_few_steps_of_the_program_follow_the_reference():
    from benchmarks.families import sdar_moe as family

    cfg = load_config("sdar_30b_a3b_chat", overrides=TINY)
    model = build_model(cfg)
    schedule = sched_lib.build_schedule(cfg.optim, cfg.train)
    state = init_state(model, cfg.optim, schedule, jax.random.PRNGKey(3),
                       sample_input(cfg))
    step = jax.jit(make_train_step(
        model, cfg.optim, schedule, cfg.data.num_classes,
        base_rng=jax.random.split(jax.random.PRNGKey(SEED))[1]))
    before = family.snapshot(state)
    xs = [clean_ids(seed, batch=4) for seed in range(3)]
    for x in xs:
        state, metrics = step(state, x, x)
    after = family.snapshot(state)
    reference = family.follow(before, (np.stack(xs), np.stack(xs)),
                              {"model": MODEL, "job": dict(
                                  lr=dict(kind="warmup_cosine", base=3e-4,
                                          warmup=2000, total=100_000),
                                  b1=0.9, b2=0.95, eps=1e-8,
                                  weight_decay=0.1, clip_norm=1.0)}, SEED)
    program = dict(after, **{k + "0": v for k, v in before.items()},
                   loss=float(metrics["loss"]),
                   gnorm=float(metrics["grad_norm"]), rows=3)
    read = family.readings(program, reference)
    assert read["loss_rel"] < 1e-6 and read["gnorm_rel"] < 1e-5
    assert read["head_cos"] < 1e-8 and read["mu_cos"] < 1e-8
    assert read["dparam_cos"] < 1e-4
    assert read["step_count"] == 0 and read["moments0"] == 0
    assert metrics["tokens"] == 4 * 32
    assert 0.2 < float(metrics["diffusion_masked_frac"]) < 0.8
    assert 0.2 < float(metrics["diffusion_t_mean"]) < 0.8
    assert 0 < float(metrics["moe_here_frac"]) < 1
    assert float(metrics["moe_dropped_frac"]) == 0


# ------------------------------------------- (2) the share ties to the model
def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of 4 of the 16 experts, each the program's layer with
    its own slice of the whole layer's weights: their partial results add
    up to what the reference gives with every expert held."""
    whole = dict(MODEL, experts_first=0, experts_held=16)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 24, 64)), jnp.float32)
    p = {k: jnp.asarray(rng.normal(size=s) * 0.1, jnp.float32)
         for k, s in (("router", (64, 16)), ("gate", (16, 64, 32)),
                      ("up", (16, 64, 32)), ("down", (16, 32, 64)))}
    want = ref.experts(p, x, whole, "none")
    total, here = 0.0, []
    for first in (0, 4, 8, 12):
        arch = dataclasses.replace(ARCH, experts_held=(first, 4))
        mine = {k: (v if k == "router" else v[first:first + 4])
                for k, v in p.items()}
        out, state = sdar_moe.ExpertLayer(arch).apply(
            {"params": mine}, x, mutable=["counters"])
        total = total + out
        here.append(float(state["counters"]["moe_here_frac"]))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-6)
    assert sum(here) == pytest.approx(1.0, abs=1e-6)


# ------------------------------------------ (3) the loss reads nothing else
def test_the_loss_reads_the_masked_noisy_positions_only():
    model, params, x0 = SdarMoe(ARCH), weights(), clean_ids()
    fed, score = sdar_moe.objective(model, step_key(5), x0, None)
    _, masked, t = sdar_moe.noise(step_key(5), x0, 4, 0.001, 127)
    logits = model.apply({"params": params}, fed, train=True,
                         mutable=["counters"])[0]
    assert logits.shape == (3, 32, 128)      # the noisy positions only
    pull = np.asarray(jax.grad(lambda z: score(z)[0])(logits))
    touched = np.abs(pull).max(axis=-1) > 0
    np.testing.assert_array_equal(touched, np.asarray(masked))
    # so whatever changes the logits elsewhere changes nothing
    bump = jnp.where(masked[..., None], 0.0, 3.0)
    assert float(score(logits + bump)[0]) == float(score(logits)[0])
    # the weight of a masked position is 1 / t of its block, over S L
    i = tuple(np.argwhere(np.asarray(masked))[0])
    assert -pull[i][int(x0[i])] / (1 - float(jax.nn.softmax(
        logits[i])[int(x0[i])])) == pytest.approx(
            1.0 / float(t[i]) / x0.size, rel=1e-5)
    scored = score(logits)[1]
    assert float(scored["diffusion_masked_frac"]) == pytest.approx(
        float(jnp.mean(masked)))
    assert 0 <= float(scored["precision"]) <= 1


def test_the_clean_copy_is_unmoved_by_the_noisy_ids():
    """Nothing sees a noisy key of another block, and the clean copy sees
    no noisy key at all: its hidden states are those of the clean text."""
    model, params, x0 = SdarMoe(ARCH), weights(), clean_ids()

    def last_hidden(xt):
        _, state = model.apply(
            {"params": params}, jnp.concatenate([xt, x0], axis=1),
            capture_intermediates=lambda m, _: isinstance(m, sdar_moe.Layer),
            mutable=["intermediates", "counters"])
        return np.asarray(
            state["intermediates"]["layer_1"]["__call__"][0])

    a = last_hidden(sdar_moe.noise(step_key(1), x0, 4, 0.001, 127)[0])
    b = last_hidden(jnp.full_like(x0, 127))
    np.testing.assert_array_equal(a[:, 32:], b[:, 32:])
    assert np.abs(a[:, :32] - b[:, :32]).max() > 1e-3
    # a noisy block is unmoved by the noisy ids of the other blocks
    xt = np.asarray(sdar_moe.noise(step_key(1), x0, 4, 0.001, 127)[0])
    other = xt.copy()
    other[:, :8] = 127
    other[:, 12:] = 127
    c = last_hidden(jnp.asarray(other))
    np.testing.assert_allclose(c[:, 8:12], a[:, 8:12], atol=1e-6)


def test_the_model_through_the_two_parts_equals_the_model_through_the_scan(
        monkeypatch):
    """Steered in the test, as one chip would choose: the model's loss on
    the masked positions and its gradients with the attention as
    the kernel over the clean keys and the diagonal beside it (interpret
    mode, the module's own tiles of 1,024), against the scan the CPU
    takes."""
    arch = dataclasses.replace(ARCH, layers=1, heads=2, kv_heads=1,
                               head_dim=128)
    length = 1024
    rng = np.random.default_rng(2)
    x0 = rng.integers(1, 127, (1, length))
    x0[0, [0, 4, 77, 512, 900]] = 0     # documents on and off a block's edge
    x0 = jnp.asarray(x0, jnp.int32)
    xt, masked, _ = sdar_moe.noise(step_key(3), x0, 4, 0.001, 127)
    fed = jnp.concatenate([xt, x0], axis=1)
    model = SdarMoe(arch)
    params = weights(arch)

    def loss(params):
        logits = model.apply({"params": params}, fed)
        assert logits.shape == (1, length, 128)
        picked = jnp.take_along_axis(logits, x0[..., None], -1)[..., 0]
        return jnp.sum((jax.nn.logsumexp(logits, -1) - picked) * masked
                       ) / length

    want = jax.value_and_grad(loss)(params)
    monkeypatch.setattr(transformer, "attention_path", lambda *_: "kernel")
    got = jax.value_and_grad(loss)(params)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(a, b, atol=2e-5 * np.abs(b).max())


# --------------------------------------------------------------- the family
def test_preset_states_the_published_widths_and_spells_its_program():
    cfg = load_config("sdar_30b_a3b_chat")
    a = build_model(cfg).arch
    assert (a.hidden, a.heads, a.kv_heads, a.head_dim) == (2048, 32, 4, 128)
    assert (a.expert_width, a.experts_total, a.top_k) == (768, 128, 8)
    assert (a.layers, a.experts_held, a.vocab_rows) == (4, (0, 16), 18992)
    assert (a.block_length, a.mask_id, a.eps) == (4, 18991, 1e-6)
    assert (cfg.data.seq_len, cfg.train.global_batch_size) == (4096, 1)
    assert spell(cfg, {"data": 1, "model": 1}) == \
        "train|tokens4096_sdar4l_e16of128_blk4_bf16|mesh1x1|b1"
    shapes = jax.eval_shape(lambda: build_model(cfg).init(
        jax.random.PRNGKey(0), sample_input(cfg)))["params"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == \
        456_346_624
    fam = models.family(cfg)
    assert fam.objective is sdar_moe.objective
    # 0.782 T for the positions' matrices, 0.550 T for the live scores
    # and values, 0.159 T for the head: 1.49 T multiply-adds, x 6
    assert fam.train_flops_per_example(cfg) == pytest.approx(8.95e12,
                                                             rel=2e-3)


@pytest.mark.parametrize("backend, devices, said", [
    ("tpu", 1, dict(path="kernel", inputs="fused", key_blocks_visited=20,
                    key_blocks_total=32, diagonal_rows=4096)),
    ("tpu", 4, dict(path="scan", inputs="composed",
                    key_blocks_visited=32 * 32,
                    key_blocks_total=32 * 32, diagonal_rows=0)),
    ("cpu", 8, dict(path="scan", inputs="composed",
                    key_blocks_visited=32 * 32,
                    key_blocks_total=32 * 32, diagonal_rows=0)),
], ids=["one_chip", "four_chips", "cpu"])
def test_attention_paths_of_the_preset(backend, devices, said):
    """On one chip the kernel steps through 20 of the 32 tiles of every
    query by the clean keys and leaves the 4,096 noisy rows' own blocks to
    the product beside it; the scan takes every block of 256 queries
    against all 8,192 keys."""
    arch = build_model(load_config("sdar_30b_a3b_chat")).arch
    rows = sdar_moe.attention_paths(arch, 4096, backend, devices)
    assert rows == [dict(layer=i, kind="block_diffusion", **said)
                    for i in range(4)]


def test_half_a_tile_of_clean_ids_takes_the_scan():
    """The kernel's tiles of 1,024 have to divide the clean copy, whose
    keys are all the kernel is given: 512 clean ids fed as 1,024 positions
    take the scan."""
    arch = build_model(load_config("sdar_30b_a3b_chat")).arch
    assert sdar_moe.attention_paths(arch, 512, "tpu", 1)[0]["path"] == "scan"
    assert sdar_moe.attention_paths(arch, 1024, "tpu", 1)[0] == dict(
        layer=0, kind="block_diffusion", path="kernel", inputs="fused",
        key_blocks_visited=2, key_blocks_total=2, diagonal_rows=1024)


@pytest.mark.parametrize("overrides,words", [
    (["mesh.partition=zero1"], "mesh.partition=zero1"),
    (["model.fused_epilogue=on"], "ResNet kernels"),
    (["data.seq_len=30"], "whole blocks"),
    (["optim.label_smoothing=0.1"], "optim.label_smoothing"),
])
def test_check_step_config_says_what_it_refuses(overrides, words):
    cfg = load_config("sdar_30b_a3b_chat", overrides=TINY + overrides)
    with pytest.raises(ValueError, match="'sdar_moe' does not train with"
                       ) as err:
        check_step_config(cfg, 1)
    assert words in str(err.value)


def test_odd_lengths_are_refused_by_the_model_itself():
    with pytest.raises(ValueError, match="whole blocks of 4"):
        SdarMoe(ARCH).init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 12), jnp.int32))


def test_tiny_preset_trains_through_train_and_says_its_path(tmp_path):
    from tpu_resnet.train.loop import train

    rng = np.random.default_rng(0)
    ids = rng.integers(1, 127, 40 * 32 + 1)
    ids[::9] = 0
    write_tokens(str(tmp_path / "data"), ids)
    cfg = load_config("sdar_30b_a3b_chat", overrides=TINY + [
        f"data.data_dir={tmp_path}/data", f"train.train_dir={tmp_path}/run",
        "train.train_steps=4", "train.log_every=2", "train.summary_every=2",
        "train.steps_per_call=2", "train.checkpoint_every=4",
        "train.memory_ledger=false", "train.comms_ledger=false",
        "train.mfu_accounting=false"])
    state = train(cfg)
    assert int(state.step) == 4
    with open(tmp_path / "run" / "metrics.jsonl") as f:
        record = [json.loads(line) for line in f][-1]
    assert record["tokens"] == 4 * 32 and np.isfinite(record["loss"])
    for name in ("diffusion_masked_frac", "diffusion_t_mean",
                 *sdar_moe.COUNTERS):
        assert name in record, name
    with open(tmp_path / "run" / "events.jsonl") as f:
        said = [e for e in map(json.loads, f)
                if e["span"] == "attention_path"]
    assert len(said) == 1 and len(said[0]["layers"]) == 2
    assert said[0]["layers"][0] == dict(
        layer=0, kind="block_diffusion", path="scan", inputs="composed",
        key_blocks_visited=1, key_blocks_total=1, diagonal_rows=0)
