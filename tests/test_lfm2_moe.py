"""The conv-hybrid sparse-expert decoder (models/lfm2_moe.py) and its way
through the trainer, on the CPU at a tiny size (d 64, heads 4/2 of 16,
filters of 3 taps, S 32, 16 experts top-4 of which 4 held, a tied
vocabulary of 128; layers conv + dense, attention + experts, 3 x conv +
experts), in float32 against the benchmark's plain reference
(benchmarks/reference/lfm2_moe.py) and against counts by hand."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import lfm2_moe as family
from benchmarks.lib.harness import flat
from benchmarks.reference import afmoe as ref_numerics
from benchmarks.reference import lfm2_moe as ref
from tpu_resnet.config import load_config
from tpu_resnet.data.tokens import write_tokens
from tpu_resnet.models import (build_model, family_of, lfm2_moe,
                               sample_input, transformer)
from tpu_resnet.models.lfm2_moe import Arch, Lfm2Moe
from tpu_resnet.programs import spell
from tpu_resnet.train import schedule as sched_lib
from tpu_resnet.train.state import init_state
from tpu_resnet.train.step import (check_step_config, make_train_step,
                                   token_xent)

LAYERS = ("dense_conv", "moe_full", "moe_conv", "moe_conv", "moe_conv")
TINY = ["lfm2_moe.hidden=64", "lfm2_moe.heads=4", "lfm2_moe.kv_heads=2",
        "lfm2_moe.head_dim=16", "lfm2_moe.dense_width=96",
        "lfm2_moe.expert_width=32", "lfm2_moe.experts_total=16",
        "lfm2_moe.experts_first=4", "lfm2_moe.experts_held=4",
        "lfm2_moe.top_k=4", "data.seq_len=32", "data.vocab_size=128",
        "model.compute_dtype=float32", "train.global_batch_size=8",
        "mesh.data=1"]
ARCH = Arch(layers=LAYERS, hidden=64, heads=4, kv_heads=2, head_dim=16,
            dense_width=96, expert_width=32, experts_total=16,
            experts_held=(4, 4), top_k=4, vocab_rows=128, attn_block=8,
            dtype=jnp.float32)
MODEL = dict(layers=list(LAYERS), hidden=64, heads=4, kv_heads=2,
             head_dim=16, conv_taps=3, dense_width=96, expert_width=32,
             experts_total=16, experts_first=4, experts_held=4, top_k=4,
             vocab_rows=128, seq_len=32, rope_theta=1e6, rms_norm_eps=1e-5,
             route_scale=1.0, balance_coeff=0.001)
JOB = dict(lr=dict(kind="warmup_cosine", base=3e-4, warmup=2000,
                   total=100_000),
           b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1, clip_norm=1.0)


@pytest.fixture(autouse=True)
def exact_products(monkeypatch):
    """Float32 to the last bits on both sides: a chip run's reference
    carries 16 bits a product (``HIGH``), which its time limit forces and
    these sizes do not."""
    monkeypatch.setattr(ref_numerics, "TERMS", ref_numerics.HIGHEST)
    with jax.default_matmul_precision("highest"):
        yield


def tokens(seed=0, batch=2, length=32):
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, 128, (batch, length + 1))
    ids[:, ::7] = 0
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


def weights(arch=ARCH, seed=1, bias_scale=0.3):
    """Seeded parameters, and expert biases away from 0 so that choosing
    with and without them differs."""
    v = Lfm2Moe(arch).init(jax.random.PRNGKey(seed), tokens()[0],
                           train=False)
    stats = jax.tree_util.tree_map(
        lambda b: bias_scale * jax.random.normal(jax.random.PRNGKey(5),
                                                 b.shape), v["batch_stats"])
    return v["params"], stats


def as_reference(tree):
    return {k: jnp.asarray(v) for k, v in flat(tree).items()}


def worst(a, b):
    assert set(a) == set(b)
    return max(float(np.max(np.abs(np.asarray(a[k]) - np.asarray(b[k])))
                     / max(1e-3, float(np.max(np.abs(np.asarray(b[k]))))))
               for k in a)


# ------------------------------------------------- against the reference
def test_forward_loss_and_gradients_match_the_reference():
    """Loss, every gradient (the tied embedding's, the sum of the head's
    and the lookup's, among them) and the ``expert_bias`` after the
    step."""
    params, stats = weights()
    x, y = tokens()
    assert "head" not in params and params["embed"].shape == (128, 64)

    def loss(p):
        logits, state = Lfm2Moe(ARCH).apply(
            {"params": p, "batch_stats": stats}, x, train=True,
            mutable=["batch_stats", "counters"])
        return token_xent(logits, y), state

    (got, state), grads = jax.value_and_grad(loss, has_aux=True)(params)
    rp, rb = as_reference(params), as_reference(stats)
    want, want_grads = jax.value_and_grad(
        lambda p: ref.forward_loss(p, rb, x, y, MODEL))(rp)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    assert worst(flat(grads), want_grads) < 1e-5
    # the reference's block-by-block gradients are its whole-graph ones
    by_block = ref.Programs(MODEL, "none").gradients(rp, rb, x, y)
    assert abs(by_block[0] - float(want)) < 1e-5 * float(want)
    assert worst(by_block[1], want_grads) < 1e-5
    # both uses reach the one table: the lookup's rows and the head's
    only_head = jax.grad(lambda t: ref.head_loss(
        rp["embedding_norm/scale"], t, jnp.ones((2, 32, 64)), y, 1e-5,
        "none"))(rp["embed"])
    assert float(jnp.max(jnp.abs(want_grads["embed"] - only_head))) > 1e-4
    # the bias update, three expert layers' worth
    new = flat(state["batch_stats"])
    assert len(by_block[2]) == 4
    for key, n in by_block[2].items():
        np.testing.assert_allclose(
            new[key], ref.bias_update(rb[key], n, 0.001), atol=1e-7)
    counters = flat(state["counters"])
    assert all(v == 0 for k, v in counters.items() if "dropped" in k)
    assert set(k.rsplit("/", 1)[-1] for k in counters) == set(
        lfm2_moe.COUNTERS)


def test_remat_keeps_the_gradients():
    params, stats = weights()
    x, y = tokens()

    def grads(arch):
        return jax.grad(lambda p: token_xent(Lfm2Moe(arch).apply(
            {"params": p, "batch_stats": stats}, x, train=True,
            mutable=["batch_stats", "counters"])[0], y))(params)

    plain, remat = grads(ARCH), grads(dataclasses.replace(ARCH, remat=True))
    assert worst(flat(remat), flat(plain)) < 1e-6


def test_the_shares_add_up_to_the_uncut_layer():
    """The parts that all the shares give (4 shares of 2 of 8 experts) add
    up to what the uncut reference gives for the whole expert layer, and
    so do the gradients with respect to ``x`` and the expert matrices."""
    arch = Arch(layers=("moe_conv",), hidden=64, expert_width=32,
                experts_total=8, experts_held=(0, 8), top_k=4,
                dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 64))
    cot = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 64))
    p = lfm2_moe.ExpertLayer(arch).init(jax.random.PRNGKey(1), x,
                                        False)["params"]
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(2), (8,))
    model = dict(MODEL, experts_total=8, experts_first=0, experts_held=8)
    stacks = ("gate", "up", "down")
    flat_p = {k: jnp.asarray(a) for k, a in flat(p).items()}

    def reference(x, experts):
        return ref.experts(dict(flat_p, **experts), bias, x, model,
                           "none")[0]

    def shares(x, experts):
        total = 0.0
        for first in range(0, 8, 2):
            share = lfm2_moe.ExpertLayer(dataclasses.replace(
                arch, experts_held=(first, 2)))
            cut = dict(p, **{k: experts[k][first:first + 2]
                             for k in stacks})
            got, state = share.apply(
                {"params": cut, "batch_stats": {"expert_bias": bias}}, x,
                False, mutable=["counters"])
            assert float(state["counters"]["moe_dropped_frac"]) == 0.0
            total = total + got
        return total

    experts = {k: p[k] for k in stacks}
    np.testing.assert_allclose(shares(x, experts), reference(x, experts),
                               atol=2e-5)
    got = jax.grad(lambda x, e: jnp.sum(shares(x, e) * cot),
                   argnums=(0, 1))(x, experts)
    want = jax.grad(lambda x, e: jnp.sum(reference(x, e) * cot),
                    argnums=(0, 1))(x, experts)
    np.testing.assert_allclose(got[0], want[0], atol=2e-5)
    for k in stacks:
        np.testing.assert_allclose(got[1][k], want[1][k], atol=2e-5,
                                   err_msg=k)


def test_the_router_is_the_shared_one_with_this_familys_two_numbers():
    """``eps`` 1e-6 and scale 1: the weights of a token's chosen experts
    add up to ``sum / (sum + 1e-6)``, and the bias chooses without
    weighing."""
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
    w = 0.02 * jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    bias = jnp.zeros((16,)).at[3].set(10.0)
    chosen, weight = transformer.sigmoid_router(x, w, bias, 4, eps=1e-6,
                                                scale=1.0)
    assert bool(jnp.all(jnp.any(chosen == 3, axis=-1)))
    s = jnp.take_along_axis(jax.nn.sigmoid(x @ w), chosen, -1)
    np.testing.assert_allclose(weight, s / (s.sum(-1, keepdims=True) + 1e-6),
                               rtol=1e-6)
    after = transformer.balanced_bias(bias, chosen, 0.001)
    n = np.bincount(np.asarray(chosen).reshape(-1), minlength=16)
    c = 0.001 * np.sign(n.mean() - n)
    np.testing.assert_allclose(after, np.asarray(bias) + c - c.mean(),
                               atol=1e-7)


# ------------------------------------------------------ packing and reach
def test_every_document_of_a_packed_sequence_gets_what_it_gets_alone():
    """Conv layers and the attention layer both: the logits of a packed
    sequence are, document by document, those of each document fed alone
    (the routing is a token's own; rotary is relative)."""
    params, stats = weights()
    rng = np.random.default_rng(3)
    ids = rng.integers(1, 128, (1, 32))
    starts = [0, 5, 6, 8, 19]           # documents of 5, 1, 2, 11 and 13
    ids[0, starts] = 0
    # the scan a query at a time: a document alone is of any length
    model = Lfm2Moe(dataclasses.replace(ARCH, attn_block=1))

    def logits(x):
        return np.asarray(model.apply(
            {"params": params, "batch_stats": stats}, jnp.asarray(x)))

    packed = logits(ids)
    for lo, hi in zip(starts, starts[1:] + [32]):
        alone = logits(ids[:, lo:hi])
        np.testing.assert_allclose(packed[:, lo:hi], alone, atol=2e-5,
                                   err_msg=f"document {lo}:{hi}")
    # and without the cut a document's first positions read the one before
    doc = jnp.zeros((1, 32), jnp.int32)
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 64))
    taps = jax.random.normal(jax.random.PRNGKey(1), (64, 3))
    cut = lfm2_moe.short_conv(u, taps, jnp.cumsum(
        jnp.asarray(ids == 0, jnp.int32), axis=1))
    uncut = lfm2_moe.short_conv(u, taps, doc)
    differ = np.any(np.asarray(cut != uncut), axis=(0, 2))
    assert set(np.flatnonzero(differ)) == {5, 6, 7, 8, 9, 19, 20}


def test_a_conv_output_moves_with_its_three_inputs_and_no_other():
    """``c_t`` depends on ``u_{t-2}``, ``u_{t-1}``, ``u_t`` (the LAST tap on
    the current position) and on nothing else; with a document begun at 6,
    position 6 reads itself alone and position 7 itself and 6."""
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 4))
    taps = jnp.asarray(np.arange(1, 13, dtype=np.float32).reshape(4, 3))
    doc = jnp.asarray((np.arange(12) >= 6).astype(np.int32))[None]
    jac = np.asarray(jax.jacobian(
        lambda u: lfm2_moe.short_conv(u, taps, doc))(u))[0, :, :, 0]
    # (t, channel, t', channel'): a depthwise filter keeps its channel
    for t in range(12):
        for c in range(4):
            row = jac[t, c]                       # (t', channel')
            assert not row[:, [k for k in range(4) if k != c]].any()
            reads = {t - j: float(taps[c, 2 - j]) for j in range(3)
                     if t - j >= 0 and (t - j >= 6) == (t >= 6)}
            want = np.zeros(12, np.float32)
            for at, w in reads.items():
                want[at] = w
            np.testing.assert_allclose(row[:, c], want, err_msg=(t, c))


def test_conv_cut_taps_frac_equals_a_count_by_hand():
    """Of the 3 x tokens taps, those that reach back into an earlier
    document: 1 + 2 a document that begins two or more positions into the
    sequence, 1 + 1 one that begins at position 1 (its second tap back
    would leave the sequence: the convolution's own padding), none for
    the first."""
    ids = np.ones((2, 16), np.int32)
    ids[0, [0, 5, 6]] = 0      # begun at 5: positions 5 and 6 lose 1 + 2...
    ids[1, [1, 10]] = 0
    doc = jnp.cumsum(jnp.asarray(ids == 0, jnp.int32), axis=1)
    by_hand = 0
    for b in range(2):
        d = np.asarray(doc[b])
        for t in range(16):
            for j in (1, 2):
                by_hand += t - j >= 0 and d[t - j] != d[t]
    # row 0: t=5 (2), t=6 (2), t=7 (1); row 1: t=1 (1), t=2 (1), t=10 (2),
    # t=11 (1)
    assert by_hand == 5 + 5
    got = float(lfm2_moe.cut_taps_frac(doc, 3))
    assert got == pytest.approx(by_hand / (3 * 32))
    _, state = Lfm2Moe(ARCH).apply(
        {"params": weights()[0], "batch_stats": weights()[1]},
        jnp.asarray(np.concatenate([ids, ids], axis=1)), mutable=["counters"])
    assert float(state["counters"]["conv_cut_taps_frac"]) > 0
    assert float(lfm2_moe.cut_taps_frac(jnp.zeros((2, 16), jnp.int32),
                                        3)) == 0.0


# -------------------------------------------------------- through the step
def test_four_steps_of_the_program_follow_the_reference():
    cfg = load_config("lfm2_24b_a2b_ep8", overrides=TINY)
    model = build_model(cfg)
    assert family_of(model).name == "lfm2_moe"
    schedule = sched_lib.build_schedule(cfg.optim, cfg.train)
    state = init_state(model, cfg.optim, schedule, jax.random.PRNGKey(3),
                       sample_input(cfg))
    step = jax.jit(make_train_step(model, cfg.optim, schedule,
                                   cfg.data.num_classes))
    before = family.snapshot(state)
    assert before["moments"] == 0.0 and len(before["stats"]) == 4
    xs, ys = zip(*(tokens(seed, batch=8) for seed in range(4)))
    for x, y in zip(xs, ys):
        state, metrics = step(state, x, y)
    after = family.snapshot(state)
    assert after["moments"] > 0 and after["step"] == 4
    reference = family.follow(
        before, (np.stack(xs), np.stack(ys)),
        {"model": MODEL, "job": JOB}, 0)
    program = dict(after, **{k + "0": v for k, v in before.items()},
                   loss=float(metrics["loss"]),
                   gnorm=float(metrics["grad_norm"]), rows=4)
    read = family.readings(program, reference)
    assert read["loss_rel"] < 1e-6 and read["gnorm_rel"] < 1e-5
    assert read["head_cos"] < 1e-8 and read["mu_cos"] < 1e-8
    assert read["dparam_cos"] < 1e-4 and read["bias_gap"] < 0.01
    assert read["step_count"] == 0 and read["moments0"] == 0
    assert metrics["tokens"] == 8 * 32
    assert 0 < float(metrics["moe_here_frac"]) < 1
    assert 0 < float(metrics["conv_cut_taps_frac"]) < 0.5
    # AdamW decays every leaf of two or more axes: the tied embedding and
    # the filters' (d, K) leaf among them, no norm's weight
    assert {k for k, v in before["params"].items() if v.ndim >= 2} >= {
        "embed", "layer_0/conv/conv"}


def test_preset_states_the_published_widths_and_spells_its_program():
    cfg = load_config("lfm2_24b_a2b_ep8")
    arch = build_model(cfg).arch
    assert (arch.hidden, arch.heads, arch.kv_heads, arch.head_dim) == (
        2048, 32, 8, 64)
    assert (arch.dense_width, arch.expert_width, arch.conv_taps) == (
        11776, 1536, 3)
    assert (arch.experts_total, arch.experts_held, arch.top_k,
            arch.route_scale) == (64, (0, 8), 4, 1.0)
    assert arch.layers == LAYERS and arch.vocab_rows == 8192
    assert spell(cfg, {"data": 1, "model": 1}) == \
        "train|tokens4096_lfm2_dcmfmcmcmc_e8of64_bf16|mesh1x1|b2"
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "lfm2_24b_a2b_ep8.json")) as f:
        stated = json.load(f)
    assert stated["hidden_size"] == 2048 and stated["num_experts"] == 8
    assert stated["published"]["num_experts"] == 64
    assert stated["model"]["layers"] == list(LAYERS)
    for key in ("hidden", "heads", "kv_heads", "head_dim", "conv_taps",
                "dense_width", "expert_width", "experts_total", "top_k",
                "rope_theta", "route_scale", "balance_coeff"):
        assert stated["model"][key] == getattr(arch, key), key
    assert stated["model"]["rms_norm_eps"] == arch.eps
    assert stated["model"]["vocab_rows"] == arch.vocab_rows


@pytest.mark.parametrize("overrides,words", [
    (["optim.optimizer=momentum"], "adamw"),
    (["mesh.partition=zero1"], "zero1"),
    (["model.fused_blocks=true"], "ResNet kernels"),
    (["data.dataset=cifar10"], "feeds model 'afmoe', 'lfm2_moe'"),
])
def test_check_step_config_says_what_it_refuses(overrides, words):
    cfg = load_config("lfm2_24b_a2b_ep8", overrides=TINY + overrides)
    with pytest.raises(ValueError, match=words):
        check_step_config(cfg, 1)


def test_serving_refuses_the_family_and_says_what_it_lacks():
    from tpu_resnet.serve.infer import make_serve_infer

    cfg = load_config("lfm2_24b_a2b_ep8", overrides=TINY)
    with pytest.raises(NotImplementedError, match="conv layer's last"):
        make_serve_infer(cfg)


def test_flop_and_parameter_counts_agree_with_a_count_from_shapes():
    """The program's count, the benchmark's and a walk over the leaves'
    shapes: a matrix of the tree is met by every token once (an expert
    stack by top_k/total of them; the tied table once, as the head; a
    filter's (d, K) leaf too: K multiply-adds a channel; the norms'
    weights apart), plus attention's live entries."""
    cfg = load_config("lfm2_24b_a2b_ep8")
    arch = build_model(cfg).arch
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "lfm2_24b_a2b_ep8.json")) as f:
        stated = json.load(f)["model"]
    shapes = jax.eval_shape(lambda: Lfm2Moe(arch).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    sizes = {k: v.shape for k, v in flat(jax.tree_util.tree_map(
        lambda s: np.broadcast_to(np.int8(0), s.shape), shapes)).items()}
    assert sum(math.prod(s) for s in sizes.values()) \
        == stated["parameters"] == 469_284_992
    macs = 0.0
    for key, shape in sizes.items():
        if len(shape) == 2:
            macs += math.prod(shape)
        elif len(shape) == 3:
            macs += math.prod(shape) * arch.top_k / arch.experts_total
    macs += 2 * 32 * 64 * (4096 + 1) / 2          # one attention layer
    assert macs == 194_537_472
    want = 6 * macs * 4096
    assert abs(lfm2_moe.train_flops_per_sequence(arch, 4096) - want) \
        < 1e-9 * want
    assert abs(family.train_flops_per_example(stated) - want) < 1e-9 * want
    assert 4.78e12 < want < 4.79e12
    assert family.example(stated) == {"what": "packed sequence",
                                      "tokens": 4096}


def test_startup_events_name_every_layers_mixer_and_attentions_path():
    events = lfm2_moe.startup_events(Lfm2Moe(ARCH), load_config(
        "lfm2_24b_a2b_ep8", overrides=TINY))
    assert [r["mixer"] for r in events["token_mixers"]["layers"]] == [
        "conv", "attention", "conv", "conv", "conv"]
    (row,) = events["attention_path"]["layers"]
    assert row["layer"] == 1 and row["path"] == "scan"
    assert row["head_dim"] == 16 and "padded_to" not in row
    big = lfm2_moe.attention_paths(Arch(layers=LAYERS), 4096, "tpu", 1)
    assert big == [dict(layer=1, kind="moe_full", path="kernel",
                        inputs="fused", head_dim=64, key_blocks_visited=10,
                        key_blocks_total=16)]


# ------------------------------------------------------- through train()
def test_tiny_preset_trains_and_reports_its_counters(tmp_path):
    from tpu_resnet.train.loop import train

    rng = np.random.default_rng(0)
    ids = rng.integers(1, 128, 64 * 32 + 1)
    ids[::13] = 0
    write_tokens(str(tmp_path / "data"), ids)
    cfg = load_config("lfm2_24b_a2b_ep8", overrides=TINY + [
        f"data.data_dir={tmp_path}/data", f"train.train_dir={tmp_path}/run",
        "train.train_steps=20", "train.log_every=5",
        "train.summary_every=5", "train.steps_per_call=5",
        "train.checkpoint_every=10", "optim.schedule=constant",
        "train.memory_ledger=false", "train.comms_ledger=false"])
    state = train(cfg)
    assert int(state.step) == 20
    with open(tmp_path / "run" / "metrics.jsonl") as f:
        records = {r["step"]: r for r in map(json.loads, f)}
    assert records[20]["loss"] < records[5]["loss"]
    assert records[20]["tokens"] == 8 * 32
    assert records[20]["moe_dropped_frac"] == 0
    # a document every 13 ids: 3 cut taps a document, 3 taps a position
    assert records[20]["conv_cut_taps_frac"] == pytest.approx(
        3 / (3 * 13), rel=0.15)
    with open(tmp_path / "run" / "events.jsonl") as f:
        events = [json.loads(line) for line in f]
    said = {e["span"]: e for e in events
            if e.get("span") in ("token_mixers", "attention_path")}
    assert len(said) == 2
    assert [r["mixer"] for r in said["token_mixers"]["layers"]].count(
        "conv") == 4
