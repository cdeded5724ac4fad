"""The sparse-expert transformer (models/afmoe.py) and the token path of
the trainer, on the CPU at a tiny size (d 64, heads 4/2 of 16, window 8,
S 32, 16 experts top-4, vocabulary 128; layers dense-sliding, 3 x
expert-sliding, expert-full), in float32 against the benchmark's plain
reference (benchmarks/reference/afmoe.py) and against explicit loops."""

import hashlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.families import afmoe as family
from benchmarks.lib.harness import flat
from benchmarks.reference import afmoe as ref
from tpu_resnet.config import load_config
from tpu_resnet.data import device_data
from tpu_resnet.data.tokens import load_tokens, write_tokens
from tpu_resnet.models import (afmoe, build_model, sample_input,
                               transformer)
from tpu_resnet.models.afmoe import Afmoe, Arch
from tpu_resnet.programs import spell
from tpu_resnet.train import schedule as sched_lib
from tpu_resnet.train.state import build_optimizer, init_state
from tpu_resnet.train.step import (check_step_config, make_train_step,
                                   token_xent)

LAYERS = ("dense_sliding", "moe_sliding", "moe_sliding", "moe_sliding",
          "moe_full")
TINY = ["afmoe.hidden=64", "afmoe.heads=4", "afmoe.kv_heads=2",
        "afmoe.head_dim=16", "afmoe.window=8", "afmoe.dense_width=96",
        "afmoe.expert_width=32", "afmoe.experts_total=16",
        "afmoe.experts_first=4", "afmoe.experts_held=4", "afmoe.top_k=4",
        "data.seq_len=32", "data.vocab_size=128",
        "model.compute_dtype=float32", "train.global_batch_size=8",
        "mesh.data=1"]
ARCH = Arch(layers=LAYERS, hidden=64, heads=4, kv_heads=2, head_dim=16,
            window=8, dense_width=96, expert_width=32, experts_total=16,
            experts_held=(4, 4), top_k=4, vocab_rows=128, attn_block=8,
            dtype=jnp.float32)
MODEL = dict(layers=list(LAYERS), hidden=64, heads=4, kv_heads=2,
             head_dim=16, window=8, dense_width=96, expert_width=32,
             experts_total=16, experts_first=4, experts_held=4, top_k=4,
             shared=1, vocab_rows=128, seq_len=32, rope_theta=10000.0,
             rms_norm_eps=1e-5, route_scale=2.826, balance_coeff=0.001)
JOB = dict(lr=dict(kind="warmup_cosine", base=3e-4, warmup=2000,
                   total=100_000),
           b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1, clip_norm=1.0)


@pytest.fixture(autouse=True)
def exact_products(monkeypatch):
    """Float32 to the last bits on both sides: a chip run's reference
    carries 16 bits a product (``ref.HIGH``), which its time limit forces
    and these sizes do not."""
    monkeypatch.setattr(ref, "TERMS", ref.HIGHEST)
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
    v = Afmoe(arch).init(jax.random.PRNGKey(seed), tokens()[0], train=False)
    stats = jax.tree_util.tree_map(
        lambda b: bias_scale * jax.random.normal(jax.random.PRNGKey(5),
                                                 b.shape), v["batch_stats"])
    return v["params"], stats


def as_reference(tree):
    return {k: jnp.asarray(v) for k, v in flat(tree).items()}


def worst(a, b):
    return max(float(np.max(np.abs(np.asarray(a[k]) - np.asarray(b[k]))))
               for k in a)


# ------------------------------------------------- against the reference
def test_forward_loss_and_gradients_match_the_reference():
    params, stats = weights()
    x, y = tokens()

    def loss(p):
        logits, state = Afmoe(ARCH).apply(
            {"params": p, "batch_stats": stats}, x, train=True,
            mutable=["batch_stats", "counters"])
        return token_xent(logits, y), state

    (got, state), grads = jax.value_and_grad(loss, has_aux=True)(params)
    rp, rb = as_reference(params), as_reference(stats)
    want, want_grads = jax.value_and_grad(
        lambda p: ref.forward_loss(p, rb, x, y, MODEL))(rp)
    assert abs(float(got) - float(want)) < 1e-6
    assert worst(flat(grads), want_grads) < 2e-6
    # the reference's layer-by-layer gradients are its whole-graph ones
    by_layer = ref.Programs(MODEL, "none").gradients(rp, rb, x, y)
    assert abs(by_layer[0] - float(want)) < 1e-6
    assert worst(by_layer[1], want_grads) < 2e-6
    # the bias update, and no gradient reaches the bias
    new = flat(state["batch_stats"])
    for key, n in by_layer[2].items():
        np.testing.assert_allclose(
            new[key], ref.bias_update(rb[key], n, 0.001), atol=1e-7)
    counters = flat(state["counters"])
    assert all(v == 0 for k, v in counters.items() if "dropped" in k)
    assert set(k.rsplit("/", 1)[1] for k in counters) == set(afmoe.COUNTERS)


def test_remat_keeps_the_gradients():
    """``model.remat`` (each layer's backward keeps its products and
    attention outputs and computes the rest again) changes no number."""
    import dataclasses

    params, stats = weights()
    x, y = tokens()

    def grads(arch):
        return jax.grad(lambda p: token_xent(Afmoe(arch).apply(
            {"params": p, "batch_stats": stats}, x, train=False), y))(params)

    assert worst(flat(grads(dataclasses.replace(ARCH, remat=True))),
                 flat(grads(ARCH))) < 1e-6
    cfg = load_config("trinity_mini_ep16", overrides=TINY + [
        "model.remat=true"])
    assert build_model(cfg).arch.remat is True


def test_the_bias_chooses_and_does_not_weigh_and_gets_no_gradient():
    layer = afmoe.ExpertLayer(32, 16, (0, 16), 4, 0, 2.826, 0.001, 2.0,
                              jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 64))
    v = layer.init(jax.random.PRNGKey(1), x, False)
    bias = jnp.zeros((16,)).at[3].set(10.0)   # expert 3 chosen by all

    def out(b, p=v["params"]):
        return layer.apply({"params": p, "batch_stats": {"expert_bias": b}},
                           x, False)

    scores = jax.nn.sigmoid(x.reshape(-1, 64) @ v["params"]["router"])
    chosen = jax.lax.top_k(scores + bias, 4)[1]
    assert bool(jnp.all(jnp.any(chosen == 3, axis=-1)))
    # the weights come from the scores alone: a second bias that chooses
    # the same experts gives the same result
    same_choice = bias.at[3].set(20.0)
    np.testing.assert_allclose(out(bias), out(same_choice), atol=1e-6)
    assert float(jnp.max(jnp.abs(out(bias) - out(jnp.zeros(16))))) > 1e-3
    grad = jax.grad(lambda b: jnp.sum(out(b) ** 2))(bias)
    assert float(jnp.max(jnp.abs(grad))) == 0.0


@pytest.mark.parametrize("slack", [2.0, 0.25])
def test_the_shares_add_up_to_the_uncut_layer(slack):
    """Every share's routed part plus the shared expert once is what the
    uncut reference gives for the whole layer, and so are the gradients
    with respect to ``x`` and the three expert matrices; at slack 2 the
    buffer holds every share's assignments, at 0.25 a quarter of an even
    total, and the rest goes through the tiers beyond it."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 64))
    cot = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 64))
    whole = afmoe.ExpertLayer(32, 16, (0, 16), 4, 1, 2.826, 0.001, 2.0,
                              jnp.float32)
    p = whole.init(jax.random.PRNGKey(1), x, False)["params"]
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    model = dict(MODEL, experts_first=0, experts_held=16)
    stacks = ("gate", "up", "down")

    flat_p = {k: jnp.asarray(a) for k, a in flat(p).items()}

    def reference(x, experts):
        return ref.experts(dict(flat_p, **experts), bias, x, model,
                           "none")[0]

    def shares(x, experts):
        shared = afmoe.SwiGLU(32, jnp.float32).apply(
            {"params": p["shared"]}, x)
        total, dropped = shared, []
        for first in range(0, 16, 4):
            share = afmoe.ExpertLayer(32, 16, (first, 4), 4, 1, 2.826,
                                      0.001, slack, jnp.float32)
            cut = dict(p, **{k: experts[k][first:first + 4]
                             for k in stacks})
            got, state = share.apply(
                {"params": cut, "batch_stats": {"expert_bias": bias}}, x,
                False, mutable=["counters"])
            dropped.append(state["counters"]["moe_dropped_frac"])
            assert transformer.buffer_rows(64, 4, 4, 16, slack, 8) == (
                128 if slack == 2.0 else 16)
            total = total + got - shared
        return total, dropped

    experts = {k: p[k] for k in stacks}
    got, dropped = shares(x, experts)
    assert all(float(d) == 0.0 for d in dropped)
    np.testing.assert_allclose(got, reference(x, experts), atol=2e-5)
    got_grads = jax.grad(lambda x, e: jnp.sum(shares(x, e)[0] * cot),
                         argnums=(0, 1))(x, experts)
    want_grads = jax.grad(lambda x, e: jnp.sum(reference(x, e) * cot),
                          argnums=(0, 1))(x, experts)
    np.testing.assert_allclose(got_grads[0], want_grads[0], atol=2e-5)
    for k in stacks:
        np.testing.assert_allclose(got_grads[1][k], want_grads[1][k],
                                   atol=2e-5, err_msg=k)


# ------------------------------------------------ against explicit loops
def loop_attention(q, k, v, doc, window):
    """Query by query, key by key."""
    b, s, kv, g, d = q.shape
    out = np.zeros((b, s, kv, g, d))
    for bi in range(b):
        for i in range(s):
            keys = [j for j in range(i + 1) if doc[bi, j] == doc[bi, i]
                    and (not window or i - j < window)]
            for h in range(kv):
                for gi in range(g):
                    sc = np.array([q[bi, i, h, gi] @ k[bi, j, h]
                                   for j in keys]) / math.sqrt(d)
                    p = np.exp(sc - sc.max())
                    p /= p.sum()
                    out[bi, i, h, gi] = sum(
                        pj * v[bi, j, h] for pj, j in zip(p, keys))
    return out


@pytest.mark.parametrize("window,block", [(8, 8), (0, 8), (8, 16), (5, 32)])
def test_masks_against_loops(window, block):
    rng = np.random.default_rng(3)
    q = rng.normal(size=(2, 32, 2, 2, 16)).astype(np.float32)
    k = rng.normal(size=(2, 32, 2, 16)).astype(np.float32)
    v = rng.normal(size=(2, 32, 2, 16)).astype(np.float32)
    doc = np.cumsum(rng.random((2, 32)) < 0.15, axis=1).astype(np.int32)
    got = afmoe.blocked_attention(jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v), jnp.asarray(doc), window,
                                  block, jnp.float32)
    np.testing.assert_allclose(got, loop_attention(q, k, v, doc, window),
                               atol=2e-5)


def test_rotary_on_sliding_layers_only():
    """A full layer carries no position: with one document and no window
    in reach, moving a sequence's tokens moves its outputs with them; a
    sliding layer's do not. And rotary against the explicit rotation."""
    x = np.random.default_rng(0).normal(size=(1, 6, 1, 8)).astype(np.float32)
    got = np.asarray(afmoe.rotary(jnp.asarray(x), 10000.0))
    for pos in range(6):
        for i in range(4):
            ang = pos * 10000.0 ** (-2 * i / 8)
            a, b = x[0, pos, 0, i], x[0, pos, 0, i + 4]
            np.testing.assert_allclose(
                got[0, pos, 0, [i, i + 4]],
                [a * math.cos(ang) - b * math.sin(ang),
                 b * math.cos(ang) + a * math.sin(ang)], atol=1e-5)
    h = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 64))
    doc = jnp.zeros((1, 8), jnp.int32)
    for window, moves in ((0, True), (8, False)):
        attn = afmoe.Attention(4, 2, 16, window, 10000.0, 1e-5, 8,
                               jnp.float32)
        v = attn.init(jax.random.PRNGKey(1), h, doc)
        last = attn.apply(v, h, doc)[0, -1]
        # the last query sees every key; reverse the keys before it
        swapped = jnp.concatenate([h[:, 6::-1], h[:, 7:]], axis=1)
        same = float(jnp.max(jnp.abs(attn.apply(v, swapped, doc)[0, -1]
                                     - last))) < 1e-5
        assert same == moves


# ----------------------------------------------------------- the optimizer
def test_adamw_decay_mask_and_clip_against_the_written_update():
    cfg = load_config("trinity_mini_ep16",
                      overrides=TINY + ["optim.schedule=constant"])
    tx = build_optimizer(cfg.optim, sched_lib.build_schedule(cfg.optim,
                                                             cfg.train))
    rng = np.random.default_rng(0)
    params = {"matrix": jnp.asarray(rng.normal(size=(6, 5)), jnp.float32),
              "stack": jnp.asarray(rng.normal(size=(2, 3, 4)), jnp.float32),
              "scale": jnp.asarray(rng.normal(size=(5,)), jnp.float32)}
    state = tx.init(params)
    mu = {k: jnp.zeros_like(v) for k, v in params.items()}
    nu = {k: jnp.zeros_like(v) for k, v in params.items()}
    want = dict(params)
    for t in (1, 2, 3):
        grads = {k: jnp.asarray(rng.normal(size=v.shape) * 3, jnp.float32)
                 for k, v in params.items()}
        updates, state = tx.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        gnorm = math.sqrt(sum(float(jnp.sum(g * g)) for g in grads.values()))
        assert gnorm > 1.0   # the clip is at work
        for k in want:
            want[k], mu[k], nu[k] = ref.adamw_leaf(
                want[k], grads[k], mu[k], nu[k], min(1.0, 1.0 / gnorm),
                3e-4, 0.9, 0.95, 1e-8, 0.1 if want[k].ndim >= 2 else 0.0, t)
        assert worst(params, want) < 1e-7
    # without the decay a matrix would have moved otherwise, a scale not
    no_decay = build_optimizer(
        load_config("trinity_mini_ep16", overrides=TINY + [
            "optim.weight_decay=0.0"]).optim, lambda _: 3e-4)
    # the preset's rate: a linear warm-up from 0, as the reference has it
    warm = sched_lib.build_schedule(load_config("trinity_mini_ep16").optim,
                                    load_config("trinity_mini_ep16").train)
    for count in (0, 1, 7, 1999, 2000, 51_000, 100_000):
        assert abs(float(warm(count)) - ref.learning_rate(JOB, count)) < 1e-10
    assert ref.learning_rate(JOB, 0) == 0 and float(warm(2000)) == \
        pytest.approx(3e-4)
    one = {k: jnp.ones_like(v) for k, v in params.items()}
    a = tx.update(one, tx.init(params), params)[0]
    b = no_decay.update(one, no_decay.init(params), params)[0]
    assert float(jnp.max(jnp.abs(a["scale"] - b["scale"]))) == 0.0
    assert float(jnp.max(jnp.abs(a["matrix"] - b["matrix"]))) > 1e-6
    assert float(jnp.max(jnp.abs(a["stack"] - b["stack"]))) > 1e-6


def test_ten_steps_of_the_program_follow_the_reference():
    cfg = load_config("trinity_mini_ep16", overrides=TINY)
    model = build_model(cfg)
    schedule = sched_lib.build_schedule(cfg.optim, cfg.train)
    state = init_state(model, cfg.optim, schedule, jax.random.PRNGKey(3),
                       sample_input(cfg))
    step = jax.jit(make_train_step(model, cfg.optim, schedule,
                                   cfg.data.num_classes))
    before = family.snapshot(state)
    assert before["moments"] == 0.0
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
    assert float(metrics["moe_load_max_over_mean"]) >= 1


# The parent's numbers (commit bef082f, before the dispatch, attention by
# path, RMSNorm and the rotary embedding moved to models/transformer.py):
# the same program gives them to the digit.
PARENTS = [
    dict(loss=4.846383094787598, grad_norm=2.5898404121398926,
         moe_here_frac=0.214111328125, precision=0.03125,
         moe_load_max_over_mean=1.8290661573410034,
         moe_rows_filled_frac=0.42822265625, learning_rate=0.0),
    dict(loss=4.844571113586426, grad_norm=2.623225688934326,
         moe_here_frac=0.203857421875, precision=0.015625,
         moe_load_max_over_mean=1.8786640167236328,
         moe_rows_filled_frac=0.40771484375,
         learning_rate=1.50000019516483e-07),
    dict(loss=4.828547954559326, grad_norm=2.678814172744751,
         moe_here_frac=0.215576171875, precision=0.02734375,
         moe_load_max_over_mean=1.6989960670471191,
         moe_rows_filled_frac=0.43115234375,
         learning_rate=3.00000039032966e-07),
]


def test_the_tiny_program_gives_the_parents_numbers_to_the_digit():
    cfg = load_config("trinity_mini_ep16", overrides=TINY)
    model = build_model(cfg)
    schedule = sched_lib.build_schedule(cfg.optim, cfg.train)
    state = init_state(model, cfg.optim, schedule, jax.random.PRNGKey(3),
                       sample_input(cfg))
    names = sorted("/".join(p.key for p in path) for path, _ in
                   jax.tree_util.tree_leaves_with_path(state.params))
    assert len(names) == 89 and hashlib.sha256(
        "\n".join(names).encode()).hexdigest() == (
        "d624dcb14fb95a60a5e29f1abbb92e65d5f7bf7b09bbf0bf638e5d9e14334dca")
    step = jax.jit(make_train_step(model, cfg.optim, schedule,
                                   cfg.data.num_classes))
    for seed, want in enumerate(PARENTS):
        state, metrics = step(state, *tokens(seed, batch=8))
        got = {k: float(v) for k, v in metrics.items()}
        assert got == dict(want, tokens=256.0, moe_dropped_frac=0.0,
                           moe_overflow_frac=0.0), (seed, got)
    total = sum(float(jnp.sum(jnp.abs(leaf)))
                for leaf in jax.tree_util.tree_leaves(state.params))
    assert total == pytest.approx(5383.480966567993, rel=1e-9)


# The parent's ``expert_bias`` after the same three steps (commit 5608166,
# before the sigmoid router and its bias update moved to
# models/transformer.py, where ``lfm2_moe`` calls them too).
PARENTS_BIAS = {
    "layer_1/moe/expert_bias": (
        [0, 1, 2, 3, 4, 6, 8, 9, 12, 14, 15], 0.0018749998416751623,
        -0.004124999977648258),
    "layer_4/moe/expert_bias": (
        [0, 2, 3, 4, 5, 6, 8, 12, 13, 15], 0.0022499999031424522,
        -0.003750000149011612),
}


def test_the_moved_router_gives_the_parents_bias_to_the_digit():
    cfg = load_config("trinity_mini_ep16", overrides=TINY)
    model = build_model(cfg)
    schedule = sched_lib.build_schedule(cfg.optim, cfg.train)
    state = init_state(model, cfg.optim, schedule, jax.random.PRNGKey(3),
                       sample_input(cfg))
    step = jax.jit(make_train_step(model, cfg.optim, schedule,
                                   cfg.data.num_classes))
    for seed in range(3):
        state, _ = step(state, *tokens(seed, batch=8))
    bias = flat(state.batch_stats)
    assert sorted(bias) == [f"layer_{i}/moe/expert_bias" for i in (1, 2, 3,
                                                                    4)]
    for key, (up, high, low) in PARENTS_BIAS.items():
        want = [high if i in up else low for i in range(16)]
        assert [float(b) for b in bias[key]] == want, key
    assert sum(float(np.sum(np.abs(b.astype(np.float64))))
               for b in bias.values()) == 0.1557499974151142
    # one router, one bias rule in the tree: the family calls the shared
    assert afmoe.sigmoid_router is transformer.sigmoid_router
    assert afmoe.balanced_bias is transformer.balanced_bias


# --------------------------------------------------------- data and config
def test_token_file_is_cut_into_consecutive_sequences(tmp_path):
    ids = np.arange(3 * 5 + 2) % 7
    write_tokens(str(tmp_path), ids)
    cfg = load_config("trinity_mini_ep16", overrides=[
        "data.seq_len=5", "data.vocab_size=7",
        f"data.data_dir={tmp_path}"])
    x, y = load_tokens(cfg.data)
    assert x.shape == y.shape == (3, 5) and x.dtype == np.int32
    np.testing.assert_array_equal(x.reshape(-1), ids[:15])
    np.testing.assert_array_equal(y.reshape(-1), ids[1:16])
    with pytest.raises(ValueError, match="outside"):
        cfg.data.vocab_size = 6
        load_tokens(cfg.data)


def test_device_dataset_takes_labels_a_position():
    from tpu_resnet import parallel

    mesh = parallel.create_mesh(None, jax.devices()[:1])
    x = np.arange(12 * 5, dtype=np.int32).reshape(12, 5)
    ds = device_data.DeviceDataset(mesh, x, x + 1, batch=4, seed=3)
    ds.ensure_epoch(0)
    assert ds.images.shape == ds.labels.shape == (3, 4, 5)
    np.testing.assert_array_equal(np.asarray(ds.images) + 1,
                                  np.asarray(ds.labels))
    rows = np.asarray(ds.images).reshape(12, 5)
    assert sorted(map(tuple, rows)) == sorted(map(tuple, x))
    ds.ensure_epoch(1)
    assert not np.array_equal(np.asarray(ds.images).reshape(12, 5), rows)


def test_preset_states_the_published_widths_and_spells_its_program():
    cfg = load_config("trinity_mini_ep16")
    arch = build_model(cfg).arch
    assert (arch.hidden, arch.heads, arch.kv_heads, arch.head_dim) == (
        2048, 32, 4, 128)
    assert (arch.dense_width, arch.expert_width, arch.window) == (
        6144, 1024, 2048)
    assert (arch.experts_total, arch.experts_held, arch.top_k,
            arch.shared) == (128, (0, 8), 8, 1)
    assert arch.layers == LAYERS and arch.vocab_rows == 25024
    assert spell(cfg, {"data": 1, "model": 1}) == \
        "train|tokens4096_afmoe5l_e8of128_bf16|mesh1x1|b2"
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "trinity_mini_ep16.json")) as f:
        stated = json.load(f)
    assert stated["hidden_size"] == 2048 and stated["num_experts"] == 8
    assert stated["published"]["num_experts"] == 128
    for key, field in (("hidden", "hidden"), ("dense_width", "dense_width"),
                       ("expert_width", "expert_width"),
                       ("window", "window"), ("top_k", "top_k")):
        assert stated["model"][key] == getattr(arch, field)


@pytest.mark.parametrize("overrides,words", [
    (["optim.optimizer=momentum"], "adamw"),
    (["mesh.partition=zero1"], "zero1"),
    (["optim.use_pallas_xent=on"], "never consults"),
    (["optim.label_smoothing=0.1"], "label_smoothing"),
    (["model.name=resnet"], "feeds model 'afmoe'"),
    (["data.dataset=cifar10"], "feeds model 'afmoe'"),
])
def test_check_step_config_says_what_it_refuses(overrides, words):
    cfg = load_config("trinity_mini_ep16", overrides=TINY + overrides)
    with pytest.raises(ValueError, match=words):
        check_step_config(cfg, 1)


def test_evaluation_serving_and_the_host_edge_refuse_a_token_model():
    from tpu_resnet.evaluation.evaluator import build_eval_step
    from tpu_resnet.serve.infer import make_serve_infer

    cfg = load_config("trinity_mini_ep16", overrides=TINY)
    with pytest.raises(NotImplementedError, match="evaluation"):
        build_eval_step(cfg, None)
    with pytest.raises(NotImplementedError, match="serving"):
        make_serve_infer(cfg)
    cfg.data.device_resident = "off"
    with pytest.raises(ValueError, match="host data engine"):
        device_data.should_use(cfg.data)
    sgd = load_config("smoke", overrides=["optim.grad_clip_norm=1.0"])
    with pytest.raises(ValueError, match="grad_clip_norm"):
        check_step_config(sgd, 1)


# ------------------------------------------------------------------ FLOPs
def test_flop_counts_agree_with_a_count_from_shapes():
    """The program's count, the benchmark's and a walk over the leaves'
    shapes: a matrix of the tree is met by every token once (an expert
    stack by top_k/total of them, the router and the norms' weights
    apart), plus attention's live entries."""
    cfg = load_config("trinity_mini_ep16")
    arch = build_model(cfg).arch
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "trinity_mini_ep16.json")) as f:
        stated = json.load(f)["model"]
    shapes = jax.eval_shape(lambda: Afmoe(arch).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert sum(math.prod(s.shape) for s in jax.tree_util.tree_leaves(
        shapes)) == stated["parameters"] == 504_147_200
    macs = 0.0
    for key, leaf in flat(jax.tree_util.tree_map(
            lambda s: np.zeros((), np.int8) if not s.shape else
            np.broadcast_to(np.int8(0), s.shape), shapes)).items():
        if leaf.ndim == 2 and key != "embed":
            macs += leaf.size
        elif leaf.ndim == 3:
            macs += leaf.size * arch.top_k / arch.experts_total
    live = {0: (4096 + 1) / 2,
            2048: (2048 * 2049 / 2 + 2048 * 2048) / 4096}
    for kind in arch.layers:
        macs += 2 * 32 * 128 * live[2048 if kind.endswith("sliding") else 0]
    want = 6 * macs * 4096
    assert abs(afmoe.train_flops_per_sequence(arch, 4096) - want) < 1e-6 * want
    assert abs(family.train_flops_per_example(stated) - want) < 1e-6 * want
    assert 8.13e12 < want < 8.15e12
    assert family.example(stated) == {"what": "packed sequence",
                                      "tokens": 4096}


# ------------------------------------------------------- through train()
def test_tiny_preset_trains_saves_and_resumes_with_the_same_loss(tmp_path):
    from tpu_resnet.train.loop import train

    rng = np.random.default_rng(0)
    ids = rng.integers(1, 128, 64 * 32 + 1)
    ids[::13] = 0
    write_tokens(str(tmp_path / "data"), ids)

    def cfg_for(name):
        return load_config("trinity_mini_ep16", overrides=TINY + [
            f"data.data_dir={tmp_path}/data",
            f"train.train_dir={tmp_path}/{name}", "train.train_steps=20",
            "train.log_every=5", "train.summary_every=5",
            "train.steps_per_call=5", "train.checkpoint_every=10",
            "optim.schedule=constant",     # past the warm-up: it learns
            "train.memory_ledger=false", "train.comms_ledger=false"])

    def records(name):
        with open(tmp_path / name / "metrics.jsonl") as f:
            return {r["step"]: r for r in map(json.loads, f)}

    state = train(cfg_for("whole"))
    assert int(state.step) == 20
    whole = records("whole")
    assert whole[20]["loss"] < whole[5]["loss"]       # it learns something
    assert whole[20]["tokens"] == 8 * 32
    assert whole[20]["moe_dropped_frac"] == 0
    assert whole[20]["mfu"] if "mfu" in whole[20] else True
    with open(tmp_path / "whole" / "flops.json") as f:
        entry = next(iter(json.load(f)["entries"].values()))
    assert entry["flops_source"] == "analytic" and entry["flops_per_step"] > 0

    assert int(train(cfg_for("parts"), max_steps=10).step) == 10
    resumed = train(cfg_for("parts"))                 # from the save at 10
    assert int(resumed.step) == 20
    parts = records("parts")
    for step in (15, 20):
        assert abs(parts[step]["loss"] - whole[step]["loss"]) < 1e-5
    np.testing.assert_allclose(
        flat(resumed.batch_stats)["layer_1/moe/expert_bias"],
        flat(state.batch_stats)["layer_1/moe/expert_bias"], atol=1e-7)
