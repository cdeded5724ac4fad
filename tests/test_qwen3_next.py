"""The hybrid Gated DeltaNet / gated attention sparse-expert decoder
(models/qwen3_next.py) and its way through the trainer, on the CPU at a
tiny size (d 64; DeltaNet 2 key and 4 value heads of 16, filters of 4
taps, chunks of 8; attention 4/2 heads of 32 rotated over their first 8
columns; 16 experts top-4 of which 4 held, a gated shared one; a
vocabulary of 128; S 32), in float32 against the benchmark's plain
reference (benchmarks/reference/qwen3_next.py) and against counts by
hand."""

import dataclasses
import json
import math
import os

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core

from benchmarks.families import qwen3_next as family
from benchmarks.lib.harness import flat
from benchmarks.reference import afmoe as ref_numerics
from benchmarks.reference import qwen3_next as ref
from tpu_resnet.config import load_config
from tpu_resnet.data.tokens import write_tokens
from tpu_resnet.models import (build_model, family_of, qwen3_next,
                               sample_input, transformer)
from tpu_resnet.models.qwen3_next import Arch, Qwen3Next
from tpu_resnet.ops import attention_inputs as inputs_pass
from tpu_resnet.ops import gated_delta
from tpu_resnet.programs import spell
from tpu_resnet.train import schedule as sched_lib
from tpu_resnet.train.state import init_state
from tpu_resnet.train.step import (check_step_config, make_train_step,
                                   token_xent)

LAYERS = ("linear", "linear", "linear", "full")
TINY = ["qwen3_next.hidden=64", "qwen3_next.heads=4", "qwen3_next.kv_heads=2",
        "qwen3_next.head_dim=32", "qwen3_next.rotary_dim=8",
        "qwen3_next.key_heads=2", "qwen3_next.value_heads=4",
        "qwen3_next.key_dim=16", "qwen3_next.value_dim=16",
        "qwen3_next.expert_width=16", "qwen3_next.shared_width=16",
        "qwen3_next.experts_total=16", "qwen3_next.experts_first=4",
        "qwen3_next.experts_held=4", "qwen3_next.top_k=4",
        "data.seq_len=32", "data.vocab_size=128",
        "model.compute_dtype=float32", "train.global_batch_size=8",
        "mesh.data=1"]
ARCH = Arch(layers=LAYERS, hidden=64, heads=4, kv_heads=2, head_dim=32,
            rotary_dim=8, key_heads=2, value_heads=4, key_dim=16,
            value_dim=16, expert_width=16, shared_width=16, experts_total=16,
            experts_held=(4, 4), top_k=4, vocab_rows=128, attn_block=8,
            chunk=8, dtype=jnp.float32)
MODEL = dict(layers=list(LAYERS), hidden=64, heads=4, kv_heads=2,
             head_dim=32, rotary_dim=8, key_heads=2, value_heads=4,
             key_dim=16, value_dim=16, conv_taps=4, expert_width=16,
             shared_width=16, experts_total=16, experts_first=4,
             experts_held=4, top_k=4, vocab_rows=128, seq_len=32, chunk=8,
             rope_theta=1e7, rms_norm_eps=1e-6)
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


def weights(arch=ARCH, seed=1, norms=0.1):
    """Seeded parameters, the zero-centred norms' weights away from 0 so
    that ``1 + w`` differs from ``w`` and from 1."""
    p = Qwen3Next(arch).init(jax.random.PRNGKey(seed), tokens()[0])["params"]
    leaves, tree = jax.tree_util.tree_flatten_with_path(p)
    names = ["/".join(str(k.key) for k in path) for path, _ in leaves]
    nudged = [norms * jax.random.normal(jax.random.PRNGKey(i), x.shape)
              if name.endswith("norm/scale") else x
              for i, (name, (_, x)) in enumerate(zip(names, leaves))]
    return jax.tree_util.tree_unflatten(tree, nudged)


def as_reference(tree):
    return {k: jnp.asarray(v) for k, v in flat(tree).items()}


def worst(a, b):
    assert set(a) == set(b)
    return max(float(np.max(np.abs(np.asarray(a[k]) - np.asarray(b[k])))
                     / max(1e-3, float(np.max(np.abs(np.asarray(b[k]))))))
               for k in a)


# ------------------------------------------------- against the reference
@pytest.mark.parametrize("path", ["scan", "kernel"])
def test_forward_loss_and_gradients_match_the_reference(monkeypatch, path):
    """Logits, loss and every gradient, the recurrence by the scan and by
    the kernels under the interpreter."""
    monkeypatch.setattr(gated_delta, "recurrence_path", lambda *_: path)
    params = weights()
    x, y = tokens()
    assert params["head"].shape == (64, 128)

    def loss(p):
        logits, state = Qwen3Next(ARCH).apply({"params": p}, x,
                                              mutable=["counters"])
        return token_xent(logits, y), (logits, state)

    (got, (logits, state)), grads = jax.value_and_grad(
        loss, has_aux=True)(params)
    rp = as_reference(params)
    want, want_grads = jax.value_and_grad(
        lambda p: ref.forward_loss(p, x, y, MODEL))(rp)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    assert worst(flat(grads), want_grads) < 1e-4
    # the logits themselves, through the reference's blocks
    h = rp["embed"][x]
    for i, kind in enumerate(LAYERS):
        mixer, ffn = ref.halves(ref.of_layer(rp, i))
        h = ref.moe_block(ffn, ref.MIXERS[kind](mixer, h, x, MODEL, "none"),
                          MODEL, "none")
    want_logits = ref.mm(ref.norm(h, rp["final_norm/scale"], 1e-6),
                         [rp["head"]], "none")[0]
    np.testing.assert_allclose(logits, want_logits, atol=2e-5)
    # the reference's block-by-block gradients are its whole-graph ones
    by_block = ref.Programs(MODEL, "none").gradients(rp, x, y)
    assert abs(by_block[0] - float(want)) < 1e-5 * float(want)
    assert worst(by_block[1], want_grads) < 1e-5
    counters = flat(state["counters"])
    assert all(v == 0 for k, v in counters.items() if "dropped" in k)
    assert set(k.rsplit("/", 1)[-1] for k in counters) == set(
        qwen3_next.COUNTERS)


def test_remat_keeps_the_gradients():
    params = weights()
    x, y = tokens()

    def grads(arch):
        return jax.grad(lambda p: token_xent(Qwen3Next(arch).apply(
            {"params": p}, x, mutable=["counters"])[0], y))(params)

    plain, remat = grads(ARCH), grads(dataclasses.replace(ARCH, remat=True))
    assert worst(flat(remat), flat(plain)) < 1e-6


def kernel_calls(jaxpr):
    """How often each ``pallas_call`` stands in ``jaxpr``, nested jaxprs
    included (a remat's recomputation among them)."""
    calls = collections.Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls[eqn.params["name"]] += 1
        for v in eqn.params.values():
            for x in v if isinstance(v, (list, tuple)) else [v]:
                if isinstance(x, (core.Jaxpr, core.ClosedJaxpr)):
                    calls += kernel_calls(getattr(x, "jaxpr", x))
    return calls


def test_remat_on_the_kernel_path_computes_each_inverse_once(monkeypatch):
    """The tiny preset's gradient step (``model.remat`` on) holds one
    inverse kernel a DeltaNet layer: remat keeps the inverses and runs the
    forward kernel again, not the inverse's. Its gradients are those of
    the step without remat."""
    monkeypatch.setattr(gated_delta, "recurrence_path",
                        lambda *_: "kernel")
    cfg = load_config("qwen3_next_80b_a3b_ep16", overrides=TINY)
    assert cfg.model.remat
    model = build_model(cfg)
    schedule = sched_lib.build_schedule(cfg.optim, cfg.train)
    state = init_state(model, cfg.optim, schedule, jax.random.PRNGKey(3),
                       sample_input(cfg))
    step = make_train_step(model, cfg.optim, schedule, cfg.data.num_classes)
    calls = kernel_calls(jax.make_jaxpr(step)(state, *tokens(batch=8)).jaxpr)
    layers = LAYERS.count("linear")
    assert calls["gated_delta_fwd_inverse"] == layers
    assert calls["gated_delta_fwd"] == 2 * layers
    assert calls["gated_delta_bwd"] == layers
    params = weights()
    x, y = tokens()

    def grads(arch):
        return jax.grad(lambda p: token_xent(Qwen3Next(arch).apply(
            {"params": p}, x, mutable=["counters"])[0], y))(params)

    plain, remat = grads(ARCH), grads(dataclasses.replace(ARCH, remat=True))
    assert worst(flat(remat), flat(plain)) < 1e-6


def test_only_the_deltanet_mixer_keeps_the_inverses():
    """The mixer's remat policy is ``_KEEP`` and the inverses by name; the
    other three token families remat under ``_KEEP`` itself, which saves no
    such name, so their steps lower as they did."""
    from tpu_resnet.models import afmoe, lfm2_moe, sdar_moe

    assert afmoe._KEEP is lfm2_moe._KEEP is sdar_moe._KEEP \
        is transformer._KEEP
    eqn = jax.make_jaxpr(lambda x: jax.ad_checkpoint.checkpoint_name(
        x, gated_delta.INVERSE))(jnp.ones(3)).eqns[0]
    kept = [policy(eqn.primitive, *[v.aval for v in eqn.invars],
                   **eqn.params)
            for policy in (transformer._KEEP, qwen3_next._MIXER_KEEP)]
    assert kept == [False, True]


def test_the_shares_add_up_to_the_uncut_layer():
    """The parts that all the shares give (4 shares of 2 of 8 experts),
    the shared expert counted once, add up to what the uncut reference
    gives for the whole expert layer, and so do the gradients with respect
    to ``x`` and the expert matrices."""
    arch = dataclasses.replace(ARCH, experts_total=8, experts_held=(0, 8))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 64))
    cot = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 64))
    p = qwen3_next.ExpertLayer(arch).init(jax.random.PRNGKey(1),
                                          x)["params"]
    model = dict(MODEL, experts_total=8, experts_first=0, experts_held=8)
    stacks = ("gate", "up", "down")
    flat_p = {k: jnp.asarray(a) for k, a in flat(p).items()}

    def reference(x, experts):
        return ref.experts(dict(flat_p, **experts), x, model, "none")

    def share(x, experts, first):
        layer = qwen3_next.ExpertLayer(dataclasses.replace(
            arch, experts_held=(first, 2)))
        got, state = layer.apply(
            {"params": dict(p, **{k: experts[k][first:first + 2]
                                  for k in stacks})}, x,
            mutable=["counters"])
        assert float(state["counters"]["moe_dropped_frac"]) == 0.0
        return got

    def shares(x, experts):
        total = sum(share(x, experts, first) for first in range(0, 8, 2))
        # what every share computes alike, the gated shared expert, once
        alike = share(x, {k: jnp.zeros_like(v) for k, v in experts.items()},
                      0)
        return total - 3 * alike

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


def test_the_router_is_the_softmax_one_renormalised():
    """Softmax over all experts, the top-k of it, weights that add to 1;
    the same function SDAR's expert layer calls."""
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
    w = 0.02 * jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    chosen, weight = transformer.softmax_router(x, w, 4)
    s = jax.nn.softmax(x @ w, axis=-1)
    np.testing.assert_array_equal(chosen, jax.lax.top_k(s, 4)[1])
    np.testing.assert_allclose(jnp.sum(weight, -1), 1.0, rtol=1e-6)
    picked = jnp.take_along_axis(s, chosen, -1)
    np.testing.assert_allclose(weight, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)


# ---------------------------------------------------- the layer's pieces
def test_partial_rotary_is_a_rotation_of_the_first_columns():
    """``rotary(..., dims=8)`` on a head of 32: pairs ``(i, i + 4)`` of the
    first 8 columns turned by ``position * theta^(-2i / 8)``, written out
    as a 2 x 2 rotation; the other 24 columns passed as they are. A whole
    head is the rotation it was."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 32))
    got = np.asarray(transformer.rotary(x, 1e7, dims=8))
    xs = np.asarray(x)
    want = xs.copy()
    for i in range(4):
        ang = np.arange(12)[None, :, None] * 1e7 ** (-2 * i / 8)
        a, b = xs[..., i], xs[..., i + 4]
        want[..., i] = a * np.cos(ang) - b * np.sin(ang)
        want[..., i + 4] = b * np.cos(ang) + a * np.sin(ang)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_array_equal(got[..., 8:], xs[..., 8:])
    np.testing.assert_array_equal(transformer.rotary(x, 1e7, dims=32),
                                  transformer.rotary(x, 1e7))


@pytest.mark.parametrize("head_dim, rot", [(128, 32), (64, 16)],
                         ids=["kernel", "plain"])
def test_the_fused_input_pass_takes_a_partial_rotary(monkeypatch, head_dim,
                                                     rot):
    """The fused pass (a Pallas kernel at heads of 128 under the
    interpreter, plain JAX at 64) with a partial rotary gives the composed
    chain's norm, partial rotary, scale and layout, and its backward pass
    autodiff's of that chain."""
    b, s, h, kv = 2, 16, 4, 2
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(keys[0], (b, s, h, head_dim))
    k = jax.random.normal(keys[1], (b, s, kv, head_dim))
    v = jax.random.normal(keys[2], (b, s, kv, head_dim))
    qs = 1.0 + 0.1 * jax.random.normal(keys[3], (head_dim,))
    ks = 1.0 + 0.1 * jax.random.normal(keys[4], (head_dim,))
    cot = jax.random.normal(keys[5], (b, kv, h // kv, s, head_dim))

    def fused(q, k, qs, ks):
        out = inputs_pass.attention_inputs(q, k, v, qs, ks, (1e7, None),
                                           jnp.float32, 1e-6, rot)
        return jnp.sum(out[0] * cot) + jnp.sum(out[1] * cot[:, :, 0])

    def composed(q, k, qs, ks):
        q = transformer.rotary(transformer.rms_norm(q, qs, 1e-6), 1e7,
                               dims=rot) / math.sqrt(head_dim)
        k = transformer.rotary(transformer.rms_norm(k, ks, 1e-6), 1e7,
                               dims=rot)
        q = jnp.transpose(q.reshape(b, s, kv, h // kv, head_dim),
                          (0, 2, 3, 1, 4))
        k = jnp.transpose(k, (0, 2, 1, 3))
        return jnp.sum(q * cot) + jnp.sum(k * cot[:, :, 0])

    np.testing.assert_allclose(fused(q, k, qs, ks), composed(q, k, qs, ks),
                               rtol=1e-5)
    got = jax.grad(fused, argnums=(0, 1, 2, 3))(q, k, qs, ks)
    want = jax.grad(composed, argnums=(0, 1, 2, 3))(q, k, qs, ks)
    for a, w in zip(got, want):
        np.testing.assert_allclose(a, w, atol=2e-5 * float(jnp.max(
            jnp.abs(w))))


def test_heads_of_256_take_a_backward_tile_of_half_the_queries():
    """The fused backward kernel's tile of 1,024 queries outgrows the VMEM
    Mosaic allows at heads of 256; heads of 128 and 64 keep the tiles the
    other token families run."""
    from tpu_resnet.ops import attention
    assert attention.blocks_for(128) is attention.blocks_for(64) \
        is attention.BLOCKS
    wide = attention.blocks_for(256)
    assert wide.block_q_dkv == attention.BLOCKS.block_q_dkv // 2
    assert dataclasses.replace(wide, block_q_dkv=1024) == attention.BLOCKS


def test_the_norms_are_zero_centred_and_the_output_norm_gated():
    """Every layer norm, the final norm and attention's q/k norms start at
    0 and scale by ``1 + w``; the DeltaNet output's norm is a plain weight
    from 1, under ``silu(z)``."""
    p = Qwen3Next(ARCH).init(jax.random.PRNGKey(0), tokens()[0])["params"]
    leaves = flat(p)
    zeroed = [k for k in leaves if k.endswith("norm/scale")]
    assert len(zeroed) == 1 + 4 * 2 + 2
    assert all(not np.any(leaves[k]) for k in zeroed)
    gated = [k for k in leaves if k.endswith("linear_attn/norm")]
    assert len(gated) == 3 and all(np.all(leaves[k] == 1) for k in gated)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 64))
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (64,))
    got = qwen3_next.Norm(1e-6).apply({"params": {"scale": w}}, x)
    want = x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-6) \
        * (1 + w)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # the gated output norm: y = rms(o) * w * silu(z), the reference's too
    o = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 4, 16))
    z = jax.random.normal(jax.random.PRNGKey(4), (2, 5, 4, 16))
    wn = 1.0 + 0.2 * jax.random.normal(jax.random.PRNGKey(5), (16,))
    y = transformer.rms_norm(o, wn, 1e-6) * jax.nn.silu(z)
    by_hand = o / np.sqrt(np.mean(np.square(o), -1, keepdims=True) + 1e-6) \
        * wn * z / (1 + np.exp(-z))
    np.testing.assert_allclose(y, by_hand, rtol=1e-5)
    np.testing.assert_allclose(ref.rms(o, wn, 1e-6) * jax.nn.silu(z),
                               by_hand, rtol=1e-5)


# ------------------------------------------------------ packing and reach
def test_every_document_of_a_packed_sequence_gets_what_it_gets_alone():
    """DeltaNet layers (documents begun inside chunks of 8) and the
    attention layer both: the logits of a packed sequence are, document by
    document, those of each document fed alone (a chunk of one position
    there: a document alone is of any length)."""
    params = weights()
    rng = np.random.default_rng(3)
    ids = rng.integers(1, 128, (1, 32))
    starts = [0, 5, 6, 11, 19]          # documents of 5, 1, 5, 8 and 13
    ids[0, starts] = 0
    packed = np.asarray(Qwen3Next(ARCH).apply({"params": params},
                                              jnp.asarray(ids)))
    alone = Qwen3Next(dataclasses.replace(ARCH, chunk=1, attn_block=1))
    for lo, hi in zip(starts, starts[1:] + [32]):
        got = np.asarray(alone.apply({"params": params},
                                     jnp.asarray(ids[:, lo:hi])))
        np.testing.assert_allclose(packed[:, lo:hi], got, atol=3e-5,
                                   err_msg=f"document {lo}:{hi}")


def test_gdn_doc_chunks_frac_is_sown_from_the_batchs_documents():
    ids = np.ones((2, 32), np.int32)
    ids[0, [0, 8, 11]] = 0             # chunk 1 cut inside
    ids[1, [3, 30]] = 0                # chunks 0 and 3
    _, state = Qwen3Next(ARCH).apply({"params": weights()},
                                     jnp.asarray(ids), mutable=["counters"])
    assert float(state["counters"]["gdn_doc_chunks_frac"]) == 3 / 8


# -------------------------------------------------------- through the step
def test_four_steps_of_the_program_follow_the_reference():
    cfg = load_config("qwen3_next_80b_a3b_ep16", overrides=TINY)
    model = build_model(cfg)
    assert family_of(model).name == "qwen3_next"
    schedule = sched_lib.build_schedule(cfg.optim, cfg.train)
    state = init_state(model, cfg.optim, schedule, jax.random.PRNGKey(3),
                       sample_input(cfg))
    step = jax.jit(make_train_step(model, cfg.optim, schedule,
                                   cfg.data.num_classes))
    before = family.snapshot(state)
    assert before["moments"] == 0.0 and before["stats"] == {}
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
    assert read["head_cos"] < 1e-8 and read["mu_cos"] < 1e-7
    assert read["dparam_cos"] < 1e-4 and "bias_gap" not in read
    assert read["step_count"] == 0 and read["moments0"] == 0
    assert metrics["tokens"] == 8 * 32
    assert 0 < float(metrics["moe_here_frac"]) < 1
    assert 0 < float(metrics["gdn_doc_chunks_frac"]) <= 1
    # AdamW decays every leaf of two or more axes: the filters' (C, K)
    # leaf among them; no norm's weight, nor A_log or dt_bias
    two = {k for k, v in before["params"].items() if v.ndim >= 2}
    assert "layer_0/linear_attn/conv" in two
    assert not {k for k in before["params"] if "A_log" in k
                or "dt_bias" in k} & two


def test_preset_states_the_published_widths_and_spells_its_program():
    cfg = load_config("qwen3_next_80b_a3b_ep16")
    arch = build_model(cfg).arch
    assert (arch.hidden, arch.heads, arch.kv_heads, arch.head_dim,
            arch.rotary_dim) == (2048, 16, 2, 256, 64)
    assert (arch.key_heads, arch.value_heads, arch.key_dim, arch.value_dim,
            arch.conv_taps) == (16, 32, 128, 128, 4)
    assert (arch.expert_width, arch.shared_width, arch.experts_total,
            arch.experts_held, arch.top_k) == (512, 512, 512, (0, 32), 10)
    assert arch.layers == LAYERS and arch.vocab_rows == 18992
    assert (arch.eps, arch.rope_theta, arch.remat) == (1e-6, 1e7, True)
    assert spell(cfg, {"data": 1, "model": 1}) == \
        "train|tokens4096_qwen3next_lllf_e32of512_bf16_remat|mesh1x1|b2"
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "qwen3_next_80b_a3b_ep16.json")) as f:
        stated = json.load(f)
    assert stated["hidden_size"] == 2048 and stated["num_experts"] == 32
    assert stated["published"]["num_experts"] == 512
    assert stated["model"]["layers"] == list(LAYERS)
    for key in ("hidden", "heads", "kv_heads", "head_dim", "rotary_dim",
                "key_heads", "value_heads", "key_dim", "value_dim",
                "conv_taps", "expert_width", "shared_width", "experts_total",
                "top_k", "rope_theta", "chunk"):
        assert stated["model"][key] == getattr(arch, key), key
    assert arch.chunk == gated_delta.CHUNK
    assert stated["model"]["rms_norm_eps"] == arch.eps
    assert stated["model"]["vocab_rows"] == arch.vocab_rows


@pytest.mark.parametrize("overrides,words", [
    (["optim.optimizer=momentum"], "adamw"),
    (["mesh.partition=zero1"], "zero1"),
    (["model.fused_blocks=true"], "ResNet kernels"),
    (["data.seq_len=200"], "whole chunks of 128"),
    (["data.dataset=cifar10"], "'lfm2_moe', 'qwen3_next'"),
])
def test_check_step_config_says_what_it_refuses(overrides, words):
    cfg = load_config("qwen3_next_80b_a3b_ep16", overrides=TINY + overrides)
    with pytest.raises(ValueError, match=words):
        check_step_config(cfg, 1)


def test_serving_refuses_the_family():
    from tpu_resnet.serve.infer import make_serve_infer

    cfg = load_config("qwen3_next_80b_a3b_ep16", overrides=TINY)
    with pytest.raises(NotImplementedError, match="token model"):
        make_serve_infer(cfg)


def test_flop_and_parameter_counts_agree_with_a_count_from_shapes():
    """The program's count, the benchmark's and a walk over the leaves'
    shapes: a matrix of the tree is met by every token once (an expert
    stack by top_k/total of them; a filter's (C, K) leaf: K multiply-adds a
    channel; the norms' weights, A_log and dt_bias apart), plus the
    recurrence's 3 dk dv a value head and attention's live entries."""
    cfg = load_config("qwen3_next_80b_a3b_ep16")
    arch = build_model(cfg).arch
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "qwen3_next_80b_a3b_ep16.json")) as f:
        stated = json.load(f)["model"]
    shapes = jax.eval_shape(lambda: Qwen3Next(arch).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    sizes = {k: v.shape for k, v in flat(jax.tree_util.tree_map(
        lambda s: np.broadcast_to(np.int8(0), s.shape), shapes)).items()}
    assert sum(math.prod(s) for s in sizes.values()) \
        == stated["parameters"] == 625_667_136
    assert sum(math.prod(s) for k, s in sizes.items()
               if k.startswith("layer_0/")) == 138_582_208
    assert sum(math.prod(s) for k, s in sizes.items()
               if k.startswith("layer_3/")) == 132_127_232
    macs = 0.0
    for key, shape in sizes.items():
        if key == "embed":
            continue                                   # a lookup
        if len(shape) == 2:
            macs += math.prod(shape)
        elif len(shape) == 3:
            macs += math.prod(shape) * arch.top_k / arch.experts_total
    macs += 3 * 3 * 32 * 128 * 128                     # the recurrence
    macs += 2 * 16 * 256 * (4096 + 1) / 2              # one attention layer
    assert macs == pytest.approx(213_463_040, abs=1)
    want = 6 * macs * 4096
    assert abs(qwen3_next.train_flops_per_sequence(arch, 4096) - want) \
        < 1e-9 * want
    assert abs(family.train_flops_per_example(stated) - want) < 1e-9 * want
    assert 5.24e12 < want < 5.25e12
    assert family.example(stated) == {"what": "packed sequence",
                                      "tokens": 4096}


def test_startup_events_name_every_layers_mixer_and_paths():
    events = qwen3_next.startup_events(Qwen3Next(ARCH), load_config(
        "qwen3_next_80b_a3b_ep16", overrides=TINY))
    assert [r["mixer"] for r in events["token_mixers"]["layers"]] == [
        "gated_delta"] * 3 + ["attention"]
    assert [(r["path"], r["inverse"])
            for r in events["recurrence_path"]["layers"]] == [
        ("scan", "in_chunk")] * 3
    (row,) = events["attention_path"]["layers"]
    assert row["layer"] == 3 and row["path"] == "scan"
    big = Arch(layers=LAYERS)
    assert qwen3_next.recurrence_paths(big, 4096, "tpu", 1)[0] == dict(
        layer=0, kind="linear", path="kernel", chunk=128, chunks=32,
        inverse="once")
    assert qwen3_next.attention_paths(big, 4096, "tpu", 1)[0]["path"] == \
        "kernel"


# ------------------------------------------------------- through train()
def test_tiny_preset_trains_and_reports_its_counters(tmp_path):
    from tpu_resnet.train.loop import train

    rng = np.random.default_rng(0)
    ids = rng.integers(1, 128, 64 * 32 + 1)
    ids[::13] = 0
    write_tokens(str(tmp_path / "data"), ids)
    cfg = load_config("qwen3_next_80b_a3b_ep16", overrides=TINY + [
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
    # a document every 13 ids: every sequence of 32, one chunk, holds a
    # start after its first position
    assert records[20]["gdn_doc_chunks_frac"] == 1.0
    with open(tmp_path / "run" / "events.jsonl") as f:
        events = [json.loads(line) for line in f]
    said = {e["span"]: e for e in events if e.get("span") in (
        "token_mixers", "recurrence_path", "attention_path", "expert_path")}
    assert len(said) == 4
    assert [r["mixer"] for r in said["token_mixers"]["layers"]].count(
        "gated_delta") == 3
