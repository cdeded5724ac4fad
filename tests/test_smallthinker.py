"""The decoder that routes before attention over window and global layers
(models/smallthinker.py) and its way through the trainer, on the CPU at a
tiny size (d 64; 14 query heads over 2 key/value heads of 16, groups of
7; a global layer and a window layer of 8; 16 ReGLU experts top-3 of which
4 held; a vocabulary of 128; S 32 with documents longer than the window),
in float32 against the benchmark's plain reference
(benchmarks/reference/smallthinker.py) and against counts by hand; and the
two pieces of shared code it grew: the expert layer's activation and
attention's inputs without a q/k norm."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import smallthinker as family
from benchmarks.lib.harness import flat
from benchmarks.reference import afmoe as ref_numerics
from benchmarks.reference import smallthinker as ref
from tpu_resnet.config import load_config
from tpu_resnet.models import build_model, family_of, smallthinker
from tpu_resnet.models import transformer
from tpu_resnet.models.smallthinker import Arch, SmallThinker
from tpu_resnet.ops import attention_inputs as inputs_pass
from tpu_resnet.programs import spell
from tpu_resnet.train.step import check_step_config, token_xent

LAYERS = ("global", "window")
PUBLISHED = ("global", "window", "window", "window")
TINY = ['smallthinker.layers=["global","window"]',
        "smallthinker.hidden=64", "smallthinker.heads=14",
        "smallthinker.kv_heads=2", "smallthinker.head_dim=16",
        "smallthinker.window=8", "smallthinker.expert_width=16",
        "smallthinker.experts_total=16", "smallthinker.experts_first=4",
        "smallthinker.experts_held=4", "smallthinker.top_k=3",
        "data.seq_len=32", "data.vocab_size=128",
        "model.compute_dtype=float32", "train.global_batch_size=8",
        "mesh.data=1"]
ARCH = Arch(layers=LAYERS, hidden=64, heads=14, kv_heads=2, head_dim=16,
            window=8, expert_width=16, experts_total=16,
            experts_held=(4, 4), top_k=3, vocab_rows=128, attn_block=8,
            dtype=jnp.float32)
MODEL = dict(layers=list(LAYERS), hidden=64, heads=14, kv_heads=2,
             head_dim=16, window=8, expert_width=16, experts_total=16,
             experts_first=4, experts_held=4, top_k=3, vocab_rows=128,
             seq_len=32, rope_theta=1.5e6, rms_norm_eps=1e-6)


@pytest.fixture(autouse=True)
def exact_products(monkeypatch):
    """Float32 to the last bits on both sides: a chip run's reference
    carries 16 bits a product (``HIGH``), which its time limit forces and
    these sizes do not. The reference's blocks of queries are cut to 8, so
    that a window layer's block takes its keys from before the block."""
    monkeypatch.setattr(ref_numerics, "TERMS", ref_numerics.HIGHEST)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    with jax.default_matmul_precision("highest"):
        yield


def tokens(seed=0, batch=2, length=32):
    """Packed ids whose documents (up to 17 long) outgrow the window of
    8."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, 128, (batch, length + 1))
    ids[0, [0, 3, 20]] = 0
    ids[1:, [5, 22]] = 0
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


def weights(arch=ARCH, seed=1, norms=0.1):
    """Seeded parameters, the norms' weights away from 1."""
    p = SmallThinker(arch).init(jax.random.PRNGKey(seed),
                                tokens()[0])["params"]
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x + norms * jax.random.normal(
            jax.random.PRNGKey(len(path)), x.shape)
        if path[-1].key == "scale" else x, p)


def as_reference(tree):
    return {k: jnp.asarray(v) for k, v in flat(tree).items()}


def worst(a, b):
    assert set(a) == set(b)
    return max(float(np.max(np.abs(np.asarray(a[k]) - np.asarray(b[k])))
                     / max(1e-3, float(np.max(np.abs(np.asarray(b[k]))))))
               for k in a)


# ------------------------------------------------- against the reference
def test_forward_loss_and_gradients_match_the_reference():
    """Logits, loss and every gradient on packed documents that cross the
    window. Float32 on both sides: what is left is the order of the sums
    (1e-5 of the loss, 1e-4 of each gradient's largest entry); the program
    in bf16 parts from it by 1e-3 and more."""
    params = weights()
    x, y = tokens()
    assert params["head"].shape == (64, 128)
    assert "q_norm" not in params["layer_1"]["attn"]

    def loss(p):
        logits, state = SmallThinker(ARCH).apply({"params": p}, x,
                                                 mutable=["counters"])
        return token_xent(logits, y), (logits, state)

    (got, (logits, state)), grads = jax.jit(jax.value_and_grad(
        loss, has_aux=True))(params)
    rp = as_reference(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.forward_loss(p, x, y, MODEL)))(rp)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    assert worst(flat(grads), want_grads) < 1e-4
    @jax.jit
    def reference_logits(rp):
        h = rp["embed"][x]
        for i, kind in enumerate(LAYERS):
            h = ref.layer(ref.of_layer(rp, i), h, x,
                          ref.window_of(kind, MODEL), MODEL, "none")
        return ref.mm(ref.rms(h, rp["final_norm/scale"], 1e-6),
                      [rp["head"]], "none")[0]

    want_logits = reference_logits(rp)
    np.testing.assert_allclose(logits, want_logits, atol=2e-5)
    # the reference's layer-by-layer gradients are its whole-graph ones
    by_layer = ref.Programs(MODEL, "none").gradients(rp, x, y)
    assert abs(by_layer[0] - float(want)) < 1e-5 * float(want)
    assert worst(by_layer[1], want_grads) < 1e-5
    # the program in bf16 products parts from the reference by more than
    # the logits' tolerance
    bf16 = dataclasses.replace(ARCH, dtype=jnp.bfloat16)
    low = jax.jit(lambda p: SmallThinker(bf16).apply(
        {"params": p}, x, mutable=["counters"])[0])(params)
    assert float(jnp.max(jnp.abs(low - want_logits))) > 10 * 2e-5
    counters = flat(state["counters"])
    assert all(v == 0 for k, v in counters.items() if "dropped" in k)
    assert set(k.rsplit("/", 1)[-1] for k in counters) == set(
        smallthinker.COUNTERS)


def test_remat_keeps_the_gradients():
    params = weights()
    x, y = tokens()

    def grads(arch):
        return jax.jit(jax.grad(lambda p: token_xent(SmallThinker(
            arch).apply({"params": p}, x, mutable=["counters"])[0], y)))(
                params)

    plain, remat = grads(ARCH), grads(dataclasses.replace(ARCH, remat=True))
    assert worst(flat(remat), flat(plain)) < 1e-6


def test_the_shares_add_up_to_the_uncut_layer():
    """The parts that the 8 shares give (2 of 16 experts each, under the
    one routing computed before attention), added up, are what the uncut
    reference gives for the whole expert layer, and so are the gradients
    with respect to the layer's input and the expert matrices."""
    arch = dataclasses.replace(ARCH, experts_total=16, experts_held=(0, 16))
    model = dict(MODEL, experts_first=0, experts_held=16)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    y = jax.random.normal(keys[0], (64, 64))
    routed = jax.random.normal(keys[1], (64, 64))
    cot = jax.random.normal(keys[2], (64, 64))
    router = 0.3 * jax.random.normal(keys[3], (64, 16))
    chosen, weight = transformer.softmax_router(routed, router, 3)
    p = smallthinker.ExpertLayer(arch).init(jax.random.PRNGKey(1), y, chosen,
                                            weight)["params"]
    stacks = ("gate", "up", "down")

    def reference(y, experts):
        return ref.experts(experts, y, ref.route(routed, router, model),
                           model, "none")

    def shares(y, experts):
        total = 0.0
        for first in range(0, 16, 2):
            layer = smallthinker.ExpertLayer(dataclasses.replace(
                arch, experts_held=(first, 2)))
            got, state = layer.apply(
                {"params": {k: experts[k][first:first + 2] for k in stacks}},
                y, chosen, weight, mutable=["counters"])
            total = total + got
        return total

    experts = {k: p[k] for k in stacks}
    np.testing.assert_allclose(jax.jit(shares)(y, experts),
                               jax.jit(reference)(y, experts), atol=2e-5)
    got = jax.jit(jax.grad(lambda y, e: jnp.sum(shares(y, e) * cot),
                           argnums=(0, 1)))(y, experts)
    want = jax.jit(jax.grad(lambda y, e: jnp.sum(reference(y, e) * cot),
                            argnums=(0, 1)))(y, experts)
    np.testing.assert_allclose(got[0], want[0], atol=2e-5)
    for k in stacks:
        np.testing.assert_allclose(got[1][k], want[1][k], atol=2e-5,
                                   err_msg=k)


# ------------------------------------------------------- the shared code
def _dispatch_case():
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(keys[0], (48, 32))
    chosen, weight = transformer.softmax_router(
        x, 0.2 * jax.random.normal(keys[1], (32, 8)), 3)
    w = [0.2 * jax.random.normal(k, s) for k, s in zip(
        keys[2:5], [(4, 32, 24), (4, 32, 24), (4, 24, 32)])]
    dense = jnp.sum(jax.nn.one_hot(chosen, 8) * weight[..., None], axis=1)
    return x, chosen, weight, w, dense


@pytest.mark.parametrize("activation", ["silu", "relu"])
def test_dispatch_experts_takes_the_activation_it_is_given(activation):
    """SiLU stays the default and computes what it computed: the same
    program as the activation named, and the dense SwiGLU over the held
    experts; ReLU is ReGLU, the reference's experts."""
    x, chosen, weight, (wg, wu, wd), dense = _dispatch_case()
    kw = dict(experts_total=8, experts_held=(2, 4), rows_slack=2.0,
              dtype=jnp.float32)
    fn = getattr(jax.nn, activation)

    def run(**more):
        return transformer.dispatch_experts(x, chosen, weight, wg, wu, wd,
                                            **kw, **more)

    got, counters = run(activation=fn)
    if activation == "silu":
        default = jax.make_jaxpr(lambda: run())()
        named = jax.make_jaxpr(lambda: run(activation=jax.nn.silu))()
        assert str(default) == str(named)
        np.testing.assert_array_equal(run()[0], got)
        want = sum(dense[:, 2 + e:3 + e] * ((jax.nn.silu(x @ wg[e])
                                             * (x @ wu[e])) @ wd[e])
                   for e in range(4))
    else:
        want = ref.experts({"gate": wg, "up": wu, "down": wd}, x, dense,
                           dict(experts_first=2, experts_held=4), "none")
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert float(counters["moe_dropped_frac"]) == 0.0


@pytest.mark.parametrize("head_dim, rotary", [
    (128, True), (128, False), (64, True)],
    ids=["kernel_rotary", "kernel_nope", "plain_rotary"])
def test_attention_inputs_without_a_norm_at_a_group_of_7(head_dim, rotary):
    """With no q/k norm (both scales None) the fused pass (a Pallas kernel
    at heads of 128 under the interpreter, plain JAX at 64) at 7 query
    heads a key/value head gives the composed chain without a norm:
    rotary where the layer has it, the scale, the layout; its backward
    pass autodiff's of that chain. A norm with unit weights would read
    otherwise."""
    b, s, h, kv = 1, 16, 14, 2
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (b, s, h, head_dim))
    k = jax.random.normal(keys[1], (b, s, kv, head_dim))
    v = jax.random.normal(keys[2], (b, s, kv, head_dim))
    cot = jax.random.normal(keys[3], (b, kv, h // kv, s, head_dim))
    rot = (1.5e6, None) if rotary else None

    def fused(q, k):
        out = inputs_pass.attention_inputs(q, k, v, None, None, rot,
                                           jnp.float32, 1e-6)
        return jnp.sum(out[0] * cot) + jnp.sum(out[1] * cot[:, :, 0])

    def composed(q, k, norm=False):
        if norm:
            ones = jnp.ones((head_dim,))
            q, k = (transformer.rms_norm(q, ones, 1e-6),
                    transformer.rms_norm(k, ones, 1e-6))
        if rotary:
            q, k = (transformer.rotary(t, 1.5e6) for t in (q, k))
        q = jnp.transpose((q / math.sqrt(head_dim)).reshape(
            b, s, kv, h // kv, head_dim), (0, 2, 3, 1, 4))
        k = jnp.transpose(k, (0, 2, 1, 3))
        return jnp.sum(q * cot) + jnp.sum(k * cot[:, :, 0])

    np.testing.assert_allclose(fused(q, k), composed(q, k), rtol=1e-5)
    assert abs(float(composed(q, k, norm=True)) - float(composed(q, k))) \
        > 1e-3 * abs(float(composed(q, k)))
    got = jax.grad(fused, argnums=(0, 1))(q, k)
    want = jax.grad(composed, argnums=(0, 1))(q, k)
    for a, w in zip(got, want):
        np.testing.assert_allclose(a, w, atol=2e-5 * float(jnp.max(
            jnp.abs(w))))


# ------------------------------------------------------- the window's cut
def test_window_cut_frac_is_a_direct_count_of_the_pairs():
    """Of the (query, key) pairs in one document with the key at or before
    the query, the share a window of 8 drops, counted pair by pair; a
    sequence that begins inside a document counts from its start."""
    rng = np.random.default_rng(4)
    ids = rng.integers(1, 128, (3, 40))
    ids[0, [0, 13, 14, 30]] = 0
    ids[1, [9]] = 0
    doc = np.cumsum(ids == 0, axis=1)
    kept = cut = 0
    for row in doc:
        for i in range(40):
            for j in range(i + 1):
                if row[j] == row[i]:
                    kept += 1
                    cut += i - j >= 8
    got = smallthinker.window_cut_frac(jnp.asarray(doc), 8)
    assert float(got) == pytest.approx(cut / kept, rel=1e-6)
    _, state = jax.jit(lambda p, ids: SmallThinker(ARCH).apply(
        {"params": p}, ids, mutable=["counters"]))(weights(),
                                                   jnp.asarray(ids[:, :32]))
    assert float(state["counters"]["window_cut_frac"]) == pytest.approx(
        float(smallthinker.window_cut_frac(jnp.asarray(doc[:, :32]), 8)))


# ------------------------------------------------------ counts and preset
def test_parameter_flop_and_live_entry_counts():
    """The program's count, the benchmark's and a walk over the leaves'
    shapes: a matrix of the tree is met by every token once (an expert
    stack by top_k/total of them; the norms' weights apart), plus
    attention's live entries under the causal mask and the window."""
    cfg = load_config("smallthinker_21b_a3b_ep8")
    arch = build_model(cfg).arch
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "smallthinker_21b_a3b_ep8.json")) as f:
        stated = json.load(f)["model"]
    shapes = jax.eval_shape(lambda: SmallThinker(arch).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    sizes = {k: v.shape for k, v in flat(jax.tree_util.tree_map(
        lambda s: np.broadcast_to(np.int8(0), s.shape), shapes)).items()}
    assert sum(math.prod(s) for s in sizes.values()) \
        == stated["parameters"] == 370_547_200
    layer = {k: math.prod(s) for k, s in sizes.items()
             if k.startswith("layer_0/")}
    assert sum(v for k, v in layer.items() if "/attn/" in k) == 20_971_520
    assert layer["layer_0/router"] == 163_840
    assert sum(v for k, v in layer.items() if "/moe/" in k) == 47_185_920
    i, j = np.arange(512)[:, None], np.arange(512)[None, :]
    assert smallthinker.live_entries(512, 0) == int((j <= i).sum())
    assert smallthinker.live_entries(512, 128) == int(
        ((j <= i) & (i - j < 128)).sum())
    assert smallthinker.live_entries(16384, 4096) == 58_722_304
    macs = 0.0
    for key, shape in sizes.items():
        if key == "embed":
            continue                                   # a lookup
        if len(shape) == 2:
            macs += math.prod(shape)
        elif len(shape) == 3:
            macs += math.prod(shape) * arch.top_k / arch.experts_total
    macs += 2 * 28 * 128 * (134_225_920 + 3 * 58_722_304) / 16384
    assert macs == pytest.approx(286_652_544, abs=1)
    want = 6 * macs * 16384
    assert abs(smallthinker.train_flops_per_sequence(arch, 16384) - want) \
        < 1e-9 * want
    assert abs(family.train_flops_per_example(stated) - want) < 1e-9 * want
    assert family.example(stated) == {"what": "packed sequence",
                                      "tokens": 16384}


def test_preset_states_the_published_widths_and_spells_its_program():
    cfg = load_config("smallthinker_21b_a3b_ep8")
    arch = build_model(cfg).arch
    assert family_of(build_model(cfg)).name == "smallthinker"
    assert (arch.hidden, arch.heads, arch.kv_heads, arch.head_dim,
            arch.window) == (2560, 28, 4, 128, 4096)
    assert (arch.expert_width, arch.experts_total, arch.experts_held,
            arch.top_k) == (768, 64, (0, 8), 6)
    assert arch.layers == PUBLISHED and arch.vocab_rows == 18992
    assert (arch.eps, arch.rope_theta, arch.remat) == (1e-6, 1.5e6, True)
    assert cfg.data.seq_len == 16384 and cfg.train.global_batch_size == 1
    assert spell(cfg, {"data": 1, "model": 1}) == \
        "train|tokens16384_smallthinker_gwww_w4096_e8of64_bf16_remat|" \
        "mesh1x1|b1"
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "smallthinker_21b_a3b_ep8.json")) as f:
        stated = json.load(f)
    for key in ("hidden", "heads", "kv_heads", "head_dim", "window",
                "expert_width", "experts_total", "top_k", "rope_theta",
                "vocab_rows"):
        assert stated["model"][key] == getattr(arch, key), key
    assert stated["model"]["rms_norm_eps"] == arch.eps
    assert stated["model"]["seq_len"] == cfg.data.seq_len


def test_startup_events_name_every_layers_window_and_path():
    """The event ``attention_path`` at the cell's shapes on one TPU chip:
    the global layer's kernel visits 136 of 256 tiles of 1,024 x 1,024,
    a window layer's 70; on the CPU every layer takes the scan."""
    events = smallthinker.startup_events(SmallThinker(ARCH), load_config(
        "smallthinker_21b_a3b_ep8", overrides=TINY))
    rows = events["attention_path"]["layers"]
    assert [(r["kind"], r["window"], r["path"]) for r in rows] == [
        ("global", 0, "scan"), ("window", 8, "scan")]
    assert set(events["expert_path"]) == {"products", "rows_to_tokens"}
    big = smallthinker.attention_paths(Arch(layers=PUBLISHED), 16384, "tpu",
                                       1)
    assert [(r["path"], r["inputs"], r["key_blocks_visited"],
             r["key_blocks_total"]) for r in big] == [
        ("kernel", "fused", 136, 256)] + [("kernel", "fused", 70, 256)] * 3


@pytest.mark.parametrize("overrides,words", [
    (["optim.optimizer=momentum"], "adamw"),
    (["mesh.partition=zero1"], "zero1"),
    (["data.dataset=cifar10"], "'smallthinker'"),
])
def test_check_step_config_says_what_it_refuses(overrides, words):
    cfg = load_config("smallthinker_21b_a3b_ep8", overrides=TINY + overrides)
    with pytest.raises(ValueError, match=words):
        check_step_config(cfg, 1)
