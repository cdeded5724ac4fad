"""The pass that prepares attention's inputs (ops/attention_inputs.py) held
to the composed chain it stands for on the kernel's path
(models/transformer.py: ``rms_norm``, then ``rotary``, then
ops/attention.py's ``_heads_first``): forward to the last bit, and the
gradients of the projections and of both norms' weights, for the four
ways the token families call it; that each family's parameter tree is
what it was; and that a layer takes the pass on the kernel's path only."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_resnet.models import afmoe, lfm2_moe, sdar_moe, transformer
from tpu_resnet.ops import attention
from tpu_resnet.ops.attention_inputs import attention_inputs, rotary_table

B, S, KV, G, EPS = 2, 32, 2, 4, 1e-6


def _positions(kind):
    """The ``rotary`` argument of each caller: Trinity's sliding layers and
    LFM2's attention count from the sequence's start, Trinity's full
    layers carry no position, the block-diffusion model gives ``[0 ..
    L-1 ; 0 .. L-1]``."""
    if kind == "none":
        return None
    if kind == "block_diffusion":
        return (1e6, jnp.tile(jnp.arange(S // 2, dtype=jnp.int32), 2)[None])
    return (1e4, None)


CASES = pytest.mark.parametrize("head_dim, kind", [
    (128, "default"), (128, "none"), (128, "block_diffusion"),
    (64, "default")],
    ids=["trinity_sliding", "trinity_full", "block_diffusion",
         "lfm2_heads_of_64"])


def _inputs(head_dim, dtype):
    keys = jax.random.split(jax.random.PRNGKey(head_dim), 8)
    q = jax.random.normal(keys[0], (B, S, KV * G, head_dim)).astype(dtype)
    k, v = (jax.random.normal(key, (B, S, KV, head_dim)).astype(dtype)
            for key in keys[1:3])
    q_scale, k_scale = (1.0 + 0.1 * jax.random.normal(key, (head_dim,))
                        for key in keys[3:5])
    weights = (jax.random.normal(keys[5], (B, KV, G, S, head_dim)),
               jax.random.normal(keys[6], (B, KV, S, head_dim)),
               jax.random.normal(keys[7], (B, KV, S, head_dim)))
    return (q, k, v, q_scale, k_scale), weights


def _composed(q, k, v, q_scale, k_scale, rotary_of, dtype):
    """The chain as the scan's path composes it, in the kernel's layout."""
    q = transformer.rms_norm(q, q_scale, EPS)
    k = transformer.rms_norm(k, k_scale, EPS)
    if rotary_of is not None:
        q = transformer.rotary(q, *rotary_of)
        k = transformer.rotary(k, *rotary_of)
    b, s, h, d = q.shape
    return attention._heads_first(q.reshape(b, s, KV, h // KV, d), k, v,
                                  dtype)


def _fused(q, k, v, q_scale, k_scale, rotary_of, dtype):
    return attention_inputs(q, k, v, q_scale, k_scale, rotary_of, dtype, EPS)


def _run(fn, args, weights, rotary_of, dtype):
    """The three prepared tensors, and the gradients of one weighted sum
    of them with respect to the projections and both weights."""
    def loss(*args):
        outs = fn(*args, rotary_of, dtype)
        return sum(jnp.sum(o.astype(jnp.float32) * w)
                   for o, w in zip(outs, weights)), outs

    (_, outs), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    return outs, grads


@CASES
def test_the_pass_is_the_composed_chain_in_float32(head_dim, kind):
    """Forward to the last bit, in the kernel's layout; the gradients of
    the projections and of both weights to 1e-6 of their largest entry
    (the sums run in another order); every cotangent in its input's
    dtype."""
    args, weights = _inputs(head_dim, jnp.float32)
    rotary_of = _positions(kind)
    want_out, want_grads = _run(_composed, args, weights, rotary_of,
                                jnp.float32)
    got_out, got_grads = _run(_fused, args, weights, rotary_of, jnp.float32)
    assert [o.shape for o in got_out] == [
        (B, KV, G, S, head_dim), (B, KV, S, head_dim), (B, KV, S, head_dim)]
    for got, want in zip(got_out, want_out):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for got, want, arg in zip(got_grads, want_grads, args):
        assert got.dtype == arg.dtype and got.shape == arg.shape
        want = np.asarray(want)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                                   atol=1e-6 * np.abs(want).max())


@CASES
def test_bf16_projections_round_where_the_chain_rounds(head_dim, kind):
    """The cells' dtypes: bf16 projections in, bf16 out with the chain's
    two rounding points (every output within one bf16 rounding of the
    chain's: a last-bit difference of the float32 value before the cast,
    which the CPU's compiler may make in one program and not in another,
    can flip one), and the projections' cotangents back in bf16 within one
    rounding of the chain's."""
    args, weights = _inputs(head_dim, jnp.bfloat16)
    rotary_of = _positions(kind)
    want_out, want_grads = _run(_composed, args, weights, rotary_of,
                                jnp.bfloat16)
    got_out, got_grads = _run(_fused, args, weights, rotary_of, jnp.bfloat16)
    for got, want in zip(got_out, want_out):
        assert got.dtype == jnp.bfloat16
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=0)
        assert np.mean(got != want) < 1e-3
    for got, want, arg in zip(got_grads, want_grads, args):
        assert got.dtype == arg.dtype
        want = np.asarray(want, np.float32)
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   rtol=2 ** -7, atol=1e-5 * np.abs(want).max())


def test_the_sine_table_folds_the_rotate_halfs_sign():
    """``y cos + half_turn(y) sin_signed`` is ``rotary``'s ``y cos +
    [-y2, y1] sin``: the tables hold the cosine over both halves and the
    sine with the first half's sign turned."""
    cos, sin = rotary_table(1e4, None, 6, 8)
    assert cos.shape == sin.shape == (1, 6, 8)
    np.testing.assert_array_equal(cos[..., :4], cos[..., 4:])
    np.testing.assert_array_equal(sin[..., :4], -sin[..., 4:])
    y = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 1, 8))
    np.testing.assert_array_equal(
        transformer.rotary(y, 1e4),
        y * cos[:, :, None] + jnp.concatenate([y[..., 4:], y[..., :4]], -1)
        * sin[:, :, None])


# ------------------------------------------------------ the families' trees
TINY = {
    "afmoe": (afmoe.Afmoe, afmoe.Arch(
        layers=("dense_sliding", "moe_full"), hidden=32, heads=4,
        kv_heads=2, head_dim=16, window=8, dense_width=48, expert_width=8,
        experts_total=4, experts_held=(0, 2), top_k=2, vocab_rows=64)),
    "sdar_moe": (sdar_moe.SdarMoe, sdar_moe.Arch(
        layers=2, hidden=32, heads=4, kv_heads=2, head_dim=16,
        expert_width=8, experts_total=4, experts_held=(0, 2), top_k=2,
        vocab_rows=64)),
    "lfm2_moe": (lfm2_moe.Lfm2Moe, lfm2_moe.Arch(
        layers=("dense_conv", "moe_full"), hidden=32, heads=4, kv_heads=2,
        head_dim=16, dense_width=48, expert_width=8, experts_total=4,
        experts_held=(0, 2), top_k=2, vocab_rows=64)),
}


@pytest.mark.parametrize("family", sorted(TINY))
def test_each_familys_attention_keeps_its_parameter_tree(family):
    """Names, shapes and dtypes of an attention layer's leaves as the
    families have always had them (``wq``, ``wk``, ``wv``, ``wo``,
    ``q_norm/scale``, ``k_norm/scale``, and Trinity's gate ``wg``), so
    checkpoints, the partitioner, eval and export read them as before."""
    cls, arch = TINY[family]
    ids = jnp.zeros((1, 16), jnp.int32)
    params = jax.eval_shape(lambda: cls(arch).init(
        jax.random.PRNGKey(0), ids))["params"]
    attn = params["layer_1"]["attn"]
    d, width, kv = 32, 4 * 16, 2 * 16
    want = {"wq": (d, width), "wk": (d, kv), "wv": (d, kv),
            "wo": (width, d), "q_norm": {"scale": (16,)},
            "k_norm": {"scale": (16,)}}
    if family == "afmoe":
        want["wg"] = (d, width)
    assert jax.tree_util.tree_map(lambda a: a.shape, attn) == want
    assert {a.dtype for a in jax.tree_util.tree_leaves(attn)} == {
        jnp.dtype(jnp.float32)}


@pytest.mark.parametrize("preset, count", [
    ("trinity_mini_ep16", 504_147_200), ("sdar_30b_a3b_chat", 456_346_624),
    ("lfm2_24b_a2b_ep8", 469_284_992)])
def test_the_cells_models_keep_their_parameter_count(preset, count):
    from tpu_resnet.config import load_config
    from tpu_resnet.models import build_model, sample_input

    cfg = load_config(preset)
    shapes = jax.eval_shape(lambda: build_model(cfg).init(
        jax.random.PRNGKey(0), sample_input(cfg)))["params"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == count


# ------------------------------------------------------------- which path
@pytest.mark.parametrize("backend, devices, inputs", [
    ("tpu", 1, "fused"), ("tpu", 4, "composed"), ("cpu", 8, "composed")],
    ids=["one_chip", "four_chips", "cpu"])
@pytest.mark.parametrize("preset, module", [
    ("trinity_mini_ep16", afmoe), ("sdar_30b_a3b_chat", sdar_moe),
    ("lfm2_24b_a2b_ep8", lfm2_moe)])
def test_every_layer_says_how_its_inputs_are_prepared(preset, module,
                                                      backend, devices,
                                                      inputs):
    """The event ``attention_path`` names the pass on every attention
    layer of the cells' models: ``fused`` where the layer takes the kernel,
    ``composed`` where it takes the scan, and nothing else decides."""
    from tpu_resnet.config import load_config
    from tpu_resnet.models import build_model

    arch = build_model(load_config(preset)).arch
    rows = module.attention_paths(arch, 4096, backend, devices)
    assert rows and {row["inputs"] for row in rows} == {inputs}
    assert {transformer.INPUTS[row["path"]] for row in rows} == {inputs}


def test_the_pass_is_called_on_the_kernels_path_only(monkeypatch):
    """On the kernel's path the attention layer calls the pass, with the
    parameters it had; on the scan's it composes the chain and never calls
    it."""
    calls = []
    real = transformer.attention_inputs

    def counted(*args, **kwargs):
        calls.append(1)
        return real(*args, **kwargs)

    monkeypatch.setattr(transformer, "attention_inputs", counted)
    monkeypatch.setattr(transformer, "heads_first_attention",
                        lambda q, *_: q)
    cls, arch = TINY["lfm2_moe"]
    ids = jnp.zeros((1, 16), jnp.int32)
    init = jax.eval_shape(lambda: cls(arch).init(jax.random.PRNGKey(0), ids))
    assert not calls
    monkeypatch.setattr(transformer, "attention_path", lambda *_: "kernel")
    assert jax.tree_util.tree_map(lambda a: a.shape, jax.eval_shape(
        lambda: cls(arch).init(jax.random.PRNGKey(0), ids))) == \
        jax.tree_util.tree_map(lambda a: a.shape, init)
    assert len(calls) == 1
