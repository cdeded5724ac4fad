"""Fused Pallas conv epilogues (tpu_resnet/ops/epilogue.py) and the
compile-time A/B probe that gates every Pallas path
(tpu_resnet/ops/autotune.py): interpret-mode CPU parity (fwd + VJP),
the guarded auto dispatch, the model integration's tree/value parity,
and the probe's fallback invariant — a Pallas path stays enabled ONLY
with a measured speedup >= 1.0."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_resnet.config import load_config
from tpu_resnet.models import build_model
from tpu_resnet.ops import autotune, epilogue


@pytest.fixture(autouse=True)
def _fresh_autotune():
    autotune.reset()
    yield
    autotune.reset()


def _args(shape=(6, 5, 5, 7), dtype=jnp.float32, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(k[0], shape, dtype)
    r = jax.random.normal(k[1], shape, dtype)
    s = jax.random.uniform(k[2], (shape[-1],), jnp.float32, 0.5, 1.5)
    b = jax.random.normal(k[3], (shape[-1],))
    return x, s, b, r


# ------------------------------------------------------------ kernel parity
@pytest.mark.parametrize("shape", [(6, 5, 5, 7), (8, 4, 4, 16),
                                   (3, 2, 2, 130)])
def test_scale_bias_relu_matches_reference(shape):
    x, s, b, _ = _args(shape)
    got = epilogue.scale_bias_relu(x, s, b, None, True)
    want = epilogue.scale_bias_relu_reference(x, s, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_scale_bias_relu_bf16_dtype_preserved():
    x, s, b, _ = _args(dtype=jnp.bfloat16)
    y = epilogue.scale_bias_relu(x, s, b, None, True)
    assert y.dtype == jnp.bfloat16
    want = epilogue.scale_bias_relu_reference(x, s, b)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-2, atol=1e-2)


def test_scale_bias_relu_grad_matches_reference():
    x, s, b, _ = _args()

    def loss(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) ** 2),
                        argnums=(0, 1, 2))(x, s, b)

    got = loss(lambda a, ss, bb: epilogue.scale_bias_relu(
        a, ss, bb, None, True))
    want = loss(epilogue.scale_bias_relu_reference)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


def test_scale_bias_relu_add_value_and_grad():
    x, s, b, r = _args()
    got = epilogue.scale_bias_relu_add(x, s, b, r, None, True)
    want = epilogue.scale_bias_relu_add_reference(x, s, b, r)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) ** 2),
                        argnums=(0, 1, 2, 3))(x, s, b, r)

    got_g = grads(lambda a, ss, bb, rr: epilogue.scale_bias_relu_add(
        a, ss, bb, rr, None, True))
    want_g = grads(epilogue.scale_bias_relu_add_reference)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)
    # the residual's cotangent is the upstream cotangent unchanged
    np.testing.assert_allclose(np.asarray(got_g[3]),
                               np.asarray(2 * np.asarray(
                                   epilogue.scale_bias_relu_add_reference(
                                       x, s, b, r))),
                               rtol=1e-5, atol=1e-5)


def test_batch_tile_must_divide():
    x, s, b, _ = _args((6, 5, 5, 7))
    with pytest.raises(ValueError, match="not divisible"):
        epilogue.scale_bias_relu(x, s, b, 4, True)
    assert epilogue.auto_batch_tile((6, 5, 5, 7)) == 6
    # one batch row never fits -> tile degrades to a divisor, min 1
    assert epilogue.auto_batch_tile((7, 64, 64, 256),
                                    budget_bytes=2 ** 20) == 1


# ------------------------------------------------------- guarded dispatch
def test_auto_dispatch_follows_autotune_decision():
    x, s, b, _ = _args((8, 4, 4, 16))
    key = epilogue.sbr_key(x.shape)

    def has_pallas():
        # The kernel path traces through the custom-VJP wrapper (under
        # the interpreter the pallas body inlines, so "pallas_call"
        # itself is backend-dependent); the XLA reference is plain ops.
        # A FRESH closure per trace: jax caches traces on (fn identity,
        # avals), which is exactly why the probe-before-compile order
        # matters in production (ops/autotune.py docstring).
        def fresh(a, ss, bb):
            return epilogue.scale_bias_relu_auto(a, ss, bb)

        return "custom_vjp_call" in str(jax.make_jaxpr(fresh)(x, s, b))

    # unprobed: safe XLA fallback
    assert not has_pallas()
    autotune._record(autotune.Decision(
        epilogue.OP_SBR, key, 1.0, 2.0, 2.0, True))
    assert has_pallas()
    autotune._record(autotune.Decision(
        epilogue.OP_SBR, key, 2.0, 1.0, 0.5, False))
    assert not has_pallas()


def test_probe_enabled_implies_speedup_at_least_one():
    """The acceptance invariant: every Pallas path that STAYS ENABLED
    carries a measured CPU A/B speedup >= 1.0; losing paths fall back."""
    epilogue.probe_epilogue((4, 4, 4, 8), iters=2, interpret=True)
    decs = list(autotune.decisions().values())
    assert decs
    for d in decs:
        assert (not d["use_pallas"]) or d["speedup"] >= 1.0, d


def test_probe_surfaces_a_broken_kernel():
    """A Pallas candidate that fails to compile/run is never turned into
    a recorded "use XLA" decision — the failure reaches the caller."""
    def broken(x):
        raise RuntimeError("mosaic exploded")

    with pytest.raises(RuntimeError, match="mosaic exploded"):
        autotune.probe("bad_op", "k", broken, lambda x: x * 2.0,
                       (jnp.ones((4, 4)),), iters=2)
    assert autotune.decision("bad_op", "k") is None


def test_dump_load_roundtrip(tmp_path):
    autotune._record(autotune.Decision("op", "8x8", 1.0, 3.0, 3.0, True))
    path = autotune.dump(str(tmp_path))
    autotune.reset()
    assert autotune.decision("op", "8x8") is None
    assert autotune.load(path) == 1
    d = autotune.decision("op", "8x8")
    assert d.use_pallas and d.speedup == 3.0


def test_xent_probe_cached_and_invariant():
    from tpu_resnet.ops import ensure_xent_probe

    d = ensure_xent_probe(16, 10, iters=2, interpret=True)
    assert ensure_xent_probe(16, 10) is d  # cached per shape
    assert (not d.use_pallas) or d.speedup >= 1.0


def test_retuned_xent_parity_b128x1000():
    """The retuned (lane-tiled) kernel at the ImageNet head shape the
    BENCH_r04 regression was measured on."""
    from tpu_resnet.ops import softmax_xent_mean, softmax_xent_reference

    logits = jax.random.normal(jax.random.PRNGKey(0), (128, 1000))
    labels = jax.random.randint(jax.random.PRNGKey(1), (128,), 0, 1000)
    got = softmax_xent_mean(logits, labels, interpret=True)
    want = softmax_xent_reference(logits, labels)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    g1 = jax.grad(lambda a: softmax_xent_mean(a, labels,
                                              interpret=True))(logits)
    g2 = jax.grad(lambda a: softmax_xent_reference(a, labels))(logits)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------------- model integration
def _smoke_cfg(epilogue_mode):
    cfg = load_config('smoke')
    cfg.model.name = 'resnet'
    cfg.model.resnet_size = 8
    cfg.model.compute_dtype = 'float32'
    cfg.model.fused_epilogue = epilogue_mode
    return cfg


def test_model_epilogue_tree_identical_and_parity():
    """fused_epilogue='on' keeps the EXACT nn.BatchNorm parameter/stat
    tree (checkpoints interchange) and matches the unfused model within
    1e-5 on values and batch-stat updates (the acceptance tolerance);
    gradient parity rides in the slow-tier sibling below."""
    m_off = build_model(_smoke_cfg('off'))
    m_on = build_model(_smoke_cfg('on'))
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32, 32, 3))
    v = m_off.init(jax.random.PRNGKey(1), x, train=False)
    # Structure check via eval_shape: no second full init compile.
    v_on = jax.eval_shape(
        lambda r: m_on.init(r, x, train=False), jax.random.PRNGKey(1))
    assert (jax.tree_util.tree_structure(v)
            == jax.tree_util.tree_structure(v_on))

    np.testing.assert_allclose(
        np.asarray(m_on.apply(v, x, train=False)),
        np.asarray(m_off.apply(v, x, train=False)),
        rtol=1e-5, atol=1e-5)

    yo, so = m_off.apply(v, x, train=True, mutable=['batch_stats'])
    yn, sn = m_on.apply(v, x, train=True, mutable=['batch_stats'])
    np.testing.assert_allclose(np.asarray(yn), np.asarray(yo),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(sn),
                    jax.tree_util.tree_leaves(so)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.slow  # two full rn8 backward compiles (~8s); the kernels'
# own VJP parity stays default-tier (test_scale_bias_relu_grad_*)
def test_model_epilogue_grad_parity():
    m_off = build_model(_smoke_cfg('off'))
    m_on = build_model(_smoke_cfg('on'))
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32, 32, 3))
    v = m_off.init(jax.random.PRNGKey(1), x, train=False)

    def loss(model):
        def f(params):
            y, _ = model.apply({'params': params,
                                'batch_stats': v['batch_stats']},
                               x, train=True, mutable=['batch_stats'])
            return jnp.sum(y ** 2)
        return jax.grad(f)(v['params'])

    for a, b in zip(jax.tree_util.tree_leaves(loss(m_on)),
                    jax.tree_util.tree_leaves(loss(m_off))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)


def test_model_epilogue_auto_unprobed_is_xla():
    """'auto' with an empty decision cache must not emit any pallas_call
    — unprobed shapes take the safe XLA lowering."""
    m = build_model(_smoke_cfg('auto'))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 32, 3))
    v = m.init(jax.random.PRNGKey(1), x, train=False)
    text = str(jax.make_jaxpr(
        lambda xx: m.apply(v, xx, train=False))(x))
    assert 'custom_vjp_call' not in text and 'pallas_call' not in text


def test_model_epilogue_bad_value_raises():
    with pytest.raises(ValueError, match="off|on|auto"):
        build_model(_smoke_cfg('sideways'))


def test_epilogue_bn_axis_raises():
    from tpu_resnet.models import cifar_resnet_v2

    with pytest.raises(ValueError, match="does not implement sync-BN"):
        cifar_resnet_v2(8, 10, fused_epilogue="on", bn_axis_name="data")


def test_model_epilogue_shapes_cover_stages():
    cfg = _smoke_cfg('auto')
    shapes = epilogue.model_epilogue_shapes(cfg, 16)
    assert (16, 32, 32, 16) in shapes and (16, 8, 8, 64) in shapes
    cfg.data.dataset = 'imagenet'
    cfg.model.resnet_size = 50
    shapes = epilogue.model_epilogue_shapes(cfg, 8)
    assert (8, 56, 56, 64) in shapes and (8, 56, 56, 256) in shapes
    assert (8, 7, 7, 2048) in shapes
    # downsampling block0's bnrelu1 runs at the INPUT resolution with
    # the new stage's width (conv2 carries the stride)
    for probe in ((8, 56, 56, 128), (8, 28, 28, 256), (8, 14, 14, 512)):
        assert probe in shapes


def test_use_pallas_xent_bad_value_raises():
    from tpu_resnet.train import build_schedule
    from tpu_resnet.train.step import make_train_step

    cfg = _smoke_cfg('off')
    cfg.optim.use_pallas_xent = 'atuo'
    sched = build_schedule(cfg.optim, cfg.train)
    with pytest.raises(ValueError, match="auto|on|off"):
        make_train_step(build_model(cfg), cfg.optim, sched, 10)


def test_check_step_config_epilogue_multichip_rule():
    from tpu_resnet.train.step import check_step_config

    cfg = _smoke_cfg('on')
    check_step_config(cfg, 1)           # single device fine
    with pytest.raises(ValueError, match="fused_epilogue"):
        check_step_config(cfg, 8)       # sync-BN multichip must raise
    cfg.model.sync_bn = False
    check_step_config(cfg, 8)           # per-replica shard_map path fine
