"""Plain reference of the fixture's second family: the one-hidden-layer
classifier (flatten, dense, ReLU, dense), softmax cross-entropy, the L2
term ``wd * sum(w**2)/2`` over every leaf, and momentum SGD (``m = g +
mu*m; p -= lr*m``), in float32 ``jax.numpy``. Imports nothing of
``tpu_resnet``. The preprocessing and the learning-rate rule belong to the
job, not to the model: they are the image job's, as the ResNet reference
states them. ``quantize="bf16"`` is the control: both operands of each
product rounded to bfloat16, the precision below the float32 the
configuration states (straight-through gradient)."""

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.resnet_v2 import learning_rate, preprocess

_ROUNDERS = {
    "none": lambda x: x,
    "bf16": lambda x: x + lax.stop_gradient(
        x.astype(jnp.bfloat16).astype(jnp.float32) - x),
}


def loss_fn(params, x, labels, job, rnd):
    def dense(x, name):
        return jnp.dot(rnd(x), rnd(params[name + "/kernel"]),
                       precision=lax.Precision.HIGHEST) + params[name + "/bias"]

    hidden = jnp.maximum(dense(x.reshape((x.shape[0], -1)), "hidden"), 0.0)
    logp = jax.nn.log_softmax(dense(hidden, "softmax_linear"), axis=-1)
    xent = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    l2 = sum(jnp.sum(jnp.square(v)) for v in params.values()) / 2
    return xent + job["weight_decay"] * l2


def follow(params, mom, images, labels, job, seed, quantize="none",
           start_step=0):
    """``len(images)`` steps from ``(params, mom)`` at ``start_step``;
    returns the final ``params, mom`` and per-step ``losses, grad_norms``.
    The per-step preprocessing key is ``fold_in(split(PRNGKey(seed))[1],
    step)``, as the job states."""
    step_rng = jax.random.split(jax.random.PRNGKey(seed))[1]
    losses, gnorms = [], []
    for k in range(len(images)):
        step = start_step + k
        x = preprocess(job["preprocess"], jax.random.fold_in(step_rng, step),
                       jnp.asarray(images[k]))
        loss, grads = jax.value_and_grad(loss_fn)(
            params, x, jnp.asarray(labels[k]), job, _ROUNDERS[quantize])
        mom = {n: grads[n] + job["momentum"] * mom[n] for n in params}
        lr = learning_rate(job, step)
        params = {n: params[n] - lr * mom[n] for n in params}
        losses.append(float(loss))
        gnorms.append(float(jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                         for g in grads.values()))))
    return params, mom, losses, gnorms
