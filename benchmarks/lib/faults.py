"""Faults planted under the timed path, for the tests that see ``correct``
come out false and for the fault readings the limits were set from. Each
takes the loop's chunk runner ``run(state, gi, gl, off, c)`` and returns a
broken one. Never part of a driver's run."""

from __future__ import annotations


def state_unchanged(inner):
    """A step that returns its state unchanged (the metrics are real)."""
    import jax

    def run(state, gi, gl, off, c):
        kept = jax.tree_util.tree_map(lambda x: x.copy(), state)
        _, metrics = inner(state, gi, gl, off, c)
        return kept, metrics

    return run


def half_batch(inner):
    """Half of the batch left out, the mean taken over the rest: the first
    half stands in for the second, so every mean is the first half's."""
    import jax.numpy as jnp

    def run(state, gi, gl, off, c):
        n = gi.shape[1] // 2
        gi2 = jnp.tile(gi[:, :n], (1, 2) + (1,) * (gi.ndim - 2))
        gl2 = jnp.tile(gl[:, :n], (1, 2))
        gi2 = jnp.asarray(gi2, gi.dtype, device=gi.sharding)
        gl2 = jnp.asarray(gl2, gl.dtype, device=gl.sharding)
        return inner(state, gi2, gl2, off, c)

    return run


def loss_altered(inner):
    """An answer altered where it is produced: the reported loss is 5%
    off (ten times what the program reads against the reference, and
    more)."""
    def run(state, gi, gl, off, c):
        state, metrics = inner(state, gi, gl, off, c)
        metrics = dict(metrics)
        metrics["loss"] = metrics["loss"] * 1.05
        return state, metrics

    return run


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "loss_altered": loss_altered}
