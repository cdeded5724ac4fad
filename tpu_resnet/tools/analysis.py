"""Model analysis — the tfprof replacement (reference resnet_single.py:58-66
dumped parameter counts and FLOPs via tf.profiler). Here: param count from
the pytree and per-step FLOPs from XLA's own compiled cost analysis."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpu_resnet.config import RunConfig
from tpu_resnet.models import build_model
from tpu_resnet.train.state import param_count


def forward_cost_analysis(model, image_size: int, batch: int = 1):
    """XLA cost analysis of the inference forward pass."""
    x = jnp.zeros((batch, image_size, image_size, 3), jnp.float32)
    variables = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), x,
                                                  train=False))

    def fwd(v, x):
        return model.apply(v, x, train=False)

    lowered = jax.jit(fwd).lower(variables, x)
    compiled = lowered.compile()
    return compiled.cost_analysis() or {}


def layer_params(params) -> list:
    """(path, shape, count) per parameter leaf, in module-definition order
    — the reference's tfprof per-variable dump (resnet_single.py:58-66).
    Walks the mapping directly because jax's tree flatten sorts keys
    lexicographically (block10 before block2, final_dense before
    initial_conv), which is not architecture order."""
    rows = []

    def walk(node, prefix):
        if hasattr(node, "items"):  # dict / FrozenDict
            for k, v in node.items():
                walk(v, prefix + [str(k)])
        else:
            rows.append(("/".join(prefix), tuple(node.shape),
                         int(node.size)))

    walk(params, [])
    return rows


def print_model_info(cfg: RunConfig, layers: bool = False):
    model = build_model(cfg)
    size = cfg.data.resolved_image_size
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, size, size, 3)), train=False)
    n_params = param_count(variables["params"])
    n_stats = param_count(variables.get("batch_stats", {}))
    print(cfg.to_json())
    print(f"model: {cfg.model.name} size={cfg.model.resnet_size} "
          f"width={cfg.model.width_multiplier} dataset={cfg.data.dataset}")
    print(f"trainable params: {n_params:,}")
    print(f"batch-norm moving stats: {n_stats:,}")
    if layers:
        rows = layer_params(variables["params"])
        width = max(len(r[0]) for r in rows)
        for name, shape, count in rows:
            print(f"  {name:<{width}}  {str(shape):>20}  {count:>12,}")
        print(f"  {'total':<{width}}  {'':>20}  {n_params:>12,}")
    try:
        cost = forward_cost_analysis(model, size)
        flops = cost.get("flops")
        if flops:
            print(f"forward FLOPs/example (XLA estimate): {int(flops):,}")
        bytes_ = cost.get("bytes accessed")
        if bytes_:
            print(f"forward bytes accessed/example: {int(bytes_):,}")
    except Exception as e:  # cost analysis is best-effort per backend
        print(f"cost analysis unavailable: {e}")
