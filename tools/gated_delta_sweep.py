"""Time Gated DeltaNet's recurrence kernels (tpu_resnet/ops/gated_delta.py)
on one TPU chip at the benchmark cell's shapes (2 sequences of 4,096, 16
key and 32 value heads of 128, bf16) for each chunk length given: the
inverse kernel alone at each number of units a grid step given
(``inverse_ms``, by units), forward alone and forward with backward, and
print one JSON line per chunk:

    python tools/gated_delta_sweep.py --chunk 64 --chunk 128 --chunk 256 \
        --units 1 --units 2 --units 4

Each time is the median of ``--reps`` calls that end in
``block_until_ready``, after one call that compiles. A run off the TPU
exits non-zero: the kernels' times exist only on the chip."""

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chunk", type=int, action="append")
    p.add_argument("--units", type=int, action="append",
                   help="(chunk, value head) units a grid step of the "
                        "inverse kernel (default: the module's UNITS)")
    p.add_argument("--reps", type=int, default=20)
    p.add_argument("--out", default="")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from tpu_resnet.ops import gated_delta

    if jax.default_backend() != "tpu":
        print(f"no TPU here (JAX found {jax.default_backend()!r})",
              file=sys.stderr)
        return 2
    b, s, hk, hv, d = 2, 4096, 16, 32, 128
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(keys[0], (b, s, hk, d))
    k = jax.random.normal(keys[1], (b, s, hk, d))
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    v = jax.random.normal(keys[2], (b, s, hv, d)).astype(jnp.bfloat16)
    beta = jax.random.uniform(keys[3], (b, s, hv))
    g = -jax.random.uniform(keys[4], (b, s, hv), maxval=3.0)
    reset = jax.random.uniform(keys[5], (b, s)) < 1 / 600
    q, k = q.astype(jnp.bfloat16), k.astype(jnp.bfloat16)

    def median_ms(f, *a):
        jax.block_until_ready(f(*a))
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*a))
            times.append(1e3 * (time.perf_counter() - t0))
        return statistics.median(times)

    rows = []
    for chunk in args.chunk or [gated_delta.CHUNK]:
        def fwd(q, k, v, beta, g):
            return gated_delta.gated_delta(q, k, v, beta, g, reset,
                                           dtype=jnp.bfloat16, chunk=chunk)

        both = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fwd(*a).astype(jnp.float32)),
            argnums=(0, 1, 2, 3, 4)))
        row = {"chunk": chunk, "inverse_ms": {}}
        for units in args.units or [gated_delta.UNITS]:
            inverse = jax.jit(functools.partial(
                gated_delta.inverses, reset=reset, dtype=jnp.bfloat16,
                chunk=chunk, units=units))
            row["inverse_ms"][units] = median_ms(inverse, k, beta, g)
        for name, f in (("fwd_ms", jax.jit(fwd)), ("fwd_bwd_ms", both)):
            row[name] = median_ms(f, q, k, v, beta, g)
        row["device"] = jax.devices()[0].device_kind
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
