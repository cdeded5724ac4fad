"""A/B the halo-tiled fused Pallas bottleneck against XLA's compilation
of the identical math, at ResNet-50's stride-1 identity bottleneck
shapes (the ~50% MFU path of docs/PERF.md "ImageNet MFU" — see
ops/fused_bottleneck.py).

Methodology matches tools/fused_block_ab.py: each arm chains L
sequential block applications inside ONE lax.scan dispatch with chained
inputs (XLA can neither hoist nor overlap iterations; per-dispatch
latency cannot mask per-block costs); the fwd_bwd arms
differentiate wrt the input AND all nine parameters so both sides
compute the full gradient set; timing is fetch-synced
(bench._fetch_sync); the JSON is rewritten after every shape so a
run that dies midway keeps the finished shapes.

    python tools/fused_bottleneck_ab.py [--out JSON] [--length 8] [--reps 5]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (batch, spatial, f): rn50's three fusable identity-bottleneck stage
# shapes (f=512 @ 7² excluded — weights alone exceed VMEM; see module
# docstring). Tile plans come from fused_bottleneck._DEFAULT_TILES.
SHAPES = [(128, 56, 64), (128, 28, 128), (128, 14, 256)]

PARAM_KEYS = ("w1", "w2", "w3", "s1", "b1", "s2", "b2", "s3", "b3")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--length", type=int, default=8,
                    help="blocks chained per dispatch")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=None,
                    help="override the per-shape batch (tiny-config tests)")
    ap.add_argument("--shapes", default=None,
                    help="override as b,h,f[;b,h,f...]")
    ap.add_argument("--batch-tile", type=int, default=None)
    ap.add_argument("--row-tile", type=int, default=None)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float32"])
    args = ap.parse_args()
    if args.length < 1 or args.reps < 1:
        raise SystemExit("--length and --reps must be >= 1")

    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench
    from tpu_resnet.ops.fused_bottleneck import (
        bottleneck_apply, bottleneck_fwd, bottleneck_fwd_reference,
        bottleneck_train_apply, bottleneck_train_fwd,
        bottleneck_train_fwd_reference)

    shapes = SHAPES
    if args.shapes:
        shapes = [tuple(int(v) for v in s.split(","))
                  for s in args.shapes.split(";")]
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    out = {"device": jax.devices()[0].device_kind, "length": args.length,
           "dtype": args.dtype, "by_shape": {}}

    def flush():
        if args.out:
            json.dump(out, open(args.out, "w"), indent=2)

    for b, h, f in shapes:
        b = args.batch or b
        c4 = 4 * f
        key = f"b{b}_{h}x{h}x{c4}_f{f}"
        try:
            rng = np.random.default_rng(f)
            x0 = jnp.asarray(rng.normal(size=(b, h, h, c4)), dtype)
            # Tiny weights: L chained residual blocks must stay finite.
            params = (
                jnp.asarray(rng.normal(size=(c4, f)) * 0.01, dtype),
                jnp.asarray(rng.normal(size=(3, 3, f, f)) * 0.01, dtype),
                jnp.asarray(rng.normal(size=(f, c4)) * 0.01, dtype),
                jnp.ones((c4,), dtype), jnp.zeros((c4,), dtype),
                jnp.ones((f,), dtype), jnp.zeros((f,), dtype),
                jnp.ones((f,), dtype), jnp.zeros((f,), dtype))

            def chained(block):
                @jax.jit
                def run(x):
                    def body(xc, _):
                        return block(xc, *params), None
                    xc, _ = jax.lax.scan(body, x, None, length=args.length)
                    return jnp.float32(jnp.sum(xc))
                return run

            def chained_grad(block):
                def loss(x, *p):
                    def body(xc, _):
                        return block(xc, *p), None
                    xc, _ = jax.lax.scan(body, x, None, length=args.length)
                    return jnp.float32(jnp.sum(xc))

                g = jax.grad(loss, argnums=tuple(range(1 + len(params))))

                @jax.jit
                def run(x):
                    grads = g(x, *params)
                    return sum(jnp.float32(jnp.sum(gr)) for gr in grads)
                return run

            def time_arm(run):
                bench._fetch_sync(run(x0))  # compile + warm
                best = float("inf")
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    bench._fetch_sync(run(x0))
                    best = min(best, time.perf_counter() - t0)
                return best / args.length * 1e6  # us per block

            entry = {}
            pallas_us = time_arm(chained(
                lambda x, *p: bottleneck_fwd(
                    x, *p, batch_tile=args.batch_tile,
                    row_tile=args.row_tile)))
            xla_us = time_arm(chained(bottleneck_fwd_reference))
            entry["fwd"] = {
                "pallas_us_per_block": round(pallas_us, 2),
                "xla_us_per_block": round(xla_us, 2),
                "speedup": round(xla_us / pallas_us, 3)}
            out["by_shape"][key] = entry
            flush()  # fwd numbers survive a bwd failure

            pallas_g_us = time_arm(chained_grad(
                lambda x, *p: bottleneck_apply(
                    x, *p, args.batch_tile, args.row_tile, None)))
            xla_g_us = time_arm(chained_grad(bottleneck_fwd_reference))
            entry["fwd_bwd"] = {
                "pallas_us_per_block": round(pallas_g_us, 2),
                "xla_us_per_block": round(xla_g_us, 2),
                "speedup": round(xla_g_us / pallas_g_us, 3)}
            flush()

            # Training direction with LIVE batch stats (staged stats
            # passes + folded apply; four-pass correction backward) —
            # the numbers that would decide model integration. The live
            # blocks return (y, moments); dropping the moments ([0])
            # reuses the folded-arm harnesses, and the folded arm's
            # identity scale/bias double as raw BN gamma/beta here.
            pallas_t_us = time_arm(chained(
                lambda x, *p: bottleneck_train_fwd(
                    x, *p, batch_tile=args.batch_tile,
                    row_tile=args.row_tile)[0]))
            xla_t_us = time_arm(chained(
                lambda x, *p: bottleneck_train_fwd_reference(x, *p)[0]))
            entry["train_fwd_live_bn"] = {
                "pallas_us_per_block": round(pallas_t_us, 2),
                "xla_us_per_block": round(xla_t_us, 2),
                "speedup": round(xla_t_us / pallas_t_us, 3)}
            flush()

            pallas_tg_us = time_arm(chained_grad(
                lambda x, *p: bottleneck_train_apply(
                    x, *p, 1e-5, args.batch_tile, args.row_tile,
                    None)[0]))
            xla_tg_us = time_arm(chained_grad(
                lambda x, *p: bottleneck_train_fwd_reference(x, *p)[0]))
            entry["train_fwd_bwd_live_bn"] = {
                "pallas_us_per_block": round(pallas_tg_us, 2),
                "xla_us_per_block": round(xla_tg_us, 2),
                "speedup": round(xla_tg_us / pallas_tg_us, 3)}
        except Exception as e:  # record and keep measuring other shapes
            out["by_shape"].setdefault(key, {})["error"] = (
                f"{type(e).__name__}: {e}"[:500])
            traceback.print_exc()
        print(key, out["by_shape"][key], flush=True)
        flush()

    print(json.dumps(out))
    flush()


if __name__ == "__main__":
    main()
