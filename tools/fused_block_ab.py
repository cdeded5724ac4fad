"""A/B the fused Pallas basic-block against XLA's compilation of the
identical math, at the CIFAR ResNet's three stage shapes (the decisive
experiment for docs/PERF.md's "CIFAR is overhead-bound" hypothesis — see
ops/fused_block.py).

Each arm chains L sequential block applications inside ONE lax.scan
dispatch (per-dispatch latency cannot mask per-block costs), with
chained inputs so XLA can neither hoist nor overlap iterations. The
fwd_bwd arms differentiate wrt the input AND every parameter so both
sides compute the full gradient set (params closed over would let XLA
dead-code-eliminate its wgrad work while the opaque Pallas kernel still
pays for it). Timing is fetch-synced (bench._fetch_sync); the output
JSON is rewritten after every shape so a run that dies midway keeps
the shapes already measured.

    python tools/fused_block_ab.py [--out JSON] [--length 32] [--reps 5]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (batch, spatial, channels, fwd_tile, bwd_tile): the three CIFAR-ResNet
# stage shapes (models/resnet.py cifar_resnet_v2 — 16@32x32, 32@16x16,
# 64@8x8). Tiles sized for ~16 MB core VMEM: the fwd kernel keeps ~6
# tile-sized fp32 buffers live, the bwd kernel ~12.
SHAPES = [(128, 32, 32, 16, 16, 8), (128, 16, 16, 32, 32, 16),
          (128, 8, 8, 64, 64, 32)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--length", type=int, default=32,
                    help="blocks chained per dispatch")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float32"])
    args = ap.parse_args()
    if args.length < 1 or args.reps < 1:
        raise SystemExit("--length and --reps must be >= 1")

    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench
    from tpu_resnet.ops.fused_block import (block_apply, block_fwd,
                                            block_fwd_reference,
                                            block_train_apply,
                                            block_train_fwd,
                                            block_train_fwd_reference)

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    out = {"device": jax.devices()[0].device_kind, "length": args.length,
           "dtype": args.dtype, "by_shape": {}}

    def flush():
        if args.out:
            json.dump(out, open(args.out, "w"), indent=2)

    for b, h, w, c, bt_fwd, bt_bwd in SHAPES:
        key = f"b{b}_{h}x{w}x{c}"
        try:
            rng = np.random.default_rng(c)
            x0 = jnp.asarray(rng.normal(size=(b, h, w, c)), dtype)
            # Tiny weights: 32 chained residual blocks must stay finite.
            params = (
                jnp.asarray(rng.normal(size=(3, 3, c, c)) * 0.01, dtype),
                jnp.asarray(rng.normal(size=(3, 3, c, c)) * 0.01, dtype),
                jnp.ones((c,), dtype), jnp.zeros((c,), dtype),
                jnp.ones((c,), dtype), jnp.zeros((c,), dtype))

            def chained(block):
                @jax.jit
                def run(x):
                    def body(xc, _):
                        return block(xc, *params), None
                    xc, _ = jax.lax.scan(body, x, None, length=args.length)
                    return jnp.float32(jnp.sum(xc))
                return run

            def chained_grad(block, block_params, tuple_out=False):
                # Params are loss ARGUMENTS (argnums 0..6): both arms must
                # compute dx and all six parameter grads. tuple_out: the
                # live-BN blocks return (y, moments); moments are unused
                # (stop-gradient EMA convention).
                def loss(x, *p):
                    def body(xc, _):
                        y = block(xc, *p)
                        return (y[0] if tuple_out else y), None
                    xc, _ = jax.lax.scan(body, x, None, length=args.length)
                    return jnp.float32(jnp.sum(xc))

                g = jax.grad(loss, argnums=tuple(range(1 + len(block_params))))

                @jax.jit
                def run(x):
                    grads = g(x, *block_params)
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
                lambda x, *p: block_fwd(x, *p, batch_tile=bt_fwd)))
            xla_us = time_arm(chained(block_fwd_reference))
            entry["fwd"] = {
                "pallas_us_per_block": round(pallas_us, 2),
                "xla_us_per_block": round(xla_us, 2),
                "speedup": round(xla_us / pallas_us, 3)}
            out["by_shape"][key] = entry
            flush()  # fwd numbers survive a bwd failure

            pallas_g_us = time_arm(chained_grad(
                lambda x, *p: block_apply(x, *p, bt_fwd, None, bt_bwd),
                params))
            xla_g_us = time_arm(chained_grad(block_fwd_reference, params))
            entry["fwd_bwd"] = {
                "pallas_us_per_block": round(pallas_g_us, 2),
                "xla_us_per_block": round(xla_g_us, 2),
                "speedup": round(xla_g_us / pallas_g_us, 3)}
            flush()

            # Training forward with LIVE batch stats (two-pass: stats
            # kernel + folded apply) — does the stats pass eat the win?
            gb = (jnp.ones((c,), dtype), jnp.zeros((c,), dtype),
                  jnp.ones((c,), dtype), jnp.zeros((c,), dtype))
            w12 = params[:2]

            def chained_train(block):
                @jax.jit
                def run(x):
                    def body(xc, _):
                        y, _moms = block(xc, *w12, *gb)
                        return y, None
                    xc, _ = jax.lax.scan(body, x, None, length=args.length)
                    return jnp.float32(jnp.sum(xc))
                return run

            pallas_t_us = time_arm(chained_train(
                lambda x, *p: block_train_fwd(x, *p, batch_tile=bt_fwd)))
            xla_t_us = time_arm(chained_train(block_train_fwd_reference))
            entry["train_fwd_live_bn"] = {
                "pallas_us_per_block": round(pallas_t_us, 2),
                "xla_us_per_block": round(xla_t_us, 2),
                "speedup": round(xla_t_us / pallas_t_us, 3)}
            flush()

            # The end-to-end training direction: fwd+bwd with live BN —
            # the number that decides model integration.
            train_params = (*w12, *gb)
            pallas_tg_us = time_arm(chained_grad(
                lambda x, *p: block_train_apply(
                    x, *p, 1e-5, bt_fwd, None),
                train_params, tuple_out=True))
            xla_tg_us = time_arm(chained_grad(
                block_train_fwd_reference, train_params, tuple_out=True))
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
