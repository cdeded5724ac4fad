"""Isolate the resident-vs-streaming CIFAR step-time gap on a live chip.

Round-2/3 puzzle: the identical chunk program measured 1.7 ms/step fed
from staged streaming superbatches (r2 window) but 4.9 ms/step fed from
the HBM-resident epoch buffer (r2 AND r3 windows, before and after the
carry-slicing unification) — so the carry-slicing theory cannot be the
whole story.  This probe times the same compiled chunk against three
input placements, all transfer-free in the timed loop, so host-to-device
bandwidth (the r3 streaming-bench confound) cancels out:

  a. `staged`   — a device_put (stage, B, ...) superbatch, reused every
                  call: the exact streaming program with transfers removed.
  b. `resident` — compile_resident_steps over a DeviceDataset epoch
                  buffer (the bench headline path).
  c. `restage`  — the resident epoch buffer, but each chunk's block is
                  first copied device-to-device into a (stage, B, ...)
                  staging buffer by a tiny jitted slice, then consumed by
                  the same staged program: costs one extra HBM round trip
                  of the block, buys a small/layout-friendly scan input.

If (a) ~ 1.7 ms and (b) ~ 4.9 ms, the epoch buffer's size/layout is the
bottleneck and (c) tells us whether restaging recovers it.  If (a) ~ (b),
the r2 streaming number came from run-to-run variance.

Usage: python tools/streaming_gap_probe.py [--out out.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--stage", type=int, default=8)
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=5)
    # Tiny-config overrides so the CPU-mesh test can smoke the exact code
    # the live window runs unattended (tests/test_streaming_gap_probe.py).
    ap.add_argument("--resnet-size", type=int, default=50)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--split", type=int, default=50_000)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench
    from tpu_resnet import parallel
    from tpu_resnet.data import cifar as cifar_data
    from tpu_resnet.data import device_data
    from tpu_resnet.data.augment import get_augment_fns
    from tpu_resnet.parallel import create_mesh
    from tpu_resnet.train.step import make_train_step

    mesh = create_mesh(None)
    stage, reps, warm = args.stage, args.reps, args.warmup
    if warm < 1 or reps < 1:
        raise SystemExit("--warmup and --reps must be >= 1 (the timed "
                         "loop syncs on the warmed metrics)")
    if args.batch < 1 or args.split < 1 or stage < 1:
        raise SystemExit("--batch/--split/--stage must be >= 1")
    if args.split // args.batch < stage:
        raise SystemExit(
            f"--split/--batch = {args.split // args.batch} steps per epoch "
            f"< --stage {stage}: the stage-sized slices would clamp and "
            "silently time overlapping data")
    out = {"device": jax.devices()[0].device_kind, "stage": stage,
           "reps": reps, "resnet_size": args.resnet_size,
           "batch": args.batch, "split": args.split}

    cfg, model, sched, state0, rng = bench._build_train_setup(
        mesh, "cifar10", resnet_size=args.resnet_size, batch=args.batch,
        dtype="bfloat16",
        image=32, synthetic=True)
    batch = cfg.train.global_batch_size
    augment_fn, _ = get_augment_fns("cifar10")
    base_step = make_train_step(model, cfg.optim, sched, 10, augment_fn,
                                base_rng=rng, mesh=mesh)
    run_staged = device_data.compile_staged_stream_steps(base_step, mesh)

    def time_loop(fn, state):
        # Scalar fetch (bench._fetch_sync): the copy cannot complete
        # before every step it depends on.
        for _ in range(warm):
            state, m = fn(state)
        bench._fetch_sync(m["loss"])
        t0 = time.perf_counter()
        for _ in range(reps):
            state, m = fn(state)
        bench._fetch_sync(m["loss"])
        dt = time.perf_counter() - t0
        return reps * stage / dt  # steps/sec

    # (a) staged superbatch resident on device, reused every call.
    sharding = parallel.staged_batch_sharding(mesh)
    rng_np = np.random.default_rng(0)
    gi = jax.device_put(
        rng_np.integers(0, 256, (stage, batch, 32, 32, 3), dtype=np.uint8),
        sharding)
    gl = jax.device_put(
        rng_np.integers(0, 10, (stage, batch), dtype=np.int32),
        sharding)
    out["staged_steps_per_sec"] = round(
        time_loop(lambda s: run_staged(s, gi, gl, 0, stage), state0), 2)
    print("staged   :", out["staged_steps_per_sec"], "st/s", flush=True)

    # (b) resident epoch buffer (fresh state — donation consumed state0).
    _, _, _, state1, _ = bench._build_train_setup(
        mesh, "cifar10", resnet_size=args.resnet_size, batch=args.batch,
        dtype="bfloat16",
        image=32, synthetic=True)
    images, labels = cifar_data.synthetic_data(args.split, 32, 10)
    ds = device_data.DeviceDataset(mesh, images, labels, batch, seed=0)
    run_res = device_data.compile_resident_steps(base_step, ds, mesh, stage)
    counter = {"step": 0}

    def res_call(s):
        off = counter["step"] % ds.steps_per_epoch
        if off + stage > ds.steps_per_epoch:
            counter["step"] += ds.steps_per_epoch - off
        s, m = run_res(s, counter["step"], stage)
        counter["step"] += stage
        return s, m

    out["resident_steps_per_sec"] = round(time_loop(res_call, state1), 2)
    print("resident :", out["resident_steps_per_sec"], "st/s", flush=True)

    # (c) restage: device-to-device copy of the chunk block into a small
    # staging buffer, then the same staged program consumes it.
    _, _, _, state2, _ = bench._build_train_setup(
        mesh, "cifar10", resnet_size=args.resnet_size, batch=args.batch,
        dtype="bfloat16",
        image=32, synthetic=True)

    @jax.jit
    def cut(bi, bl, off):
        return (jax.lax.dynamic_slice_in_dim(bi, off, stage, axis=0),
                jax.lax.dynamic_slice_in_dim(bl, off, stage, axis=0))

    counter2 = {"step": 0}

    def restage_call(s):
        off = counter2["step"] % ds.steps_per_epoch
        if off + stage > ds.steps_per_epoch:
            counter2["step"] += ds.steps_per_epoch - off
            off = 0
        ds.ensure_epoch(ds.epoch_of(counter2["step"]))
        si, sl = cut(ds.images, ds.labels, jnp.int32(off))
        s, m = run_staged(s, si, sl, 0, stage)
        counter2["step"] += stage
        return s, m

    out["restage_steps_per_sec"] = round(time_loop(restage_call, state2), 2)
    print("restage  :", out["restage_steps_per_sec"], "st/s", flush=True)

    print(json.dumps(out))
    if args.out:
        json.dump(out, open(args.out, "w"), indent=2)


if __name__ == "__main__":
    main()
