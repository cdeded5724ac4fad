"""Where-the-time-goes analysis for the ImageNet train step (VERDICT r2
item 4): compiled-HLO inventory + XLA cost analysis + optional profiler
trace, on the ambient backend.

    python tools/mfu_probe.py [--batch 128] [--trace-dir D] [--out JSON]
                              [--hlo-gz PATH] [--steps 12] [--no-s2d]

Reports per-category HLO op counts (convolution / fusion / transpose /
copy / all-reduce), the cost-analysis FLOPs+bytes, measured step time,
and achieved MFU vs the chip peak — the evidence behind the MFU number in
BENCH_r03 (the reference's analog was tfprof's FLOP dump,
reference resnet_single.py:58-66).
"""

import argparse
import gzip
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="imagenet",
                    choices=["imagenet", "cifar10"],
                    help="cifar10 analyzes the CIFAR-shaped step (32x32, "
                         "synthetic split, on-device augmentation included "
                         "like the real train step); note its single-step "
                         "dispatch rate carries per-dispatch latency — "
                         "bench.py's fused chunks are the rate authority, "
                         "the cost analysis is what this adds")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--resnet-size", type=int, default=50)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--no-s2d", action="store_true")
    ap.add_argument("--remat", action="store_true",
                    help="block rematerialization (for batches past the "
                         "HBM ceiling, e.g. 512)")
    ap.add_argument("--trace-dir", default="")
    ap.add_argument("--hlo-gz", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    import jax
    import numpy as np

    import bench
    from tpu_resnet import parallel
    from tpu_resnet.train.step import make_train_step, shard_step

    is_cifar = args.preset == "cifar10"
    if is_cifar and (args.no_s2d or args.image != 224):
        # The CIFAR generator has a 3x3/1 stem (no s2d to ablate) and a
        # fixed 32x32 shape — fail loudly rather than record metadata for
        # a configuration that was never compiled (bench.py's
        # conflicting-override convention).
        raise SystemExit("--no-s2d/--image do not apply to --preset "
                         "cifar10 (3x3 stem, fixed 32x32)")
    image = 32 if is_cifar else args.image
    classes = 10 if is_cifar else 1000

    mesh = parallel.create_mesh(None)
    cfg, model, sched, state, rng = bench._build_train_setup(
        mesh, args.preset, resnet_size=args.resnet_size, batch=args.batch,
        dtype="bfloat16", image=image, synthetic=is_cifar)
    if args.no_s2d or args.remat:
        from tpu_resnet.models import build_model
        cfg.model.stem_space_to_depth = not args.no_s2d
        cfg.model.remat = args.remat
        model = build_model(cfg)

    bs = parallel.batch_sharding(mesh)
    if is_cifar:
        from tpu_resnet.data.augment import get_augment_fns
        augment_fn, _ = get_augment_fns("cifar10")
        images = jax.device_put(
            np.random.RandomState(0).randint(
                0, 256, (args.batch, image, image, 3)).astype(np.uint8), bs)
    else:
        augment_fn = None
        images = jax.device_put(
            np.random.RandomState(0).uniform(
                -114.0, 141.0,
                (args.batch, image, image, 3)).astype(np.float32), bs)
    labels = jax.device_put(
        np.random.RandomState(1).randint(0, classes, args.batch)
        .astype(np.int32), bs)

    step_fn = shard_step(
        make_train_step(model, cfg.optim, sched, classes, augment_fn,
                        base_rng=rng, mesh=mesh), mesh)
    # donate_state=True (the default, what train/loop.py runs): XLA may
    # update params in place instead of allocating a fresh state tree —
    # the measured step is the production configuration.
    t0 = time.perf_counter()
    compiled = step_fn.lower(state, images, labels).compile()
    compile_secs = time.perf_counter() - t0

    hlo = compiled.as_text()
    ops = {}
    for m in re.finditer(r"= \S+ ([a-z][a-z0-9\-]*)\(", hlo):
        ops[m.group(1)] = ops.get(m.group(1), 0) + 1
    interesting = {k: ops.get(k, 0) for k in
                   ("convolution", "fusion", "transpose", "copy",
                    "all-reduce", "custom-call", "reduce", "scatter")}
    # async collective form some backends emit
    interesting["all-reduce"] += ops.get("all-reduce-start", 0)

    from tpu_resnet.obs.mfu import program_flops

    cost = compiled.cost_analysis() or {}

    # measure
    for _ in range(3):
        state, m = compiled(state, images, labels)
    bench._fetch_sync(m["loss"])
    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, m = compiled(state, images, labels)
    bench._fetch_sync(m["loss"])
    sps = args.steps / (time.perf_counter() - t0)

    kind = jax.devices()[0].device_kind
    # Shared tables/extraction: tpu_resnet/obs/mfu.py is the one home of
    # the peak-FLOPs table and the cost-analysis parsing; the probe's MFU
    # is computed exactly like the live gauge's.
    peak = bench._peak_flops(kind)
    flops = program_flops(cost) or 0.0
    out = {
        "backend": jax.default_backend(), "device_kind": kind,
        "preset": args.preset, "image": image,
        "batch": args.batch,
        # s2d only exists on the ImageNet 7x7/2 stem; None = not applicable
        "stem_space_to_depth": None if is_cifar else not args.no_s2d,
        "remat": args.remat,
        "compile_secs": round(compile_secs, 1),
        "steps_per_sec": round(sps, 3),
        "images_per_sec": round(sps * args.batch, 1),
        "hlo_op_counts": interesting,
        "hlo_total_instructions": sum(ops.values()),
        "cost_flops_per_step_per_device": flops,
        "cost_bytes_accessed": float(cost.get("bytes accessed", 0) or 0),
        "mfu": round(flops * sps / peak, 4) if peak and flops else None,
        "peak_flops_assumed": peak,
    }

    if args.trace_dir:
        os.makedirs(args.trace_dir, exist_ok=True)
        with jax.profiler.trace(args.trace_dir):
            for _ in range(5):
                state, m = compiled(state, images, labels)
            bench._fetch_sync(m["loss"])
        out["trace_dir"] = args.trace_dir

    if args.hlo_gz:
        with gzip.open(args.hlo_gz, "wt") as f:
            f.write(hlo)
        out["hlo_gz"] = args.hlo_gz

    print(json.dumps(out, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
