"""Integrated fused-block model A/B (VERDICT r3 item 1's second half):
``model.fused_blocks`` on vs off through the REAL headline measurement
path — resident HBM split, on-device augmentation, fused multi-step
dispatch, fetch-synced timing (bench._measure_cifar) — at the CIFAR
ResNet-50 b128 configuration the driver benches.

Battery stage 05 (tools/fused_block_ab.py) decides at the KERNEL level
(isolated block shapes, both directions); this measures what the headline
actually gains end to end, where XLA may already overlap the per-op
overheads the kernel removes. Both numbers together make the
integrate-or-retire decision (docs/PERF.md "CIFAR is overhead-bound":
4.9 ms/step measured vs 1.34 ms byte roofline).

    python tools/fused_model_ab.py --out docs/runs/fused_model_ab_r4.json
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--resnet-size", type=int, default=None,
                    help="default: the preset's 50")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--split", type=int, default=50_000)
    ap.add_argument("--steps-per-call", type=int, default=25)
    ap.add_argument("--warmup-chunks", type=int, default=2)
    ap.add_argument("--measure-chunks", type=int, default=6)
    ap.add_argument("--batch-tile", type=int, default=None,
                    help="fused-kernel forward batch tile")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    import bench
    from tpu_resnet.parallel import create_mesh

    mesh = create_mesh(None)
    plans = [(args.steps_per_call, args.warmup_chunks, args.measure_chunks)]
    arms = {}
    for name, fused in (("xla", False), ("fused", True)):
        def mutate(cfg, fused=fused):
            cfg.model.fused_blocks = fused
            if args.batch_tile is not None:
                cfg.model.fused_block_tile = args.batch_tile
        sps = bench._measure_cifar(
            mesh, plans, resnet_size=args.resnet_size,
            batch=args.batch, split=args.split,
            mutate_cfg=mutate)[args.steps_per_call]
        arms[name] = sps
        print(f"[fused_model_ab] {name}: {sps:.2f} st/s", flush=True)

    # Ratios from the UNROUNDED rates, with zero guards: a degenerate
    # measurement (0.0 steps/s) must record an artifact, not crash the
    # battery stage with ZeroDivisionError (ADVICE r4).
    out = {
        "what": ("model.fused_blocks A/B through the headline resident "
                 "path (fetch-synced, steps_per_call="
                 f"{args.steps_per_call}, b{args.batch})"),
        "preset": "cifar10",
        "resnet_size": args.resnet_size or 50,
        "batch": args.batch,
        "steps_per_sec": {k: round(v, 2) for k, v in arms.items()},
        "fused_speedup": (round(arms["fused"] / arms["xla"], 3)
                          if arms["xla"] > 0 else None),
        "fused_wins": arms["fused"] > arms["xla"] > 0,
        "ms_per_step": {k: (round(1000.0 / v, 3) if v > 0 else None)
                        for k, v in arms.items()},
    }
    print(json.dumps(out))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
