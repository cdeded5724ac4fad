"""The whole step's share of the chips' peak while the device runs it:
model FLOPs of forward and backward per image (benchmarks/lib/flops.py,
nothing recomputed) times the images of the traced window's steps, over
the device's busy time in that window and peak bf16 FLOP/s x chips. Device
time only: what the loop loses between steps is ``device_idle_pct``."""


def read(run):
    if run.trace is None or run.peaks is None or not run.images:
        return None
    achieved = run.flops_per_image * run.images / run.trace["busy_s"]
    return 100.0 * achieved / (run.peaks["bf16_flops_per_s"] * run.chips)
