"""The whole step's share of the chips' peak while the device runs it:
model FLOPs of forward and backward per example (the model family's
``train_flops_per_example``, nothing recomputed; the field is named
``flops_per_image``, an image being the only kind of example so far) times
the examples of the traced window's steps, over the device's busy time in
that window and peak bf16 FLOP/s x chips. Device time only: what the loop
loses between steps is ``device_idle_pct``."""


def read(run):
    if run.trace is None or run.peaks is None or not run.images:
        return None
    achieved = run.flops_per_image * run.images / run.trace["busy_s"]
    return 100.0 * achieved / (run.peaks["bf16_flops_per_s"] * run.chips)
