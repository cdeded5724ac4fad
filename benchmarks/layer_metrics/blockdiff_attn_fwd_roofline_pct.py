"""``blockdiff_attn_fwd_roofline_pct``: the forward attention kernel's
share of its roofline under the three-part mask of training by diffusion
over blocks. Compute-bound: the family's FLOPs of scores and values over
the LIVE entries (``benchmarks/families/sdar_moe.py::attention_fwd_flops``;
live entries, not tiles, so a sound run reads under 100) times the
sequences of the traced window's steps, over the seconds of the
``splash_mqa_fwd*`` rows of the trace's ``device_ops`` and the chips' bf16
peak. None where the trace holds no such row among its largest, or the
configuration is of another family."""

from benchmarks.families.sdar_moe import attention_fwd_flops, kernel_share


def read(run):
    return kernel_share(run, "splash_mqa_fwd", attention_fwd_flops)
