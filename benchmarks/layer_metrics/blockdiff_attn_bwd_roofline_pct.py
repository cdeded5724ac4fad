"""``blockdiff_attn_bwd_roofline_pct``: the backward attention kernel's
share of its roofline under the three-part mask of training by diffusion
over blocks (one fused kernel: ``dq`` with ``dk`` and ``dv``, the scores
computed again). Compute-bound: 2.5 x the forward's FLOPs over the live
entries (``benchmarks/families/sdar_moe.py::attention_bwd_flops``) times
the sequences of the traced window's steps, over the seconds of the
``splash_mqa_dkv*`` rows of the trace's ``device_ops`` and the chips' bf16
peak. None where the trace holds no such row among its largest, or the
configuration is of another family."""

from benchmarks.families.sdar_moe import attention_bwd_flops, kernel_share


def read(run):
    return kernel_share(run, "splash_mqa_dkv", attention_bwd_flops)
