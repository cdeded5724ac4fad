"""``mixed_attn_fwd_roofline_pct``: the forward attention kernel's share
of its roofline over a model's global and window layers together: the
larger of its FLOPs over the chips' bf16 peak and its bytes over their HBM
bandwidth (``benchmarks/families/smallthinker.py::attention_flops`` and
``attention_bytes``: scores and values over the LIVE entries of the causal
mask on a global layer and of the window on a window layer) times the
sequences of the traced window's steps, over the seconds of the
``splash_mqa_fwd*`` rows of the trace's ``device_ops``. Compute-bound.
None where the trace holds no such row among its largest, or the
configuration is of another family."""

from benchmarks.families.smallthinker import roofline


def read(run):
    return roofline(run, "splash_mqa_fwd", backward=False)
