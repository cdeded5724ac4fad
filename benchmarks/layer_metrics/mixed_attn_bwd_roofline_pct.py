"""``mixed_attn_bwd_roofline_pct``: the backward attention kernel's share
of its roofline over a model's global and window layers together (one
fused kernel: ``dq`` with ``dk`` and ``dv``, the scores computed again):
2.5 x the forward's FLOPs over the live entries
(``benchmarks/families/smallthinker.py::attention_flops``) or its bytes,
whichever takes longer at the chips' peaks, times the sequences of the
traced window's steps, over the seconds of the ``splash_mqa_dkv*`` rows of
the trace's ``device_ops``. Compute-bound. None where the trace holds no
such row among its largest, or the configuration is of another family."""

from benchmarks.families.smallthinker import roofline


def read(run):
    return roofline(run, "splash_mqa_dkv", backward=True)
