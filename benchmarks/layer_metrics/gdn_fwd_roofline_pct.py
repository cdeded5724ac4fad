"""``gdn_fwd_roofline_pct``: the Gated DeltaNet forward kernel's share of
its roofline: the larger of its operations over the chips' bf16 peak and
its bytes over their HBM bandwidth (``benchmarks/families/qwen3_next.py::
gdn_ops`` and ``gdn_bytes``, the chunked form at the kernel's chunk) times
the sequences of the traced window's steps, over the seconds of the
``gated_delta_fwd*`` rows of the trace's ``device_ops``. None where the
trace holds no such row among its largest, or the configuration is of
another family."""

from benchmarks.families.qwen3_next import roofline


def read(run):
    return roofline(run, "gated_delta_fwd", backward=False)
