"""``gdn_bwd_roofline_pct``: the Gated DeltaNet backward kernel's share of
its roofline, as ``gdn_fwd_roofline_pct`` reads the forward's: the
backward's operations (each chunk's forward computed again among them) and
bytes over the seconds of the ``gated_delta_bwd*`` rows. None where the
trace holds no such row among its largest, or the configuration is of
another family."""

from benchmarks.families.qwen3_next import roofline


def read(run):
    return roofline(run, "gated_delta_bwd", backward=True)
