"""``conv_cut_taps_pct``: of the ``taps x tokens`` taps a short-convolution
operator reads in a step, the share that the document cut sets to 0 (a tap
that would reach back into an earlier document of a packed sequence), in
percent: the loop's ``conv_cut_taps_frac`` (one number a step, counted
from the batch's documents, written with every step's metrics) meaned
over the window's records. About 0.1 at documents of a median 600 ids; 0
if the cut is ever dropped. None where the program reports no such
counter."""


def read(run):
    seen = [r["conv_cut_taps_frac"] for r in run.records
            if "conv_cut_taps_frac" in r]
    return 100.0 * sum(seen) / len(seen) if seen else None
