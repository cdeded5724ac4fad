"""``moe_dropped_pct``: of the assignments that fell on an expert held
here, the share that was not computed, in percent: the loop's
``moe_dropped_frac`` (a mean over the expert layers, written with every
step's metrics) meaned over the window's records. 0 by the layer's
construction, counted from its dispatch tables all the same. None where
the program reports no such counter."""


def read(run):
    seen = [r["moe_dropped_frac"] for r in run.records
            if "moe_dropped_frac" in r]
    return 100.0 * sum(seen) / len(seen) if seen else None
