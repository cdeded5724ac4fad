"""``moe_load_max_over_mean``: the largest held expert's assignments over
the held experts' mean, the loop's counter of that name (a mean over the
expert layers, of the step at each log boundary) meaned over the window's
records. 1 is an even routing; the busiest expert sets the time of the
grouped products and, past ``fast_slack``, sends rows down the overflow
path. None where the program reports no such counter."""


def read(run):
    seen = [r["moe_load_max_over_mean"] for r in run.records
            if "moe_load_max_over_mean" in r]
    return sum(seen) / len(seen) if seen else None
