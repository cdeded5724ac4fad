"""``window_cut_pct``: of the same-document causal (query, key) pairs of a
window layer, the share that its window drops, in percent: the loop's
``window_cut_frac`` (one number a step, counted from the batch's
documents, ``sum max(0, n_i - window) / sum n_i`` with ``n_i`` a
position's offset in its document plus one; written with every step's
metrics) meaned over the window's records. About 34 at documents of a
median 8,192 ids and a window of 4,096; 0 where no document outgrows the
window. None where the program reports no such counter."""


def read(run):
    seen = [r["window_cut_frac"] for r in run.records
            if "window_cut_frac" in r]
    return 100.0 * sum(seen) / len(seen) if seen else None
