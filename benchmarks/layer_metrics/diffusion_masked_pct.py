"""``diffusion_masked_pct``: of a step's clean ids, the share that the
noise masked (and the loss is therefore read at), in percent: the loop's
``diffusion_masked_frac`` (written with every step's metrics) meaned over
the window's records. About 50 under a noise level uniform in (0, 1].
None where the program reports no such counter."""


def read(run):
    seen = [r["diffusion_masked_frac"] for r in run.records
            if "diffusion_masked_frac" in r]
    return 100.0 * sum(seen) / len(seen) if seen else None
