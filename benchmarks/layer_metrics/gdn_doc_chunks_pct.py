"""``gdn_doc_chunks_pct``: of the (sequence, chunk) pairs of the Gated
DeltaNet recurrence, the share in which a document begins after the
chunk's first position (the kernel's masked case: the carried state's
reach and the decays inside the chunk are cut), in percent: the loop's
``gdn_doc_chunks_frac`` (one number a step, counted from the batch's
documents, written with every step's metrics) meaned over the window's
records. About 19 at documents of a median 600 ids and chunks of 128.
None where the program reports no such counter."""


def read(run):
    seen = [r["gdn_doc_chunks_frac"] for r in run.records
            if "gdn_doc_chunks_frac" in r]
    return 100.0 * sum(seen) / len(seen) if seen else None
