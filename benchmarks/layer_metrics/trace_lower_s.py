"""Seconds spent tracing programs to jaxprs and lowering them to MLIR up to
the window's first boundary: the loop's ``trace_lower_sec``, the sum of
its ``trace`` and ``lower`` spans (each the time jax reports for one
program, less that of the traces it encloses). The part of a first
dispatch that the persistent compile cache does not save; the backend
compile beside it is ``compile_load_s``."""


def read(run):
    if not run.records or "trace_lower_sec" not in run.records[0]:
        return None
    return float(run.records[0]["trace_lower_sec"])
