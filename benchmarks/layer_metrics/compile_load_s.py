"""Seconds spent in backend compiles and persistent-cache loads up to the
window's first boundary: the loop's ``compile_load_sec``, the sum of the
durations jax reports for every program it compiled or loaded (each also a
``compile`` span in ``events.jsonl``). Warm it is what loading the cached
step programs costs, cold what XLA costs."""


def read(run):
    for r in run.records:
        if "compile_load_sec" in r:
            return float(r["compile_load_sec"])
    return None
